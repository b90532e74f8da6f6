"""Whole-step model FLOP utilization: the useful FLOPs of every request
the window served (``counts.request_flops`` through the configuration's
reference module: layer matmuls and attention at every processed
position, the LM head where a token is selected, no padding) over the
window's seconds, over the chip's peak."""
from benchlib import counts


def read(run):
    if run.peaks is None:
        return None
    flops = sum(counts.request_flops(run.arch, run.model, len(r.prompt),
                                     len(r.out_tokens))
                for rec in run.records for r in rec.requests
                if r.out_tokens)
    return flops / run.window_s / run.peaks["bf16_flops"] * 100.0
