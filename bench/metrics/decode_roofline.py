"""Share of its roofline that the mean decode step reaches: the least time
of that step (the larger of its FLOPs over peak FLOP/s and its bytes over
peak HBM bandwidth, counted from the configuration's shapes by its
reference module, with weights in the declared QMC format and KV at the
model's dtype) over the measured median step time. The result names the
bound that applies."""
from benchlib import counts, harness, trace_reduce


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    sec = trace_reduce.median_seconds(run.trace, harness.STEP_PROGRAMS)
    steps = sum(r.stats.decode_steps for r in run.records)
    if not sec or not steps:
        return None
    lanes = sum(r.stats.tokens_out - r.stats.prefills
                for r in run.records) / steps
    ctx = sum(r.stats.kv_pages_live for r in run.records) * run.page / steps
    need = counts.decode_step(run.arch, run.model, run.weights, lanes, ctx,
                              run.kv_dtype)
    least, bound = counts.least_time(need["flops"], need["bytes"],
                                     run.peaks)
    return {"value": least / sec * 100.0, "bound": bound}
