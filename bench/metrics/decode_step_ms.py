"""Median device time of one execution of the serving step (the batched
ragged step or its single-lane form) in the traced window, from the
profiler's ``XLA Modules`` line. Decode rounds are most executions in
every cell, so the median is a decode step."""
from benchlib import harness, trace_reduce


def read(run):
    if run.trace is None:
        return None
    sec = trace_reduce.median_seconds(run.trace, harness.STEP_PROGRAMS)
    return None if sec is None else sec * 1e3
