"""Gigabytes (1e9 bytes) of the matmul weights the serving step reads:
``EngineStats.exec_weight_bytes``, which the engine records on every
``run()`` call from the weight execution plan it built at set-up (int8
code planes with their scales, and any matmul weight left dense). The
plan is fixed per engine, so the window's calls agree; the largest is
read. None where the program keeps no such counter."""


def read(run):
    vals = [getattr(r.stats, "exec_weight_bytes", None) for r in run.records]
    if not vals or any(v is None for v in vals):
        return None
    return max(vals) / 1e9
