"""Host milliseconds per engine round spent starting the step: building
its device inputs (``round/step/upload``) and the jitted call until it
returns (``round/step/launch``), from ``EngineStats.step_part_seconds``
summed over the window's ``run()`` calls, over the rounds that ran a
step. None where the program keeps no step parts."""

PARTS = ("round/step/upload", "round/step/launch")


def read(run):
    rounds = sum(r.stats.rounds for r in run.records)
    parts = [getattr(r.stats, "step_part_seconds", None)
             for r in run.records]
    if not rounds or any(p is None for p in parts):
        return None
    return sum(p.get(k, 0.0) for p in parts for k in PARTS) / rounds * 1e3
