"""Host milliseconds per engine round spent copying the step's selected
tokens and logprobs to the host once the device is done
(``round/step/readback`` in ``EngineStats.step_part_seconds``), summed
over the window's ``run()`` calls, over the rounds that ran a step.
None where the program keeps no step parts."""

PART = "round/step/readback"


def read(run):
    rounds = sum(r.stats.rounds for r in run.records)
    parts = [getattr(r.stats, "step_part_seconds", None)
             for r in run.records]
    if not rounds or any(p is None for p in parts):
        return None
    return sum(p.get(PART, 0.0) for p in parts) / rounds * 1e3
