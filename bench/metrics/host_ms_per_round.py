"""Host milliseconds per engine round: the engine's host-side round phases
(admission, grants, host array prep, emission; ``EngineStats.
host_seconds()``) summed over the window's ``run()`` calls, over the
rounds that ran a step."""


def read(run):
    rounds = sum(r.stats.rounds for r in run.records)
    if not rounds:
        return None
    host = sum(r.stats.host_seconds() for r in run.records)
    return host / rounds * 1e3
