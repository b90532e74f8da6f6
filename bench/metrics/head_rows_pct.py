"""Share of the computed positions that the LM head and token selection
ran on: every launched step computes rows x C positions (``EngineStats.
positions_computed``) and runs the head on rows x W of them, W the
columns a lane can emit from (``head_rows``); 100 x sum of head rows /
sum of positions over the window's ``run()`` calls. None where the
program counts no head rows."""


def read(run):
    computed = head = 0
    for r in run.records:
        c = getattr(r.stats, "positions_computed", None)
        h = getattr(r.stats, "head_rows", None)
        if c is None or h is None:
            return None
        computed += c
        head += h
    if not computed:
        return None
    return 100.0 * head / computed
