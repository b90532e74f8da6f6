"""Padded share of the positions the window's steps computed: every
launched step computes rows x C positions (``EngineStats.
positions_computed``), of which its live lanes' ``n_new`` are useful
(``positions_useful``); 100 x (1 - useful / computed) over the window's
``run()`` calls. None where the program counts no positions."""


def read(run):
    computed = useful = 0
    for r in run.records:
        c = getattr(r.stats, "positions_computed", None)
        u = getattr(r.stats, "positions_useful", None)
        if c is None or u is None:
            return None
        computed += c
        useful += u
    if not computed:
        return None
    return 100.0 * (1.0 - useful / computed)
