"""Cost counters of AOT compilation artifacts.

``Compiled.cost_analysis()`` returns a flat ``{counter: value}`` dict,
or ``None`` (or raises) when the backend exposes no cost model at all.
Every consumer in this repo — the dry-run roofline (``launch/dryrun.py``)
and the live serving cost-attribution layer (``obs/costs.py``) — parses
through THIS module so the normalization logic exists exactly once.
"""
from __future__ import annotations

from typing import Dict, Tuple


def cost_analysis_dict(compiled) -> Dict:
    """Normalized ``compiled.cost_analysis()``: always a flat dict.

    ``None`` or a raising backend collapses to ``{}`` — callers decide
    whether a missing cost model is an error (``obs/costs.py`` raises on
    a TPU).
    """
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    return dict(ca or {})


def flops_bytes(compiled) -> Tuple[float, float]:
    """(FLOPs, bytes accessed) per invocation, per device; zeros when the
    backend reports no cost model (the CPU-interpret degradation path)."""
    d = cost_analysis_dict(compiled)

    def num(key: str) -> float:
        v = d.get(key, 0.0)
        return float(v) if isinstance(v, (int, float)) else 0.0

    return num("flops"), num("bytes accessed")
