"""Per-step cost attribution: XLA cost_analysis x engine counters x DSE.

The paper's headline numbers are deployment *costs* (external transfers,
energy, latency per token) but the serving stack only measures wall time.
This module closes that gap three ways, per engine run:

  1. **Capture** — with :func:`enable_capture` on, every serving jit
     wrapped in ``serve/steps.py:TracedJit`` AOT-lowers each new call
     shape once and records ``cost_analysis()`` FLOPs / bytes-accessed
     per call (the ``launch/xla_compat.py`` shim). On a TPU a failed
     capture raises; off it (the CPU tests) it degrades to zeros.
  2. **Attribute** — :func:`attribute` diffs the step set's per-shape
     call/wall tables across a run and scores each (fn, shape) against
     its roofline bound (``launch/roofline.py``): measured wall seconds
     vs ``calls * max(flops/peak FLOP/s, bytes/peak HBM B/s)`` with the
     peaks of the device that ran (``roofline.device_peaks``), plus
     arithmetic intensity. A device that is not in the peak table gets
     no roofline columns; capture on an accelerator that is not in it
     refuses to start. The drift ratio (measured / roofline) is the
     model-vs-measured health signal — a QMC step 5x over its roofline
     is kernel overhead, not bandwidth.
  3. **Model** — the same run's ``EngineStats`` page/token counters feed
     the Eq. (3)/(4) DSE (``memsys/workload.py`` traffic +
     ``memsys/system.py`` evaluate_hetero / evaluate_conventional), so
     each run also reports *modeled* bytes / energy / latency per round
     and per token for the weight format it actually served.

Exports land on the existing obs surfaces via :func:`flush_metrics`
(``serve_cost_*`` instruments per the ``obs/metrics.py`` contract).
Wired end to end by ``launch/serve.py --cost-report`` and the
``cost_attribution`` section of ``benchmarks/serving.py``.

Sampling and speculative verification need no rows of their own: token
selection is fused INTO the step (``serve/sampling.py`` — its FLOPs land
in the step's per-width cost, and no out-of-jit argmax dispatch exists
to go unattributed any more), and a speculative verify call is just the
step at a ``width_ladder`` rung, so it lands in that rung's ``C<width>``
row. The invariant the regression tests pin: one engine round == exactly
one attributed ``step``/``solo_step`` dispatch.

Capture is OFF by default: the only cost any other path pays is one
module-bool branch per traced call. Turning it on makes each TracedJit
call synchronous (``block_until_ready`` inside the timed window) so the
per-shape wall tables measure device time, not async dispatch — a
measurement mode, not a serving mode.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from repro.launch import roofline as rl
from repro.launch import xla_compat
from repro.memsys.system import (MemSystemConfig, evaluate_conventional,
                                 evaluate_hetero)
from repro.memsys.workload import (act_bits_per_step, kv_bits_per_step,
                                   make_traffic)

# ---------------------------------------------------------------------------
# capture switch
# ---------------------------------------------------------------------------
_CAPTURE = False


def enable_capture(on: bool = True) -> bool:
    """Turn per-call cost capture on/off; returns the previous state.

    Raises ValueError when turning it on for an accelerator whose
    ``device_kind`` has no row in ``launch.roofline.PEAKS``: its roofline
    columns would have no honest bound to compare against."""
    global _CAPTURE
    if on:
        import jax
        dev = jax.devices()[0]
        if dev.platform != "cpu" and rl.device_peaks(dev) is None:
            raise ValueError(
                f"no peak table row for device kind {dev.device_kind!r} "
                f"(launch/roofline.py PEAKS); add its published peaks "
                f"before capturing costs on it")
    prev, _CAPTURE = _CAPTURE, bool(on)
    return prev


def capture_enabled() -> bool:
    return _CAPTURE


def capture_costs(fn, args, kw) -> Dict[str, float]:
    """AOT-lower one call shape and read its cost analysis.

    ``{"flops": f, "bytes": b}`` per invocation (per device). On a TPU
    any failure — a callable without ``lower``, a compile error, an
    empty cost model — raises. Off the TPU it degrades to zeros:
    attribution then reports measured wall time with no roofline
    columns and the drift gauge suppressed.
    """
    import jax
    on_tpu = jax.default_backend() == "tpu"
    try:
        compiled = fn.lower(*args, **kw).compile()
        flops, nbytes = xla_compat.flops_bytes(compiled)
    except Exception:
        if on_tpu:
            raise
        return {"flops": 0.0, "bytes": 0.0}
    if on_tpu and not (flops or nbytes):
        raise RuntimeError("TPU cost_analysis() reported no FLOPs and no "
                           "bytes for a serving step")
    return {"flops": flops, "bytes": nbytes}


# ---------------------------------------------------------------------------
# per-(fn, shape) attribution rows
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class FnCost:
    """One (step function, call shape) row of the attribution table."""
    fn: str                          # TracedJit name: step / page_copy / ...
    key: str                         # call-shape key, e.g. "C1" / "C16"
    calls: int
    wall_s: float                    # measured (synchronous) wall seconds
    flops_per_call: float
    bytes_per_call: float
    # peaks of the device the calls ran on; None (a device not in the
    # peak table, e.g. the CPU) leaves the row without roofline columns
    peaks: Optional[rl.Peaks] = None

    @property
    def label(self) -> str:
        return f"{self.fn}/{self.key}"

    @property
    def captured(self) -> bool:
        return self.flops_per_call > 0 or self.bytes_per_call > 0

    @property
    def has_roofline(self) -> bool:
        """Captured costs AND a known device: the roofline columns
        (bound, drift, fraction) mean something."""
        return self.captured and self.peaks is not None

    def roofline(self) -> rl.Roofline:
        return rl.from_artifacts(
            self.fn, self.key, "-", 1,
            {"flops": self.flops_per_call,
             "bytes accessed": self.bytes_per_call},
            {}, model_flops=0.0, peaks=self.peaks)

    @property
    def roofline_s(self) -> float:
        """Bound time for all calls: max(compute, memory) per call (0
        without a peak table row)."""
        if self.peaks is None:
            return 0.0
        return self.calls * self.roofline().roofline_time

    @property
    def drift(self) -> float:
        """Measured / roofline-bound wall time (>= 1 in practice; 0 when
        capture degraded to zeros)."""
        r = self.roofline_s
        return self.wall_s / r if r > 0 else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the roofline bound achieved (roofline / measured)."""
        return self.roofline_s / self.wall_s if self.wall_s > 0 else 0.0

    @property
    def arithmetic_intensity(self) -> float:
        """FLOPs per byte accessed — where on the roofline this shape sits."""
        return (self.flops_per_call / self.bytes_per_call
                if self.bytes_per_call > 0 else 0.0)

    def to_dict(self) -> dict:
        d = {"fn": self.fn, "key": self.key, "calls": self.calls,
             "wall_s": self.wall_s,
             "flops_per_call": self.flops_per_call,
             "bytes_per_call": self.bytes_per_call,
             "arithmetic_intensity": self.arithmetic_intensity}
        if self.peaks is not None:
            d.update(roofline_s=self.roofline_s, drift=self.drift,
                     roofline_fraction=self.roofline_fraction)
        return d


def _traced_members(step_set):
    """The step set's TracedJit-like members, duck-typed (no import of
    ``serve.steps`` — it imports this module)."""
    for name in ("step", "solo_step", "page_copy", "reset_state",
                 "apply_page_ops"):
        fn = getattr(step_set, name, None)
        if fn is not None and hasattr(fn, "cost_by_key"):
            yield fn


def snapshot(step_set) -> Dict[Tuple[str, str], Tuple[int, float]]:
    """Per-(fn, shape) (calls, wall seconds) tables right now — diff two
    of these around a run to attribute that run only."""
    out = {}
    for fn in _traced_members(step_set):
        for key, n in fn.calls_by_key.items():
            out[(fn.name, key)] = (n, fn.seconds_by_key.get(key, 0.0))
    return out


def collect(step_set, baseline=None,
            peaks: Optional[rl.Peaks] = None) -> List[FnCost]:
    """Attribution rows for a step set, minus an optional prior
    :func:`snapshot` (so warm engines report only their own run), scored
    against ``peaks`` (None: no roofline columns)."""
    baseline = baseline or {}
    rows = []
    for fn in _traced_members(step_set):
        for key, n in fn.calls_by_key.items():
            n0, s0 = baseline.get((fn.name, key), (0, 0.0))
            calls = n - n0
            if calls <= 0:
                continue
            cost = fn.cost_by_key.get(key, {})
            rows.append(FnCost(
                fn=fn.name, key=key, calls=calls,
                wall_s=fn.seconds_by_key.get(key, 0.0) - s0,
                flops_per_call=float(cost.get("flops", 0.0)),
                bytes_per_call=float(cost.get("bytes", 0.0)),
                peaks=peaks))
    rows.sort(key=lambda r: -r.wall_s)
    return rows


# ---------------------------------------------------------------------------
# modeled memory-system cost from EngineStats counters
# ---------------------------------------------------------------------------
def detect_weights_method(params) -> str:
    """Map a serving params tree to a ``make_traffic`` method name.

    QTensor / ShardedQTensor leaves anywhere -> ``qmc``; else the widest
    float dtype decides ``fp32`` vs ``fp16`` (bf16 streams 16 bits too).
    """
    import jax
    import jax.numpy as jnp
    from repro.core.qtensor import QTensor
    from repro.core.qtensor_sharded import ShardedQTensor

    q = (QTensor, ShardedQTensor)
    leaves = jax.tree_util.tree_flatten(
        params, is_leaf=lambda x: isinstance(x, q))[0]
    if any(isinstance(x, q) for x in leaves):
        return "qmc"
    for x in leaves:
        if hasattr(x, "dtype") and x.dtype == jnp.float32:
            return "fp32"
    return "fp16"


def modeled_memsys(cfg, stats, *, method: str, page: int,
                   kv_dtype_bits: int = 16, qmc=None,
                   sys_cfg: Optional[MemSystemConfig] = None) -> dict:
    """Eq. (3)/(4) cost of the run the engine just measured.

    Rebinds a :func:`make_traffic` stream to THIS run's averages: per
    round (one jit step), weights stream once, the KV stream is the page
    count the engine actually gathered/wrote (``kv_pages_live`` decode
    reads + ``prefill_kv_pages_live`` chunk reads + page-rounded writes,
    the same accounts ``kv_traffic_paged/chunked`` charge), and
    activations scale with the lane-steps the round carried. Returns a
    JSON-able dict with per-round bits, bytes/token and the
    ``evaluate_hetero`` / ``evaluate_conventional`` results; degenerate
    runs (no rounds or no tokens) report zeros with ``degenerate=True``.
    """
    from repro.core.qconfig import QMCConfig
    sys_cfg = sys_cfg or MemSystemConfig()
    rounds = int(getattr(stats, "rounds", 0))
    tokens = int(getattr(stats, "tokens_out", 0))
    if rounds <= 0 or tokens <= 0:
        return {"method": method, "degenerate": True,
                "rounds": rounds, "tokens_out": tokens,
                "bytes_per_round": 0.0, "bytes_per_token": 0.0,
                "weight_bits_per_round": 0.0, "kv_bits_per_round": 0.0,
                "act_bits_per_round": 0.0}

    per_page_bits = (kv_bits_per_step(cfg, page, kv_dtype_bits)
                     - kv_bits_per_step(cfg, 0, kv_dtype_bits))
    ssm_bits = kv_bits_per_step(cfg, 0, kv_dtype_bits)
    lane_steps = tokens + int(getattr(stats, "prefill_chunks", 0))
    pages_read = (int(getattr(stats, "kv_pages_live", 0))
                  + int(getattr(stats, "prefill_kv_pages_live", 0)))
    kv_read = pages_read * per_page_bits + lane_steps * ssm_bits
    kv_write = (int(getattr(stats, "prefill_kv_pages_written", 0))
                * per_page_bits + tokens * per_page_bits / page)

    base = make_traffic(cfg, method, qmc=qmc or QMCConfig())
    traffic = dataclasses.replace(
        base, name=f"{base.name}+run",
        kv_bits=(kv_read + kv_write) / rounds,
        act_bits=act_bits_per_step(cfg) * lane_steps / rounds)
    het = evaluate_hetero(traffic, sys_cfg)
    conv = evaluate_conventional(traffic, sys_cfg, legacy_flash=False)

    bits_per_round = traffic.weight_bits + traffic.kv_bits \
        + traffic.act_bits

    def _res(r) -> dict:
        return {"latency_s": r.latency_s, "energy_j": r.energy_j,
                "external_bits": r.external_bits, "power_w": r.power_w,
                "feasible": r.feasible}

    return {
        "method": method, "degenerate": False,
        "rounds": rounds, "tokens_out": tokens,
        "weight_bits_per_round": traffic.weight_bits,
        "kv_bits_per_round": traffic.kv_bits,
        "act_bits_per_round": traffic.act_bits,
        "bytes_per_round": bits_per_round / 8.0,
        "bytes_per_token": bits_per_round * rounds / 8.0 / tokens,
        "hetero": _res(het),
        "conventional": _res(conv),
        "energy_j_per_token": het.energy_j * rounds / tokens,
        "latency_s_per_token": het.latency_s * rounds / tokens,
    }


# ---------------------------------------------------------------------------
# the full report
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class CostReport:
    """One run's cost attribution: per-(fn, shape) roofline rows + the
    modeled memory-system cost of the same run."""
    fns: List[FnCost]
    modeled: dict
    measured_wall_s: float
    measured_device_s: float
    tokens_out: int

    def to_dict(self) -> dict:
        return {"fns": [r.to_dict() for r in self.fns],
                "modeled": self.modeled,
                "measured_wall_s": self.measured_wall_s,
                "measured_device_s": self.measured_device_s,
                "tokens_out": self.tokens_out}

    def table(self) -> str:
        lines = [f"{'fn/shape':14s} {'calls':>6s} {'wall_s':>9s} "
                 f"{'roofline_s':>10s} {'drift':>7s} {'ai':>7s}"]
        for r in self.fns:
            roof = (f"{r.roofline_s:10.6f} {r.drift:7.1f}"
                    if r.has_roofline else f"{'-':>10s} {'-':>7s}")
            ai = (f"{r.arithmetic_intensity:7.2f}" if r.captured
                  else f"{'-':>7s}")
            lines.append(f"{r.label:14s} {r.calls:6d} {r.wall_s:9.4f} "
                         f"{roof} {ai}")
        m = self.modeled
        if m and not m.get("degenerate"):
            lines.append(
                f"modeled[{m['method']}]: "
                f"{m['bytes_per_token'] / 1e6:.2f} MB/token, "
                f"hetero {m['energy_j_per_token'] * 1e3:.3f} mJ/token "
                f"{m['latency_s_per_token'] * 1e3:.3f} ms/token "
                f"(feasible={m['hetero']['feasible']})")
        return "\n".join(lines)


def attribute(step_set, stats, *, cfg, params=None,
              method: Optional[str] = None, page: int,
              kv_dtype_bits: int = 16, baseline=None, qmc=None,
              sys_cfg: Optional[MemSystemConfig] = None) -> CostReport:
    """Assemble a run's :class:`CostReport` from its step set + stats,
    scored against the peaks of the device that ran."""
    if method is None:
        method = detect_weights_method(params) if params is not None \
            else "fp16"
    return CostReport(
        fns=collect(step_set, baseline, rl.device_peaks()),
        modeled=modeled_memsys(cfg, stats, method=method, page=page,
                               kv_dtype_bits=kv_dtype_bits, qmc=qmc,
                               sys_cfg=sys_cfg),
        measured_wall_s=float(getattr(stats, "wall_s", 0.0)),
        measured_device_s=float(stats.device_seconds()
                                if hasattr(stats, "device_seconds")
                                else 0.0),
        tokens_out=int(getattr(stats, "tokens_out", 0)))


def flush_metrics(reg, report: CostReport) -> None:
    """Fold a report into a metrics registry per the ``serve_cost_*``
    contract (``obs/metrics.py``). The drift gauge is only set for rows
    with a roofline (captured costs on a device in the peak table) — a
    backend without a cost model, or a device without peaks, suppresses
    it rather than reporting drift=0 as if the step hit its roofline."""
    flops = reg.counter("serve_cost_flops_total",
                        "captured XLA FLOPs executed, per fn/shape",
                        labels=("fn",))
    nbytes = reg.counter("serve_cost_bytes_total",
                         "captured XLA bytes accessed, per fn/shape",
                         labels=("fn",))
    drift = reg.gauge("serve_cost_drift_ratio",
                      "measured wall / roofline bound, per fn/shape",
                      labels=("fn",))
    for r in report.fns:
        flops.inc(r.flops_per_call * r.calls, fn=r.label)
        nbytes.inc(r.bytes_per_call * r.calls, fn=r.label)
        if r.has_roofline:
            drift.set(r.drift, fn=r.label)
    m = report.modeled
    if m and not m.get("degenerate"):
        reg.gauge("serve_cost_modeled_bytes_per_token",
                  "Eq.(3)/(4) modeled memory traffic per emitted token"
                  ).set(m["bytes_per_token"])
        e = reg.gauge("serve_cost_modeled_energy_j",
                      "modeled per-round memory energy", labels=("system",))
        lat = reg.gauge("serve_cost_modeled_latency_s",
                        "modeled per-round memory latency",
                        labels=("system",))
        for system in ("hetero", "conventional"):
            e.set(m[system]["energy_j"], system=system)
            lat.set(m[system]["latency_s"], system=system)
