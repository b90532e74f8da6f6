"""Cost-attribution layer (``obs/costs.py``) invariants:

  * capture degrades to zeros — never raises — on callables/backends
    without AOT cost analysis, and the drift gauge is SUPPRESSED (not
    set to 0) for such rows;
  * a real jax.jit call shape captures nonzero cost exactly once per
    shape per wrapper, even when the underlying jit is lru-warm;
  * FnCost roofline math matches the v5e constants by hand;
  * modeled bytes/token for a known config + fabricated EngineStats
    matches an explicit hand computation, and qmc is strictly below
    fp32 on identical counters;
  * an engine run under capture produces a CostReport, the
    ``serve_cost_*`` metrics, and the pool/queue Perfetto counter
    tracks — and produces NONE of it with capture off (the default).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.launch.roofline import PEAKS
from repro.memsys.workload import make_traffic
from repro.obs import costs as obs_costs
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.serve.engine import EngineStats, Request, ServeEngine
from repro.serve.steps import TracedJit

PAGE = 16
V5E = PEAKS["TPU v5 lite"]


@pytest.fixture
def capture():
    prev = obs_costs.enable_capture()
    yield
    obs_costs.enable_capture(prev)


def _reqs(n=3, lo=8, hi=20, seed=0, max_new=4):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(2, 64, size=int(L)).astype(np.int32),
                    max_new_tokens=max_new)
            for i, L in enumerate(rng.integers(lo, hi, size=n))]


# ==========================================================================
# capture mechanics + fallback
# ==========================================================================
def test_capture_off_by_default_costs_nothing():
    tj = TracedJit("probe", jax.jit(lambda x: x * 2))
    tj(jnp.ones(4))
    assert tj.cost_by_key == {}
    assert tj.calls_by_key == {}
    assert tj.calls == 1               # plain counters still work


def test_capture_fallback_never_raises(capture):
    # a plain Python callable has no .lower — capture must degrade to
    # zeros and the call must still go through
    tj = TracedJit("plain", lambda x: x + 1)
    assert tj(41) == 42
    assert tj.cost_by_key["call"] == {"flops": 0.0, "bytes": 0.0}
    assert tj.calls_by_key["call"] == 1
    rows = obs_costs.collect(_StepSet(tj))
    assert len(rows) == 1 and not rows[0].captured
    assert rows[0].drift == 0.0        # no roofline -> no drift claim


class _FakeDevice:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


@pytest.mark.parametrize("platform,kind,raises", [
    ("cpu", "cpu", False), ("tpu", "TPU v5 lite", False),
    ("tpu", "TPU v9 imaginary", True), ("gpu", "Some GPU", True)])
def test_capture_refuses_accelerator_without_peaks(monkeypatch, platform,
                                                   kind, raises):
    """Only the CPU or a device kind with a peak-table row may capture:
    another accelerator would get no honest roofline bound."""
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeDevice(platform, kind)])
    prev = obs_costs.capture_enabled()
    try:
        if raises:
            with pytest.raises(ValueError):
                obs_costs.enable_capture()
            assert obs_costs.capture_enabled() == prev
        else:
            obs_costs.enable_capture()
            assert obs_costs.capture_enabled()
    finally:
        obs_costs.enable_capture(prev)


def test_device_peaks_by_kind():
    from repro.launch.roofline import device_peaks
    assert device_peaks(_FakeDevice("tpu", "TPU v5 lite")) is V5E
    assert device_peaks(_FakeDevice("cpu", "cpu")) is None
    assert V5E.flops == 197e12 and V5E.hbm_bw == 819e9


def test_capture_failure_raises_on_tpu(monkeypatch):
    """On a TPU a failed capture raises instead of reading as zero."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(AttributeError):
        obs_costs.capture_costs(lambda x: x + 1, (1,), {})


def test_capture_real_jit_per_shape(capture):
    tj = TracedJit("f", jax.jit(lambda x: x @ x))
    for _ in range(3):
        tj(jnp.ones((8, 8)))
    tj(jnp.ones((16, 16)))
    assert set(tj.calls_by_key) == {"call"}   # default key: one bucket
    assert tj.calls_by_key["call"] == 4
    cost = tj.cost_by_key["call"]
    assert cost["flops"] >= 0 and cost["bytes"] >= 0


def test_capture_fires_on_warm_jit(capture):
    # capture keys on shapes THIS wrapper has seen, not on jit-cache
    # growth: a second wrapper over the same (warm) jit still captures
    jitted = jax.jit(lambda x: x + 1)
    jitted(jnp.ones(4))                # warm the executable cache
    tj = TracedJit("warm", jitted)
    tj(jnp.ones(4))
    assert "call" in tj.cost_by_key
    assert tj.calls_by_key["call"] == 1


def test_cost_key_failure_degrades_to_default(capture):
    tj = TracedJit("f", jax.jit(lambda x: x),
                   cost_key=lambda a, k: a[5].shape)   # IndexError
    tj(jnp.ones(2))
    assert set(tj.calls_by_key) == {"call"}


# ==========================================================================
# FnCost roofline math
# ==========================================================================
def test_fncost_roofline_by_hand():
    # one call whose FLOPs take exactly 1s at peak and whose bytes take
    # 0.5s at HBM bandwidth: the bound is the max stream = 1s
    r = obs_costs.FnCost(fn="step", key="C1", calls=2, wall_s=6.0,
                         flops_per_call=V5E.flops,
                         bytes_per_call=V5E.hbm_bw * 0.5, peaks=V5E)
    assert r.roofline_s == pytest.approx(2.0)          # 2 calls x 1s
    assert r.drift == pytest.approx(3.0)               # 6s wall / 2s bound
    assert r.roofline_fraction == pytest.approx(1 / 3)
    assert r.arithmetic_intensity == pytest.approx(
        V5E.flops / (V5E.hbm_bw * 0.5))
    assert r.captured
    d = r.to_dict()
    assert d["drift"] == pytest.approx(3.0)
    assert d["fn"] == "step" and d["key"] == "C1"


class _StepSet:
    """Duck-typed step-set stand-in: any attrs with cost tables count."""

    def __init__(self, step, page_copy=None):
        self.step = step
        self.page_copy = page_copy
        self.reset_state = None


def test_collect_diffs_against_baseline(capture):
    tj = TracedJit("f", jax.jit(lambda x: x * 3))
    ss = _StepSet(tj)
    tj(jnp.ones(4))
    base = obs_costs.snapshot(ss)
    tj(jnp.ones(4))
    tj(jnp.ones(4))
    rows = obs_costs.collect(ss, base)
    assert len(rows) == 1 and rows[0].calls == 2       # this run only
    assert obs_costs.collect(ss, obs_costs.snapshot(ss)) == []


# ==========================================================================
# modeled memsys cost: hand-pinned formula + qmc < fp32
# ==========================================================================
def _fake_stats():
    s = EngineStats()
    s.rounds = 10
    s.tokens_out = 20
    s.prefill_chunks = 4
    s.kv_pages_live = 30
    s.prefill_kv_pages_live = 12
    s.prefill_kv_pages_written = 6
    return s


def test_modeled_bytes_per_token_by_hand(serve_cfg):
    cfg = serve_cfg                    # 2 attn layers, kv_dim 32
    bits = 32                          # fp32 KV cache
    m = obs_costs.modeled_memsys(cfg, _fake_stats(), method="fp32",
                                 page=PAGE, kv_dtype_bits=bits)
    # per-page KV bits: 2 (K+V) x n_attn_layers x kv_dim x page x dtype
    kv_dim = cfg.n_kv_heads * (cfg.d_model // cfg.n_heads)
    per_page = 2 * cfg.n_layers * kv_dim * PAGE * bits
    assert per_page == 2 * 2 * 32 * 16 * 32
    lane_steps = 20 + 4                # tokens_out + prefill_chunks
    kv_read = (30 + 12) * per_page     # decode + chunk page reads (no SSM)
    kv_write = 6 * per_page + 20 * per_page / PAGE
    kv_per_round = (kv_read + kv_write) / 10
    act_per_round = 4 * cfg.n_layers * cfg.d_model * 16 * lane_steps / 10
    w_per_round = cfg.active_param_count() * 32.0
    expect = (w_per_round + kv_per_round + act_per_round) * 10 / 8 / 20
    assert m["kv_bits_per_round"] == pytest.approx(kv_per_round)
    assert m["act_bits_per_round"] == pytest.approx(act_per_round)
    assert m["weight_bits_per_round"] == pytest.approx(w_per_round)
    assert m["bytes_per_token"] == pytest.approx(expect)
    assert not m["degenerate"]
    assert m["hetero"]["energy_j"] > 0
    assert m["hetero"]["latency_s"] > 0
    assert m["conventional"]["latency_s"] > 0


def test_modeled_qmc_strictly_below_fp32(serve_cfg):
    stats = _fake_stats()
    fp32 = obs_costs.modeled_memsys(serve_cfg, stats, method="fp32",
                                    page=PAGE)
    qmc = obs_costs.modeled_memsys(serve_cfg, stats, method="qmc",
                                   page=PAGE)
    assert qmc["bytes_per_token"] < fp32["bytes_per_token"]
    # identical KV/act streams — only the weight stream shrinks
    assert qmc["kv_bits_per_round"] == fp32["kv_bits_per_round"]
    assert qmc["weight_bits_per_round"] < fp32["weight_bits_per_round"]


def test_modeled_degenerate_run(serve_cfg):
    m = obs_costs.modeled_memsys(serve_cfg, EngineStats(), method="fp16",
                                 page=PAGE)
    assert m["degenerate"] and m["bytes_per_token"] == 0.0


def test_make_traffic_fp32_baseline(serve_cfg):
    t32 = make_traffic(serve_cfg, "fp32")
    t16 = make_traffic(serve_cfg, "fp16")
    assert t32.weight_bits == pytest.approx(2 * t16.weight_bits)


def test_detect_weights_method(serve_cfg, serve_params):
    assert obs_costs.detect_weights_method(serve_params) == "fp32"
    from repro.core.qconfig import QMCConfig
    from repro.core.serving_quant import quantize_for_serving
    q = quantize_for_serving(serve_params,
                             QMCConfig(rho=0.3, granularity="subtile"),
                             tp_shards=1, min_dim=64)
    assert obs_costs.detect_weights_method(q) == "qmc"


# ==========================================================================
# flush: drift suppression + metric names
# ==========================================================================
def test_flush_suppresses_drift_for_uncaptured_rows():
    reg = obs_metrics.Registry()
    rows = [obs_costs.FnCost(fn="step", key="C1", calls=4, wall_s=1.0,
                             flops_per_call=1e9, bytes_per_call=1e6,
                             peaks=V5E),
            obs_costs.FnCost(fn="page_copy", key="call", calls=2,
                             wall_s=0.1, flops_per_call=0.0,
                             bytes_per_call=0.0)]
    report = obs_costs.CostReport(fns=rows, modeled={"degenerate": True},
                                  measured_wall_s=1.1,
                                  measured_device_s=1.0, tokens_out=8)
    obs_costs.flush_metrics(reg, report)
    snap = reg.snapshot()
    assert snap["serve_cost_flops_total"]["series"] == [
        {"labels": {"fn": "page_copy/call"}, "value": 0.0},
        {"labels": {"fn": "step/C1"}, "value": 4e9}]
    drift = snap["serve_cost_drift_ratio"]["series"]
    assert [s["labels"]["fn"] for s in drift] == ["step/C1"]
    # degenerate modeled section -> no modeled gauges at all
    assert "serve_cost_modeled_bytes_per_token" not in snap


# ==========================================================================
# end to end through the engine
# ==========================================================================
def test_engine_run_attributes_costs(serve_cfg, serve_params, capture):
    reg = obs_metrics.Registry()
    trc = obs_trace.Tracer(enabled=True)
    eng = ServeEngine(serve_cfg, serve_params, slots=2, max_len=64,
                      page_size=PAGE, metrics=reg, tracer=trc)
    eng.run(_reqs())
    rep = eng.last_cost_report
    assert rep is not None
    step_rows = [r for r in rep.fns if r.fn in ("step", "solo_step")]
    assert step_rows and all(r.key.startswith("C") for r in step_rows)
    # every round reaches the device through exactly one step dispatch —
    # the batch step or the B=1 solo lane
    assert sum(r.calls for r in step_rows) == eng.stats.rounds
    assert rep.tokens_out == eng.stats.tokens_out
    assert rep.measured_wall_s > 0
    assert not rep.modeled["degenerate"]
    assert rep.modeled["method"] == "fp32"
    assert rep.table()                 # renders without raising
    snap = reg.snapshot()
    assert "serve_cost_flops_total" in snap
    assert "serve_cost_modeled_bytes_per_token" in snap
    # each row with a roofline reports drift; rows without one (no cost
    # model, or a device such as the CPU that has no peak table row)
    # suppress it instead of claiming drift=0
    drift_fns = {s["labels"]["fn"]
                 for s in snap.get("serve_cost_drift_ratio",
                                   {"series": []})["series"]}
    for r in rep.fns:
        assert (r.label in drift_fns) == r.has_roofline
    # pool-pressure counter tracks, one sample per round
    counters = [e for e in trc.events if e["ph"] == "C"]
    pool = [e for e in counters if e["name"] == "pool/pages"]
    queue = [e for e in counters if e["name"] == "sched/queue"]
    assert len(pool) == eng.stats.rounds == len(queue)
    assert {"live", "free"} <= set(pool[0]["args"])
    assert "prefill_pending" in queue[0]["args"]


def test_engine_run_no_capture_no_report(serve_cfg, serve_params):
    reg = obs_metrics.Registry()
    eng = ServeEngine(serve_cfg, serve_params, slots=2, max_len=64,
                      page_size=PAGE, metrics=reg)
    eng.run(_reqs())
    assert eng.last_cost_report is None
    assert "serve_cost_flops_total" not in reg.snapshot()


def test_cost_counter_track_via_default_tracer(serve_cfg, serve_params,
                                               capture):
    # the cumulative cost/<fn> track goes to the PROCESS tracer (same
    # routing as the jit/compile instants deep call sites use)
    trc = obs_trace.Tracer(enabled=True)
    prev = obs_trace.set_tracer(trc)
    try:
        eng = ServeEngine(serve_cfg, serve_params, slots=2, max_len=64,
                          page_size=PAGE,
                          metrics=obs_metrics.Registry())
        eng.run(_reqs())
    finally:
        obs_trace.set_tracer(prev)
    # solo-lane rounds emit on their own cost/solo_step track; every
    # round lands on exactly one of the two
    cost_tracks = [e for e in trc.events if e["ph"] == "C"
                   and e["name"] in ("cost/step", "cost/solo_step")]
    rows = [r for r in eng.last_cost_report.fns
            if r.fn in ("step", "solo_step")]
    if any(r.captured for r in rows):      # backend exposes a cost model
        assert len(cost_tracks) == eng.stats.rounds
        for name in ("cost/step", "cost/solo_step"):
            cum = [e["args"]["bytes"] for e in cost_tracks
                   if e["name"] == name]
            assert cum == sorted(cum)      # cumulative, monotonic
    else:
        assert cost_tracks == []           # zero-cost rows emit no track
