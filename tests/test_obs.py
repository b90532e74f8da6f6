"""Observability subsystem: metrics registry + span tracer mechanics, and
the engine integration invariants the obs contract promises —

  * token conservation: ``tokens_out == sum(step_tokens) -
    tokens_discarded`` on every run, preemptions included;
  * exactly ONE ``req/first_token`` instant per emitting request, even
    across preemption/recompute;
  * round phase spans are non-overlapping per thread and nested inside
    their round's umbrella span;
  * the exported Chrome trace parses and carries the schema Perfetto
    needs (name/ph/ts/pid/tid, dur on "X" events);
  * the four ``round/step/*`` parts tile the synchronous step and nest
    in the pipelined phases, without entering host/device seconds;
  * position counters match a hand count; collector spans and profiler
    annotations exist only under an enabled tracer;
  * page-op counters (adopt / page_copy / tables_rebuild) land in both
    ``EngineStats`` and ``serve_page_ops_total``;
  * TracedJit attributes compiles to the cold engine only and flags
    cache growth beyond a declared compile surface.
"""
import collections
import gc
import json

import jax
import numpy as np
import pytest

from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.metrics import Registry, log_buckets
from repro.obs.trace import Tracer
from repro.serve.engine import EngineStats, Request, ServeEngine
from repro.serve.steps import TracedJit

PAGE = 8


# ==========================================================================
# metrics mechanics
# ==========================================================================
def test_counter_inc_value_and_labels():
    reg = Registry()
    c = reg.counter("hits_total", "hits", labels=("kind",))
    c.inc(kind="a")
    c.inc(2, kind="a")
    c.inc(5, kind="b")
    assert c.value(kind="a") == 3
    assert c.value(kind="b") == 5
    assert c.value(kind="never") == 0
    with pytest.raises(ValueError):
        c.inc(-1, kind="a")            # counters only go up
    with pytest.raises(ValueError):
        c.inc(wrong="a")               # undeclared label name


def test_gauge_set_add():
    g = Registry().gauge("pages")
    g.set(4)
    g.add(-1)
    assert g.value() == 3


def test_histogram_buckets_and_sum():
    h = Registry().histogram("lat", buckets=(0.001, 0.01, 0.1))
    for v in (0.0005, 0.005, 0.005, 5.0):
        h.observe(v)
    assert h.count() == 4
    assert h.sum() == pytest.approx(5.0105)
    row = h._series[()]
    assert row[0] == [1, 2, 0, 1]      # last slot = implicit +Inf bucket
    with pytest.raises(ValueError):
        Registry().histogram("bad", buckets=(1.0, 1.0, 2.0))


def test_log_buckets_span():
    b = log_buckets()
    assert b[0] == pytest.approx(1e-6)
    assert b[-1] > 1.0                 # reaches into cold-compile seconds
    assert all(x < y for x, y in zip(b, b[1:]))


def test_registry_get_or_create_and_mismatch():
    reg = Registry()
    c1 = reg.counter("x_total", labels=("k",))
    assert reg.counter("x_total", labels=("k",)) is c1
    with pytest.raises(ValueError):
        reg.counter("x_total", labels=("other",))   # label drift
    with pytest.raises(ValueError):
        reg.gauge("x_total")                        # type drift
    h = reg.histogram("h", buckets=(1.0, 2.0))
    assert reg.histogram("h", buckets=(1.0, 2.0)) is h
    with pytest.raises(ValueError):
        reg.histogram("h", buckets=(1.0, 3.0))      # bucket drift


def test_snapshot_json_roundtrip(tmp_path):
    reg = Registry()
    reg.counter("a_total", labels=("k",)).inc(2, k="x")
    reg.histogram("h", buckets=(1.0,)).observe(0.5)
    path = tmp_path / "m.json"
    reg.write_json(str(path))
    snap = json.loads(path.read_text())
    assert snap["a_total"]["series"] == [
        {"labels": {"k": "x"}, "value": 2}]
    assert snap["h"]["type"] == "histogram"
    assert snap["h"]["series"][0]["count"] == 1


# ==========================================================================
# tracer mechanics
# ==========================================================================
def test_disabled_tracer_is_noop():
    t = Tracer(enabled=False)
    s1 = t.span("a")
    s2 = t.span("b", x=1)
    assert s1 is s2                    # shared null span, no allocation
    with s1:
        t.instant("i", u=1)
        t.counter("c", v=2)
    assert t.events == []


def test_chrome_trace_schema(tmp_path):
    t = Tracer()
    with t.span("outer", tag="o"):
        with t.span("inner"):
            pass
        t.instant("point", uid=3)
    t.counter("pages", used=4)
    for ev in t.events:
        assert {"name", "ph", "ts", "pid"} <= set(ev)
        if ev["ph"] == "X":
            assert ev["dur"] >= 0 and "tid" in ev
        if ev["ph"] == "i":
            assert ev["s"] == "t" and "tid" in ev
    path = tmp_path / "t.json"
    n = t.export(str(path))
    doc = json.loads(path.read_text())
    assert len(doc["traceEvents"]) == n == 4
    assert doc["displayTimeUnit"] == "ms"


def test_span_nesting():
    t = Tracer()
    with t.span("outer"):
        with t.span("inner"):
            pass
    inner = next(e for e in t.events if e["name"] == "inner")
    outer = next(e for e in t.events if e["name"] == "outer")
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: records the names
    entered and exited."""

    def __init__(self):
        self.opened, self.closed = [], []

    def __call__(self, name):
        rec = self

        class Ann:
            def __enter__(self):
                rec.opened.append(name)

            def __exit__(self, *exc):
                rec.closed.append(name)

        return Ann()


def test_enabled_tracer_opens_one_annotation_per_span(monkeypatch):
    ann = _Annotations()
    monkeypatch.setattr(obs_trace, "_annotation", ann)
    t = Tracer()
    with t.span("outer"):
        with t.span("inner", k=1):
            pass
        t.instant("point")
        t.counter("c", v=1)
    t.complete("measured", 0.0, 0.0)   # after the fact: nothing to hold
    assert ann.opened == ["outer", "inner"]
    assert ann.closed == ["inner", "outer"]
    with t.annotation("timed"):        # the caller times and records it
        pass
    assert ann.opened == ["outer", "inner", "timed"]
    off = Tracer(enabled=False)
    with off.span("x"), off.annotation("y"):
        pass
    assert ann.opened == ["outer", "inner", "timed"]


def test_tracer_collector_spans():
    before = list(gc.callbacks)
    t = Tracer()
    with t.collector_spans():
        assert len(gc.callbacks) == len(before) + 1
        gc.collect()
    assert gc.callbacks == before
    ev = [e for e in t.events if e["name"] == "round/gc"]
    assert len(ev) == 1 and ev[0]["ph"] == "X"
    assert ev[0]["args"]["generation"] == 2
    assert ev[0]["args"]["collected"] >= 0
    off = Tracer(enabled=False)
    with off.collector_spans():
        assert gc.callbacks == before
        gc.collect()
    assert off.events == []


def test_default_tracer_swap():
    assert not obs_trace.get_tracer().enabled   # process default is off
    mine = Tracer()
    prev = obs_trace.set_tracer(mine)
    try:
        assert obs_trace.active(None) is mine
        other = Tracer(enabled=False)
        assert obs_trace.active(other) is other
    finally:
        obs_trace.set_tracer(prev)


# ==========================================================================
# engine integration
# ==========================================================================
def _reqs(n=6, seed=3, vocab=64, max_new=6, lo=4, hi=20):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(2, vocab, int(L)).astype(np.int32),
                    max_new_tokens=max_new)
            for i, L in enumerate(rng.integers(lo, hi, size=n))]


def _is_phase(name):
    """A round phase: a ``round/`` span that is neither a step part
    nested inside a phase nor a collector span."""
    return (name.startswith("round/") and name != "round/gc"
            and not name.startswith("round/step/"))


def _check_phase_spans(events):
    """Round phase spans must not overlap within a thread, and must sit
    inside their round's umbrella span."""
    phases = [e for e in events if e["ph"] == "X" and _is_phase(e["name"])]
    rounds = [e for e in events if e["ph"] == "X" and e["name"] == "round"]
    assert phases and rounds
    by_tid = {}
    for e in phases:
        by_tid.setdefault(e["tid"], []).append(e)
    for evs in by_tid.values():
        evs.sort(key=lambda e: e["ts"])
        for a, b in zip(evs, evs[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + 1.0, \
                f"{a['name']} overlaps {b['name']}"
    # an aborted round (everything preempted/idled) records admit/grant
    # spans but no umbrella — containment is only promised for the phases
    # that imply the round completed
    for e in phases:
        if e["name"] in ("round/host_prep", "round/device_step",
                         "round/emit"):
            assert any(r["ts"] - 1.0 <= e["ts"] and
                       e["ts"] + e["dur"] <= r["ts"] + r["dur"] + 1.0
                       for r in rounds), f"{e['name']} outside any round"


def test_engine_trace_and_conservation(serve_cfg, serve_params, tmp_path):
    tracer = Tracer()
    reg = Registry()
    eng = ServeEngine(serve_cfg, serve_params, slots=2, max_len=32,
                      page_size=PAGE, tracer=tracer, metrics=reg)
    # 2 slots x small pool, 6 requests: rounds interleave admits/finishes
    out = eng.run(_reqs())
    s = eng.stats
    assert all(r.done for r in out)
    # token conservation: every emitted token is either delivered or
    # accounted as discarded by a preemption
    assert s.tokens_out == sum(s.step_tokens) - s.tokens_discarded
    assert s.tokens_out == sum(len(r.out_tokens) for r in out)
    # exactly one first_token instant per emitting request
    firsts = [e["args"]["uid"] for e in tracer.events
              if e["name"] == "req/first_token"]
    emitting = {r.uid for r in out if r.out_tokens}
    assert sorted(firsts) == sorted(emitting)
    # every admission got an instant; finishes cover every request
    admitted = [e for e in tracer.events if e["name"] == "req/admitted"]
    finished = {e["args"]["uid"] for e in tracer.events
                if e["name"] == "req/finished"}
    assert len(admitted) >= len(out)
    assert finished == {r.uid for r in out}
    _check_phase_spans(tracer.events)
    # phase accounting mirrors the trace (both sides of the same clock)
    assert set(s.phase_seconds) >= {"round/admit", "round/host_prep",
                                    "round/device_step", "round/emit"}
    assert s.rounds == sum(1 for e in tracer.events
                           if e["ph"] == "X" and e["name"] == "round"
                           and "c_len" in e.get("args", {}))
    assert s.host_seconds() > 0 and s.device_seconds() > 0
    # exported file is valid Chrome trace JSON
    path = tmp_path / "trace.json"
    n = tracer.export(str(path))
    doc = json.loads(path.read_text())
    assert len(doc["traceEvents"]) == n > 0
    # metrics flushed: registry totals equal the stats the run reported
    assert reg.counter("serve_rounds_total").value() == s.rounds
    assert reg.counter("serve_tokens_total", labels=("kind",)) \
              .value(kind="emitted") == s.tokens_out
    hist = reg.histogram("serve_phase_seconds", labels=("phase",))
    assert hist.count(phase="round/device_step") == s.rounds


def test_engine_itl_from_emission_timestamps(serve_cfg, serve_params):
    eng = ServeEngine(serve_cfg, serve_params, slots=2, max_len=32,
                      page_size=PAGE, metrics=Registry())
    out = eng.run(_reqs(n=3, max_new=5))
    s = eng.stats
    gaps = s.itl_s()
    # each surviving request contributes len(times) - 1 gaps
    want = sum(max(0, len(t) - 1) for t in s.emit_times.values())
    assert len(gaps) == want > 0
    assert all(g >= 0 for g in gaps)
    # no preemption here: emission timestamps cover every delivered token
    assert s.tokens_discarded == 0
    assert sum(len(t) for t in s.emit_times.values()) == \
        sum(len(r.out_tokens) for r in out)


def test_engine_page_op_counters(serve_cfg, serve_params):
    """Shared-prefix tenants: adopts, COW page copies and table rebuilds
    all fire, land in EngineStats AND in serve_page_ops_total."""
    rng = np.random.default_rng(5)
    sys_prompt = rng.integers(2, 64, 2 * PAGE)      # two full shared pages
    reqs = [Request(uid=i,
                    prompt=np.concatenate(
                        [sys_prompt, rng.integers(2, 64, 4)]
                    ).astype(np.int32),
                    max_new_tokens=3)
            for i in range(4)]
    # whole-prompt page-aligned hits: the recomputed final token's KV
    # write COWs the shared page -> page_copy dispatches must fire
    reqs += [Request(uid=4 + i, prompt=sys_prompt.astype(np.int32),
                     max_new_tokens=3) for i in range(2)]
    reg = Registry()
    eng = ServeEngine(serve_cfg, serve_params, slots=2, max_len=32,
                      page_size=PAGE, prefix_cache=True, metrics=reg)
    eng.run(reqs)
    s = eng.stats
    assert s.adopt_calls > 0                  # later tenants adopted pages
    assert s.page_copy_calls == s.cow_copies > 0
    assert s.device_tables_rebuilds > 0
    ops = reg.counter("serve_page_ops_total", labels=("op",))
    assert ops.value(op="adopt") == s.adopt_calls
    assert ops.value(op="page_copy") == s.page_copy_calls
    assert ops.value(op="tables_rebuild") == s.device_tables_rebuilds
    adm = reg.counter("serve_admissions_total", labels=("kind",))
    assert adm.value(kind="hit") == s.cache_hits
    assert adm.value(kind="miss") >= 1


STEP_PARTS = ("round/step/upload", "round/step/launch", "round/step/wait",
              "round/step/readback")


def _xs(events, name):
    return [e for e in events if e["ph"] == "X" and e["name"] == name]


def _inside(inner, outer, slack=1.0):
    return (outer["ts"] - slack <= inner["ts"] and inner["ts"]
            + inner["dur"] <= outer["ts"] + outer["dur"] + slack)


def _parts_in(events, outer):
    parts = [e for e in events if e["ph"] == "X"
             and e["name"] in STEP_PARTS and _inside(e, outer)]
    return sorted(parts, key=lambda e: e["ts"])


def test_step_parts_tile_the_sync_device_step(serve_cfg, serve_params):
    tracer = Tracer()
    eng = ServeEngine(serve_cfg, serve_params, slots=2, max_len=32,
                      page_size=PAGE, tracer=tracer, metrics=Registry())
    eng.run(_reqs())
    steps = _xs(tracer.events, "round/device_step")
    assert len(steps) == eng.stats.rounds
    gcs = _xs(tracer.events, "round/gc")
    for step in steps:
        parts = _parts_in(tracer.events, step)
        assert [p["name"] for p in parts] == list(STEP_PARTS)
        for a, b in zip(parts, parts[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + 1.0
        # the upload starts with the step; launch follows the upload and
        # readback the wait on the same clock readings
        upload, launch, wait, readback = parts
        assert upload["ts"] == step["ts"]
        for a, b in ((upload, launch), (wait, readback)):
            assert a["ts"] + a["dur"] == pytest.approx(b["ts"], abs=1e-3)
        # a collection that starts between two parts is a span of its
        # own; durations in microseconds: the parts and such collections
        # leave under 1 ms of the step uncovered
        between = sum(g["dur"] for g in gcs if _inside(g, step)
                      and not any(_inside(g, p) for p in parts))
        uncovered = step["dur"] - sum(p["dur"] for p in parts) - between
        assert 0 <= uncovered + 1.0 and uncovered < 1e3
    launches = _xs(tracer.events, "round/step/launch")
    assert {(e["args"]["rows"], e["args"]["solo"]) for e in launches} \
        <= {(1, True), (2, False)}
    s = eng.stats
    assert set(s.step_part_seconds) == set(STEP_PARTS)
    assert sum(s.step_part_seconds.values()) <= \
        s.phase_seconds["round/device_step"]


def test_step_parts_nest_in_pipelined_phases(serve_cfg, serve_params):
    tracer = Tracer()
    eng = ServeEngine(serve_cfg, serve_params, slots=2, max_len=48,
                      page_size=PAGE, pipelined=True, tracer=tracer,
                      metrics=Registry())
    eng.run(_reqs(n=4, max_new=10))
    assert eng.stats.pipelined_rounds > 0
    ev = tracer.events
    dispatches = _xs(ev, "round/dispatch")
    retires = _xs(ev, "round/retire")
    assert dispatches and retires
    for d in dispatches:
        assert [p["name"] for p in _parts_in(ev, d)] == list(STEP_PARTS[:2])
    for r in retires:
        assert [p["name"] for p in _parts_in(ev, r)] == list(STEP_PARTS[2:])
    holders = dispatches + retires + _xs(ev, "round/device_step")
    for name in STEP_PARTS:
        parts = _xs(ev, name)
        assert len(parts) == eng.stats.rounds
        assert all(any(_inside(p, h) for h in holders) for p in parts)


def test_step_parts_leave_host_and_device_seconds(serve_cfg, serve_params):
    tracer = Tracer()
    eng = ServeEngine(serve_cfg, serve_params, slots=2, max_len=32,
                      page_size=PAGE, tracer=tracer, metrics=Registry())
    eng.run(_reqs())
    s = eng.stats
    assert not set(s.step_part_seconds) & set(s.phase_seconds)
    assert all(_is_phase(k) for k in s.phase_seconds)
    device = EngineStats._DEVICE_PHASES
    assert s.host_seconds() == sum(v for k, v in s.phase_seconds.items()
                                   if k not in device)
    assert s.device_seconds() == sum(s.phase_seconds.get(k, 0.0)
                                     for k in device)
    # the trace's phase spans come from the same clock readings as the
    # stats: each sum agrees to rounding
    phases = [e for e in tracer.events
              if e["ph"] == "X" and _is_phase(e["name"])]
    host = sum(e["dur"] for e in phases if e["name"] not in device)
    dev = sum(e["dur"] for e in phases if e["name"] in device)
    assert s.host_seconds() == pytest.approx(host * 1e-6, rel=1e-9)
    assert s.device_seconds() == pytest.approx(dev * 1e-6, rel=1e-9)


def test_positions_counted_by_hand(serve_cfg, serve_params):
    """Prompts of 5 and 11 tokens (off the 4/8/16/32 rungs) prefill
    together at C = 16 on both rows; one decode round of both lanes,
    then two solo rounds of the longer request."""
    tracer = Tracer()
    eng = ServeEngine(serve_cfg, serve_params, slots=2, max_len=32,
                      page_size=PAGE, tracer=tracer, metrics=Registry())
    rng = np.random.default_rng(7)
    reqs = [Request(uid=0, prompt=rng.integers(2, 64, 5).astype(np.int32),
                    max_new_tokens=2),
            Request(uid=1, prompt=rng.integers(2, 64, 11).astype(np.int32),
                    max_new_tokens=4)]
    eng.run(reqs)
    s = eng.stats
    assert s.rounds == 4 and s.solo_rounds == 2
    assert s.positions_computed == 2 * 16 + 2 * 1 + 1 + 1
    assert s.positions_useful == (5 + 11) + 2 + 1 + 1
    rounds = [e["args"] for e in _xs(tracer.events, "round")
              if "c_len" in e.get("args", {})]
    assert [(a["c_len"], a["positions"], a["useful"]) for a in rounds] \
        == [(16, 32, 16), (1, 2, 2), (1, 1, 1), (1, 1, 1)]


def test_collector_spans_only_with_an_enabled_tracer(serve_cfg,
                                                     serve_params):
    before = list(gc.callbacks)
    for enabled in (True, False):
        tracer = Tracer(enabled=enabled)
        eng = ServeEngine(serve_cfg, serve_params, slots=2, max_len=32,
                          page_size=PAGE, tracer=tracer,
                          metrics=Registry())
        hooks = []

        def collect(slot, tok, req):
            hooks.append(len(gc.callbacks))
            gc.collect()

        eng.run(_reqs(n=2, max_new=3), on_token=collect)
        assert gc.callbacks == before
        assert hooks and set(hooks) == {len(before) + enabled}
        spans = _xs(tracer.events, "round/gc")
        if enabled:
            assert len(spans) >= len(hooks)
            assert all({"generation", "collected"} <= set(e["args"])
                       for e in spans)
        else:
            assert tracer.events == []


def test_collector_hook_removed_when_run_raises(serve_cfg, serve_params):
    before = list(gc.callbacks)

    def fail(slot, tok, req):
        raise RuntimeError("stream consumer failed")

    eng = ServeEngine(serve_cfg, serve_params, slots=2, max_len=32,
                      page_size=PAGE, tracer=Tracer(), metrics=Registry())
    with pytest.raises(RuntimeError, match="stream consumer"):
        eng.run(_reqs(n=2), on_token=fail)
    assert gc.callbacks == before


def test_engine_spans_are_profiler_annotations(serve_cfg, serve_params,
                                               monkeypatch):
    ann = _Annotations()
    monkeypatch.setattr(obs_trace, "_annotation", ann)
    tracer = Tracer()
    eng = ServeEngine(serve_cfg, serve_params, slots=2, max_len=32,
                      page_size=PAGE, tracer=tracer, metrics=Registry())
    eng.run(_reqs())
    spans = collections.Counter(e["name"] for e in tracer.events
                                if e["ph"] == "X")
    assert collections.Counter(ann.opened) == spans
    assert collections.Counter(ann.closed) == spans
    assert {"round", "round/device_step", *STEP_PARTS} <= set(spans)
    ann.opened.clear()
    quiet = ServeEngine(serve_cfg, serve_params, slots=2, max_len=32,
                        page_size=PAGE, tracer=Tracer(enabled=False),
                        metrics=Registry())
    quiet.run(_reqs())
    assert ann.opened == []


def test_traced_jit_cold_vs_warm(serve_cfg, serve_params):
    """Cold geometry pays compiles; a second engine on the same (lru-warm)
    geometry observes zero compiles of its own."""
    # slots=3 is unique to this test -> guaranteed-cold jit geometry
    kw = dict(slots=3, max_len=32, page_size=PAGE, chunk_tokens=PAGE)
    cold = ServeEngine(serve_cfg, serve_params, metrics=Registry(), **kw)
    cold.run(_reqs(n=3))
    assert cold.stats.jit_compiles >= 2       # step widths C in {1, chunk}
    assert cold.stats.jit_compile_s > 0
    warm = ServeEngine(serve_cfg, serve_params, metrics=Registry(), **kw)
    warm.run(_reqs(n=3))
    assert warm.stats.jit_compiles == 0
    assert warm.stats.jit_compile_s == 0.0


def test_traced_jit_unexpected_retrace():
    """Cache growth beyond the declared compile surface raises the
    retrace counter and instant — the late-flag-flip bug class."""
    reg = Registry()
    tracer = Tracer()
    prev_reg = obs_metrics.set_registry(reg)
    prev_trc = obs_trace.set_tracer(tracer)
    try:
        tj = TracedJit("probe", jax.jit(lambda x: x * 2),
                       expected_shapes=1)
        tj(np.zeros(4, np.float32))            # expected first shape
        tj(np.zeros(8, np.float32))            # surprise second shape
        assert tj.compiles == 2
        retr = reg.counter("serve_jit_retraces_unexpected_total",
                           labels=("fn",))
        assert retr.value(fn="probe") == 1
        assert reg.counter("serve_jit_compiles_total",
                           labels=("fn",)).value(fn="probe") == 2
        names = [e["name"] for e in tracer.events]
        assert names.count("jit/compile") == 2
        assert names.count("jit/unexpected_retrace") == 1
    finally:
        obs_metrics.set_registry(prev_reg)
        obs_trace.set_tracer(prev_trc)


def test_traced_jit_tolerates_non_jit():
    calls = []
    tj = TracedJit("plain", lambda x: calls.append(x) or x)
    assert tj(3) == 3
    assert tj.calls == 1 and tj.compiles == 0
