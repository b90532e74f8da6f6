"""Unit tests of the benchmark's yardstick: trace reduction, traffic, the
operation and byte counts, the peak table, and discovery by name."""
import json
import os
import sys
from types import SimpleNamespace as NS

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

from benchlib import counts, harness, peaks, trace_reduce, traffic  # noqa: E402

STABLELM_PATH = os.path.join(os.path.dirname(HERE), "configs",
                             "stablelm-1.6b.json")
STABLELM = json.load(open(STABLELM_PATH))
DENSE = harness.reference_module(STABLELM, STABLELM_PATH)


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _trace():
    """Window 0..1000 ns; ops busy 100-300 and 250-400 (union 300 ns) and
    800-900; one op before the window; two step executions."""
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[_ev("fusion.1", 100, 200),
                                   _ev("fusion.2", 250, 150),
                                   _ev("fusion.1", 800, 100),
                                   _ev("copy.3", -500, 100)]),
        NS(name="XLA Modules", events=[_ev("jit_solo(7)", 100, 300),
                                       _ev("jit_solo(7)", 800, 100),
                                       _ev("jit__apply(9)", 950, 10)]),
    ])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench/window", 0, 1000), _ev("bench/call", 0, 1000),
        _ev("other", 0, 1000)])])
    return NS(planes=[host, device])


def test_trace_busy_union_idle_and_program_median():
    s = trace_reduce.reduce(_trace(), [("round/emit", 400, 800)])
    assert s["window_s"] == pytest.approx(1000e-9)
    assert s["busy_s"] == pytest.approx(400e-9)      # 100-400 and 800-900
    assert 1 - s["busy_s"] / s["window_s"] == pytest.approx(0.6)
    assert trace_reduce.median_seconds(s, ["jit_solo"]) == \
        pytest.approx(200e-9)
    assert trace_reduce.median_seconds(s, ["jit_step"]) is None
    # the op before the window is left out of the op table
    assert dict(s["device_ops"]) == pytest.approx(
        {"fusion.1": 300e-9, "fusion.2": 150e-9})
    # gaps: 0-100 (call), 400-800 (emit inside call), 900-1000 (call)
    gaps = {round(sec * 1e9): label for label, sec in s["idle_gaps"]}
    assert gaps == {400: "round/emit", 100: "bench/call"}


def test_trace_without_device_or_window_reads_nothing():
    t = _trace()
    assert trace_reduce.reduce(NS(planes=[t.planes[0]])) is None
    t.planes[0].lines[0].events = t.planes[0].lines[0].events[1:]
    t.planes[0].lines[0].events = [
        e for e in t.planes[0].lines[0].events if e.name != "bench/window"]
    assert trace_reduce.reduce(t) is None


def test_union_and_gaps():
    merged = trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert merged == [[0, 3], [5, 8]]
    assert trace_reduce.gaps(merged, 0, 10) == [(3, 5), (8, 10)]
    assert trace_reduce.program_name("jit_step(123)") == "jit_step"


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(
    os.path.dirname(HERE), "traffic")) if f.endswith(".json"))


def _mix(name):
    return traffic.load_mix(os.path.join(os.path.dirname(HERE), "traffic",
                                         f"{name}.json"))


@pytest.mark.parametrize("name", MIXES)
def test_traffic_is_deterministic_per_seed(name):
    mix = _mix(name)
    big = 2 ** 33 + 5
    a = [next(traffic.calls(mix, 1000, big)) for _ in range(1)]
    b = [next(traffic.calls(mix, 1000, big)) for _ in range(1)]
    for (pa, oa), (pb, ob) in zip(a[0], b[0]):
        assert oa == ob and (pa == pb).all()
    c = next(traffic.calls(mix, 1000, big + 1))
    assert [len(p) for p, _ in a[0]] != [len(p) for p, _ in c] or \
        any((pa != pc).any() for (pa, _), (pc, _) in zip(a[0], c))


@pytest.mark.parametrize("name", MIXES)
def test_traffic_keeps_lengths_in_clips_and_pool(name):
    mix = _mix(name)
    pool = sorted(traffic.pool_sizes(mix))
    for p, o in pool:
        assert mix["prompt"]["min"] <= p <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= o <= mix["output"]["max"]
    for seed in (0, 7, 2 ** 31 + 3):
        it = traffic.size_stream(mix, seed)
        cycle = sorted(next(it) for _ in range(mix["pool"]))
        assert cycle == pool           # every seed serves the same sizes
    it = traffic.calls(mix, 50, 11)
    for _ in range(3):
        for prompt, _ in next(it):
            assert prompt.dtype.name == "int32"
            assert prompt.min() >= 2 and prompt.max() < 50


@pytest.mark.parametrize("name", MIXES)
def test_traffic_orders_the_pool_afresh_every_cycle(name):
    mix = _mix(name)
    pool = sorted(traffic.pool_sizes(mix))
    it = traffic.size_stream(mix, 2 ** 32 + 17)
    cycles = [[next(it) for _ in range(mix["pool"])] for _ in range(4)]
    for c in cycles:
        assert sorted(c) == pool
    assert len({tuple(c) for c in cycles}) > 1


def test_traffic_mix_refuses_calls_that_straddle_cycles(tmp_path):
    mix = dict(json.load(open(os.path.join(HERE, "data", "tiny-mix.json"))),
               requests_per_call=4)
    path = tmp_path / "bad.json"
    path.write_text(json.dumps(mix))
    with pytest.raises(ValueError, match="multiple of requests_per_call"):
        traffic.load_mix(str(path))


class _ClockEngine:
    """Stands in for the engine: each ``run`` call takes ``per_call``
    seconds of a fake clock."""

    def __init__(self, clock, per_call):
        self.clock, self.per_call, self.stats = clock, per_call, None

    def run(self, reqs):
        self.clock[0] += self.per_call


@pytest.mark.parametrize("seconds,cycles",
                         [(0.1, 1), (5.9, 2), (7.4, 2), (7.6, 3)])
def test_window_ends_with_the_whole_cycle_nearest_seconds(
        monkeypatch, seconds, cycles):
    # tiny mix: 6 sizes, 3 requests a call, so 2 calls of 1.5 s a cycle
    mix = traffic.load_mix(os.path.join(HERE, "data", "tiny-mix.json"))
    clock = [100.0]
    monkeypatch.setattr(harness, "time", NS(monotonic=lambda: clock[0]))
    records = harness.run_window(_ClockEngine(clock, 1.5), NS(mix=mix),
                                 2 ** 31 + 5, seconds, 50)
    assert len(records) == 2 * cycles
    served = sorted((len(r.prompt), r.max_new_tokens)
                    for rec in records for r in rec.requests)
    assert served == sorted(traffic.pool_sizes(mix) * cycles)


def test_window_parts_by_hand():
    # two calls of one request each; the second request stalls once
    reqs = [NS(uid=0), NS(uid=1)]
    stats = NS(emit_times={0: [1.2, 1.3, 1.4], 1: [2.5, 2.6, 3.6, 3.7]})
    records = [harness.CallRecord(1.0, 1.5, [reqs[0]], stats),
               harness.CallRecord(2.0, 3.75, [reqs[1]], stats)]
    parts = harness.window_parts(records)
    assert parts["first_s"] == pytest.approx(0.2 + 0.5)
    assert parts["gaps_s"] == pytest.approx(0.2 + 1.2)
    assert parts["gap_median_ms"] == pytest.approx(100.0)
    assert parts["gap_excess_s"] == pytest.approx(1.0 - 0.2)
    assert parts["tails_s"] == pytest.approx(0.1 + 0.05)
    assert parts["between_s"] == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# operations and bytes (StableLM-2-1.6B by hand)
# ---------------------------------------------------------------------------


def test_avg_bits_and_matrix_bytes():
    w = STABLELM["weights"]
    assert counts.avg_bits(w) == pytest.approx(0.7 * 3 + 0.3 * 5)
    assert counts.avg_bits(w) == pytest.approx(3.6)
    # 2048x2048: 4096 subtiles, round(0.3*4096)=1229 outliers, 2867 inliers
    codes = (2867 * 3 + 1229 * 5) * 1024 / 8
    meta = 4096 / 8 + 4096 * 4
    scales = 2 * 2048 * 4
    assert counts.qmc_matrix_bytes(2048, 2048, w) == codes + meta + scales


def test_stablelm_params_flops_and_bytes():
    m, w = STABLELM["model"], STABLELM["weights"]
    p = counts.matmul_params(DENSE, m)
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert p["layers"] == 24 * per_layer == 1_233_125_376
    assert p["head"] == 2048 * 100352 == 205_520_896
    qbytes = counts.qmc_weight_bytes(DENSE, m, w)
    # 3.6 bits a weight plus ~0.06 bits of tags, positions and scales
    assert qbytes == pytest.approx((p["layers"] + p["head"]) * 3.66 / 8,
                                   rel=0.002)
    step = counts.decode_step(DENSE, m, w, batch=1, ctx_tokens=512,
                              kv_dtype="bfloat16")
    kv = 512 * 24 * 2 * 2048 * 2
    assert step["bytes"] == pytest.approx(
        qbytes + kv + (24 * (2 * 2048 + 3 * 2048) + 2048 + 2048) * 2)
    assert step["flops"] == 2 * (p["layers"] + p["head"]) \
        + 4 * 512 * 2048 * 24
    least, bound = counts.least_time(step["flops"], step["bytes"],
                                     peaks.peaks_for("TPU v5 lite"))
    assert bound == "memory"
    assert least == pytest.approx(step["bytes"] / 819e9)


def test_request_flops_counts_no_padding():
    m = STABLELM["model"]
    p = counts.matmul_params(DENSE, m)
    # prompt 3, 2 outputs: positions 0..3 processed, head at 2 positions
    want = 2 * 4 * p["layers"] + 2 * 2 * p["head"] \
        + 4 * (1 + 2 + 3 + 4) * 2048 * 24
    assert counts.request_flops(DENSE, m, 3, 2) == want


def test_mfu_reader_by_hand():
    m = STABLELM["model"]
    read = harness.metric_reader("step_mfu")
    req = NS(prompt=[0] * 100, out_tokens=[1] * 10)
    view = NS(records=[NS(requests=[req, req])], window_s=2.0, model=m,
              arch=DENSE, peaks=peaks.peaks_for("TPU v5 lite"))
    want = 2 * counts.request_flops(DENSE, m, 100, 10) / 2.0 / 197e12 * 100
    assert read(view) == pytest.approx(want)


def test_peak_table_refuses_unknown_device():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v4")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


# ---------------------------------------------------------------------------
# discovery by name
# ---------------------------------------------------------------------------


def test_new_traffic_and_metric_files_are_found_by_name(tmp_path):
    import benchroot
    extra = {"name": "requests_per_round", "unit": "1", "better": "higher",
             "source": "program_counter", "layer": "test",
             "moves": "output_tok_s"}
    root = benchroot.make_root(tmp_path, per_layer=[extra])
    with open(os.path.join(root, "bench", "metrics",
                           "requests_per_round.py"), "w") as f:
        f.write("def read(run):\n"
                "    return sum(len(r.requests) for r in run.records) / "
                "sum(r.stats.rounds for r in run.records)\n")
    cell = harness.load_cell("tiny.tiny", root)
    assert cell.mix["slots"] == 2 and cell.config["name"] == "tiny"
    view = NS(records=[NS(requests=[1, 2, 3], stats=NS(rounds=6))])
    out = harness.per_layer(cell, view, root)
    assert out == {"requests_per_round": {"value": 0.5, "unit": "1"}}


def test_benchmark_json_names_every_file():
    repo = os.path.dirname(os.path.dirname(HERE))
    bench = json.load(open(os.path.join(repo, "BENCHMARK.json")))
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer
        assert 0 < cell.limits["max_logit_gap"]
    for m in bench["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))
