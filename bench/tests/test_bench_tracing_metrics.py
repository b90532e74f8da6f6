"""The readers of the step's parts and the position counters, on
synthetic runs: by hand, None without rounds, and None on a program that
keeps neither (so a traced run of an older program leaves them out)."""
import os
import sys
from types import SimpleNamespace as NS

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchlib import harness  # noqa: E402

NAMES = ("launch_ms_per_round", "readback_ms_per_round", "step_pad_pct")


def _stats(rounds, parts, computed, useful):
    return NS(rounds=rounds, step_part_seconds=parts,
              positions_computed=computed, positions_useful=useful)


def _view(*stats):
    """A RunView holding one ``run()`` call per stats object."""
    return harness.RunView(
        records=[harness.CallRecord(0.0, 1.0, [], s) for s in stats],
        window_s=1.0, slots=1, page=16, model={}, arch=None, weights={},
        kv_dtype="bfloat16", peaks=None, trace=None)


def _two_calls():
    a = _stats(3, {"round/step/upload": 0.003, "round/step/launch": 0.006,
                   "round/step/wait": 0.040,
                   "round/step/readback": 0.0015},
               computed=512 + 2, useful=402 + 2)
    b = _stats(2, {"round/step/upload": 0.001, "round/step/launch": 0.002,
                   "round/step/wait": 0.030,
                   "round/step/readback": 0.0005},
               computed=1024 + 1, useful=1024 + 1)
    return _view(a, b)


def test_launch_ms_per_round_by_hand():
    read = harness.metric_reader("launch_ms_per_round")
    # (upload + launch) over both calls, over 5 rounds; wait is left out
    assert read(_two_calls()) == pytest.approx(
        (0.003 + 0.006 + 0.001 + 0.002) / 5 * 1e3)


def test_readback_ms_per_round_by_hand():
    read = harness.metric_reader("readback_ms_per_round")
    assert read(_two_calls()) == pytest.approx((0.0015 + 0.0005) / 5 * 1e3)


def test_step_pad_pct_by_hand():
    read = harness.metric_reader("step_pad_pct")
    computed, useful = 514 + 1025, 404 + 1025
    assert read(_two_calls()) == pytest.approx(
        100 * (1 - useful / computed))


def test_step_pad_pct_is_zero_without_padding():
    read = harness.metric_reader("step_pad_pct")
    assert read(_view(_stats(4, {}, 4, 4))) == 0.0


@pytest.mark.parametrize("name", NAMES)
def test_readers_read_nothing_without_rounds(name):
    read = harness.metric_reader(name)
    assert read(_view()) is None
    assert read(_view(_stats(0, {}, 0, 0))) is None


@pytest.mark.parametrize("name", NAMES)
def test_readers_read_nothing_from_a_program_without_the_counters(name):
    # an older program's EngineStats: rounds, but no parts or positions
    read = harness.metric_reader(name)
    old = NS(rounds=5, phase_seconds={"round/device_step": 0.1})
    assert read(_view(old)) is None


def test_new_metrics_are_declared_as_read():
    import json
    repo = os.path.dirname(os.path.dirname(HERE))
    bench = json.load(open(os.path.join(repo, "BENCHMARK.json")))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    want = {"launch_ms_per_round": ("ms", "program_span", "itl_p95_ms"),
            "readback_ms_per_round": ("ms", "program_span", "itl_p95_ms"),
            "step_pad_pct": ("%", "program_counter", "ttft_p95_ms")}
    for name, (unit, source, moves) in want.items():
        m = by_name[name]
        assert (m["unit"], m["source"], m["moves"], m["better"]) == \
            (unit, source, moves, "lower")
