"""The reader of the weight plan's bytes, on synthetic runs: by hand, and
None on a program that keeps no such counter (so a traced run of an older
program leaves it out)."""
import json
import os
import sys
from types import SimpleNamespace as NS

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchlib import harness  # noqa: E402


def _view(*stats):
    """A RunView holding one ``run()`` call per stats object."""
    return harness.RunView(
        records=[harness.CallRecord(0.0, 1.0, [], s) for s in stats],
        window_s=1.0, slots=1, page=16, model={}, arch=None, weights={},
        kv_dtype="bfloat16", peaks=None, trace=None)


def test_exec_weight_gb_by_hand():
    # the engine records its plan on every run() call: the calls agree
    read = harness.metric_reader("exec_weight_gb")
    plan = 1_440_000_000 + 4_200_000
    view = _view(NS(rounds=3, exec_weight_bytes=plan),
                 NS(rounds=2, exec_weight_bytes=plan))
    assert read(view) == pytest.approx(1.4442)


def test_exec_weight_gb_reads_nothing_from_a_program_without_it():
    read = harness.metric_reader("exec_weight_gb")
    assert read(_view()) is None
    old = NS(rounds=5, positions_computed=5, positions_useful=5)
    assert read(_view(old)) is None


def test_exec_weight_gb_is_declared_as_read():
    repo = os.path.dirname(os.path.dirname(HERE))
    with open(os.path.join(repo, "BENCHMARK.json")) as f:
        bench = json.load(f)
    m = {m["name"]: m for m in bench["per_layer"]}["exec_weight_gb"]
    assert (m["unit"], m["source"], m["moves"], m["better"], m["layer"]) == \
        ("GB", "program_counter", "output_tok_s", "lower",
         "weights and kernels (core/serving_quant.py, kernels/)")
    assert "workloads" not in m
