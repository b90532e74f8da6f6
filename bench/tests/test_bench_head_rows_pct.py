"""The reader of the head's share of the computed positions, on synthetic
runs: by hand, and None on a program that keeps no such counter (so a
traced run of an older program leaves it out)."""
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


def test_head_rows_pct_by_hand():
    # one request: a 1,024-wide prefill (one head row) and 99 decode
    # steps; then a 512-wide prefill and 49 decode steps
    read = harness.metric_reader("head_rows_pct")
    view = _view(NS(positions_computed=1024 + 99, head_rows=1 + 99),
                 NS(positions_computed=512 + 49, head_rows=1 + 49))
    assert read(view) == pytest.approx(100.0 * 150 / 1684)


def test_head_rows_pct_reads_nothing_from_a_program_without_it():
    read = harness.metric_reader("head_rows_pct")
    assert read(_view()) is None
    old = NS(rounds=5, positions_computed=1028, positions_useful=1028)
    assert read(_view(old)) is None


def test_head_rows_pct_is_declared_as_read():
    repo = os.path.dirname(os.path.dirname(HERE))
    with open(os.path.join(repo, "BENCHMARK.json")) as f:
        bench = json.load(f)
    m = {m["name"]: m for m in bench["per_layer"]}["head_rows_pct"]
    assert (m["unit"], m["source"], m["moves"], m["better"], m["layer"]) == \
        ("%", "program_counter", "ttft_p95_ms", "lower",
         "serving step (serve/steps.py)")
    assert "workloads" not in m
