"""CPU rehearsal of a whole benchmark run at a tiny size.

The harness's look for a chip is skipped (``allow_cpu``); everything else
runs as on the chip: PTQ and its cache, the engine through the CLI's
entry points, warm-up, the closed-loop window, the metrics and the check
against the plain reference. The control (the reference at float8) and
faults planted in the timed path must make ``correct`` come out false.
"""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import benchroot  # noqa: E402
import control  # noqa: E402
from benchlib import harness  # noqa: E402

# the tiny cell's limit: above the program's CPU readings (float32
# throughout: 0.0 on 12 seeds) and below the float8 control's (0.0495 to
# 0.475 on the same 12 seeds, 0.3 s windows)
TINY_LIMIT = 0.01
SEED = 2 ** 31 + 12345


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchroot.make_root(tmp_path_factory.mktemp("root"),
                               limit=TINY_LIMIT)


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return str(tmp_path_factory.mktemp("cache"))


def _run(root, cache, trace=0, seed=SEED):
    opts = harness.parse(["--workload", "tiny.tiny", "--seed", str(seed),
                          "--seconds", "0.5", "--trace", str(trace)])
    return harness.run(opts, 0.0, root=root, cache_dir=cache,
                       allow_cpu=True, persistent_cache=False)


@pytest.fixture
def fresh_steps():
    """Serving jits traced anew around a planted fault."""
    from repro.serve import steps
    steps._single_device_steps.cache_clear()
    yield
    steps._single_device_steps.cache_clear()


def test_measurement_path_refuses_the_cpu(capsys):
    rc = harness.main(["--workload", "stablelm-1.6b.chat-b1", "--seed",
                       "1", "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_cpu_rehearsal_is_correct(root, cache):
    res = _run(root, cache)
    assert res["device"]["platform"] == "cpu"
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"output_tok_s", "ttft_p95_ms",
                                   "itl_p95_ms", "setup_s"}
    assert list(res)[-1] == "checks"
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] <= gap["limit"] == TINY_LIMIT
    # the second run loads the quantized tree from the cache
    assert os.listdir(os.path.join(cache, "weights"))


def test_cpu_rehearsal_traced_reports_host_metrics(root, cache):
    res = _run(root, cache, trace=1, seed=SEED + 1)
    assert res["correct"] is True
    # no device plane on the CPU: device-trace metrics and peak-based
    # shares are left out, the program's spans and counters stay
    cell = harness.load_cell("tiny.tiny", root)
    host = {m["name"] for m in cell.per_layer
            if m["source"] in ("program_span", "program_counter")}
    assert host and set(res["metrics"]) == host
    for m in res["metrics"].values():
        assert m["value"] > 0


def test_control_fails_the_limit(root, cache):
    rows = control.readings("tiny.tiny", [3, 4, 2 ** 31 + 9], 0.3, 0.0,
                            root=root, cache_dir=cache, allow_cpu=True,
                            persistent_cache=False)
    for row in rows:
        assert row["failed"] == 0
        assert row["program"] <= TINY_LIMIT < row[control.CONTROL]


def test_altered_token_is_not_correct(root, cache, fresh_steps,
                                      monkeypatch):
    from repro.serve import sampling
    real = sampling.select_tokens

    def shifted(logits, *a):
        tok, logp = real(logits, *a)
        vocab = logits.shape[-1]
        return (tok >= 0) * ((tok + 1) % vocab) + (tok < 0) * tok, logp

    monkeypatch.setattr(sampling, "select_tokens", shifted)
    res = _run(root, cache, seed=SEED + 2)
    assert res["correct"] is False
    assert res["checks"]["max_logit_gap"]["value"] > TINY_LIMIT


def test_unwritten_kv_state_is_not_correct(root, cache, fresh_steps,
                                           monkeypatch):
    from repro.models import attention

    def unchanged(cache_in, k, v, positions, valid_len=None):
        return cache_in

    monkeypatch.setattr(attention, "paged_cache_write", unchanged)
    res = _run(root, cache, seed=SEED + 3)
    assert res["correct"] is False
