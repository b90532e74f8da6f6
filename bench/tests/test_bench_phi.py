"""Phi-1.5 in the benchmark: its reference (``bench/references/phi.py``)
decides ``correct`` in a CPU rehearsal of the reduced Phi preset, the
float8 control and faults planted in that reference fail it, and its
operation and byte counts read their hand-counted values at the published
sizes."""
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import benchroot  # noqa: E402
import control  # noqa: E402
from benchlib import counts, harness  # noqa: E402

# the tiny cells' limit (test_bench_rehearsal.py): above the program's CPU
# readings on Phi (float32 throughout: 0.0 on 12 seeds) and below the
# float8 control's (0.116 to 0.367 on the same 12 seeds). The mix
# (data/phi-tiny-mix.json) checks six requests of 10-24 output tokens, about
# 100 tokens a window: the reduced Phi's argmax moves less under float8
# than StableLM's, and the tiny mix's ~20 tokens left the control at 0.0
# on some windows
TINY_LIMIT = 0.01
SEED = 2 ** 31 + 34567

# faults planted in a copy of phi.py: (line as written, line planted)
HEAD_BIAS_LEFT_OUT = (
    '    return _mm(xn, head["w"], matmul) + head["b"]\n',
    '    return _mm(xn, head["w"], matmul)\n')
SEQUENTIAL_BLOCK = (
    '    m = _mlp(h, w, matmul)\n',
    '    m = _mlp(_layernorm(x + a, 1.0 + w["norm1"], w["norm1_bias"], eps),'
    ' w, matmul)\n')


def phi_root(tmp, plant=None):
    """A checkout whose one tiny cell runs the reduced Phi preset on the
    Phi test mix, with ``plant`` made in its copy of ``phi.py``."""
    root = benchroot.make_root(tmp, limit=TINY_LIMIT)
    shutil.copy(os.path.join(HERE, "data", "phi-tiny.json"),
                os.path.join(root, "bench", "configs", "tiny.json"))
    shutil.copy(os.path.join(HERE, "data", "phi-tiny-mix.json"),
                os.path.join(root, "bench", "traffic", "tiny.json"))
    if plant is not None:
        path = os.path.join(root, "bench", "references", "phi.py")
        with open(path) as f:
            source = f.read()
        assert source.count(plant[0]) == 1
        with open(path, "w") as f:
            f.write(source.replace(*plant))
    return root


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return str(tmp_path_factory.mktemp("cache"))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return phi_root(tmp_path_factory.mktemp("root"))


def _run(root, cache, seed):
    opts = harness.parse(["--workload", "tiny.tiny", "--seed", str(seed),
                          "--seconds", "0.5", "--trace", "0"])
    return harness.run(opts, 0.0, root=root, cache_dir=cache,
                       allow_cpu=True, persistent_cache=False)


def test_phi_rehearsal_is_correct(root, cache):
    cell = harness.load_cell("tiny.tiny", root)
    assert cell.config["arch"] == "phi-1.5b"
    assert os.path.basename(cell.arch.__file__) == "phi.py"
    res = _run(root, cache, SEED)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] <= gap["limit"] == TINY_LIMIT


def test_phi_control_fails_the_limit(root, cache):
    rows = control.readings("tiny.tiny", [5, 6, 2 ** 31 + 11], 0.3, 0.0,
                            root=root, cache_dir=cache, allow_cpu=True,
                            persistent_cache=False)
    for row in rows:
        assert row["failed"] == 0
        assert row["program"] <= TINY_LIMIT < row[control.CONTROL]


@pytest.mark.parametrize("plant", [HEAD_BIAS_LEFT_OUT, SEQUENTIAL_BLOCK],
                         ids=["head_bias_left_out", "sequential_block"])
def test_planted_phi_reference_is_not_correct(tmp_path, cache, plant):
    res = _run(phi_root(tmp_path / "root", plant), cache, SEED + 1)
    assert res["failed"] == 0
    assert res["correct"] is False
    assert res["checks"]["max_logit_gap"]["value"] > TINY_LIMIT


# ---------------------------------------------------------------------------
# counts at the published sizes, by hand
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def published():
    path = os.path.join(BENCH, "configs", "phi-1.5b.json")
    with open(path) as f:
        config = json.load(f)
    return config, harness.reference_module(config, path)


def test_phi_counts_at_published_sizes(published):
    config, phi = published
    m, w = config["model"], config["weights"]
    layer, head = phi.matmuls(m)
    assert layer == [("wq", 2048, 2048, 24), ("wk", 2048, 2048, 24),
                     ("wv", 2048, 2048, 24), ("wo", 2048, 2048, 24),
                     ("w_up", 2048, 8192, 24), ("w_down", 8192, 2048, 24)]
    assert head == (2048, 51200)
    # per layer: LayerNorm gain and bias 2 x 2048, biases of q, k, v and
    # dense 4 x 2048, of fc1 8192 and fc2 2048: 22,528; then the final
    # LayerNorm 4096, the head's bias 51,200 and one embedding row 2048;
    # two bytes each
    assert phi.other_bytes(m, 1, "bfloat16") == \
        2 * (24 * 22_528 + 4096 + 51_200 + 2048) == 1_196_032
    # K and V of 1,024 positions: 24 layers x 2 x 32 heads x 64 x 2 bytes
    assert phi.state_bytes(m, 1, 1024, "bfloat16") == \
        1024 * 24 * 2 * 32 * 64 * 2 == 201_326_592

    # the QMC format, per matrix: codes at 3 bits (inlier subtiles) and 5
    # (30%, rounded, outlier subtiles) of 8 x 128, a tag bit and an int32
    # position a subtile, two float32 scales an output column.
    # 2048 x 2048: 4,096 subtiles, 1,229 outliers:
    #   (2867 x 3 + 1229 x 5) x 128 + 512 + 16,384 + 16,384 = 1,920,768
    # 2048 x 8192: 16,384 subtiles, 4,915 outliers:
    #   (11469 x 3 + 4915 x 5) x 128 + 2048 + 65,536 + 65,536 = 7,682,816
    # 8192 x 2048: the same codes and tags, scales 16,384: 7,633,664
    # head 2048 x 51200: 102,400 subtiles, 30,720 outliers:
    #   (71680 x 3 + 30720 x 5) x 128 + 12,800 + 409,600 + 409,600
    #   = 48,017,920
    weights = 24 * (4 * 1_920_768 + 7_682_816 + 7_633_664) + 48_017_920
    assert weights == 600_007_168
    assert counts.qmc_weight_bytes(phi, m, w) == weights
    step = counts.decode_step(phi, m, w, 1, 1024.0, "bfloat16")
    assert step["bytes"] == weights + 1_196_032 + 201_326_592 \
        == 802_529_792
    # 2 x (24 x (4 x 2048^2 + 2 x 2048 x 8192) + 2048 x 51200) multiply-
    # adds, and QK^T and PV over 1,024 keys: 4 x 1024 x 32 x 64 x 24
    assert step["flops"] == 2 * 1_312_817_152 + 201_326_592 \
        == 2_826_960_896
