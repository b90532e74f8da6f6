"""Each configuration names the module that holds its block's plain
reference and its operation and byte counts (``"reference"`` in the
configuration file, ``bench/references/<name>.py``), and the harness, the
control and the readers go through that module: a configuration with a
block of its own joins the benchmark in new files alone."""
import hashlib
import json
import os
import sys
from types import SimpleNamespace as NS

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import benchroot  # noqa: E402
from benchlib import counts, harness, peaks  # noqa: E402

TINY_LIMIT = 0.01          # the tiny cell's limit (test_bench_rehearsal.py)
SEED = 2 ** 31 + 23456

with open(os.path.join(BENCH, "references", "dense.py")) as f:
    DENSE_SOURCE = f.read()

# dense's block, with every call of the reference interface recorded
COUNTED = DENSE_SOURCE + '''

CALLS = []


def _recorded(name, fn):
    def call(*args, **kw):
        CALLS.append(name)
        return fn(*args, **kw)
    return call


for _name in ("hidden_states", "head", "logits", "matmuls", "other_bytes",
              "state_bytes", "attn_flops", "seq_attn_flops"):
    globals()[_name] = _recorded(_name, globals()[_name])
'''

# dense's block with the MLP's contribution to the residual dropped
MLP_OUT = "    return x + _mm(a, w[\"w_down\"], matmul).reshape(n, L, d)\n"
PLANTED = DENSE_SOURCE.replace(MLP_OUT, "    return x\n")


def _files(root):
    out = {}
    for dirpath, _, names in os.walk(os.path.join(root, "bench")):
        for name in names:
            path = os.path.join(dirpath, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = \
                    hashlib.sha256(f.read()).hexdigest()
    return out


def _add_config(root, name, source):
    """A configuration with its own reference module, in new files and
    ``BENCHMARK.json`` entries only: the tiny model under ``name``, its
    cell ``<name>.tiny`` on the tiny traffic."""
    with open(os.path.join(root, "bench", "references", f"{name}.py"),
              "w") as f:
        f.write(source)
    with open(os.path.join(HERE, "data", "tiny.json")) as f:
        config = json.load(f)
    config.update(name=name, reference=name)
    with open(os.path.join(root, "bench", "configs", f"{name}.json"),
              "w") as f:
        json.dump(config, f)
    workload = f"{name}.tiny"
    with open(os.path.join(root, "bench", "cells", f"{workload}.json"),
              "w") as f:
        json.dump({"max_logit_gap": TINY_LIMIT}, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": name,
                             "file": f"bench/configs/{name}.json"})
    bench["workloads"].append({"name": workload, "config": name,
                               "traffic": "tiny", "chips": 1})
    with open(path, "w") as f:
        json.dump(bench, f)
    return workload


def _run(root, cache, workload, seed):
    opts = harness.parse(["--workload", workload, "--seed", str(seed),
                          "--seconds", "0.5", "--trace", "0"])
    return harness.run(opts, 0.0, root=root, cache_dir=cache,
                       allow_cpu=True, persistent_cache=False)


def test_added_configuration_runs_through_its_own_reference(tmp_path):
    root = benchroot.make_root(tmp_path / "root", limit=TINY_LIMIT)
    before = _files(root)
    workload = _add_config(root, "counted", COUNTED)
    after = _files(root)
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == {
        os.path.join("bench", "references", "counted.py"),
        os.path.join("bench", "configs", "counted.json"),
        os.path.join("bench", "cells", "counted.tiny.json")}

    cell = harness.load_cell(workload, root)
    assert cell.arch.__file__ == os.path.realpath(
        os.path.join(root, "bench", "references", "counted.py"))
    res = _run(root, str(tmp_path / "cache"), workload, SEED)
    assert res["correct"] is True
    assert {"hidden_states", "head", "logits"} <= set(cell.arch.CALLS)

    # the counts go through it too, and read as dense's
    dense = harness.load_cell("tiny.tiny", root).arch
    m, w = cell.config["model"], cell.config["weights"]
    cell.arch.CALLS.clear()
    assert counts.decode_step(cell.arch, m, w, 2, 300.0, "bfloat16") \
        == counts.decode_step(dense, m, w, 2, 300.0, "bfloat16")
    assert counts.request_flops(cell.arch, m, 40, 9) \
        == counts.request_flops(dense, m, 40, 9)
    assert set(cell.arch.CALLS) == {"matmuls", "other_bytes", "state_bytes",
                                    "attn_flops", "seq_attn_flops"}


def test_planted_reference_makes_the_rehearsal_not_correct(tmp_path):
    assert PLANTED != DENSE_SOURCE
    root = benchroot.make_root(tmp_path / "root", limit=TINY_LIMIT)
    workload = _add_config(root, "planted", PLANTED)
    res = _run(root, str(tmp_path / "cache"), workload, SEED + 1)
    assert res["failed"] == 0
    assert res["correct"] is False
    assert res["checks"]["max_logit_gap"]["value"] > TINY_LIMIT


@pytest.mark.parametrize("reference,module,message", [
    (None, None, "needs a \"reference\" key"),
    ("absent", None, "has no module"),
    ("../references/dense", None, "needs a \"reference\" key"),
    ("partial", "def head(model, weights, seed):\n    return None\n",
     "lacks hidden_states, logits, matmuls"),
])
def test_configuration_without_a_usable_reference_is_refused(
        tmp_path, reference, module, message):
    root = benchroot.make_root(tmp_path)
    path = os.path.join(root, "bench", "configs", "tiny.json")
    with open(path) as f:
        config = json.load(f)
    config.pop("reference")
    if reference is not None:
        config["reference"] = reference
    with open(path, "w") as f:
        json.dump(config, f)
    if module is not None:
        with open(os.path.join(root, "bench", "references",
                               f"{reference}.py"), "w") as f:
            f.write(module)
    with pytest.raises(ValueError, match=message) as err:
        harness.load_cell("tiny.tiny", root)
    assert path in str(err.value)


def test_benchmark_json_configs_name_modules_that_load():
    repo = os.path.dirname(BENCH)
    with open(os.path.join(repo, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for entry in bench["configs"]:
        path = os.path.join(repo, entry["file"])
        with open(path) as f:
            config = json.load(f)
        arch = harness.reference_module(config, path)
        for name in harness.REFERENCE_API:
            assert callable(getattr(arch, name))
        step = counts.decode_step(arch, config["model"], config["weights"],
                                  1, 512.0, config["kv_dtype"])
        assert step["flops"] > 0 and step["bytes"] > 0


def test_dense_readers_read_their_pinned_values():
    # decode_roofline and step_mfu on one view of StableLM-2-1.6B, pinned
    # to the last digit: any change to the counts' arithmetic or its order
    # moves them
    path = os.path.join(BENCH, "configs", "stablelm-1.6b.json")
    with open(path) as f:
        config = json.load(f)
    stats = NS(decode_steps=2297, tokens_out=2313, prefills=16,
               kv_pages_live=2297 * 52)
    req = NS(prompt=[0] * 1000, out_tokens=[1] * 200)
    view = harness.RunView(
        records=[harness.CallRecord(0.0, 1.0, [req, req], stats)],
        window_s=37.5, slots=1, page=16, model=config["model"],
        arch=harness.reference_module(config, path),
        weights=config["weights"], kv_dtype=config["kv_dtype"],
        peaks=peaks.peaks_for("TPU v5 lite"),
        trace={"module_seconds": {"jit_solo": [0.010014, 0.0100141, 0.2]}})
    assert harness.metric_reader("decode_roofline")(view) == {
        "value": 10.024893959346382, "bound": "memory"}
    assert harness.metric_reader("step_mfu")(view) == 0.0861098559796413
