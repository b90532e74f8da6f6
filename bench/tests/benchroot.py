"""A throw-away checkout layout for CPU rehearsals of the harness: a
``BENCHMARK.json`` with one tiny cell, the real metric readers and
reference modules, and the test configuration and traffic mix under
``data/``."""
import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)


def make_root(tmp, limit=1.0, per_layer=None, workload="tiny.tiny"):
    """Lay out ``tmp`` as a checkout whose one cell is the tiny one."""
    root = str(tmp)
    for sub in ("configs", "traffic", "cells"):
        os.makedirs(os.path.join(root, "bench", sub), exist_ok=True)
    for sub in ("metrics", "references"):
        shutil.copytree(os.path.join(BENCH, sub),
                        os.path.join(root, "bench", sub),
                        dirs_exist_ok=True)
    shutil.copy(os.path.join(HERE, "data", "tiny.json"),
                os.path.join(root, "bench", "configs", "tiny.json"))
    shutil.copy(os.path.join(HERE, "data", "tiny-mix.json"),
                os.path.join(root, "bench", "traffic", "tiny.json"))
    with open(os.path.join(root, "bench", "cells",
                           f"{workload}.json"), "w") as f:
        json.dump({"max_logit_gap": limit}, f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    bench = {
        "configs": [{"name": "tiny", "file": "bench/configs/tiny.json"}],
        "workloads": [{"name": workload, "config": "tiny",
                       "traffic": "tiny", "chips": 1}],
        "end_to_end": real["end_to_end"],
        "per_layer": per_layer if per_layer is not None
        else [dict(m, workloads=[workload]) if "workloads" in m else m
              for m in real["per_layer"]],
    }
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
