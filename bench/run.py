#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

Run from the root of a checkout. The cells, their configurations, traffic
mixes, limits and per-layer metrics are named in ``BENCHMARK.json`` and
found by name under ``bench/`` (see ``bench/benchlib/harness.py``). The
run refuses, with a nonzero exit and no result, when JAX finds no TPU or
fewer chips than the cell asks for.
"""
import time

T_PROCESS = time.monotonic()

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, HERE)

from benchlib import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_process=T_PROCESS))
