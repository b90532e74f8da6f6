#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, in one process.

  python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 10

Builds the cell once, runs one window of ``--seconds`` per seed on the
timed path, frees the program, and then runs the plain reference once over
every seed's check sample: for each seed the widest gap of a served token
(the program's reading) and the widest gap of the token the float8 control
puts first (the control's reading). Prints one JSON line per seed, then a
summary line with the largest program reading and the smallest control
reading. Needs the chip the cell asks for, like ``run.py``.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

from benchlib import harness, reference  # noqa: E402

CONTROL = "fp8"


def readings(workload, seeds, seconds, t_process, **kw):
    cell = harness.load_cell(workload, kw.pop("root", harness.ROOT))
    st = harness.build(cell, seeds[0], False, t_process, **kw)
    samples, failed = [], []
    for seed in seeds:
        records = harness.run_window(st.eng, cell, seed, seconds, st.vocab)
        failed.append(harness.window_summary(records, st.vocab)["failed"])
        samples.append(harness.check_sample(
            records, cell.mix["check_requests"], seed))
    del st
    gc.collect()
    flat = [s for smp in samples for s in smp]
    owner = np.repeat(np.arange(len(seeds)), [len(s) for s in samples])
    gaps = reference.served_gaps(cell.arch, cell.config["model"],
                                 cell.config["weights"],
                                 cell.config["weight_seed"], flat,
                                 controls=(CONTROL,))
    seed_of = owner[gaps["seq"]]
    out = []
    for i, seed in enumerate(seeds):
        sel = seed_of == i
        out.append({"seed": seed, "failed": failed[i],
                    "tokens": int(sel.sum()),
                    "program": float(gaps["served"][sel].max()),
                    CONTROL: float(gaps[CONTROL][sel].max())})
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one window each")
    ap.add_argument("--seconds", type=float, default=10.0)
    a = ap.parse_args(argv)
    seeds = [int(s) for s in a.seeds.split(",")]
    try:
        rows = readings(a.workload, seeds, a.seconds, T_PROCESS)
    except harness.NoAccelerator as e:
        harness.log(f"refused: {e}")
        return 2
    for row in rows:
        print(json.dumps(row), flush=True)
    print(json.dumps({
        "workload": a.workload,
        "program_max": max(r["program"] for r in rows),
        f"{CONTROL}_min": min(r[CONTROL] for r in rows),
        "failed": sum(r["failed"] for r in rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
