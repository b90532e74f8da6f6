#!/usr/bin/env python
"""Microbenchmark for the QMC qmm dispatch (``kernels/ops.qmm``).

Times x @ W for the serving-relevant M widths — decode (M=1..8), small
chunk (M=16) and training/prefill width (M=128) — through every
dispatch path ``kernels.ops.qmm_plan`` can pick:

  * ``ref``        — full ``qmm_ref`` dequant + dense matmul (oracle)
  * ``xla``        — ``qmm(x, qt)``: the plan's XLA route (skinny-M
                     stream einsum at M <= 2, ref above)
  * ``pallas``     — ``qmm(x, qt, use_pallas=True)``: decode-width
                     tiling for skinny M, column-strip at M % 128 == 0
  * ``dense``      — fp32 ``x @ w`` (what the serving weight plan
                     executes per call after its one-time dequant)

On the CPU backend the Pallas paths run in interpret mode
(``kernels.resolve_interpret``) — those columns validate the tiling
architecture, not kernel speed; compare them on a real TPU backend.
Prints the standard ``name,us_per_call,derived`` CSV rows and writes
``BENCH_qmm.json`` (``BENCH_QMM_OUT`` overrides; ``BENCH_QMM_MS``
narrows the M sweep, e.g. ``BENCH_QMM_MS=1,8``).

  PYTHONPATH=src python scripts/bench_qmm.py
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.qconfig import QMCConfig
from repro.core.qtensor import dequantize_qtensor, quantize_qtensor
from repro.kernels import ops as kops
from repro.kernels.ref import qmm_ref

K, N = 128, 256
MS = tuple(int(m) for m in os.environ.get(
    "BENCH_QMM_MS", "1,3,8,16,128").split(","))
OUT = os.environ.get(
    "BENCH_QMM_OUT",
    os.path.join(os.path.dirname(__file__), "..", "BENCH_qmm.json"))


def _time(fn, iters: int, warmup: int = 2) -> float:
    """Seconds per call, min over iters (lower envelope — see the
    serving bench's REPEATS note on noisy shared hosts)."""
    for _ in range(warmup):
        jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def run() -> dict:
    interp = jax.default_backend() == "cpu"
    w = jax.random.normal(jax.random.PRNGKey(0), (K, N), jnp.float32)
    qt = quantize_qtensor(w, QMCConfig(rho=0.3, granularity="subtile"))
    w_exec = dequantize_qtensor(qt, jnp.float32)   # the weight plan's form
    rows = []
    for m in MS:
        x = jax.random.normal(jax.random.PRNGKey(m), (m, K), jnp.float32)
        plan_x = kops.qmm_plan(m, K, N, qt.subtile)
        plan_p = kops.qmm_plan(m, K, N, qt.subtile, use_pallas=True)
        # jit each route once so dispatch overhead, not tracing, is timed
        ref = jax.jit(lambda x: qmm_ref(x, qt))
        xla = jax.jit(lambda x: kops.qmm(x, qt))
        pal = jax.jit(lambda x: kops.qmm(x, qt, use_pallas=True))
        dense = jax.jit(lambda x: x @ w_exec)
        cells = {"ref": _time(lambda: ref(x), 20),
                 "xla": _time(lambda: xla(x), 20),
                 # interpret-mode Pallas is orders slower on CPU — a few
                 # iterations bound the runtime without losing the shape
                 # of the comparison
                 "pallas": _time(lambda: pal(x), 3),
                 "dense": _time(lambda: dense(x), 20)}
        row = {"m": m, "k": K, "n": N,
               "path_xla": plan_x["path"], "path_pallas": plan_p["path"],
               "us_per_call": {k: v * 1e6 for k, v in cells.items()},
               "xla_vs_ref": cells["ref"] / max(cells["xla"], 1e-12),
               "dense_vs_ref": cells["ref"] / max(cells["dense"], 1e-12)}
        rows.append(row)
        print(f"qmm/m{m}_{plan_x['path']},"
              f"{row['us_per_call']['xla']:.1f},"
              f"xla_vs_ref={row['xla_vs_ref']:.2f}x "
              f"dense={row['us_per_call']['dense']:.1f}us "
              f"pallas[{plan_p['path']}]="
              f"{row['us_per_call']['pallas']:.0f}us"
              f"{'(interp)' if interp else ''}")
    out = {"config": {"k": K, "n": N, "backend": jax.default_backend(),
                      "pallas_interpret": interp},
           "rows": rows}
    with open(OUT, "w") as f:
        json.dump(out, f, indent=2)
    print(f"qmm/json,0,{os.path.abspath(OUT)}")
    return out


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run()
