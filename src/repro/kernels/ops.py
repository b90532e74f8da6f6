"""Jitted public wrappers around the Pallas kernels, with XLA fallbacks.

`qmm` is the dispatch point used by models.layers.matmul_any. The tiling
decision is made by `qmm_plan` keyed on the flattened activation width M
(= B*C when called from the serving step, so the plan is effectively keyed
on the engine's compiled step width C ∈ {1, chunk}):

* pallas backends: M is right-padded to the next multiple of 8 and the
  result sliced back — decode never falls back to a full-matrix dequant.
  M >= 128 (and M % 128 == 0 after padding) selects the column-strip
  kernel (128-deep MXU accumulation); smaller M selects the decode-width
  kernel with the widest N strip that divides N.
* XLA backends (use_pallas=False): M <= 2 lowers `qmm_skinny`, a
  stream-direct einsum + segment-scatter that skips the dense dequant
  entirely (wins at single-lane decode); wider M lowers the `qmm_ref`
  oracle, whose one-shot dequant amortizes better.

Shapes the kernels cannot tile (K or N not a multiple of 128, or a
non-(8,128) subtile) fall back to `qmm_ref` regardless of M.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.qtensor import QTensor
from repro.kernels import ref as kref

# Widest XLA stream-direct width: below this, qmm_skinny's gather/einsum
# beats qmm_ref's dense dequant on CPU; above, the dequant amortizes.
_SKINNY_XLA_MAX_M = 2


def qmm_plan(m: int, k: int, n: int, subtile: tuple[int, int],
             use_pallas: bool = False) -> dict:
    """Pick the qmm lowering for an [m, k] @ [k, n] call.

    Returns {"path", "pad_m", "block_m", "block_n"}; path is one of
    "colstrip" | "decode" | "skinny_xla" | "ref". pad_m is the padded M
    the kernel runs at (== m when no padding is needed).
    """
    tileable = (subtile == (8, 128) and k % 128 == 0 and n % 128 == 0)
    if use_pallas and tileable:
        pad_m = -(-m // 8) * 8
        if pad_m >= 128 and pad_m % 128 == 0:
            return {"path": "colstrip", "pad_m": pad_m,
                    "block_m": 128, "block_n": 128}
        block_n = next(bn for bn in (512, 256, 128) if n % bn == 0)
        return {"path": "decode", "pad_m": pad_m,
                "block_m": 8, "block_n": block_n}
    if not use_pallas and m <= _SKINNY_XLA_MAX_M:
        return {"path": "skinny_xla", "pad_m": m,
                "block_m": m, "block_n": n}
    return {"path": "ref", "pad_m": m, "block_m": m, "block_n": n}


def qmm_skinny(x: jax.Array, qt: QTensor) -> jax.Array:
    """Stream-direct skinny-M matmul: einsum each packed subtile against
    its activation slice and scatter-add into per-stream accumulators —
    no dense [K, N] weight matrix is ever materialized."""
    m, k = x.shape
    r, c = qt.subtile
    gr, gc = qt.is_out.shape
    n = qt.shape[1]
    pos = qt.stream_pos.reshape(-1)
    tags = qt.is_out.reshape(-1)
    n_in = qt.in_codes.shape[0]
    codes = jnp.concatenate([qt.in_codes.astype(jnp.float32),
                             qt.out_codes.astype(jnp.float32)], axis=0)
    slot = jnp.where(tags, n_in + pos, pos)           # [gr*gc]
    sub = jnp.arange(gr * gc, dtype=jnp.int32)
    row_of = sub // gc
    col_of = sub % gc
    xt = x.reshape(m, gr, r).transpose(1, 0, 2)       # [gr, m, r]
    xg = xt[row_of]                                   # [n_sub, m, r]
    wg = codes[slot]                                  # [n_sub, r, c]
    contrib = jnp.einsum("smr,src->smc", xg, wg)
    seg = tags.astype(jnp.int32)                      # 0 = in, 1 = out
    acc = jnp.zeros((2, gc, m, c), jnp.float32)
    acc = acc.at[seg, col_of].add(contrib)
    y_in = acc[0].transpose(1, 0, 2).reshape(m, n)
    y_out = acc[1].transpose(1, 0, 2).reshape(m, n)
    return (y_in * qt.scale_in + y_out * qt.scale_out).astype(x.dtype)


def qmm(x: jax.Array, qt: QTensor, use_pallas: bool = False) -> jax.Array:
    """x [..., K] @ dequant(qt) [K, N] with batch dims preserved."""
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = qt.shape[1]
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    plan = qmm_plan(m, k, n, qt.subtile, use_pallas=use_pallas)
    if plan["path"] in ("decode", "colstrip"):
        from repro.kernels.qmm import qmm_pallas, qmm_pallas_colstrip
        if plan["pad_m"] != m:
            x2 = jnp.pad(x2, ((0, plan["pad_m"] - m), (0, 0)))
        if plan["path"] == "colstrip":
            y = qmm_pallas_colstrip(x2, qt, block_m=plan["block_m"])
        else:
            y = qmm_pallas(x2, qt, block_m=plan["block_m"],
                           block_n=plan["block_n"])
        return y[:m].reshape(*lead, n)
    if plan["path"] == "skinny_xla":
        return qmm_skinny(x2, qt).reshape(*lead, n)
    return kref.qmm_ref(x2, qt).reshape(*lead, n)


def unpack3b(packed: jax.Array, n: int, use_pallas: bool = False
             ) -> jax.Array:
    if use_pallas and n % 8 == 0:
        block = 1024 if n % 1024 == 0 else (8 if n % 8 == 0 else None)
        if block is not None:
            from repro.kernels.unpack3b import unpack3b_pallas
            return unpack3b_pallas(packed, n, block_codes=block)
    return kref.unpack3b_ref(packed, n)
