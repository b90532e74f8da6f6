"""Pallas TPU kernels: dual-stream QMC matmul (the Model Weight Controller).

The paper's heterogeneous memory controller fetches outlier weights from
MRAM and inlier weights from MLC ReRAM concurrently and merges them before
they reach the compute unit (Eq. 3: T = max(T_mram, T_reram) + T_sync).
On TPU the analogue is these kernels: the two packed code streams live in
HBM; for every weight tile the kernel pulls the constituent (8, 128)
subtiles from whichever stream owns them, dequantizes them next to the MXU
in VMEM, and feeds the reconstructed slice to the matmul accumulator.

Tiling contract (decode-width vs column-strip)
----------------------------------------------
Two tilings share one stream format and one kernel body, selected by
``kernels.ops.qmm_plan`` on the flattened activation width M (= B*C under
the serving step, so the choice is keyed on the engine's compiled step
widths C):

* **Decode-width** (``qmm_pallas``, ``block_m=8``, wide ``block_n``) — the
  skinny-M shape decode drives (M = live slots; ops.qmm right-pads M to
  the next multiple of 8 and slices the result). Grid
  ``(M/bm, N/bn, K/128, 16 * bn/128)``: the innermost axis walks the
  ``bn/128`` column subtiles of each of the 16 subtile rows of the
  current K tile, dequantizing each into a ``(128, bn)`` VMEM staging
  tile; the x block (``bm x 128``, indexed by (i, k) only) stays
  resident across the whole K tile, and after its last subtile the MXU
  sees ONE 128-deep ``x[bm, 128] @ staging[128, bn]`` op. Per-step VMEM
  at bm=8, bn=512: x (8x128x4B) + 2 code subtiles (2x8x128) + scales
  (2x128x4B) + staging (128x512x4B) + fp32 accumulator and y
  (2x8x512x4B) ~= 300 KB — deep double-buffering headroom inside a
  ~16 MB VMEM budget.
* **Column-strip** (``qmm_pallas_colstrip``, ``block_m>=128``) — the
  compute-bound prefill/training shape: the same kernel at ``bn = 128``,
  so the grid is ``(M/bm, N/128, K/128, 16)`` and contiguous same-stream
  subtile runs of a column are fetched back to back while the x tile
  stays resident. Per-step VMEM at bm=128: x (128x128x4B) + staging
  (128x128x4B) + acc (128x128x4B) + 2 code subtiles + scales ~= 200 KB.

Every block meets the TPU's (8, 128)-or-full-dim tiling rule: x
``(bm, 128)`` with ``bm % 8 == 0``, code subtiles ``(1, 8, 128)``, scales
``(1, 128)``, y ``(bm, bn)``.

Both tilings route every *dead-stream* fetch through a hold table
(``_hold_tables``): instead of loading stream slot 0, the BlockSpec index
map re-issues the most recently fetched live slot of that stream, so the
Pallas pipeline's same-index elision turns the paper's "2x weight
traffic" select-merge into at most one subtile fetch per stream per run
of equal tags — on real hardware the dead stream costs no DMA at all.
Block-granular index maps cannot express arbitrary element offsets, so
a literal contiguous-run burst is not representable; repeated-index
elision is the TPU-native equivalent.

``interpret=None`` resolves to interpret mode on the CPU backend only
(``kernels.resolve_interpret``), where it validates the kernel body; the
serving CPU path is the XLA lowering in ``kernels/ops.py``, not these.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.qtensor import QTensor
from repro.kernels import resolve_interpret


def _hold_tables(tags: jax.Array, pos: jax.Array):
    """Per-stream DMA-elision index tables, [gr, gc] each.

    ``hold_in[gi, j]`` is the inlier-stream slot to *fetch* when the
    kernel is at subtile row gi of column j: the subtile's own slot when
    the tag routes it to the inlier stream, else the most recently
    fetched inlier slot of that column (so the fetch index repeats and
    the Pallas pipeline elides the copy). Rows before the first live
    slot fall back to 0 — always valid because ``quantize_qtensor`` pads
    empty streams with one dummy tile. ``hold_out`` is the mirror image.
    Pure jnp (runs under jit: tags/pos are traced pytree leaves).
    """
    def hold(mine):
        marked = jnp.where(mine, pos, -1)                     # [gr, gc]
        # "last non-(-1) above me" prefix scan down the subtile rows
        last = jax.lax.associative_scan(
            lambda a, b: jnp.where(b >= 0, b, a), marked, axis=0)
        return jnp.maximum(last, 0).astype(jnp.int32)

    return hold(~tags), hold(tags)


def _qmm_kernel(tags_ref, hin_ref, hout_ref,   # scalar prefetch (SMEM)
                x_ref, in_ref, out_ref, sin_ref, sout_ref,  # VMEM in
                y_ref,                          # VMEM out
                acc_ref, stage_ref,             # VMEM scratch
                *, n_sub_k: int, cn: int, gc: int, out_dtype):
    """One (subtile row s, strip column jj) step: dequantize the subtile
    into the (128, block_n) staging tile; after the last subtile of the
    K tile, issue ONE 128-deep ``x[bm, 128] @ stage[128, bn]`` MXU op."""
    t = pl.program_id(3)                        # s * cn + jj
    s = t // cn
    jj = t % cn
    k = pl.program_id(2)

    @pl.when((k == 0) & (t == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Merge point: choose the stream this subtile was routed to at PTQ
    # time (the dead stream's ref re-fetched its held slot — no new DMA).
    gi = k * n_sub_k + s
    is_out = tags_ref[gi * gc + pl.program_id(1) * cn + jj]
    w_in = in_ref[0].astype(jnp.float32) * sin_ref[...]
    w_out = out_ref[0].astype(jnp.float32) * sout_ref[...]
    stage_ref[pl.ds(pl.multiple_of(s * 8, 8), 8),
              pl.ds(pl.multiple_of(jj * 128, 128), 128)] = jnp.where(
                  is_out > 0, w_out, w_in)

    @pl.when(t == pl.num_programs(3) - 1)
    def _accum():
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...].astype(jnp.float32), stage_ref[...],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when((k == pl.num_programs(2) - 1)
             & (t == pl.num_programs(3) - 1))
    def _done():
        y_ref[...] = acc_ref[...].astype(out_dtype)


def qmm_pallas(x: jax.Array, qt: QTensor, *, block_m: int = 8,
               block_n: int = 128,
               interpret: Optional[bool] = None) -> jax.Array:
    """x [M, K] @ dequant(qt) [K, N] via the decode-width tiling.

    Requires M % block_m == 0, K % 128 == 0, N % block_n == 0 and
    block_n % 128 == 0 (``kernels.ops.qmm`` pads M and picks the blocks;
    see the module docstring for the tiling contract). ``interpret=None``
    runs the kernel body in interpret mode on the CPU backend only.
    """
    m, k_dim = x.shape
    k_w, n = qt.shape
    if k_dim != k_w:
        raise ValueError(f"x {x.shape} does not match weight {qt.shape}")
    r, c = qt.subtile
    if (r, c) != (8, 128):
        raise ValueError(f"kernel tiles (8, 128) subtiles, got {(r, c)}")
    if m % block_m or k_dim % 128 or block_n % 128 or n % block_n:
        raise ValueError(f"untileable qmm: x {x.shape}, weight {qt.shape},"
                         f" block_m={block_m}, block_n={block_n}")

    n_sub_k = 128 // r                       # 16 subtile rows per K tile
    cn = block_n // 128                      # column subtiles per N strip
    grid = (m // block_m, n // block_n, k_dim // 128, n_sub_k * cn)

    # the routing tables ride in SMEM flattened to [gr * gc]: a 2-D SMEM
    # array pads its minor dim to 128 words, which overflows SMEM at
    # d_ff-sized K (5632 x 2048: three [704, 16] tables)
    gc = qt.is_out.shape[1]
    tags = qt.is_out.astype(jnp.int32).reshape(-1)
    hold_in, hold_out = (h.reshape(-1) for h in
                         _hold_tables(qt.is_out, qt.stream_pos))

    def sub(k, j, t):                        # flat subtile id
        return (k * n_sub_k + t // cn) * gc + j * cn + t % cn

    def x_map(i, j, k, t, tags_ref, hin_ref, hout_ref):
        return (i, k)

    def in_map(i, j, k, t, tags_ref, hin_ref, hout_ref):
        return (hin_ref[sub(k, j, t)], 0, 0)

    def out_map(i, j, k, t, tags_ref, hin_ref, hout_ref):
        return (hout_ref[sub(k, j, t)], 0, 0)

    def scale_map(i, j, k, t, tags_ref, hin_ref, hout_ref):
        return (0, j * cn + t % cn)

    def y_map(i, j, k, t, tags_ref, hin_ref, hout_ref):
        return (i, j)

    kernel = functools.partial(_qmm_kernel, n_sub_k=n_sub_k, cn=cn, gc=gc,
                               out_dtype=x.dtype)
    # The kernel consumes codes as int8; on TPU the int4->int8 container
    # conversion happens in the load path for free.
    in_codes = qt.in_codes.astype(jnp.int8)

    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_m, 128), x_map),
                pl.BlockSpec((1, r, c), in_map),
                pl.BlockSpec((1, r, c), out_map),
                pl.BlockSpec((1, 128), scale_map),
                pl.BlockSpec((1, 128), scale_map),
            ],
            out_specs=pl.BlockSpec((block_m, block_n), y_map),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32),
                            pltpu.VMEM((128, block_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=resolve_interpret(interpret),
    )
    return call(tags, hold_in, hold_out, x, in_codes, qt.out_codes,
                qt.scale_in, qt.scale_out)


def qmm_pallas_colstrip(x: jax.Array, qt: QTensor, *, block_m: int = 128,
                        interpret: Optional[bool] = None) -> jax.Array:
    """x [M, K] @ dequant(qt) [K, N] via the column-strip tiling
    (128-deep MXU accumulation over one 128-column strip — the
    compute-bound prefill shape).

    Requires M % block_m == 0 (block_m >= 128), K % 128 == 0,
    N % 128 == 0."""
    if block_m < 128:
        raise ValueError(f"column-strip tiling needs block_m >= 128, "
                         f"got {block_m}")
    return qmm_pallas(x, qt, block_m=block_m, block_n=128,
                      interpret=interpret)
