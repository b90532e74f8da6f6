"""Pallas TPU kernel: ragged paged attention over the serving KV arena.

ONE kernel serves every attention step the paged engine runs: batched
decode (one query token per lane), chunked prefill (a block of query
tokens per lane, scattered straight into the arena first) and the mixed
rounds where both co-schedule in the same jit step. The XLA reference
gather (``models.attention.paged_cache_read``) materializes the FULL
block-table width for every lane — it survives only as the differential
oracle and the fallback for geometries the kernel cannot shard; all
serving traffic streams through here, which is exactly the page-granular
LPDDR5 traffic ``memsys.workload`` (``kv_traffic_paged`` for decode,
``kv_traffic_chunked`` for prefill chunks) charges the Eq. (3)/(4) DSE.

Grid / BlockSpec contract
-------------------------
  * Grid ``(B, QB, P)`` — batch lane x **q block** x block-table slot.
    The q-block axis is the multi-query extension: each lane's ``S``
    query tokens are split into ``QB = ceil(S/q_blk)`` blocks of
    ``q_blk`` rows. The page axis stays innermost so the online-softmax
    scratch accumulates one (lane, q-block)'s pages before moving on.
    Heads are not a grid axis: each step fetches one page of EVERY KV
    head and the body loops over the heads.
  * Queries are ragged: lane ``b``'s queries sit at absolute positions
    ``q_start[b] + t`` (``t < S``) and attend KV positions
    ``<= q_start[b] + t`` that are ``< kv_len[b]`` — causal masking at
    intra-page granularity, so a chunk attends the pages it just wrote
    plus every earlier page, exactly like one-shot prefill. Query rows at
    positions ``>= kv_len`` (right padding of a short chunk, or a lane
    idling in a mixed round with ``n_new = 0``) emit exactly 0.
  * The arena is read in its own layout, ``[n_pages, page, KV*hd]``
    (plus ``[n_pages, page, KV]`` scales for the int8 layout). Per grid
    step the BlockSpec index map does a data-dependent fetch of ONE page
    (all heads) at row ``tbl[b, p]`` — the ``PrefetchScalarGridSpec``
    scalar-prefetch mechanism, same as ``kernels/qmm.py``'s stream
    routing. Queries and outputs are ``[B, QB, q_blk, H*hd]`` and the
    softmax state ``[B, QB, q_blk, H]``. Every block's last two dims
    are either whole array dims or (page, ...) with ``page % 8 == 0``,
    which is the TPU's (8, 128)-or-full-dim tiling rule; heads are
    static lane slices of width ``hd`` inside the body.
  * Scalar prefetch operands: ``tbl [B, P]`` (block tables), ``q_start
    [B]``, ``kv_len [B]`` and ``meta = [page_offset, n_local_pages]``
    (shard-local page-id window; ``[0, n_pages]`` on a single device).
  * Dead, causally-future, out-of-shard or padding-only fetches are
    remapped to arena row 0 by the index map (never a live page — row 0
    is the reserved null page) and fully masked in the body: q block
    ``qb`` streams page ``p`` only when the block holds a valid query
    (``q_start + qb*q_blk < kv_len``) and the page is causally visible
    to it (``p*page < min(kv_len, q_start + (qb+1)*q_blk)``). Per-lane
    gather work is what ``memsys.workload.chunk_pages_streamed`` counts
    — for decode (``S = 1``) that collapses to ``ceil(kv_len/page)``
    pages, never ``B * P``.
  * Online softmax (flash-style running max / sum) keeps exactly one
    page of K/V resident per step; the G query heads of a KV head (GQA)
    ride as one ``G * q_blk``-row block against it. int8-KV dequant
    (per-page-slot, per-head scales from ``models.kvcache.quantize_kv``)
    is fused before the dot.
  * Outputs: normalized ``o`` plus the running ``(m, l)`` softmax state
    per query row — the state is what makes the kernel mesh-composable:
    under the PR-3 sharding contract the arena's page axis shards over
    ``data``, so each shard runs the kernel over its own page slice and
    the partial ``(o, m, l)`` triples merge with a flash-decoding-style
    ``pmax``/``psum`` reduction (``shard_map`` over the full
    ``(data, model)`` mesh; KV heads stay ``model``-local like
    ``qmm_shard_map``).

``interpret=None`` resolves to interpret mode on the CPU backend only
(``kernels.resolve_interpret``), so CI runs the same kernel body the TPU
backend compiles.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret
from repro.launch.mesh import axis_size as _mesh_axis

# default q-block rows per grid step; mirrored by the host-side stream
# accounting (memsys.workload.chunk_pages_streamed and the engine's
# prefill_kv_pages_live counter), which must stay page-for-page with the
# index map below
Q_BLOCK = 16


# ---------------------------------------------------------------------------
# kernel body
# ---------------------------------------------------------------------------
def _accumulate(tbl_ref, qs_ref, kl_ref, meta_ref, q_ref, k_ref, v_ref,
                ks_ref, vs_ref, o_ref, mo_ref, lo_ref,
                acc_ref, m_ref, l_ref, *, page: int, q_blk: int, n_kv: int,
                g: int, hd: int, window: Optional[int],
                attn_softcap: Optional[float], scale: float):
    b = pl.program_id(0)
    qb = pl.program_id(1)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    kl = kl_ref[b]
    qs = qs_ref[b]
    local = tbl_ref[b, p] - meta_ref[0]
    owned = (local >= 0) & (local < meta_ref[1])
    limit = jnp.minimum(kl, qs + (qb + 1) * q_blk)
    live = ((p * page) < limit) & (qs + qb * q_blk < kl)

    # the G query heads of one KV head ride as one row block, head-major:
    # row r is query token r % q_blk of head r // q_blk
    rows = g * q_blk
    pos_q = qs + qb * q_blk + jax.lax.broadcasted_iota(
        jnp.int32, (rows, 1), 0) % q_blk                   # [rows, 1]
    pos_k = p * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    mask = (pos_k <= pos_q) & (pos_k < kl) & (pos_q < kl) & owned & live
    if window is not None:
        mask = mask & (pos_q - pos_k < window)

    for kh in range(n_kv):
        cols = slice(kh * hd, (kh + 1) * hd)
        k = k_ref[0, :, cols].astype(jnp.float32)          # [page, hd]
        v = v_ref[0, :, cols].astype(jnp.float32)
        if ks_ref is not None:                             # fused dequant
            k = k * ks_ref[0, :, kh:kh + 1].astype(jnp.float32)
            v = v * vs_ref[0, :, kh:kh + 1].astype(jnp.float32)
        heads = range(kh * g, (kh + 1) * g)
        q = jnp.concatenate([q_ref[0, 0, :, h * hd:(h + 1) * hd]
                             for h in heads], axis=0)
        q = q.astype(jnp.float32) * scale                  # [rows, hd]
        scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        if attn_softcap:
            scores = attn_softcap * jnp.tanh(scores / attn_softcap)
        scores = jnp.where(mask, scores, -1e30)            # [rows, page]
        m_prev = m_ref[kh]                                 # [rows, 1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        # probs masked explicitly: with every score at -1e30 AND m still
        # at its -1e30 init (a fully dead row) exp(score - m_new) would
        # be 1
        probs = jnp.where(mask, jnp.exp(scores - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[kh] = l_ref[kh] * corr + jnp.sum(probs, axis=-1,
                                               keepdims=True)
        acc_ref[kh] = acc_ref[kh] * corr + jax.lax.dot_general(
            probs, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[kh] = m_new

    @pl.when(p == pl.num_programs(2) - 1)
    def _done():
        # a row with no live position keeps l == 0 -> output exactly 0
        for kh in range(n_kv):
            for j in range(g):
                h, r = kh * g + j, slice(j * q_blk, (j + 1) * q_blk)
                o_ref[0, 0, :, h * hd:(h + 1) * hd] = (
                    acc_ref[kh, r] / jnp.maximum(l_ref[kh, r], 1e-30))
                mo_ref[0, 0, :, h:h + 1] = m_ref[kh, r]
                lo_ref[0, 0, :, h:h + 1] = l_ref[kh, r]


def _make_kernel(quantized, **static):
    body = functools.partial(_accumulate, **static)
    if quantized:
        def kernel(tbl, qs, kl, meta, q, k, v, ks, vs, o, mo, lo,
                   acc, m, l):
            body(tbl, qs, kl, meta, q, k, v, ks, vs, o, mo, lo, acc, m, l)
    else:
        def kernel(tbl, qs, kl, meta, q, k, v, o, mo, lo, acc, m, l):
            body(tbl, qs, kl, meta, q, k, v, None, None, o, mo, lo,
                 acc, m, l)
    return kernel


# ---------------------------------------------------------------------------
# shard-local call
# ---------------------------------------------------------------------------
def _ragged_call(q4, kp, vp, ksp, vsp, tbl, qs, kl, meta, *, n_kv,
                 window, attn_softcap, interpret):
    """One shard's kernel call.

    q4 [B, QB, q_blk, H*hd]; kp/vp [n_pages, page, KV*hd]; ksp/vsp
    [n_pages, page, KV] or None; tbl [B, P]; qs/kl [B];
    meta = [page_offset, n_local_pages]. Returns (o, m, l) — normalized
    output plus the online-softmax state for cross-shard merging, shapes
    o [B, QB, q_blk, H*hd] and m/l [B, QB, q_blk, H]."""
    bsz, qb_n, q_blk, hw = q4.shape
    page, kvw = kp.shape[1], kp.shape[2]
    hd = kvw // n_kv
    h = hw // hd
    n_tbl = tbl.shape[1]
    quantized = ksp is not None

    def _page_sel(b, qb, p, tbl_ref, qs_ref, kl_ref, meta_ref):
        local = tbl_ref[b, p] - meta_ref[0]
        limit = jnp.minimum(kl_ref[b], qs_ref[b] + (qb + 1) * q_blk)
        ok = ((local >= 0) & (local < meta_ref[1])
              & ((p * page) < limit)
              & (qs_ref[b] + qb * q_blk < kl_ref[b]))
        return jnp.where(ok, local, 0)

    def q_map(b, qb, p, *refs):
        return (b, qb, 0, 0)

    def kv_map(b, qb, p, *refs):
        return (_page_sel(b, qb, p, *refs), 0, 0)

    in_specs = [pl.BlockSpec((1, 1, q_blk, hw), q_map),
                pl.BlockSpec((1, page, kvw), kv_map),
                pl.BlockSpec((1, page, kvw), kv_map)]
    operands = [q4, kp, vp]
    if quantized:
        in_specs += [pl.BlockSpec((1, page, n_kv), kv_map),
                     pl.BlockSpec((1, page, n_kv), kv_map)]
        operands += [ksp, vsp]

    call = pl.pallas_call(
        _make_kernel(quantized, page=page, q_blk=q_blk, n_kv=n_kv,
                     g=h // n_kv, hd=hd, window=window,
                     attn_softcap=attn_softcap, scale=float(hd) ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(bsz, qb_n, n_tbl),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((1, 1, q_blk, hw), q_map),
                       pl.BlockSpec((1, 1, q_blk, h), q_map),
                       pl.BlockSpec((1, 1, q_blk, h), q_map)],
            scratch_shapes=[pltpu.VMEM((n_kv, h // n_kv * q_blk, hd),
                                       jnp.float32),
                            pltpu.VMEM((n_kv, h // n_kv * q_blk, 1),
                                       jnp.float32),
                            pltpu.VMEM((n_kv, h // n_kv * q_blk, 1),
                                       jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((bsz, qb_n, q_blk, hw),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((bsz, qb_n, q_blk, h),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((bsz, qb_n, q_blk, h),
                                        jnp.float32)],
        interpret=interpret,
    )
    return call(tbl, qs, kl, meta, *operands)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------
def shard_compatible(mesh, n_pages_total: int, n_kv: int) -> bool:
    """Whether the shard-local kernel honors the PR-3 arena sharding:

    the page axis must divide ``data`` (each shard owns an equal page
    slice) and the KV head count must divide ``model`` (heads stay
    TP-local; a fused-kv_dim split through the middle of a head — legal
    for the XLA gather — cannot run head-local)."""
    if mesh is None:
        return True
    d = _mesh_axis(mesh, "data")
    m = _mesh_axis(mesh, "model")
    return n_pages_total % max(d, 1) == 0 and n_kv % max(m, 1) == 0


def ragged_paged_attention(q: jax.Array, cache: dict, q_start: jax.Array,
                           kv_len: jax.Array, *, n_kv: int, head_dim: int,
                           window: Optional[int] = None,
                           attn_softcap: Optional[float] = None,
                           mesh=None,
                           interpret: Optional[bool] = None,
                           q_block: int = Q_BLOCK) -> jax.Array:
    """Ragged multi-query attention straight off the paged arena.

    q ``[B, S, H, hd]`` — lane ``b``'s queries sit at absolute positions
    ``q_start[b] + t``; ``cache`` holds ``k_pages/v_pages
    [n_pages, page, KV*hd]`` (int8 layouts add ``{k,v}_scale_pages
    [n_pages, page, KV]``) and ``block_tbl [B, max_pages]``;
    ``kv_len [B]`` is each lane's valid KV bound (for a chunk that just
    scattered ``n_new`` tokens, ``q_start + n_new``; for decode,
    position + 1). Query rows at positions ``>= kv_len`` emit exactly 0
    (a 0-token lane emits all zeros). Returns ``[B, S, H, hd]`` in q's
    dtype.

    With a mesh the kernel runs shard-local under ``shard_map`` over the
    full ``(data, model)`` mesh: each data shard streams only its slice
    of the page pool and the partial softmax states merge with a
    flash-decoding ``pmax``/``psum``; KV heads split over ``model``.
    Callers must check :func:`shard_compatible` first.
    """
    b, s, h, hd = q.shape
    if hd != head_dim or h % n_kv:
        raise ValueError((q.shape, n_kv, head_dim))
    interpret = resolve_interpret(interpret)

    kp = cache["k_pages"]
    vp = cache["v_pages"]
    n_pages = kp.shape[0]
    ksp = cache.get("k_scale_pages")
    vsp = cache.get("v_scale_pages")

    q_blk = min(q_block, s)
    qb_n = -(-s // q_blk)
    s_pad = qb_n * q_blk
    q4 = q.reshape(b, s, h * hd)
    if s_pad != s:
        q4 = jnp.pad(q4, ((0, 0), (0, s_pad - s), (0, 0)))
    q4 = q4.reshape(b, qb_n, q_blk, h * hd)
    tbl = cache["block_tbl"].astype(jnp.int32)
    qs = q_start.astype(jnp.int32)
    kl = kv_len.astype(jnp.int32)
    kw = dict(n_kv=n_kv, window=window, attn_softcap=attn_softcap,
              interpret=interpret)

    def _finish(o):
        o = o.reshape(b, s_pad, h, hd)[:, :s]
        return o.astype(q.dtype)

    d_n = _mesh_axis(mesh, "data") if mesh is not None else 1
    m_n = _mesh_axis(mesh, "model") if mesh is not None else 1
    if mesh is None or d_n * m_n == 1:
        meta = jnp.array([0, n_pages], jnp.int32)
        o, _, _ = _ragged_call(q4, kp, vp, ksp, vsp, tbl, qs, kl, meta,
                               **kw)
        return _finish(o)

    if not shard_compatible(mesh, n_pages, n_kv):
        raise ValueError("arena/head geometry does not divide the mesh; "
                         "gate on shard_compatible() before dispatching")
    from jax.sharding import PartitionSpec as P
    n_local = n_pages // d_n
    kw["n_kv"] = n_kv // m_n

    def body(q4, kp, vp, ksp, vsp, tbl, qs, kl):
        off = jax.lax.axis_index("data").astype(jnp.int32) * n_local
        meta = jnp.stack([off, jnp.int32(n_local)])
        o, m, l = _ragged_call(q4, kp, vp, ksp, vsp, tbl, qs, kl, meta,
                               **kw)
        # flash-decoding merge of per-shard softmax states over `data`
        mg = jax.lax.pmax(m, "data")
        w = jnp.exp(m - mg) * l                  # [B, QB, q_blk, Hl]
        den = jax.lax.psum(w, "data")
        o = o.reshape(w.shape + (hd,))
        num = jax.lax.psum(o * w[..., None], "data")
        out = num / jnp.maximum(den, 1e-30)[..., None]
        return out.reshape(q4.shape)

    q_spec = P(None, None, None, "model")
    page_spec = P("data", None, "model")
    if ksp is None:
        def body2(q4, kp, vp, tbl, qs, kl):
            return body(q4, kp, vp, None, None, tbl, qs, kl)
        specs = (q_spec, page_spec, page_spec, P(None, None), P(None),
                 P(None))
        o = jax.shard_map(body2, mesh=mesh, in_specs=specs,
                          out_specs=q_spec,
                          check_vma=False)(q4, kp, vp, tbl, qs, kl)
    else:
        specs = (q_spec, page_spec, page_spec, page_spec, page_spec,
                 P(None, None), P(None), P(None))
        o = jax.shard_map(body, mesh=mesh, in_specs=specs,
                          out_specs=q_spec,
                          check_vma=False)(q4, kp, vp, ksp, vsp, tbl, qs,
                                           kl)
    return _finish(o)


def paged_decode_attention(q: jax.Array, cache: dict, seq_len: jax.Array,
                           *, n_kv: int, head_dim: int,
                           window: Optional[int] = None,
                           attn_softcap: Optional[float] = None,
                           mesh=None,
                           interpret: Optional[bool] = None) -> jax.Array:
    """Single-token decode view of :func:`ragged_paged_attention`.

    q ``[B, 1, H, hd]``; ``seq_len [B]`` is each lane's valid KV length
    (decode position + 1; 0 marks an inactive lane, whose output is
    exactly 0). Kept as the S == 1 wrapper so decode call sites and the
    differential harness read naturally — there is only ONE kernel."""
    if q.shape[1] != 1:
        raise ValueError(
            f"decode wrapper takes one query token, got S={q.shape[1]}; "
            f"call ragged_paged_attention for multi-query chunks")
    seq = seq_len.astype(jnp.int32)
    return ragged_paged_attention(q, cache, jnp.maximum(seq - 1, 0), seq,
                                  n_kv=n_kv, head_dim=head_dim,
                                  window=window, attn_softcap=attn_softcap,
                                  mesh=mesh, interpret=interpret)
