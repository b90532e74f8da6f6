"""Model-level QMC serving-format conversion (concrete AND abstract).

`quantize_for_serving(params, ...)` converts eligible weight leaves of a
model pytree into the deployment format:

  * stacked 2-D projections  [G, din, dout]  -> ShardedQTensor per group,
    fields stacked over G (TP-shard streams, shard_map matmul);
  * MoE expert tensors       [G, E, d, ff]   -> QTensor per (G, E), fields
    stacked (dequant-on-the-fly grouped einsum, streams sharded over E);
  * everything else (norms, embeddings, small/non-tileable leaves) stays
    dense.

`serving_params_struct(...)` builds the same pytree out of
ShapeDtypeStructs without allocating — the multi-pod dry-run lowers against
this (the 314B/398B models never exist on the CPU host).

`build_exec_weights(params)` is the serving **weight execution plan**: a
one-time, per-process lowering of the stream-format leaves into the form
the step reads. The QMC streams are the *storage and transport* format —
they are what the memsys DSE charges bytes/energy for, and the
stream-direct Pallas kernels (``kernels/qmm.py``) and the mesh path
consume them as-is. Gathering subtiles out of two streams inside every
step is what a dense XLA matmul cannot fuse, so the plan un-streams each
leaf once, at engine setup, into a ``CodePlane``: one int8 code per
weight in dense layout plus the two per-column scales. The step then
dequantizes inside the fusion that reads the weight
(``models/layers.py:matmul_any``) and streams 1 byte a weight, where a
dense fp32 lowering would stream 4, with the same fp32 weight values.
``ServeEngine`` keeps the stream-format tree as the source of truth for
cost attribution (``obs/costs.py`` models bytes/token from the streams);
``exec_weight_counts`` says what the plan did.
"""
from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.apply import is_quantizable, path_str
from repro.core.qconfig import QMCConfig
from repro.core.qtensor import (CodePlane, QTensor, code_plane,
                                quantize_qtensor)
from repro.core.qtensor_sharded import (ShardedQTensor, dequantize_sharded,
                                        quantize_qtensor_sharded)

ROW_SHARDED = ("wo", "w_down", "out_proj")   # TP shards the input dim


def _shard_axis_for(path: str) -> int:
    name = path.split("/")[-1]
    return 0 if name in ROW_SHARDED else 1


def _tileable(din: int, dout: int, cfg: QMCConfig, shards: int,
              shard_axis: int) -> bool:
    r, c = cfg.subtile
    d0, d1 = din, dout
    if shard_axis == 0:
        d0 = din // shards if din % shards == 0 else 0
    else:
        d1 = dout // shards if dout % shards == 0 else 0
    return d0 >= r and d1 >= c and d0 % r == 0 and d1 % c == 0


def stream_sizes(din: int, dout: int, cfg: QMCConfig):
    r, c = cfg.subtile
    gr, gc = din // r, dout // c
    n_sub = gr * gc
    k_out = int(round(cfg.rho * n_sub))
    k_in = n_sub - k_out
    return gr, gc, max(k_in, 1), max(k_out, 1)


def qtensor_struct(din: int, dout: int, cfg: QMCConfig,
                   use_int4: bool = True) -> QTensor:
    """Abstract QTensor (ShapeDtypeStruct fields) for the dry-run."""
    r, c = cfg.subtile
    gr, gc, k_in, k_out = stream_sizes(din, dout, cfg)
    sds = jax.ShapeDtypeStruct
    idt = jnp.int4 if use_int4 else jnp.int8
    return QTensor(
        in_codes=sds((k_in, r, c), idt),
        out_codes=sds((k_out, r, c), jnp.int8),
        stream_pos=sds((gr, gc), jnp.int32),
        is_out=sds((gr, gc), jnp.bool_),
        scale_in=sds((1, dout), jnp.float32),
        scale_out=sds((1, dout), jnp.float32),
        shape=(din, dout), bits_in=cfg.bits_in, bits_out=cfg.bits_out,
        subtile=(r, c))


def sharded_qtensor_struct(din: int, dout: int, cfg: QMCConfig, shards: int,
                           shard_axis: int,
                           use_int4: bool = True) -> ShardedQTensor:
    ldin = din // shards if shard_axis == 0 else din
    ldout = dout // shards if shard_axis == 1 else dout
    base = qtensor_struct(ldin, ldout, cfg, use_int4)
    sds = jax.ShapeDtypeStruct

    def stk(f):
        return sds((shards,) + f.shape, f.dtype)
    return ShardedQTensor(
        in_codes=stk(base.in_codes), out_codes=stk(base.out_codes),
        stream_pos=stk(base.stream_pos), is_out=stk(base.is_out),
        scale_in=stk(base.scale_in), scale_out=stk(base.scale_out),
        shape=(din, dout), bits_in=cfg.bits_in, bits_out=cfg.bits_out,
        subtile=cfg.subtile, shard_axis=shard_axis, n_shards=shards)


def _stack_pytrees(items):
    return jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *items)


def _stack_structs(items):
    def stk(*ls):
        f = ls[0]
        return jax.ShapeDtypeStruct((len(ls),) + f.shape, f.dtype)
    return jax.tree_util.tree_map(stk, *items)


def _convert_leaf(path: str, leaf, cfg: QMCConfig, shards: int,
                  abstract: bool, use_int4: bool) -> Any:
    """leaf: array or ShapeDtypeStruct. Returns converted leaf (or input)."""
    shape = leaf.shape
    is_moe = len(shape) == 4
    sa = _shard_axis_for(path)

    if is_moe:                           # [G, E, d, ff] -> QTensor stacks
        g, e, din, dout = shape
        if not _tileable(din, dout, cfg, 1, 1):
            return leaf
        if abstract:
            base = qtensor_struct(din, dout, cfg, use_int4)
            return jax.tree_util.tree_map(
                lambda f: jax.ShapeDtypeStruct((g, e) + f.shape, f.dtype),
                base)
        per_g = []
        for gi in range(g):
            per_e = [quantize_qtensor(leaf[gi, ei], cfg, use_int4)
                     for ei in range(e)]
            per_g.append(_stack_pytrees(per_e))
        return _stack_pytrees(per_g)

    if len(shape) == 3:                  # [G, din, dout] -> ShardedQTensor
        g, din, dout = shape
        eff_shards = shards if _tileable(din, dout, cfg, shards, sa) else 1
        if not _tileable(din, dout, cfg, eff_shards, sa):
            return leaf
        if abstract:
            base = sharded_qtensor_struct(din, dout, cfg, eff_shards, sa,
                                          use_int4)
            return jax.tree_util.tree_map(
                lambda f: jax.ShapeDtypeStruct((g,) + f.shape, f.dtype),
                base)
        per_g = [quantize_qtensor_sharded(leaf[gi], cfg, eff_shards, sa,
                                          use_int4) for gi in range(g)]
        return _stack_pytrees(per_g)

    if len(shape) == 2:                  # unstacked projection
        din, dout = shape
        eff_shards = shards if _tileable(din, dout, cfg, shards, sa) else 1
        if not _tileable(din, dout, cfg, eff_shards, sa):
            return leaf
        if abstract:
            return sharded_qtensor_struct(din, dout, cfg, eff_shards, sa,
                                          use_int4)
        return quantize_qtensor_sharded(leaf, cfg, eff_shards, sa, use_int4)
    return leaf


def quantize_for_serving(params, qmc: QMCConfig, tp_shards: int = 1,
                         use_int4: bool = True, min_dim: int = 128):
    """Concrete conversion (small models, tests, examples)."""
    return _walk(params, qmc, tp_shards, abstract=False, use_int4=use_int4,
                 min_dim=min_dim)


def serving_params_struct(params_struct, qmc: QMCConfig, tp_shards: int = 1,
                          use_int4: bool = True, min_dim: int = 128):
    """Abstract conversion (dry-run): params_struct holds ShapeDtypeStructs."""
    return _walk(params_struct, qmc, tp_shards, abstract=True,
                 use_int4=use_int4, min_dim=min_dim)


def _is_q(x) -> bool:
    return isinstance(x, (QTensor, ShardedQTensor))


def build_exec_weights(params):
    """Lower a serving-format pytree to its execution form (see module
    docstring): every QTensor leaf, and every single-shard ShardedQTensor
    leaf, becomes a ``CodePlane`` (stacked ``[G]``/``[G, E]`` leaves via
    vmap over the extra leading dims; ``code_plane`` raises on codes too
    wide for a plane); a multi-shard leaf, which a one-chip step reads
    whole, dequantizes to dense fp32; everything else passes through.
    Returns ``params`` unchanged (same object) when no stream leaves are
    present, so dense engines pay nothing."""
    if not any(_is_q(l) for l in
               jax.tree_util.tree_leaves(params, is_leaf=_is_q)):
        return params

    def lower(leaf):
        if not _is_q(leaf):
            return leaf
        if isinstance(leaf, ShardedQTensor):
            extra = leaf.in_codes.ndim - 4   # [shards, k, r, c] is rank 4
            if leaf.n_shards == 1:
                fn = lambda q: code_plane(q.local(0))  # noqa: E731
            else:
                fn = lambda q: dequantize_sharded(  # noqa: E731
                    q, jnp.float32)
        else:
            extra = leaf.in_codes.ndim - 3   # [k, r, c] is rank 3
            fn = code_plane
        for _ in range(extra):               # stacked [G]/[G, E] leaves
            fn = jax.vmap(fn)
        # one jit per leaf: XLA fuses the gather and select into the
        # output buffer, where op-by-op dispatch would hold several
        # plane-sized temporaries at once
        return jax.jit(fn)(leaf)

    return jax.tree_util.tree_map(lower, params, is_leaf=_is_q)


def exec_weight_counts(params) -> dict:
    """What a step consuming ``params`` reads for its matmul weights:
    ``exec_weight_bytes`` (every array of every matmul weight: code
    planes with their scales, streams, dense matrices),
    ``exec_leaves_coded`` (CodePlane leaves) and ``exec_leaves_dense``
    (dense matmul weights: untileable or multi-shard leaves of a QMC
    tree, or every weight of a dense one). A dense matmul weight is a
    float leaf PTQ would consider (``core.apply.is_quantizable``, any
    width) that is at least 2-D per layer (leaves under ``blocks`` carry
    the stacked layer dim), so embeddings, norms and biases never
    count."""
    def is_w(x):
        return _is_q(x) or isinstance(x, CodePlane)

    counts = {"exec_weight_bytes": 0, "exec_leaves_coded": 0,
              "exec_leaves_dense": 0}
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            params, is_leaf=is_w)[0]:
        p = path_str(path)
        if is_w(leaf):
            counts["exec_leaves_coded"] += isinstance(leaf, CodePlane)
            counts["exec_weight_bytes"] += sum(
                int(a.nbytes) for a in jax.tree_util.tree_leaves(leaf))
        elif (is_quantizable(p, leaf, min_dim=1)
              and leaf.ndim - ("blocks" in p.split("/")) >= 2):
            counts["exec_leaves_dense"] += 1
            counts["exec_weight_bytes"] += int(leaf.nbytes)
    return counts


def _walk(params, qmc, tp_shards, abstract, use_int4, min_dim):
    from repro.core.apply import EXCLUDE_SUBSTRINGS
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for path, leaf in flat:
        p = path_str(path)
        shape_ok = (hasattr(leaf, "shape") and 2 <= len(leaf.shape) <= 4
                    and min(leaf.shape[-2:]) >= min_dim)
        name_ok = not any(s in p.lower() for s in EXCLUDE_SUBSTRINGS)
        dt = getattr(leaf, "dtype", None)
        dtype_ok = dt in (jnp.float32, jnp.bfloat16, jnp.float16)
        if shape_ok and name_ok and dtype_ok:
            out.append(_convert_leaf(p, leaf, qmc, tp_shards, abstract,
                                     use_int4))
        else:
            out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)
