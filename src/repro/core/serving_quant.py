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
one-time, per-process lowering of the stream-format leaves into whatever
the executing backend multiplies fastest. The QMC streams are the
*storage and transport* format — they are what the memsys DSE charges
bytes/energy for, and on TPU the stream-direct Pallas kernels
(``kernels/qmm.py``) consume them as-is. XLA backends without a fused
dequant-matmul (the CPU serving bench) would otherwise re-materialize
the dense working set inside every step call; the plan does that
re-materialization exactly once at engine setup instead (the same
load-time repack idiom llama.cpp/ExecuTorch use for formats their
matmul kernels cannot consume directly), so the per-call serving graph
degenerates to a dense matmul. ``ServeEngine`` builds it lazily and
keeps the stream-format tree as the source of truth for cost
attribution (``obs/costs.py`` models bytes/token from the streams).
"""
from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.apply import is_quantizable, path_str
from repro.core.qconfig import QMCConfig
from repro.core.qtensor import QTensor, quantize_qtensor
from repro.core.qtensor_sharded import (ShardedQTensor,
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


def build_exec_weights(params, dtype=jnp.float32):
    """Lower a serving-format pytree to its execution form (see module
    docstring): every QTensor / ShardedQTensor leaf dequantizes to a
    dense ``dtype`` array of its logical shape (stacked leaves via vmap
    over the extra leading dims); everything else passes through.
    Returns ``params`` unchanged (same object) when no stream leaves are
    present, so dense engines pay nothing."""
    from repro.core.qtensor import dequantize_qtensor
    from repro.core.qtensor_sharded import dequantize_sharded

    def is_q(x):
        return isinstance(x, (QTensor, ShardedQTensor))

    if not any(is_q(l) for l in
               jax.tree_util.tree_leaves(params, is_leaf=is_q)):
        return params

    def lower(leaf):
        if isinstance(leaf, ShardedQTensor):
            fn = lambda q: dequantize_sharded(q, dtype)  # noqa: E731
            extra = leaf.in_codes.ndim - 4   # [shards, k, r, c] is rank 4
        elif isinstance(leaf, QTensor):
            fn = lambda q: dequantize_qtensor(q, dtype)  # noqa: E731
            extra = leaf.in_codes.ndim - 3   # [k, r, c] is rank 3
        else:
            return leaf
        for _ in range(extra):               # stacked [G]/[G, E] leaves
            fn = jax.vmap(fn)
        # one jit per leaf: XLA fuses gather/select/scale into the output
        # buffer, where op-by-op dispatch would hold several dense-sized
        # temporaries at once (GBs for a full-width stack or LM head)
        return jax.jit(fn)(leaf)

    return jax.tree_util.tree_map(lower, params, is_leaf=is_q)


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
