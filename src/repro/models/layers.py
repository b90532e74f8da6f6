"""Primitive layers: norms, linears (dense or QMC), rotary embeddings.

All functions are pure; parameters are plain pytree leaves. `matmul_any`
is the single dispatch point where QMC-quantized serving weights enter the
compute graph.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.qtensor import CodePlane, QTensor
from repro.kernels import ops as kops


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * (1.0 + scale.astype(
        jnp.float32))).astype(dt)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    """LayerNorm with a bias. Like ``rms_norm``, the stored scale is the
    gain's offset from 1."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + eps)
            * (1.0 + scale.astype(jnp.float32))
            + bias.astype(jnp.float32)).astype(dt)


def matmul_any(x: jax.Array, w, use_pallas: bool = False,
               tp_dim: int = 1) -> jax.Array:
    """x @ w where w is dense, a QTensor, a ShardedQTensor or a CodePlane
    (QMC serving).

    A CodePlane dequantizes right before the dot, so XLA fuses the
    convert, select and scale into the fusion that reads the int8 codes
    and no dense weight is materialized per call.

    tp_dim: which weight dim carries tensor parallelism (1 = column-
    parallel wq/w_up..., 0 = row-parallel wo/w_down) — used by the ZeRO-3
    weight-gathering constraint."""
    from repro.core.qtensor_sharded import (ShardedQTensor, qmm_shard_map,
                                            qmm_sharded_ref)
    if isinstance(w, ShardedQTensor):
        from repro import runtime_context as ctx
        mesh = ctx.current_mesh()
        if mesh is not None and "model" in mesh.axis_names \
                and w.n_shards == mesh.devices.shape[
                    list(mesh.axis_names).index("model")]:
            return qmm_shard_map(x, w, mesh, dp=ctx.current_dp(),
                                 use_pallas=use_pallas)
        if w.n_shards == 1:
            # Single-shard serving stack: dispatch through kernels.ops so
            # the block_m plan (skinny-XLA / decode-width / column-strip)
            # applies; multi-shard-without-mesh keeps the sharded oracle.
            return kops.qmm(x, w.local(0), use_pallas=use_pallas)
        return qmm_sharded_ref(x, w)
    if isinstance(w, QTensor):
        return kops.qmm(x, w, use_pallas=use_pallas)
    if isinstance(w, CodePlane):
        return jnp.matmul(x, w.dequantize(x.dtype))
    w = _gather_weight_for_use(x, w, tp_dim)
    return jnp.matmul(x, w.astype(x.dtype))


def _gather_weight_for_use(x: jax.Array, w, tp_dim: int) -> jax.Array:
    """ZeRO-3 weight gathering (§Perf): FSDP shards every large weight's

    non-TP dim over `data`; at use time the cheap move is to all-gather the
    weight (MBs) — left alone, GSPMD instead computes partial products over
    the sharded contraction dim and all-reduces [tokens, features] f32
    activations (GBs). Pin the gathered layout for sequence compute
    (train/prefill); decode (seq==1) keeps fully-sharded weights."""
    from repro import runtime_context as rctx
    mesh = rctx.current_mesh()
    if mesh is None or getattr(w, "ndim", 0) != 2 or x.ndim < 3 \
            or x.shape[-2] <= 1 or "model" not in mesh.axis_names:
        return w
    tp = mesh.devices.shape[list(mesh.axis_names).index("model")]
    from jax.sharding import NamedSharding, PartitionSpec as P
    if tp_dim == 0 and w.shape[0] % tp == 0:
        spec = P("model", None)
    elif tp_dim == 1 and w.shape[1] % tp == 0:
        spec = P(None, "model")
    else:
        return w
    return jax.lax.with_sharding_constraint(w, NamedSharding(mesh, spec))


def linear(x: jax.Array, w, b: Optional[jax.Array] = None,
           use_pallas: bool = False, tp_dim: int = 1) -> jax.Array:
    y = matmul_any(x, w, use_pallas=use_pallas, tp_dim=tp_dim)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return x
    return (cap * jnp.tanh(x.astype(jnp.float32) / cap)).astype(x.dtype)


def rotary_cos_sin(positions: jax.Array, dim: int, theta: float,
                   dtype=jnp.float32):
    """positions [..., S] -> (cos, sin) of shape [..., S, dim//2]."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2,
                                           dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(ang).astype(dtype), jnp.sin(ang).astype(dtype)


def apply_rotary(x: jax.Array, cos: jax.Array, sin: jax.Array,
                 rotary_pct: float = 1.0) -> jax.Array:
    """x [B, S, H, D]; cos/sin [B, S, D_rot//2]. Partial rotary supported."""
    d = x.shape[-1]
    d_rot = int(d * rotary_pct) // 2 * 2
    xr, xp = x[..., :d_rot], x[..., d_rot:]
    x1, x2 = jnp.split(xr, 2, axis=-1)
    c = cos[..., None, : d_rot // 2]
    s = sin[..., None, : d_rot // 2]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), xp], axis=-1)


def embed_tokens(tokens: jax.Array, table: jax.Array,
                 scale: bool = False) -> jax.Array:
    x = jnp.take(table, tokens, axis=0)
    if scale:
        x = x * jnp.asarray(table.shape[1] ** 0.5, dtype=x.dtype)
    return x


def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean token NLL in fp32. logits [B,S,V], labels [B,S] int32."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None],
                               axis=-1).squeeze(-1)
    nll = lse - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def glu_mlp(x: jax.Array, p: dict, act: str = "silu", gated: bool = True,
            use_pallas: bool = False, tap=None) -> jax.Array:
    # "gelu" is jax's default tanh form (HF's "gelu_new")
    actf = {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
            "relu": jax.nn.relu}[act]
    if tap:
        tap("w_up", x)
    if gated:
        h = actf(linear(x, p["w_gate"], use_pallas=use_pallas)) \
            * linear(x, p["w_up"], p.get("b_up"), use_pallas=use_pallas)
    else:
        h = actf(linear(x, p["w_up"], p.get("b_up"), use_pallas=use_pallas))
    if tap:
        tap("w_down", h)
    return linear(h, p["w_down"], p.get("b_down"), use_pallas=use_pallas,
                  tp_dim=0)
