"""Mixture-of-Experts FFN with sort-based capacity dispatch.

FLOP-proportional dispatch (no dense all-experts compute): tokens are
argsorted by assigned expert, scattered into an [E, C, d] buffer (capacity
C = tokens * topk / E * capacity_factor), processed by a grouped einsum whose
FLOPs equal active-expert FLOPs, and combined back with router gates.
Tokens beyond an expert's capacity are dropped (standard GShard semantics);
an auxiliary load-balancing loss keeps the router near-uniform.

Sharding: experts live on the `model` mesh axis, tokens on `data`; GSPMD
inserts the all-to-alls at the scatter/gather boundaries.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import linear


def moe_block(p: dict, x: jax.Array, cfg, *, tap=None,
              use_pallas: bool = False) -> Tuple[jax.Array, jax.Array]:
    """x [B,S,d] -> (y [B,S,d], aux_loss scalar)."""
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.topk
    cap = int(math.ceil(t * k / e * cfg.capacity_factor))
    cap = max(cap, k)

    xf = x.reshape(t, d)
    if tap:
        tap("router", xf)
    logits = linear(xf, p["router"]).astype(jnp.float32)   # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)        # [T, k]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # ---- load-balancing auxiliary loss (Switch-style) -------------------
    me = jnp.mean(probs, axis=0)                           # [E]
    ce = jnp.mean(
        jax.nn.one_hot(expert_ids[:, 0], e, dtype=jnp.float32), axis=0)
    aux = jnp.sum(me * ce) * e

    # ---- sort-based dispatch -------------------------------------------
    flat_expert = expert_ids.reshape(-1)                   # [T*k]
    flat_token = jnp.repeat(jnp.arange(t), k)
    flat_gate = gate_vals.reshape(-1)
    order = jnp.argsort(flat_expert, stable=True)
    se, st, sg = flat_expert[order], flat_token[order], flat_gate[order]
    # position within expert group
    starts = jnp.searchsorted(se, jnp.arange(e), side="left")
    seg_pos = jnp.arange(t * k) - starts[se]
    keep = seg_pos < cap

    buf = jnp.zeros((e, cap, d), x.dtype)
    buf = buf.at[se, jnp.where(keep, seg_pos, cap - 1)].add(
        jnp.where(keep[:, None], xf[st], 0).astype(x.dtype),
        mode="drop")

    # ---- grouped expert FFN (FLOPs = E * C * d * ff terms) --------------
    # Serving with QMC weights: expert streams are QTensor stacks (fields
    # carry a leading E dim, sharded on `model`), or their code planes
    # (core/serving_quant.build_exec_weights); dequantize on the fly.
    def _w(name):
        wp = p[name]
        from repro.core.qtensor import CodePlane, QTensor, dequantize_qtensor
        if isinstance(wp, CodePlane):
            return wp.dequantize(x.dtype)
        if isinstance(wp, QTensor):
            return jax.vmap(lambda q: dequantize_qtensor(q, x.dtype))(wp)
        return wp.astype(x.dtype)

    if cfg.gated_mlp:
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, _w("w_gate"))) \
            * jnp.einsum("ecd,edf->ecf", buf, _w("w_up"))
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", buf, _w("w_up")))
    y_buf = jnp.einsum("ecf,efd->ecd", h, _w("w_down"))

    # ---- combine back ----------------------------------------------------
    gathered = y_buf[se, jnp.where(keep, seg_pos, 0)]      # [T*k, d]
    contrib = jnp.where(keep[:, None], gathered
                        * sg[:, None].astype(x.dtype), 0)
    y = jnp.zeros((t, d), x.dtype).at[st].add(contrib)
    return y.reshape(b, s, d), aux.astype(jnp.float32)
