"""The dense decoder block, as the repository's model definition has it for
block pattern ``("attn",)``: the plain reference's forward pass and the
operations and bytes of its step (the interface in
``benchlib/reference.py``).

  x = embed[tokens]
  per layer:  h = rmsnorm(x) * (1 + norm1)
              q, k, v = h @ wq + bq, h @ wk + bk, h @ wv + bv
              partial rotary (rotate-half) on the first rotary_pct of
              each head; causal softmax(q k^T / sqrt(hd)) v
              x = x + attn @ wo
              h = rmsnorm(x) * (1 + norm2)
              x = x + (silu(h @ w_gate) * (h @ w_up)) @ w_down   (gated)
                  x + gelu_tanh(h @ w_up) @ w_down                (plain)
  logits = rmsnorm(x) * (1 + final_norm) @ lm_head

Norm scales and qkv biases start at zero in the model definition, so the
forward pass leaves them out; the counts read them. Every layer holds K
and V of every cached position and attends causally to all of them.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchlib.counts import DTYPE_BYTES
from benchlib.reference import (_attention, _embed_rows, _gelu_tanh, _items,
                                _mm, _normal, _rmsnorm, _rotary,
                                served_matrix, top_keys)

# ---------------------------------------------------------------------------
# forward pass
# ---------------------------------------------------------------------------


def init_layer(model: dict, key) -> Dict[str, jax.Array]:
    """One decoder layer's float32 initial weights from its group key."""
    d, ff, nl = model["d_model"], model["d_ff"], model["n_layers"]
    q_dim = model["n_heads"] * model["head_dim"]
    kv_dim = model["n_kv_heads"] * model["head_dim"]
    (block_key,) = jax.random.split(key, 1)   # pattern ("attn",): one slot
    ks = jax.random.split(block_key, 3)
    ka = jax.random.split(ks[0], 4)
    km = jax.random.split(ks[2], 3)
    w = {
        "wq": _normal(ka[0], (d, q_dim), 1.0 / math.sqrt(d)),
        "wk": _normal(ka[1], (d, kv_dim), 1.0 / math.sqrt(d)),
        "wv": _normal(ka[2], (d, kv_dim), 1.0 / math.sqrt(d)),
        "wo": _normal(ka[3], (q_dim, d),
                      1.0 / math.sqrt(q_dim * 2 * nl)),
        "w_up": _normal(km[1], (d, ff), 1.0 / math.sqrt(d)),
        "w_down": _normal(km[2], (ff, d), 1.0 / math.sqrt(ff * 2 * nl)),
    }
    if model["gated_mlp"]:
        w["w_gate"] = _normal(km[0], (d, ff), 1.0 / math.sqrt(d))
    return w


@functools.partial(jax.jit, static_argnames=("model_items", "weight_items",
                                             "matmul"))
def _layer(x, key, *, model_items, weight_items, matmul):
    """One decoder layer over x [N, L, d], its weights made from ``key``."""
    model, weights = dict(model_items), dict(weight_items)
    weights["subtile"] = tuple(weights["subtile"])
    w = {k: served_matrix(v, weights)
         for k, v in init_layer(model, key).items()}
    n, L, d = x.shape
    nh, nkv, hd = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    eps = model["norm_eps"]
    pos = jnp.arange(L)
    h = _rmsnorm(x, 0.0, eps).reshape(n * L, d)
    q = _mm(h, w["wq"], matmul).reshape(n, L, nh, hd)
    k = _mm(h, w["wk"], matmul).reshape(n, L, nkv, hd)
    v = _mm(h, w["wv"], matmul).reshape(n, L, nkv, hd)

    def one(args):
        qi, ki, vi = args
        qi = _rotary(qi, pos, model["rotary_pct"], model["rope_theta"])
        ki = _rotary(ki, pos, model["rotary_pct"], model["rope_theta"])
        return _attention(qi, ki, vi, matmul)

    att = jax.lax.map(one, (q, k, v)).reshape(n * L, nh * hd)
    x = x + _mm(att, w["wo"], matmul).reshape(n, L, d)
    h = _rmsnorm(x, 0.0, eps).reshape(n * L, d)
    if model["gated_mlp"]:
        a = jax.nn.silu(_mm(h, w["w_gate"], matmul)) * _mm(h, w["w_up"],
                                                           matmul)
    else:
        a = _gelu_tanh(_mm(h, w["w_up"], matmul))
    return x + _mm(a, w["w_down"], matmul).reshape(n, L, d)


@functools.partial(jax.jit, static_argnames=("model_items", "weight_items"))
def _head(key, *, model_items, weight_items):
    model, weights = dict(model_items), dict(weight_items)
    weights["subtile"] = tuple(weights["subtile"])
    d = model["d_model"]
    w = _normal(key, (d, model["vocab"]), d ** -0.5)
    return served_matrix(w, weights)


@functools.partial(jax.jit, static_argnames=("eps", "matmul"))
def _logits(x, head, *, eps, matmul):
    return _mm(_rmsnorm(x, 0.0, eps), head, matmul)


def hidden_states(model: dict, weights: dict, seed: int, tokens: np.ndarray,
                  matmul: str = "highest") -> jax.Array:
    """Final-layer hidden states [N, L, d] of right-padded ``tokens``
    [N, L] (causal, so padding never reaches a real position)."""
    keys = top_keys(seed)
    mi, wi = _items(model), _items(weights)
    x = _embed_rows(jnp.asarray(tokens), keys[0], model_items=mi)
    lkeys = jax.random.split(keys[2], model["n_layers"])
    for layer in range(model["n_layers"]):
        x = _layer(x, lkeys[layer], model_items=mi, weight_items=wi,
                   matmul=matmul)
    return x


def head(model: dict, weights: dict, seed: int) -> jax.Array:
    """The LM head as served: [d, vocab], dequantized from QMC."""
    return _head(top_keys(seed)[1], model_items=_items(model),
                 weight_items=_items(weights))


def logits(model: dict, x, head, matmul: str) -> jax.Array:
    """Final RMSNorm of x [M, d], then the head: [M, vocab]."""
    return _logits(x, head, eps=model["norm_eps"], matmul=matmul)


# ---------------------------------------------------------------------------
# operations and bytes
# ---------------------------------------------------------------------------


def matmuls(model: dict) -> Tuple[List[Tuple[str, int, int, int]],
                                  Tuple[int, int]]:
    """([(name, din, dout, layers holding it)], lm head (din, dout))."""
    d, ff, nl = model["d_model"], model["d_ff"], model["n_layers"]
    q = model["n_heads"] * model["head_dim"]
    kv = model["n_kv_heads"] * model["head_dim"]
    layer = [("wq", d, q), ("wk", d, kv), ("wv", d, kv), ("wo", q, d)]
    if model["gated_mlp"]:
        layer.append(("w_gate", d, ff))
    layer += [("w_up", d, ff), ("w_down", ff, d)]
    return [(name, i, o, nl) for name, i, o in layer], (d, model["vocab"])


def other_bytes(model: dict, batch: float, dtype: str) -> float:
    """Norm scales and qkv biases of every layer, the final norm, and the
    embedding rows of ``batch`` tokens, at ``dtype``."""
    d = model["d_model"]
    per_layer = 2 * d
    if model["qkv_bias"]:
        per_layer += (model["n_heads"] + 2 * model["n_kv_heads"]) \
            * model["head_dim"]
    n = per_layer * model["n_layers"] + d + batch * d
    return n * DTYPE_BYTES[dtype]


def state_bytes(model: dict, batch: float, ctx_tokens: float,
                dtype: str) -> float:
    """K and V of ``ctx_tokens`` cached positions over all layers (the
    lanes hold no state but their KV)."""
    return (ctx_tokens * model["n_layers"] * 2 * model["n_kv_heads"]
            * model["head_dim"] * DTYPE_BYTES[dtype])


def attn_flops(model: dict, ctx_tokens: float) -> float:
    """QK^T and PV of one query over ``ctx_tokens`` keys, all layers
    (summed over queries when ``ctx_tokens`` is a sum of contexts)."""
    return (4 * ctx_tokens * model["n_heads"] * model["head_dim"]
            * model["n_layers"])


def seq_attn_flops(model: dict, n: int) -> float:
    """Causal attention of ``n`` positions processed in order."""
    return attn_flops(model, n * (n + 1) / 2)   # position t: t + 1 keys
