"""Phi-1.5 (``microsoft/phi-1_5``, HF ``PhiForCausalLM``): the plain
reference's forward pass and the operations and bytes of its step (the
interface in ``benchlib/reference.py``).

  x = embed[tokens]
  per layer:  h = layernorm(x) * gamma1 + beta1        (one norm a layer)
              q, k, v = h @ wq + bq, h @ wk + bk, h @ wv + bv
              partial rotary (rotate-half) on the first rotary_pct of
              each head; causal softmax(q k^T / sqrt(hd)) v
              x = x + (attn @ wo + bo)
                    + (gelu_tanh(h @ w_up + b_up) @ w_down + b_down)
  logits = (layernorm(x) * gamma_f + beta_f) @ lm_head + lm_head_bias

Weights are drawn as the model definition draws them. The matrices, and
the counts that do not depend on the block's form (``matmuls``,
``state_bytes``, ``attn_flops``, ``seq_attn_flops``), are the sequential
block's, taken from ``dense.py`` beside this file. The norm
gains and biases and every bias come from ``fold_in(key, AFFINE_KEY_DATA)``
of the layer's block key (for the final norm and the head bias, of the
root key), split into one key a leaf in a fixed order, each a normal of
std 0.02 (the head's bias 0.1), the gains as 1 + that. Only the matrices
are served through QMC.
"""
from __future__ import annotations

import functools
import os
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchlib.counts import DTYPE_BYTES
from benchlib.harness import _load_module
from benchlib.reference import (_attention, _embed_rows, _gelu_tanh, _items,
                                _mm, _normal, _rotary, served_matrix,
                                top_keys)

# the module object the harness loads for configurations naming "dense"
dense = _load_module(os.path.join(os.path.dirname(os.path.realpath(
    __file__)), "dense.py"), "bench_reference_dense")
matmuls = dense.matmuls
state_bytes = dense.state_bytes
attn_flops = dense.attn_flops
seq_attn_flops = dense.seq_attn_flops

AFFINE_KEY_DATA = 0x51CE
AFFINE_STD = 0.02
HEAD_BIAS_STD = 0.1
LAYER_AFFINE = ("norm1", "norm1_bias", "bq", "bk", "bv", "bo", "b_up",
                "b_down")
TOP_AFFINE = ("final_norm", "final_norm_bias", "lm_head_bias")

# ---------------------------------------------------------------------------
# forward pass
# ---------------------------------------------------------------------------


def _affine(key, shapes: Dict[str, tuple]) -> Dict[str, jax.Array]:
    ks = jax.random.split(jax.random.fold_in(key, AFFINE_KEY_DATA),
                          len(shapes))
    return {name: _normal(k, shape, HEAD_BIAS_STD if name == "lm_head_bias"
                          else AFFINE_STD)
            for k, (name, shape) in zip(ks, shapes.items())}


def init_layer(model: dict, key) -> Dict[str, jax.Array]:
    """One decoder layer's float32 initial weights from its group key: the
    sequential block's matrices (no gate), then the norm and bias leaves
    from the block key."""
    d, ff = model["d_model"], model["d_ff"]
    q_dim = model["n_heads"] * model["head_dim"]
    kv_dim = model["n_kv_heads"] * model["head_dim"]
    w = dense.init_layer(model, key)
    (block_key,) = jax.random.split(key, 1)   # pattern ("attn",): one slot
    shapes = dict(zip(LAYER_AFFINE, [(d,), (d,), (q_dim,), (kv_dim,),
                                     (kv_dim,), (d,), (ff,), (d,)]))
    w.update(_affine(block_key, shapes))
    return w


def _layernorm(x, gamma, beta, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * gamma + beta


def _mlp(h, w, matmul):
    return _mm(_gelu_tanh(_mm(h, w["w_up"], matmul) + w["b_up"]),
               w["w_down"], matmul) + w["b_down"]


@functools.partial(jax.jit, static_argnames=("model_items", "weight_items",
                                             "matmul"))
def _layer(x, key, *, model_items, weight_items, matmul):
    """One decoder layer over x [N, L, d], its weights made from ``key``."""
    model, weights = dict(model_items), dict(weight_items)
    weights["subtile"] = tuple(weights["subtile"])
    w = {k: served_matrix(v, weights) if v.ndim == 2 else v
         for k, v in init_layer(model, key).items()}
    n, L, d = x.shape
    nh, nkv, hd = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    eps = model["norm_eps"]
    pos = jnp.arange(L)
    x = x.reshape(n * L, d)
    h = _layernorm(x, 1.0 + w["norm1"], w["norm1_bias"], eps)
    q = (_mm(h, w["wq"], matmul) + w["bq"]).reshape(n, L, nh, hd)
    k = (_mm(h, w["wk"], matmul) + w["bk"]).reshape(n, L, nkv, hd)
    v = (_mm(h, w["wv"], matmul) + w["bv"]).reshape(n, L, nkv, hd)

    def one(args):
        qi, ki, vi = args
        qi = _rotary(qi, pos, model["rotary_pct"], model["rope_theta"])
        ki = _rotary(ki, pos, model["rotary_pct"], model["rope_theta"])
        return _attention(qi, ki, vi, matmul)

    att = jax.lax.map(one, (q, k, v)).reshape(n * L, nh * hd)
    a = _mm(att, w["wo"], matmul) + w["bo"]
    m = _mlp(h, w, matmul)
    return (x + a + m).reshape(n, L, d)


@functools.partial(jax.jit, static_argnames=("model_items", "weight_items"))
def _head(keys, root, *, model_items, weight_items):
    model, weights = dict(model_items), dict(weight_items)
    weights["subtile"] = tuple(weights["subtile"])
    d, v = model["d_model"], model["vocab"]
    top = _affine(root, dict(zip(TOP_AFFINE, [(d,), (d,), (v,)])))
    return {"w": served_matrix(_normal(keys[1], (d, v), d ** -0.5),
                               weights),
            "b": top["lm_head_bias"], "gamma": 1.0 + top["final_norm"],
            "beta": top["final_norm_bias"]}


@functools.partial(jax.jit, static_argnames=("eps", "matmul"))
def _logits(x, head, *, eps, matmul):
    xn = _layernorm(x, head["gamma"], head["beta"], eps)
    return _mm(xn, head["w"], matmul) + head["b"]


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


def head(model: dict, weights: dict, seed: int) -> dict:
    """What follows the last layer, as served: the final LayerNorm's gain
    and bias, the LM head [d, vocab] dequantized from QMC, and its bias."""
    return _head(top_keys(seed), jax.random.PRNGKey(seed),
                 model_items=_items(model), weight_items=_items(weights))


def logits(model: dict, x, head, matmul: str) -> jax.Array:
    """Final LayerNorm of x [M, d], then the head and its bias:
    [M, vocab]."""
    return _logits(x, head, eps=model["norm_eps"], matmul=matmul)


# ---------------------------------------------------------------------------
# operations and bytes
# ---------------------------------------------------------------------------


def other_bytes(model: dict, batch: float, dtype: str) -> float:
    """Every layer's LayerNorm gain and bias and its six biases, the final
    LayerNorm, the head's bias, and the embedding rows of ``batch``
    tokens, at ``dtype``."""
    d, ff = model["d_model"], model["d_ff"]
    q = model["n_heads"] * model["head_dim"]
    kv = model["n_kv_heads"] * model["head_dim"]
    per_layer = 2 * d + (q + 2 * kv + d) + (ff + d)
    n = per_layer * model["n_layers"] + 2 * d + model["vocab"] + batch * d
    return n * DTYPE_BYTES[dtype]
