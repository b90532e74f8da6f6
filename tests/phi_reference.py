"""Plain float32 forward of Phi-1.5, written from the published model
(HF ``PhiForCausalLM``, ``model_type: phi``): one sequence, the whole of it
at once, no cache, no kernels, no batching, every matmul at
``precision="highest"``.

  per layer:  h = LayerNorm(x; gamma1, beta1)
              q, k, v = h @ Wq + bq, h @ Wk + bk, h @ Wv + bv
              rotary (rotate-half) on the first rotary_pct of each head
              a = softmax(q k^T / sqrt(head_dim), causal) v
              x = x + (a @ Wdense + bdense) + (gelu_tanh(h @ W1 + b1) @ W2 + b2)
  logits = LayerNorm(x; gamma_f, beta_f) @ Wlm + blm

It reads the program's dense parameter tree: layer ``l`` of a leaf under
``blocks/b0`` is its row ``l``, and a norm's gain is stored as its offset
from 1 (``gamma = 1 + norm1``), as the program stores every norm scale.
"""
import math

import jax
import jax.numpy as jnp


def _mm(a, b):
    return jnp.matmul(a, b, precision="highest")


def layer_norm(x, gamma, beta, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gamma + beta


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def rotary(x, rotary_dim, theta):
    """x [S, H, hd]: rotate-half over the first ``rotary_dim`` dims of
    each head, at positions 0..S-1."""
    inv = 1.0 / theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                          / rotary_dim)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None, :]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None, :]
    xr, xp = x[..., :rotary_dim], x[..., rotary_dim:]
    x1, x2 = xr[..., :rotary_dim // 2], xr[..., rotary_dim // 2:]
    rotated = jnp.concatenate([-x2, x1], -1)
    return jnp.concatenate([xr * cos + rotated * sin, xp], -1)


def attention(h, p, cfg):
    """Causal self-attention of one sequence h [S, d]; p holds one
    layer's attention leaves."""
    s = h.shape[0]
    hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    q = (_mm(h, p["wq"]) + p["bq"]).reshape(s, nh, hd)
    k = (_mm(h, p["wk"]) + p["bk"]).reshape(s, nkv, hd)
    v = (_mm(h, p["wv"]) + p["bv"]).reshape(s, nkv, hd)
    rot = int(hd * cfg.rotary_pct)
    q, k = rotary(q, rot, cfg.rope_theta), rotary(k, rot, cfg.rope_theta)
    k = jnp.repeat(k, nh // nkv, axis=1)       # HF repeat_kv
    v = jnp.repeat(v, nh // nkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision="highest") \
        / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    a = jnp.einsum("hqk,khd->qhd", probs, v, precision="highest")
    return _mm(a.reshape(s, nh * hd), p["wo"]) + p["bo"]


def mlp(h, p):
    return _mm(gelu_tanh(_mm(h, p["w_up"]) + p["b_up"]), p["w_down"]) \
        + p["b_down"]


def forward(cfg, params, tokens):
    """Logits [S, vocab] of the token sequence ``tokens`` [S]."""
    eps = cfg.norm_eps
    blocks = params["blocks"]["b0"]
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tok"][jnp.asarray(tokens)].astype(jnp.float32)
        for layer in range(cfg.n_layers):
            lp = jax.tree_util.tree_map(lambda a: a[layer], blocks)
            h = layer_norm(x, 1.0 + lp["norm1"], lp["norm1_bias"], eps)
            x = x + attention(h, lp["attn"], cfg) + mlp(h, lp["ffn"])
        x = layer_norm(x, 1.0 + params["final_norm"],
                       params["final_norm_bias"], eps)
        return _mm(x, params["lm_head"]) + params["lm_head_bias"]
