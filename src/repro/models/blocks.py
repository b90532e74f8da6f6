"""Block-level init/apply: one transformer "layer" of any supported kind.

A block = pre-norm mixer (attention / mamba / parallel-hybrid) + residual,
then (if the config has an FFN) pre-norm FFN (dense MLP or MoE) + residual.
With ``cfg.parallel_block`` attention and FFN both read the one pre-norm
output and add to the same residual: ``x + attn(h) + ffn(h)`` (Phi).

The norms, the attention-only mixer and the FFN run under
``jax.named_scope``s ``norm``, ``attn`` and ``mlp``, so compiled op names
say which part an op belongs to.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.attention import attn_block
from repro.models.layers import glu_mlp, layer_norm, rms_norm
from repro.models.moe import moe_block
from repro.models.ssm import mamba_block

# ``jax.random.fold_in`` data of the key that draws a block's (or the top
# level's) norm and bias leaves: with jax's partitionable threefry,
# ``split(k, n)[i] == fold_in(k, i)``, so this lies far above every split
# count of the same key and leaves the keys of every other leaf as they are
AFFINE_KEY_DATA = 0x51CE
# biases and gain offsets: never zero, and as small as the source's own
# initializer_range (0.02 for Phi), so that summed over the residual
# stream they do not swamp what each token brings (at 0.1 the logits of a
# random 24-layer model hardly depend on the token)
AFFINE_STD = 0.02
# the head's bias sums over nothing and meets logits of std ~1
HEAD_BIAS_STD = 0.1


def _init_linear(key, din, dout, scale=None, dtype=jnp.float32):
    std = scale if scale is not None else (1.0 / math.sqrt(din))
    return (jax.random.normal(key, (din, dout)) * std).astype(dtype)


def init_attn_params(key, cfg, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    p = {
        "wq": _init_linear(ks[0], d, cfg.attn_dim, dtype=dtype),
        "wk": _init_linear(ks[1], d, cfg.kv_dim, dtype=dtype),
        "wv": _init_linear(ks[2], d, cfg.kv_dim, dtype=dtype),
        "wo": _init_linear(ks[3], cfg.attn_dim, d,
                           scale=1.0 / math.sqrt(cfg.attn_dim
                                                 * 2 * cfg.n_layers),
                           dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.attn_dim,), dtype)
        p["bk"] = jnp.zeros((cfg.kv_dim,), dtype)
        p["bv"] = jnp.zeros((cfg.kv_dim,), dtype)
    return p


def init_mlp_params(key, cfg, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 3)
    d, ff = cfg.d_model, cfg.d_ff
    p = {"w_up": _init_linear(ks[1], d, ff, dtype=dtype),
         "w_down": _init_linear(ks[2], ff, d,
                                scale=1.0 / math.sqrt(ff * 2 * cfg.n_layers),
                                dtype=dtype)}
    if cfg.gated_mlp:
        p["w_gate"] = _init_linear(ks[0], d, ff, dtype=dtype)
    return p


def init_moe_params(key, cfg, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 4)
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    std_in = 1.0 / math.sqrt(d)
    std_out = 1.0 / math.sqrt(ff * 2 * cfg.n_layers)
    p = {
        "router": _init_linear(ks[0], d, e, scale=0.02, dtype=jnp.float32),
        "w_up": (jax.random.normal(ks[1], (e, d, ff)) * std_in
                 ).astype(dtype),
        "w_down": (jax.random.normal(ks[2], (e, ff, d)) * std_out
                   ).astype(dtype),
    }
    if cfg.gated_mlp:
        p["w_gate"] = (jax.random.normal(ks[3], (e, d, ff)) * std_in
                       ).astype(dtype)
    return p


def init_mamba_params(key, cfg, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 4)
    d, di = cfg.d_model, cfg.d_inner
    nh = cfg.ssm_nheads
    in_dim = 2 * di + 2 * cfg.ssm_ngroups * cfg.d_state + nh
    dt = jnp.exp(jax.random.uniform(ks[2], (nh,)) *
                 (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))   # inverse softplus
    return {
        "in_proj": _init_linear(ks[0], d, in_dim, dtype=dtype),
        "conv_w": (jax.random.normal(ks[1], (cfg.conv_dim, cfg.d_conv))
                   / math.sqrt(cfg.d_conv)).astype(dtype),
        "a_log": jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32)),
        "d_skip": jnp.ones((nh,), jnp.float32),
        "dt_bias": dt_bias.astype(jnp.float32),
        "norm_scale": jnp.zeros((di,), jnp.float32),
        "out_proj": _init_linear(ks[3], di, d,
                                 scale=1.0 / math.sqrt(di * 2 * cfg.n_layers),
                                 dtype=dtype),
    }


def affine_leaves(key, shapes: dict) -> dict:
    """Norm and bias leaves drawn from ``key`` (small normals, so that
    every one of them moves the output), in the order of ``shapes``."""
    ks = jax.random.split(jax.random.fold_in(key, AFFINE_KEY_DATA),
                          len(shapes))
    return {name: jax.random.normal(k, shape, jnp.float32)
            * (HEAD_BIAS_STD if name == "lm_head_bias" else AFFINE_STD)
            for k, (name, shape) in zip(ks, shapes.items())}


def _drawn_block_leaves(key, cfg, p: dict) -> None:
    """Phi's leaves: LayerNorm scales and biases, and a bias on every
    projection (the qkv biases that ``qkv_bias`` alone keeps at zero are
    drawn too). ``ModelConfig`` allows ``linear_bias`` only on attention
    blocks with a dense FFN."""
    d = cfg.d_model
    shapes = {}
    norms = [n for n in ("norm1", "norm2") if n in p]
    if cfg.norm == "layer":
        for n in norms:
            shapes[n] = (d,)
            shapes[f"{n}_bias"] = (d,)
    if cfg.linear_bias:
        shapes.update(bq=(cfg.attn_dim,), bk=(cfg.kv_dim,),
                      bv=(cfg.kv_dim,), bo=(d,))
        if "ffn" in p:
            shapes.update(b_up=(cfg.d_ff,), b_down=(d,))
    for name, leaf in affine_leaves(key, shapes).items():
        if name in ("bq", "bk", "bv", "bo"):
            p["attn"][name] = leaf
        elif name in ("b_up", "b_down"):
            p["ffn"][name] = leaf
        else:
            p[name] = leaf


def block_norm(x: jax.Array, p: dict, name: str, cfg) -> jax.Array:
    if cfg.norm == "layer":
        return layer_norm(x, p[name], p[f"{name}_bias"], cfg.norm_eps)
    return rms_norm(x, p[name], cfg.norm_eps)


def init_block(key, kind: str, use_moe: bool, cfg, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 3)
    p = {"norm1": jnp.zeros((cfg.d_model,), jnp.float32)}
    if kind.startswith("attn"):
        p["attn"] = init_attn_params(ks[0], cfg, dtype)
    elif kind == "mamba":
        p["mamba"] = init_mamba_params(ks[0], cfg, dtype)
    elif kind.startswith("hybrid"):
        p["attn"] = init_attn_params(ks[0], cfg, dtype)
        p["mamba"] = init_mamba_params(ks[1], cfg, dtype)
    else:
        raise ValueError(kind)
    if cfg.d_ff > 0:
        if not cfg.parallel_block:
            p["norm2"] = jnp.zeros((cfg.d_model,), jnp.float32)
        p["ffn"] = (init_moe_params(ks[2], cfg, dtype) if use_moe
                    else init_mlp_params(ks[2], cfg, dtype))
    if cfg.norm == "layer" or cfg.linear_bias:
        _drawn_block_leaves(key, cfg, p)
    return p


def apply_block(p: dict, x: jax.Array, kind: str, use_moe: bool, cfg, *,
                positions: jax.Array,
                cache: Optional[dict] = None,
                pos: Optional[jax.Array] = None,
                valid_len: Optional[jax.Array] = None,
                tap=None, use_pallas: bool = False,
                paged_attention: bool = False
                ) -> Tuple[jax.Array, Optional[dict], jax.Array]:
    """Returns (x_out, new_cache, moe_aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    with jax.named_scope("norm"):
        h = block_norm(x, p, "norm1", cfg)
    new_cache = {}
    window = cfg.window if kind.endswith("_local") else None
    # attention takes valid_len as an ABSOLUTE position bound (chunked
    # prefill runs at positions start..start+S-1); the SSM recurrence
    # wants the count of valid columns in THIS input window
    ssm_valid = (valid_len - positions[:, 0]
                 if valid_len is not None else None)

    if kind.startswith("attn"):
        with jax.named_scope("attn"):
            mix, ac = attn_block(p["attn"], h, cfg, positions=positions,
                                 window=window,
                                 cache=cache.get("attn") if cache else None,
                                 pos=pos, valid_len=valid_len,
                                 tap=_sub(tap, "attn"),
                                 use_pallas=use_pallas,
                                 paged_attention=paged_attention)
        if ac is not None:
            new_cache["attn"] = ac
    elif kind == "mamba":
        mix, mc = mamba_block(p["mamba"], h, cfg,
                              cache=cache.get("mamba") if cache else None,
                              valid_len=ssm_valid,
                              tap=_sub(tap, "mamba"), use_pallas=use_pallas)
        if mc is not None:
            new_cache["mamba"] = mc
    elif kind.startswith("hybrid"):
        mix_a, ac = attn_block(p["attn"], h, cfg, positions=positions,
                               window=window,
                               cache=cache.get("attn") if cache else None,
                               pos=pos, valid_len=valid_len,
                               tap=_sub(tap, "attn"),
                               use_pallas=use_pallas,
                               paged_attention=paged_attention)
        mix_m, mc = mamba_block(p["mamba"], h, cfg,
                                cache=cache.get("mamba") if cache else None,
                                valid_len=ssm_valid,
                                tap=_sub(tap, "mamba"),
                                use_pallas=use_pallas)
        mix = 0.5 * (mix_a + mix_m)
        if ac is not None:
            new_cache["attn"] = ac
        if mc is not None:
            new_cache["mamba"] = mc
    else:
        raise ValueError(kind)
    x = x + mix

    if "ffn" in p:
        if not cfg.parallel_block:   # else the FFN reads the mixer's norm
            with jax.named_scope("norm"):
                h = block_norm(x, p, "norm2", cfg)
        with jax.named_scope("mlp"):
            if use_moe:
                y, aux = moe_block(p["ffn"], h, cfg, tap=_sub(tap, "moe"),
                                   use_pallas=use_pallas)
            else:
                y = glu_mlp(h, p["ffn"], cfg.act, cfg.gated_mlp,
                            use_pallas=use_pallas, tap=_sub(tap, "ffn"))
        x = x + y
    return x, (new_cache or None), aux


def _sub(tap, prefix):
    if tap is None:
        return None

    def inner(name, value):
        tap(f"{prefix}/{name}", value)
    return inner
