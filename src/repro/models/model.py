"""Top-level model API: init_params / forward / train_loss / prefill / decode.

One code path serves every assigned architecture; the config's `pattern`
(block kinds per group) plus family flags (encdec, vlm) select behaviour.
Layer groups run under lax.scan (HLO size independent of depth) unless
`scan_layers=False` (used for calibration taps and tiny smoke tests).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import blocks as B
from repro.models import kvcache as KV
from repro.models.attention import (attn_block, cross_attn_block,
                                    precompute_cross_kv)
from repro.models.config import ModelConfig
from repro.models.layers import (cross_entropy_loss, embed_tokens, glu_mlp,
                                 linear, rms_norm, softcap)

MOE_AUX_WEIGHT = 0.01


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------
def init_params(cfg: ModelConfig, key: jax.Array,
                dtype=jnp.float32) -> Dict[str, Any]:
    keys = jax.random.split(key, 8)
    d = cfg.d_model
    params: Dict[str, Any] = {
        "embed": {"tok": (jax.random.normal(keys[0], (cfg.vocab, d))
                          * 0.02).astype(dtype)},
        "final_norm": jnp.zeros((d,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(keys[1], (d, cfg.vocab))
                             * (d ** -0.5)).astype(dtype)
    drawn = {}
    if cfg.norm == "layer":
        drawn.update(final_norm=(d,), final_norm_bias=(d,))
    if cfg.linear_bias and not cfg.tie_embeddings:
        drawn["lm_head_bias"] = (cfg.vocab,)
    if drawn:
        params.update(B.affine_leaves(key, drawn))

    # decoder blocks, stacked over groups
    def one_group(gkey):
        ks = jax.random.split(gkey, len(cfg.pattern))
        return {f"b{i}": B.init_block(ks[i], kind, cfg.moe_slots[i], cfg,
                                      dtype)
                for i, kind in enumerate(cfg.pattern)}

    gkeys = jax.random.split(keys[2], cfg.n_groups)
    groups = [one_group(gk) for gk in gkeys]
    params["blocks"] = jax.tree_util.tree_map(
        lambda *ls: jnp.stack(ls), *groups)

    if cfg.is_encdec:
        def enc_layer(k):
            ks = jax.random.split(k, 2)
            return {"norm1": jnp.zeros((d,), jnp.float32),
                    "attn": B.init_attn_params(ks[0], cfg, dtype),
                    "norm2": jnp.zeros((d,), jnp.float32),
                    "ffn": B.init_mlp_params(ks[1], cfg, dtype)}

        def dec_xattn(k):
            return {"norm_x": jnp.zeros((d,), jnp.float32),
                    "xattn": B.init_attn_params(k, cfg, dtype)}

        ekeys = jax.random.split(keys[3], cfg.n_enc_layers)
        params["encoder"] = {
            "blocks": jax.tree_util.tree_map(
                lambda *ls: jnp.stack(ls), *[enc_layer(k) for k in ekeys]),
            "pos_emb": (jax.random.normal(keys[4], (cfg.enc_seq, d))
                        * 0.02).astype(dtype),
            "final_norm": jnp.zeros((d,), jnp.float32),
        }
        xkeys = jax.random.split(keys[5], cfg.n_layers)
        params["xattn"] = jax.tree_util.tree_map(
            lambda *ls: jnp.stack(ls), *[dec_xattn(k) for k in xkeys])
    return params


# --------------------------------------------------------------------------
# group application (one scan step)
# --------------------------------------------------------------------------
def _apply_group(cfg: ModelConfig, grp_params, x, grp_cache, positions, pos,
                 xattn_params=None, enc_kv=None, valid_len=None, tap=None,
                 use_pallas: bool = False, paged_attention: bool = False):
    aux_total = jnp.zeros((), jnp.float32)
    new_cache = {}
    for i, kind in enumerate(cfg.pattern):
        bp = grp_params[f"b{i}"]
        bc = grp_cache[f"b{i}"] if grp_cache is not None else None
        if xattn_params is not None:
            # encdec decoder: cross-attention between self-attn and FFN
            h = rms_norm(x, bp["norm1"], cfg.norm_eps)
            mix, ac = attn_block(bp["attn"], h, cfg, positions=positions,
                                 window=None,
                                 cache=bc.get("self") if bc else None,
                                 pos=pos, use_pallas=use_pallas)
            x = x + mix
            hx = rms_norm(x, xattn_params["norm_x"], cfg.norm_eps)
            kv = enc_kv if enc_kv is not None else bc["cross"]
            x = x + cross_attn_block(xattn_params["xattn"], hx, kv, cfg,
                                     use_pallas=use_pallas)
            h2 = rms_norm(x, bp["norm2"], cfg.norm_eps)
            x = x + glu_mlp(h2, bp["ffn"], cfg.act, cfg.gated_mlp,
                            use_pallas=use_pallas)
            nc = {}
            if ac is not None:
                nc["self"] = ac
                nc["cross"] = kv
            new_cache[f"b{i}"] = nc or None
        else:
            x, nc, aux = B.apply_block(
                bp, x, kind, cfg.moe_slots[i], cfg, positions=positions,
                cache=bc, pos=pos, valid_len=valid_len,
                tap=_tap_prefix(tap, f"b{i}"), use_pallas=use_pallas,
                paged_attention=paged_attention)
            new_cache[f"b{i}"] = nc
            aux_total = aux_total + aux
    any_cache = any(v is not None for v in new_cache.values())
    return x, (new_cache if any_cache else None), aux_total


def _tap_prefix(taps, prefix):
    if taps is None:
        return None

    def inner(name, value):
        taps(f"{prefix}/{name}", value)
    return inner


# --------------------------------------------------------------------------
# encoder (whisper)
# --------------------------------------------------------------------------
def encode(cfg: ModelConfig, params, frames: jax.Array,
           use_pallas: bool = False, scan_layers: bool = True) -> jax.Array:
    enc = params["encoder"]
    x = frames + enc["pos_emb"][None, : frames.shape[1]].astype(frames.dtype)
    b, t, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))

    def layer_fn(x, lp):
        h = rms_norm(x, lp["norm1"], cfg.norm_eps)
        q = linear(h, lp["attn"]["wq"], use_pallas=use_pallas
                   ).reshape(b, t, cfg.n_heads, cfg.head_dim)
        k = linear(h, lp["attn"]["wk"], use_pallas=use_pallas
                   ).reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
        v = linear(h, lp["attn"]["wv"], use_pallas=use_pallas
                   ).reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
        from repro.models.attention import attend
        o = attend(q, k, v, q_positions=positions, kv_positions=positions,
                   causal=False, window=None)
        x = x + linear(o.reshape(b, t, -1), lp["attn"]["wo"],
                       use_pallas=use_pallas, tp_dim=0)
        h2 = rms_norm(x, lp["norm2"], cfg.norm_eps)
        return x + glu_mlp(h2, lp["ffn"], cfg.act, cfg.gated_mlp,
                           use_pallas=use_pallas)

    if scan_layers:
        def body(carry, lp):
            return layer_fn(carry, lp), None
        x, _ = jax.lax.scan(body, x, enc["blocks"])
    else:
        n = jax.tree_util.tree_leaves(enc["blocks"])[0].shape[0]
        for i in range(n):
            lp = jax.tree_util.tree_map(lambda l: l[i], enc["blocks"])
            x = layer_fn(x, lp)
    return rms_norm(x, enc["final_norm"], cfg.norm_eps)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------
def forward(cfg: ModelConfig, params, tokens: jax.Array, *,
            positions: Optional[jax.Array] = None,
            cache=None, pos: Optional[jax.Array] = None,
            vis_embeds: Optional[jax.Array] = None,
            frames: Optional[jax.Array] = None,
            enc_out: Optional[jax.Array] = None,
            valid_len: Optional[jax.Array] = None,
            taps: Optional[dict] = None,
            use_pallas: bool = False, scan_layers: bool = True,
            remat: bool = False, skip_head: bool = False,
            paged_attention: bool = False
            ) -> Tuple[jax.Array, Any, jax.Array]:
    """Returns (logits [B,S_text,V], new_cache, moe_aux).

    skip_head=True returns the final-norm hidden states instead of logits
    (the chunked-CE loss fuses the lm_head into the loss).
    valid_len [B]: true lengths when tokens are right-padded to a prefill
    bucket — attention is causally immune to right padding, but the SSM
    recurrence needs it to keep its carried state clean."""
    b, s = tokens.shape
    x = embed_tokens(tokens, params["embed"]["tok"], cfg.scale_embed)

    n_vis = 0
    if cfg.n_vis_tokens and vis_embeds is not None:
        n_vis = vis_embeds.shape[1]
        x = jnp.concatenate([vis_embeds.astype(x.dtype), x], axis=1)

    total = x.shape[1]
    if positions is None:
        if pos is not None:  # decode step
            pos = jnp.asarray(pos, jnp.int32)
            positions = (jnp.full((b, total), pos, jnp.int32)
                         if pos.ndim == 0 else pos[:, None])
        else:
            positions = jnp.broadcast_to(jnp.arange(total)[None],
                                         (b, total))

    enc_kv_all = None
    if cfg.is_encdec:
        if enc_out is None and frames is not None:
            enc_out = encode(cfg, params, frames, use_pallas, scan_layers)
        if enc_out is not None:
            # per-layer cross KV, stacked: computed functionally inside scan
            pass

    grp = functools.partial(_apply_group, cfg, valid_len=valid_len,
                            use_pallas=use_pallas,
                            paged_attention=paged_attention)
    if remat:
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if cfg.remat_policy == "dots" else None)
        grp = jax.checkpoint(grp, policy=policy)

    aux_total = jnp.zeros((), jnp.float32)
    if cfg.is_encdec:
        # decoder layers are NOT grouped (pattern=("attn",)); scan over
        # layers with per-layer cross-attn params.
        xattn = params["xattn"]

        def dec_body(carry, inp):
            xx, aux = carry
            lp, xp, lc = inp
            kv = (precompute_cross_kv(xp["xattn"], enc_out, cfg, use_pallas)
                  if enc_out is not None else None)
            xx, nc, a = _apply_group(cfg, lp, xx, lc, positions, pos,
                                     xattn_params=xp, enc_kv=kv,
                                     use_pallas=use_pallas)
            return (xx, aux + a), nc

        blocks = params["blocks"]
        if scan_layers and taps is None:
            (x, aux_total), new_cache = jax.lax.scan(
                dec_body, (x, aux_total), (blocks, xattn, cache))
        else:
            ncs = []
            n = cfg.n_groups
            for i in range(n):
                lp = jax.tree_util.tree_map(lambda l: l[i], blocks)
                xp = jax.tree_util.tree_map(lambda l: l[i], xattn)
                lc = (jax.tree_util.tree_map(lambda l: l[i], cache)
                      if cache is not None else None)
                (x, aux_total), nc = dec_body((x, aux_total), (lp, xp, lc))
                ncs.append(nc)
            new_cache = (jax.tree_util.tree_map(lambda *ls: jnp.stack(ls),
                                                *ncs)
                         if ncs and ncs[0] is not None else None)
    else:
        def body(carry, inp):
            xx, aux = carry
            lp, lc = inp
            xx, nc, a = grp(lp, xx, lc, positions, pos)
            return (xx, aux + a), nc

        if scan_layers and taps is None:
            (x, aux_total), new_cache = jax.lax.scan(
                body, (x, aux_total), (params["blocks"], cache))
        else:
            ncs = []
            for i in range(cfg.n_groups):
                lp = jax.tree_util.tree_map(lambda l: l[i], params["blocks"])
                lc = (jax.tree_util.tree_map(lambda l: l[i], cache)
                      if cache is not None else None)
                x, nc, a = _apply_group(
                    cfg, lp, x, lc, positions, pos, valid_len=valid_len,
                    tap=_make_tap(taps, i), use_pallas=use_pallas,
                    paged_attention=paged_attention)
                aux_total = aux_total + a
                ncs.append(nc)
            new_cache = (jax.tree_util.tree_map(lambda *ls: jnp.stack(ls),
                                                *ncs)
                         if ncs and ncs[0] is not None else None)

    with jax.named_scope("norm"):
        x = B.block_norm(x, params, "final_norm", cfg)
    if n_vis:
        x = x[:, n_vis:]
    if skip_head:
        return x, new_cache, aux_total
    return apply_head(cfg, params, x, use_pallas), new_cache, aux_total


def apply_head(cfg: ModelConfig, params, x: jax.Array,
               use_pallas: bool = False) -> jax.Array:
    """The LM head on final-norm hidden states ``x [..., d]``: the tied
    embedding or ``lm_head`` (with ``lm_head_bias`` where the config has
    one), then the logit softcap. ``forward`` ends with it; the serving
    step runs it on the few rows that emit a token."""
    with jax.named_scope("head"):
        if cfg.tie_embeddings:
            logits = jnp.matmul(x, params["embed"]["tok"].T.astype(x.dtype))
        else:
            logits = linear(x, params["lm_head"],
                            params.get("lm_head_bias"),
                            use_pallas=use_pallas)
        return softcap(logits, cfg.logit_softcap)


def _make_tap(taps, layer_idx):
    if taps is None:
        return None

    def inner(name, value):
        key = f"blocks/{layer_idx}/{name}"
        prev = taps.get(key)
        v = value.reshape(-1, value.shape[-1])
        # subsample calibration rows to bound memory
        if v.shape[0] > 512:
            v = v[:: v.shape[0] // 512][:512]
        taps[key] = v if prev is None else jnp.concatenate([prev, v])
    return inner


# --------------------------------------------------------------------------
# public steps
# --------------------------------------------------------------------------
def train_loss(cfg: ModelConfig, params, batch: Dict[str, jax.Array], *,
               use_pallas: bool = False, scan_layers: bool = True,
               remat: bool = True) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    # the chunked loss fuses a bias-free head; a head bias takes the plain one
    if (cfg.chunked_ce and batch.get("loss_mask") is None
            and "lm_head_bias" not in params):
        from repro.models.chunked_ce import sharded_ce_loss
        hidden, _, aux = forward(
            cfg, params, batch["tokens"],
            vis_embeds=batch.get("vis_embeds"),
            frames=batch.get("frames"), use_pallas=use_pallas,
            scan_layers=scan_layers, remat=remat, skip_head=True)
        w_head = (params["embed"]["tok"].T if cfg.tie_embeddings
                  else params["lm_head"])
        loss = sharded_ce_loss(hidden, w_head, batch["labels"],
                               logit_softcap=cfg.logit_softcap)
    else:
        logits, _, aux = forward(
            cfg, params, batch["tokens"],
            vis_embeds=batch.get("vis_embeds"), frames=batch.get("frames"),
            use_pallas=use_pallas, scan_layers=scan_layers, remat=remat)
        loss = cross_entropy_loss(logits, batch["labels"],
                                  batch.get("loss_mask"))
    total = loss + MOE_AUX_WEIGHT * aux
    return total, {"loss": loss, "aux": aux}


def prefill(cfg: ModelConfig, params, tokens: jax.Array, *,
            max_len: int, cache_dtype=jnp.bfloat16,
            vis_embeds: Optional[jax.Array] = None,
            frames: Optional[jax.Array] = None,
            use_pallas: bool = False, scan_layers: bool = True):
    """Full forward over the prompt; returns (last_logits, cache)."""
    b = tokens.shape[0]
    if cfg.is_encdec:
        cache = KV.init_encdec_cache(cfg, b, max_len, cache_dtype)
        enc_out = encode(cfg, params, frames, use_pallas, scan_layers)
        logits, new_cache, _ = forward(
            cfg, params, tokens, cache=_encdec_cache_names(cache),
            enc_out=enc_out, use_pallas=use_pallas, scan_layers=scan_layers)
    else:
        cache = KV.init_cache(cfg, b, max_len, cache_dtype)
        logits, new_cache, _ = forward(
            cfg, params, tokens, cache=cache, vis_embeds=vis_embeds,
            use_pallas=use_pallas, scan_layers=scan_layers)
    return logits[:, -1], new_cache


def _encdec_cache_names(cache):
    # encdec caches are stored as {"self":..., "cross":...} per layer but the
    # scan body expects {"b0": {...}}
    return {"b0": cache}


def decode_step(cfg: ModelConfig, params, token: jax.Array, cache,
                pos: jax.Array, *, use_pallas: bool = False,
                scan_layers: bool = True, paged_attention: bool = False):
    """One token step. token [B,1]; pos scalar int32 (current position).

    ``paged_attention=True``: paged caches attend through the Pallas
    page-table kernel instead of the full-width XLA gather."""
    logits, new_cache, _ = forward(
        cfg, params, token, cache=cache, pos=pos,
        use_pallas=use_pallas, scan_layers=scan_layers,
        paged_attention=paged_attention)
    return logits[:, -1], new_cache
