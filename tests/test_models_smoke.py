"""Per-architecture smoke tests: REDUCED config of the same family, one

forward/train step on CPU, asserting output shapes + no NaNs (assignment
requirement), plus decode-vs-forward consistency for the serving path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, PAPER_ARCHS, reduced_config
from repro.models.model import (decode_step, forward, init_params, prefill,
                                train_loss)

ALL = ASSIGNED_ARCHS + PAPER_ARCHS


def _batch_for(cfg, b=2, s=16):
    key = jax.random.PRNGKey(7)
    batch = {"tokens": jax.random.randint(key, (b, s), 0, cfg.vocab)}
    batch["labels"] = batch["tokens"]
    if cfg.n_vis_tokens:
        batch["vis_embeds"] = jnp.full((b, cfg.n_vis_tokens, cfg.d_model),
                                       0.01, jnp.float32)
    if cfg.is_encdec:
        batch["frames"] = jnp.full((b, cfg.enc_seq, cfg.d_model), 0.01,
                                   jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ALL)
def test_smoke_train_step(arch):
    cfg = reduced_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch_for(cfg)
    logits, _, _ = forward(cfg, params, batch["tokens"],
                           vis_embeds=batch.get("vis_embeds"),
                           frames=batch.get("frames"))
    assert logits.shape == (2, 16, cfg.vocab)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))
    loss, metrics = train_loss(cfg, params, batch, remat=False)
    assert np.isfinite(float(loss))
    grads = jax.grad(lambda p: train_loss(cfg, p, batch)[0])(params)
    gn = sum(float(jnp.sum(jnp.square(g)))
             for g in jax.tree_util.tree_leaves(grads))
    assert np.isfinite(gn) and gn > 0


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "gemma2-2b",
                                  "mamba2-370m", "jamba-1.5-large-398b",
                                  "dbrx-132b", "whisper-medium",
                                  "internvl2-2b", "hymba-1.5b",
                                  "phi-1.5b"])
def test_smoke_decode_matches_forward(arch):
    import dataclasses
    # capacity drops legitimately differ between full-forward (B*S tokens)
    # and decode (B tokens); disable drops for the consistency check
    cfg = dataclasses.replace(reduced_config(arch), capacity_factor=16.0)
    params = init_params(cfg, jax.random.PRNGKey(0))
    b, s = 2, 12
    batch = _batch_for(cfg, b, s)
    tokens = batch["tokens"]
    kw = {k: batch[k] for k in ("vis_embeds", "frames") if k in batch}
    full, _, _ = forward(cfg, params, tokens, **kw)
    lg, cache = prefill(cfg, params, tokens[:, : s - 3],
                        max_len=s + cfg.n_vis_tokens + 2,
                        cache_dtype=jnp.float32, **kw)
    errs = [float(jnp.max(jnp.abs(lg - full[:, s - 4])))]
    for i in range(s - 3, s):
        pos = i + cfg.n_vis_tokens
        lg, cache = decode_step(cfg, params, tokens[:, i:i + 1], cache,
                                jnp.asarray(pos))
        if i + 1 < s:
            errs.append(float(jnp.max(jnp.abs(lg - full[:, i]))))
    assert max(errs) < 5e-4, errs
