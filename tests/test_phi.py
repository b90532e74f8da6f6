"""Phi-1.5 as published (parallel attention and MLP from one LayerNorm with
bias, biases on every projection and on the head, tanh GELU) through the
serving path, against the plain reference in ``phi_reference.py``.

The engine's normal path (paged cache, chunked prefill, then decode, QMC
code-plane weights) is compared with the reference's full forward on the
logits of every position the step computes, captured inside the step just
before token selection. The reference reads the weights the step reads:
the code planes dequantized. Three faults planted in the program must each
fail the same comparison. StableLM's tree and logits are pinned to values
taken before the Phi block existed.
"""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import phi_reference
from repro.configs import get_config, reduced_config
from repro.core.apply import path_str
from repro.core.qconfig import QMCConfig
from repro.core.qtensor import CodePlane
from repro.core.serving_quant import (build_exec_weights,
                                      quantize_for_serving,
                                      serving_params_struct)
from repro.models import blocks
from repro.models.layers import rms_norm
from repro.models.model import apply_head, forward, init_params
from repro.serve import steps as serve_steps
from repro.serve.engine import Request, ServeEngine

QMC = QMCConfig(rho=0.3, granularity="subtile")
MATMULS = ("wq", "wk", "wv", "wo", "w_up", "w_down", "lm_head")
# engine against reference, float32 on both sides over the same weights:
# the paged step and the reference sum in different orders (attention
# over pages in chunks, fused bias epilogues), which leaves gaps of about
# 1e-6 (measured 1.8e-6); the planted faults below move the logits by
# 0.27 (head bias), 0.79 (RMSNorm) and 3.7 (sequential block)
LOGIT_TOL = 2e-5
PROMPTS = (21, 13)        # prompt lengths: three and two prefill chunks
NEW_TOKENS = 5


def phi_cfg():
    """The reduced Phi preset, at MHA as published (the preset halves the
    KV heads), so that wk and wv tile into QMC subtiles too."""
    cfg = reduced_config("phi-1.5b")
    return dataclasses.replace(cfg, n_kv_heads=cfg.n_heads)


@pytest.fixture(scope="module")
def cfg():
    return phi_cfg()


@pytest.fixture(scope="module")
def qparams(cfg):
    params = init_params(cfg, jax.random.PRNGKey(3))
    return quantize_for_serving(params, QMC, tp_shards=1, min_dim=64)


def served_dense(qparams):
    """The weights the step reads, as a dense float32 tree."""
    exec_tree = build_exec_weights(qparams)
    return jax.tree_util.tree_map(
        lambda x: x.dequantize(jnp.float32) if isinstance(x, CodePlane)
        else x, exec_tree, is_leaf=lambda x: isinstance(x, CodePlane))


@pytest.fixture
def step_logits(monkeypatch):
    """Logits of every live column of every step, by absolute position
    (one request at a time, so lane 0), recorded from inside the step:
    the head over the final hidden states of all columns (the step
    itself runs it only on the columns that emit)."""
    seen = []
    real = serve_steps._forward

    def record(logits, positions, n_new):
        n = int(n_new[0])
        for t in range(n):
            seen.append((int(positions[0, t]), np.asarray(logits[0, t])))

    def forward_all(cfg, params, tokens, **kw):
        out = real(cfg, params, tokens, **kw)
        positions = kw["positions"]
        jax.debug.callback(record, apply_head(cfg, params, out[0]),
                           positions, kw["valid_len"] - positions[:, 0])
        return out

    monkeypatch.setattr(serve_steps, "_forward", forward_all)
    serve_steps._single_device_steps.cache_clear()
    yield seen
    serve_steps._single_device_steps.cache_clear()


def engine_gap(cfg, step_params, ref_params, seen):
    """Largest |engine logit - reference logit| over every position of
    each request, served one at a time through chunked prefill (chunks of
    8) and greedy decode."""
    eng = ServeEngine(cfg, step_params, slots=1, max_len=40, page_size=8,
                      chunk_tokens=8)
    rng = np.random.default_rng(11)
    gaps = []
    for uid, n in enumerate(PROMPTS):
        seen.clear()
        req = Request(uid=uid, prompt=rng.integers(
            2, cfg.vocab, size=n).astype(np.int32),
            max_new_tokens=NEW_TOKENS)
        eng.run([req])
        assert len(req.out_tokens) == NEW_TOKENS
        full = np.concatenate([req.prompt, req.out_tokens[:-1]])
        ref = np.asarray(phi_reference.forward(cfg, ref_params, full))
        got = dict(seen)
        # every prompt position (prefill chunks) and every decode step
        assert sorted(got) == list(range(len(full)))
        gaps.append(max(float(np.max(np.abs(got[p] - ref[p])))
                        for p in got))
    return max(gaps)


def test_engine_matches_reference_logits(cfg, qparams, step_logits):
    ref_params = served_dense(qparams)
    gap = engine_gap(cfg, qparams, ref_params, step_logits)
    assert gap < LOGIT_TOL, gap


def test_head_bias_left_out_fails(cfg, qparams, step_logits):
    planted = {k: v for k, v in qparams.items() if k != "lm_head_bias"}
    gap = engine_gap(cfg, planted, served_dense(qparams), step_logits)
    assert gap > 10 * LOGIT_TOL, gap


def test_sequential_block_fails(cfg, qparams, step_logits):
    """The same leaves through the sequential block: the MLP reads the
    LayerNorm of x + attn instead of the one shared norm."""
    b0 = dict(qparams["blocks"]["b0"])
    b0["norm2"], b0["norm2_bias"] = b0["norm1"], b0["norm1_bias"]
    planted = dict(qparams, blocks={"b0": b0})
    seq_cfg = dataclasses.replace(cfg, parallel_block=False)
    gap = engine_gap(seq_cfg, planted, served_dense(qparams), step_logits)
    assert gap > 10 * LOGIT_TOL, gap


def test_rms_norm_for_layer_norm_fails(cfg, qparams, step_logits,
                                       monkeypatch):
    """RMSNorm (no mean taken out) with the same gains and biases."""
    monkeypatch.setattr(blocks, "layer_norm",
                        lambda x, s, b, eps: rms_norm(x, s, eps) + b)
    gap = engine_gap(cfg, qparams, served_dense(qparams), step_logits)
    assert gap > 10 * LOGIT_TOL, gap


# ---------------------------------------------------------------------------
# the tree: parameter count, drawn leaves, serving plan
# ---------------------------------------------------------------------------


def test_param_counts():
    assert get_config("phi-1.5b").param_count() == 1_418_270_720
    # StableLM's count as it was before the Phi block (qkv biases, which
    # it keeps at zero, have never been counted)
    assert get_config("stablelm-1.6b").param_count() == 1_644_267_520
    for cfg in (reduced_config("phi-1.5b"), phi_cfg()):
        params = jax.eval_shape(lambda: init_params(
            cfg, jax.random.PRNGKey(0)))
        assert sum(int(np.prod(x.shape)) for x in
                   jax.tree_util.tree_leaves(params)) == cfg.param_count()


@pytest.mark.parametrize("stack", [dict(pattern=("attn", "mamba")),
                                   dict(n_experts=4, topk=2,
                                        moe_pattern=(True,))],
                         ids=["mamba", "moe"])
@pytest.mark.parametrize("field", ["parallel_block", "linear_bias"])
def test_block_form_refused_outside_dense_attention(field, stack):
    """The parallel block and the biases exist only for attention blocks
    with a dense FFN (the mamba mixer and the MoE FFN carry no biases)."""
    base = reduced_config("stablelm-1.6b")
    with pytest.raises(ValueError, match="attention-only, dense-FFN"):
        dataclasses.replace(base, **{field: True}, **stack)


def test_bias_and_norm_leaves_are_drawn(cfg):
    """Every bias and LayerNorm leaf moves the output: none is zero."""
    params = init_params(cfg, jax.random.PRNGKey(0))
    names = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        p = path_str(path)
        name = p.rsplit("/", 1)[-1]
        if "norm" in name or "bias" in name or name.startswith("b"):
            names.append(name)
            assert float(jnp.min(jnp.abs(leaf))) > 0.0, p
            want = 0.1 if name == "lm_head_bias" else 0.02
            assert 0.5 * want < float(jnp.std(leaf)) < 2 * want, p
    assert sorted(names) == sorted(
        ["norm1", "norm1_bias", "bq", "bk", "bv", "bo", "b_up", "b_down",
         "final_norm", "final_norm_bias", "lm_head_bias"])


def test_serving_plan_keeps_biases_and_norms_dense(cfg, qparams):
    """Exactly the seven matmul leaves become code planes; every bias and
    norm leaf stays dense float32; the plan's bytes are the hand count."""
    exec_tree = build_exec_weights(qparams)
    coded, dense = [], []
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            exec_tree, is_leaf=lambda x: isinstance(x, CodePlane))[0]:
        name = path_str(path).rsplit("/", 1)[-1]
        if isinstance(leaf, CodePlane):
            coded.append(name)
        else:
            dense.append(name)
            assert leaf.dtype == jnp.float32, name
    assert sorted(coded) == sorted(MATMULS)
    assert sorted(dense) == sorted(
        ["tok", "norm1", "norm1_bias", "bq", "bk", "bv", "bo", "b_up",
         "b_down", "final_norm", "final_norm_bias", "lm_head_bias"])

    eng = ServeEngine(cfg, qparams, slots=1, max_len=32, page_size=8)
    g, d, ff, v = cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.vocab

    def plane(layers, din, dout):       # int8 codes + two f32 scale rows
        return layers * (din * dout + 2 * dout * 4)

    want = (4 * plane(g, d, d) + plane(g, d, ff) + plane(g, ff, d)
            + plane(1, d, v))
    assert (eng.stats.exec_leaves_coded, eng.stats.exec_leaves_dense) == \
        (7, 0)
    assert eng.stats.exec_weight_bytes == want == 346_112


def test_published_tree_quantizes_only_matmuls():
    """At the published sizes (shapes only): the stacked [24, d] and
    [24, d_ff] bias and norm leaves and the [vocab] head bias stay out of
    PTQ, and the seven matmuls go in."""
    cfg = get_config("phi-1.5b")
    struct = jax.eval_shape(lambda: init_params(cfg,
                                                jax.random.PRNGKey(0)))
    served = serving_params_struct(struct, QMC, tp_shards=1, min_dim=64)
    flat = jax.tree_util.tree_flatten_with_path(
        served, is_leaf=lambda x: not isinstance(x, dict))[0]
    kept = {path_str(p).rsplit("/", 1)[-1]: leaf for p, leaf in flat
            if isinstance(leaf, jax.ShapeDtypeStruct)}
    quantized = sorted(path_str(p).rsplit("/", 1)[-1] for p, leaf in flat
                       if not isinstance(leaf, jax.ShapeDtypeStruct))
    assert quantized == sorted(MATMULS)
    assert kept["b_up"].shape == (24, 8192)
    assert kept["norm1_bias"].shape == (24, 2048)
    assert kept["lm_head_bias"].shape == (51200,)
    assert all(leaf.dtype == jnp.float32 for leaf in kept.values())


# ---------------------------------------------------------------------------
# StableLM stays bit for bit
# ---------------------------------------------------------------------------

# taken on the tree before the Phi block: SHA-256 over every leaf's path,
# dtype, shape and bytes of the reduced StableLM init at PRNGKey(0)
STABLELM_TREE_SHA256 = \
    "4968f1fb422ee6da73e3a8b5b8685bfce5a73ac7d371d93ca1454c85b773e76d"
# and a slice of one reduced forward's logits, with their sum (a CPU
# matmul may block its sums differently on another host, which moves the
# last bits; any change to StableLM's mathematics moves these by far more)
STABLELM_LOGITS_SUM = -23.685470581054688
STABLELM_LOGITS_0_0_8 = [
    0.7939463, -0.425176, 0.7796054, 0.8785787, 1.1366124, -0.8508072,
    -0.0780156, -0.3073408]


def test_stablelm_tree_and_logits_unchanged():
    cfg = reduced_config("stablelm-1.6b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        h.update(path_str(path).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == STABLELM_TREE_SHA256
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        2, cfg.vocab, size=(2, 16)), jnp.int32)
    logits, _, _ = forward(cfg, params, tokens)
    np.testing.assert_allclose(float(jnp.sum(logits)), STABLELM_LOGITS_SUM,
                               rtol=0, atol=1e-3)
    np.testing.assert_allclose(np.asarray(logits[0, 0, :8]),
                               STABLELM_LOGITS_0_0_8, rtol=0, atol=1e-5)
