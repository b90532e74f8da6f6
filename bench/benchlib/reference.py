"""The plain float32 reference: weights from the seed, QMC, forward pass.

It imports nothing of the program and takes nothing the program made.
From ``weight_seed`` it draws the same random initial weights the model
definition draws (``jax.random`` with the model's key layout), quantizes
every matrix with its own copy of the QMC post-training quantizer
(subtile outlier selection, noise-aware inlier scale search, plain MSE
outlier scale search, round-to-nearest codes), dequantizes, and runs the
block of the configuration's architecture at ``precision="highest"``,
layer by layer, so that it fits on one chip once the program's state is
freed. ``matmul="fp8"`` is the control: every matmul operand rounded to
float8 e4m3 with a per-tensor scale, the step below the bfloat16 passes
the configuration states.

What every architecture shares lives here: the quantizer, the matmul and
its control, rotary, causal attention, RMSNorm, the tanh GELU, the model
definition's top-level keys and the embedding rows, and ``served_gaps``.
The block lives in ``bench/references/<name>.py``, the module that the
configuration file's ``"reference"`` key names (``harness.load_cell``
loads it). Such a module defines

  hidden_states(model, weights, seed, tokens, matmul)  final hidden states
                                                       [N, L, d]
  head(model, weights, seed)          the LM head's served weights
  logits(model, x, head, matmul)      final norm and head: [M, vocab]

and, for ``counts.py``, the operations and bytes that depend on the block:

  matmuls(model)                      ([(name, din, dout, copies)], head
                                      (din, dout)): every weight matrix a
                                      token is multiplied by, with how
                                      many of it the model holds
  other_bytes(model, batch, dtype)    norms, biases and embedding rows a
                                      decode step of ``batch`` lanes reads
  state_bytes(model, batch, ctx_tokens, dtype)
                                      cached state that step reads
  attn_flops(model, ctx_tokens)       attention of one query per lane
                                      over ``ctx_tokens`` cached positions
  seq_attn_flops(model, n)            attention of ``n`` positions
                                      processed in order
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# the model definition's top-level keys
# ---------------------------------------------------------------------------


def _normal(key, shape, std):
    return jax.random.normal(key, shape) * std


def top_keys(seed: int):
    """The model definition's eight top-level keys: [0] embedding,
    [1] LM head, [2] split into one key per group of layers (one layer a
    group where the block pattern has one slot)."""
    return jax.random.split(jax.random.PRNGKey(seed), 8)


# ---------------------------------------------------------------------------
# QMC post-training quantization (Algorithm 1, subtile granularity)
# ---------------------------------------------------------------------------


def _qrange(bits: int) -> Tuple[int, int]:
    return -(2 ** (bits - 1)), 2 ** (bits - 1) - 1


def _codes(w, scale, bits):
    lo, hi = _qrange(bits)
    s = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(w / s), lo, hi)


def _scale_search(w, mask, bits: int, p_flip: float, grid):
    """Per-output-channel scale minimizing the masked squared error plus
    ``n * p_flip * s^2`` (the expected cost of +-1 code flips), over
    ``alpha * absmax / qmax`` for alpha on the grid (first minimum)."""
    _, hi = _qrange(bits)
    base = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True),
                       1e-8) / float(hi)
    m = mask.astype(w.dtype)
    n = jnp.sum(m, axis=0, keepdims=True)
    alphas = jnp.linspace(grid[0], grid[1], grid[2])

    def body(i, carry):
        best_loss, best_alpha = carry
        s = base * alphas[i]
        deq = _codes(w, s, bits).astype(s.dtype) * s
        err = (w - deq) * m
        loss = (jnp.sum(jnp.square(err), axis=0, keepdims=True)
                + n * p_flip * jnp.square(s))
        take = loss < best_loss
        return (jnp.where(take, loss, best_loss),
                jnp.where(take, alphas[i], best_alpha))

    init = (jnp.full_like(base, jnp.inf), jnp.ones_like(base))
    _, best = jax.lax.fori_loop(0, grid[2], body, init)
    return base * best


def _outlier_subtiles(w, rho: float, subtile):
    """[gr, gc] mask of exactly round(rho * n) subtiles with the largest
    max |w| (ties to the first in row-major order)."""
    r, c = subtile
    gr, gc = w.shape[0] // r, w.shape[1] // c
    score = jnp.max(jnp.abs(w.reshape(gr, r, gc, c)), axis=(1, 3)).reshape(-1)
    n = gr * gc
    k = int(round(rho * n))
    if k <= 0:
        return jnp.zeros((gr, gc), bool)
    if k >= n:
        return jnp.ones((gr, gc), bool)
    thresh = jnp.sort(score)[n - k]
    sel = score >= thresh
    sel = sel & (jnp.cumsum(sel.astype(jnp.int32)) <= k)
    return sel.reshape(gr, gc)


def qmc_dequant(w, weights: dict):
    """The dense matrix a QMC-served step multiplies by."""
    r, c = weights["subtile"]
    sub = _outlier_subtiles(w, weights["rho"], (r, c))
    emask = jnp.repeat(jnp.repeat(sub, r, axis=0), c, axis=1)
    grid = tuple(weights["scale_grid"])
    s_in = _scale_search(w, ~emask, weights["bits_in"], weights["p_flip"],
                         grid)
    s_out = _scale_search(w, emask, weights["bits_out"], 0.0, grid)
    d_in = _codes(w, s_in, weights["bits_in"]).astype(jnp.float32)
    d_out = _codes(w, s_out, weights["bits_out"]).astype(jnp.float32)
    return jnp.where(emask, d_out, d_in) * jnp.where(emask, s_out, s_in)


def quantizable(shape, weights: dict) -> bool:
    r, c = weights["subtile"]
    return (min(shape) >= weights["min_dim"] and shape[0] % r == 0
            and shape[1] % c == 0)


def served_matrix(w, weights: dict):
    return qmc_dequant(w, weights) if quantizable(w.shape, weights) else w


# ---------------------------------------------------------------------------
# forward pass
# ---------------------------------------------------------------------------


def _fp8(x):
    """Round to float8 e4m3 under a per-tensor scale (the control)."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = amax / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, matmul: str):
    if matmul == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision="highest")


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _rotary(x, positions, rotary_pct, theta):
    """x [L, H, hd]: rotate-half on the first rotary_pct of each head."""
    hd = x.shape[-1]
    d_rot = int(hd * rotary_pct) // 2 * 2
    inv = 1.0 / (theta ** (jnp.arange(0, d_rot, 2, dtype=jnp.float32)
                           / d_rot))
    ang = positions.astype(jnp.float32)[:, None] * inv      # [L, d_rot/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d_rot // 2], x[..., d_rot // 2:d_rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., d_rot:]], axis=-1)


def _attention(q, k, v, matmul: str):
    """Causal attention of one sequence: q [L, H, hd], k/v [L, KV, hd]."""
    L, h, hd = q.shape
    g = h // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    qh, kh, vh = (jnp.swapaxes(t, 0, 1) for t in (q, k, v))  # [H, L, hd]
    if matmul == "fp8":
        qh, kh = _fp8(qh), _fp8(kh)
    s = jnp.einsum("hqd,hkd->hqk", qh, kh, precision="highest") \
        * (hd ** -0.5)
    causal = jnp.tril(jnp.ones((L, L), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -1e30), axis=-1)
    if matmul == "fp8":
        p, vh = _fp8(p), _fp8(vh)
    o = jnp.einsum("hqk,hkd->hqd", p, vh, precision="highest")
    return jnp.swapaxes(o, 0, 1).reshape(L, h * hd)


@functools.partial(jax.jit, static_argnames=("model_items",))
def _embed_rows(tokens, key, *, model_items):
    model = dict(model_items)
    table = _normal(key, (model["vocab"], model["d_model"]), 0.02)
    return jnp.take(table, tokens, axis=0)


def _items(d: dict):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in d.items()))


def served_gaps(arch, model: dict, weights: dict, seed: int,
                seqs: Sequence[Tuple[np.ndarray, List[int]]],
                controls: Sequence[str] = ()) -> Dict[str, np.ndarray]:
    """Per served token, how far its reference logit lies below the
    reference's best at that position, through ``arch``, the
    configuration's module under ``bench/references/``.

    ``seqs`` holds (prompt, served tokens) pairs. Returns ``{"served":
    gaps}`` and, for each control precision in ``controls``, the gap of
    the token that precision puts first at the same positions."""
    n = len(seqs)
    L = max(len(p) + len(o) for p, o in seqs)
    L = -(-L // 128) * 128
    tokens = np.zeros((n, L), np.int32)
    rows, cols, served = [], [], []
    for i, (p, o) in enumerate(seqs):
        full = np.concatenate([np.asarray(p), np.asarray(o, np.int32)])
        tokens[i, :len(full)] = full
        for j, t in enumerate(o):           # logits at position len(p)-1+j
            rows.append(i)
            cols.append(len(p) - 1 + j)
            served.append(int(t))
    rows, cols = np.asarray(rows), np.asarray(cols)
    served = np.asarray(served)
    head = arch.head(model, weights, seed)
    out = {}
    with jax.default_matmul_precision("highest"):
        x = arch.hidden_states(model, weights, seed, tokens)
        ref = arch.logits(model, x[rows, cols], head, "highest")
        del x
        best = jnp.max(ref, axis=-1)
        out["seq"] = rows
        out["served"] = np.asarray(
            best - ref[jnp.arange(len(served)), served])
        for c in controls:
            xc = arch.hidden_states(model, weights, seed, tokens, matmul=c)
            lc = arch.logits(model, xc[rows, cols], head, c)
            top = jnp.argmax(lc, axis=-1)
            out[c] = np.asarray(best - ref[jnp.arange(len(served)), top])
    return out
