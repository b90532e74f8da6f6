"""Uniform affine quantizers and scale optimization.

All quantizers here are symmetric, per-channel, and functional. Codes are
kept in float32/int8 depending on context; dequantization is `codes * scale`.

The noise-aware scale search implements Eq. (5)-(7) of the paper: the
expected distortion of storing Q(W; s) in a noisy MLC memory is

    L(s) ~= ||W - Q(W; s)||^2 + N * (p_- + p_+) * Delta(s)^2

with Delta(s) = s for a uniform quantizer. We minimize L over a grid of
candidate scales per channel.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.qconfig import NoiseModel


def qrange(bits: int) -> Tuple[int, int]:
    """Symmetric signed range for `bits` (e.g. 3 -> [-4, 3])."""
    return -(2 ** (bits - 1)), 2 ** (bits - 1) - 1


def quantize_codes(w: jax.Array, scale: jax.Array, bits: int) -> jax.Array:
    """Round-to-nearest codes, clipped to the signed range. Float carrier."""
    qmin, qmax = qrange(bits)
    s = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(w / s), qmin, qmax)


def dequantize(codes: jax.Array, scale: jax.Array) -> jax.Array:
    return codes.astype(scale.dtype) * scale


def fake_quant(w: jax.Array, scale: jax.Array, bits: int) -> jax.Array:
    return dequantize(quantize_codes(w, scale, bits), scale)


def _move_channel_last(w: jax.Array, channel_axis: int) -> jax.Array:
    if channel_axis in (-1, w.ndim - 1):
        return w
    return jnp.moveaxis(w, channel_axis, -1)


def minmax_scale(w: jax.Array, bits: int, channel_axis: int = -1,
                 eps: float = 1e-8) -> jax.Array:
    """Per-channel abs-max scale. Returns shape broadcastable against w."""
    qmin, qmax = qrange(bits)
    red = tuple(a for a in range(w.ndim) if a != channel_axis % w.ndim)
    amax = jnp.max(jnp.abs(w), axis=red, keepdims=True)
    return jnp.maximum(amax, eps) / float(qmax)


def _grid(lo: float, hi: float, n: int) -> jnp.ndarray:
    return jnp.linspace(lo, hi, n)


def mse_scale_search(w: jax.Array, bits: int, channel_axis: int = -1,
                     grid_lo: float = 0.3, grid_hi: float = 1.05,
                     grid_n: int = 48,
                     mask: Optional[jax.Array] = None) -> jax.Array:
    """Per-channel grid search minimizing ||W - Q(W;s)||^2 (Alg. 1, Step 3).

    `mask` (same shape as w, bool) restricts the objective to a subset of
    entries (used so inlier/outlier scale searches only see their own set).
    """
    return noise_aware_scale_search(
        w, bits, noise=None, channel_axis=channel_axis,
        grid_lo=grid_lo, grid_hi=grid_hi, grid_n=grid_n, mask=mask)


@functools.partial(jax.jit, static_argnames=(
    "bits", "noise", "channel_axis", "grid_lo", "grid_hi", "grid_n"))
def noise_aware_scale_search(
        w: jax.Array, bits: int, noise: Optional[NoiseModel],
        channel_axis: int = -1, grid_lo: float = 0.3, grid_hi: float = 1.05,
        grid_n: int = 48, mask: Optional[jax.Array] = None) -> jax.Array:
    """Per-channel grid search minimizing Eq. (7).

    With `noise=None` this degrades to the plain MSE objective (Step 3);
    otherwise the per-channel inlier count times (p-+p+) * s^2 penalizes
    large steps (Step 2). Runs as a fori-loop over grid points so peak
    memory stays O(|W|) instead of O(|W| * grid_n); jitted, so PTQ over
    many same-shaped matrices compiles the loop once per shape.
    """
    ch = channel_axis % w.ndim
    red = tuple(a for a in range(w.ndim) if a != ch)
    base = minmax_scale(w, bits, channel_axis=ch)
    if mask is None:
        n_per_ch = jnp.array(float(w.size) / w.shape[ch])
        wm = w
    else:
        mask = mask.astype(w.dtype)
        n_per_ch = jnp.sum(mask, axis=red, keepdims=True)
        wm = w * mask  # zeros contribute 0 to masked objective below

    p_flip = 0.0 if noise is None else float(noise.p_flip)
    alphas = _grid(grid_lo, grid_hi, grid_n)

    def objective(alpha):
        s = base * alpha
        deq = fake_quant(w, s, bits)
        err = (w - deq) if mask is None else (w - deq) * mask
        dist = jnp.sum(jnp.square(err), axis=red, keepdims=True)
        return dist + n_per_ch * p_flip * jnp.square(s)

    def body(i, carry):
        best_loss, best_alpha = carry
        loss = objective(alphas[i])
        take = loss < best_loss
        return (jnp.where(take, loss, best_loss),
                jnp.where(take, alphas[i], best_alpha))

    init = (jnp.full_like(base, jnp.inf), jnp.ones_like(base))
    _, best_alpha = jax.lax.fori_loop(0, grid_n, body, init)
    del wm
    return base * best_alpha


def rtn_quantize(w: jax.Array, bits: int = 4, channel_axis: int = -1
                 ) -> jax.Array:
    """Rounding-to-nearest baseline: per-channel abs-max scale, fake-quant."""
    s = minmax_scale(w, bits, channel_axis=channel_axis)
    return fake_quant(w, s, bits)


def expected_noise_mse(w: jax.Array, scale: jax.Array, bits: int,
                       noise: NoiseModel) -> jax.Array:
    """Closed-form E_e ||W - (Q(W;s)+e)||^2 under the +-1-step flip model."""
    deq = fake_quant(w, scale, bits)
    dist = jnp.sum(jnp.square(w - deq))
    step2 = jnp.sum(jnp.broadcast_to(jnp.square(scale), w.shape)) * noise.p_flip
    return dist + step2
