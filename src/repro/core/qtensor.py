"""QTensor — the QMC-TPU deployment format (dual-stream quantized weights).

A weight matrix W[din, dout] is tiled into (8, 128) subtiles. The rho
fraction of subtiles with the largest max-|w| form the *outlier stream*
(5-bit codes in an int8 container); the rest form the *inlier stream*
(3-bit codes in an int4/int8 container, scale chosen noise-aware). A
per-subtile tag + stream position index reconstructs the dense tile — the
role the paper's Model Weight Controller plays when merging MRAM and ReRAM
fetches.

QTensor is a registered JAX pytree: it flows through jit/pjit/shardings and
optimizer-free serving paths like any other parameter leaf.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import partition as part
from repro.core.qconfig import QMCConfig
from repro.core.quantizers import (fake_quant, noise_aware_scale_search,
                                   mse_scale_search, quantize_codes, qrange)


@partial(jax.tree_util.register_dataclass,
         data_fields=["in_codes", "out_codes", "stream_pos", "is_out",
                      "scale_in", "scale_out"],
         meta_fields=["shape", "bits_in", "bits_out", "subtile"])
@dataclasses.dataclass
class QTensor:
    in_codes: jax.Array      # [n_in, 8, 128] int4/int8 container (3-bit codes)
    out_codes: jax.Array     # [n_out, 8, 128] int8 container (5-bit codes)
    stream_pos: jax.Array    # [gr, gc] int32: index into own stream
    is_out: jax.Array        # [gr, gc] bool tag
    scale_in: jax.Array      # [1, dout] f32 per-output-channel inlier scale
    scale_out: jax.Array     # [1, dout] f32 per-output-channel outlier scale
    shape: Tuple[int, int]
    bits_in: int
    bits_out: int
    subtile: Tuple[int, int]

    @property
    def dtype(self):  # logical dtype when dequantized
        return self.scale_in.dtype

    @property
    def ndim(self):
        return len(self.shape)

    def nbytes_packed(self) -> int:
        """Memory-cell accounting (logical bits, no container padding)."""
        n_in = int(np.prod(self.in_codes.shape))
        n_out = int(np.prod(self.out_codes.shape))
        meta = self.is_out.size / 8 + self.stream_pos.size * 4
        scales = (self.scale_in.size + self.scale_out.size) * 4
        return int((n_in * self.bits_in + n_out * self.bits_out) / 8
                   + meta + scales)

    def nbytes_container(self) -> int:
        """What the TPU actually stores (int4/int8 containers + metadata)."""
        in_bits = 4 if self.in_codes.dtype == jnp.int4 else 8
        n_in = int(np.prod(self.in_codes.shape))
        n_out = int(np.prod(self.out_codes.shape))
        meta = self.is_out.size + self.stream_pos.size * 4
        scales = (self.scale_in.size + self.scale_out.size) * 4
        return int(n_in * in_bits / 8 + n_out + meta + scales)


def quantize_qtensor(w: jax.Array, cfg: QMCConfig,
                     use_int4: bool = True) -> QTensor:
    """Build the dual-stream format from a dense weight matrix (PTQ-time)."""
    assert w.ndim == 2, "QTensor holds 2-D weights"
    r, c = cfg.subtile
    din, dout = w.shape
    gr, gc = din // r, dout // c
    n_sub = gr * gc

    sub_mask = part.subtile_outlier_mask(w, cfg.rho, cfg.subtile)  # [gr, gc]
    elem_mask = part.expand_subtile_mask(sub_mask, w.shape, cfg.subtile)

    scale_in = noise_aware_scale_search(
        w, cfg.bits_in, cfg.noise, channel_axis=-1,
        grid_lo=cfg.scale_grid_lo, grid_hi=cfg.scale_grid_hi,
        grid_n=cfg.scale_grid_n, mask=~elem_mask)
    scale_out = mse_scale_search(
        w, cfg.bits_out, channel_axis=-1,
        grid_lo=cfg.scale_grid_lo, grid_hi=cfg.scale_grid_hi,
        grid_n=cfg.scale_grid_n, mask=elem_mask)

    codes_in = quantize_codes(w, scale_in, cfg.bits_in)
    codes_out = quantize_codes(w, scale_out, cfg.bits_out)

    # --- compact streams (static sizes; PTQ runs eagerly) ---------------
    flat_mask = np.asarray(sub_mask).reshape(-1)
    k_out = int(flat_mask.sum())
    k_in = n_sub - k_out
    order = np.arange(n_sub)
    in_ids = order[~flat_mask]
    out_ids = order[flat_mask]

    # subtile view [n_sub, r, c] in grid scan order
    def tiles_of(x):
        return (x.reshape(gr, r, gc, c).transpose(0, 2, 1, 3)
                .reshape(n_sub, r, c))

    t_in = tiles_of(codes_in)[in_ids].astype(
        jnp.int4 if use_int4 else jnp.int8)
    t_out = tiles_of(codes_out)[out_ids].astype(jnp.int8)

    pos = np.zeros(n_sub, np.int32)
    pos[in_ids] = np.arange(k_in, dtype=np.int32)
    pos[out_ids] = np.arange(k_out, dtype=np.int32)

    # guarantee non-empty streams so the pytree keeps static structure
    if k_in == 0:
        t_in = jnp.zeros((1, r, c), t_in.dtype)
    if k_out == 0:
        t_out = jnp.zeros((1, r, c), jnp.int8)

    return QTensor(
        in_codes=t_in, out_codes=t_out,
        stream_pos=jnp.asarray(pos.reshape(gr, gc)),
        is_out=jnp.asarray(flat_mask.reshape(gr, gc)),
        scale_in=scale_in.astype(jnp.float32),
        scale_out=scale_out.astype(jnp.float32),
        shape=(din, dout), bits_in=cfg.bits_in, bits_out=cfg.bits_out,
        subtile=(r, c))


def dequantize_qtensor(qt: QTensor, dtype=jnp.bfloat16) -> jax.Array:
    """Reassemble the dense weight matrix (the jnp oracle for the kernel)."""
    r, c = qt.subtile
    gr, gc = qt.is_out.shape
    din, dout = qt.shape
    pos = qt.stream_pos.reshape(-1)
    tags = qt.is_out.reshape(-1)

    take_in = jnp.take(qt.in_codes, jnp.where(tags, 0, pos), axis=0)
    take_out = jnp.take(qt.out_codes, jnp.where(tags, pos, 0), axis=0)
    tiles = jnp.where(tags[:, None, None],
                      take_out.astype(jnp.float32),
                      take_in.astype(jnp.float32))          # [n_sub, r, c]
    dense = (tiles.reshape(gr, gc, r, c).transpose(0, 2, 1, 3)
             .reshape(din, dout))
    emask = part.expand_subtile_mask(qt.is_out, (din, dout), qt.subtile)
    scale = jnp.where(emask, qt.scale_out, qt.scale_in)
    return (dense * scale).astype(dtype)


def qmatmul_ref(x: jax.Array, qt: QTensor,
                out_dtype=jnp.bfloat16) -> jax.Array:
    """x @ dequant(qt) — reference path used when the Pallas kernel is off."""
    w = dequantize_qtensor(qt, dtype=x.dtype)
    return jnp.matmul(x, w).astype(out_dtype)
