"""Pallas kernel: 3-bit bitstream decode (the ReRAM read path).

Decodes a packed little-endian 3-bit code stream (uint8 bytes) into signed
integer codes. This models the paper's "bit packing/unpacking" stage — the
mismatch between logical 3-bit weights and physical cell storage — as a
vectorizable shift/mask pipeline: each block of 3 bytes yields 8 codes, so a
(block_n*3,) byte tile expands to a (block_n*8,) code tile with only
word-aligned loads, shifts and masks (VPU-friendly; no gathers).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _unpack3b_kernel(p_ref, o_ref, *, codes_per_block: int):
    """p_ref: [3*codes_per_block//8] uint8 -> o_ref: [codes_per_block] int32."""
    n_groups = codes_per_block // 8
    byts = p_ref[...].astype(jnp.int32).reshape(n_groups, 3)
    b0, b1, b2 = byts[:, 0], byts[:, 1], byts[:, 2]
    word = b0 | (b1 << 8) | (b2 << 16)       # 24 bits = 8 codes
    shifts = jnp.arange(8, dtype=jnp.int32) * 3
    codes = (word[:, None] >> shifts[None, :]) & 0x7
    o_ref[...] = (codes - 4).reshape(codes_per_block)


def unpack3b_pallas(packed: jax.Array, n: int, *, block_codes: int = 1024,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Decode `n` 3-bit codes from a packed uint8 stream.

    n must be a multiple of 8 and of block_codes; the stream length must be
    exactly 3*n/8 bytes (pad upstream — core.packing pads the final byte).
    """
    assert n % 8 == 0 and n % block_codes == 0
    nbytes = 3 * n // 8
    assert packed.shape == (nbytes,), (packed.shape, nbytes)
    bytes_per_block = 3 * block_codes // 8

    kernel = functools.partial(_unpack3b_kernel,
                               codes_per_block=block_codes)
    return pl.pallas_call(
        kernel,
        grid=(n // block_codes,),
        in_specs=[pl.BlockSpec((bytes_per_block,), lambda i: (i,))],
        out_specs=pl.BlockSpec((block_codes,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(packed)
