"""ShardedQTensor — QMC deployment format for tensor-parallel serving.

Production PTQ quantizes each weight *shard* independently (quantize-after-
shard), so every device holds the compact streams of its own TP slice and the
qmm kernel runs fully locally; column-sharded weights concat outputs, row-
sharded weights psum partials. All fields carry a leading TP-shard dim and
are sharded P("model", ...) — see launch/sharding.py.

Stream sizes are equal across shards because the subtile top-rho rule picks
exactly round(rho * n_sub_shard) outlier subtiles per shard.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.qconfig import QMCConfig
from repro.core.qtensor import QTensor, dequantize_qtensor, quantize_qtensor


@partial(jax.tree_util.register_dataclass,
         data_fields=["in_codes", "out_codes", "stream_pos", "is_out",
                      "scale_in", "scale_out"],
         meta_fields=["shape", "bits_in", "bits_out", "subtile",
                      "shard_axis", "n_shards"])
@dataclasses.dataclass
class ShardedQTensor:
    in_codes: jax.Array      # [S, n_in, 8, 128]
    out_codes: jax.Array     # [S, n_out, 8, 128]
    stream_pos: jax.Array    # [S, gr, gc]
    is_out: jax.Array        # [S, gr, gc]
    scale_in: jax.Array      # [S, 1, dout_shard]
    scale_out: jax.Array     # [S, 1, dout_shard]
    shape: Tuple[int, int]   # full (unsharded) weight shape
    bits_in: int
    bits_out: int
    subtile: Tuple[int, int]
    shard_axis: int          # 0 = row-sharded (input dim), 1 = column
    n_shards: int

    @property
    def ndim(self):
        return 2

    def local(self, i: int) -> QTensor:
        shard_shape = list(self.shape)
        shard_shape[self.shard_axis] //= self.n_shards
        return QTensor(self.in_codes[i], self.out_codes[i],
                       self.stream_pos[i], self.is_out[i],
                       self.scale_in[i], self.scale_out[i],
                       tuple(shard_shape), self.bits_in, self.bits_out,
                       self.subtile)


def quantize_qtensor_sharded(w: jax.Array, cfg: QMCConfig, n_shards: int,
                             shard_axis: int = 1,
                             use_int4: bool = True) -> ShardedQTensor:
    """Quantize each TP shard of W independently and stack the streams."""
    assert w.ndim == 2
    assert w.shape[shard_axis] % n_shards == 0
    shards = jnp.split(w, n_shards, axis=shard_axis)
    qts = [quantize_qtensor(s, cfg, use_int4=use_int4) for s in shards]
    sizes = {(q.in_codes.shape[0], q.out_codes.shape[0]) for q in qts}
    assert len(sizes) == 1, "per-shard stream sizes must match"
    stack = lambda f: jnp.stack([getattr(q, f) for q in qts])  # noqa: E731
    return ShardedQTensor(
        in_codes=stack("in_codes"), out_codes=stack("out_codes"),
        stream_pos=stack("stream_pos"), is_out=stack("is_out"),
        scale_in=stack("scale_in"), scale_out=stack("scale_out"),
        shape=tuple(w.shape), bits_in=cfg.bits_in, bits_out=cfg.bits_out,
        subtile=cfg.subtile, shard_axis=shard_axis, n_shards=n_shards)


def dequantize_sharded(sqt: ShardedQTensor, dtype=jnp.bfloat16) -> jax.Array:
    parts = [dequantize_qtensor(sqt.local(i), dtype)
             for i in range(sqt.n_shards)]
    return jnp.concatenate(parts, axis=sqt.shard_axis)


def qmm_sharded_ref(x: jax.Array, sqt: ShardedQTensor,
                    dtype=None) -> jax.Array:
    """Oracle: x [..., K] @ dequant(sqt) [K, N]."""
    w = dequantize_sharded(sqt, dtype or x.dtype)
    return jnp.matmul(x, w)


def qmm_shard_map(x: jax.Array, sqt: ShardedQTensor, mesh,
                  axis: str = "model",
                  dp: Tuple[str, ...] = (),
                  use_pallas: bool = False) -> jax.Array:
    """TP-local quantized matmul under shard_map.

    Column-sharded (shard_axis=1): every device computes its N/S output
    columns from its batch slice of x. Row-sharded (shard_axis=0): devices
    hold K/S input rows; x arrives sharded on its last dim; partials psum.
    Batch rows ride the dp axes untouched. The shard-local matmul goes
    through kernels.ops.qmm, so the block_m plan (decode-width vs
    column-strip, skinny-XLA vs ref) is picked per compiled step width
    exactly as on the single-device path.
    """
    from jax.sharding import PartitionSpec as P

    from repro.kernels import ops as kops

    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    bspec = tuple(dp) if dp else None
    if bspec is not None:
        names = list(mesh.axis_names)
        dp_n = 1
        for a in bspec:
            dp_n *= mesh.devices.shape[names.index(a)]
        if x2.shape[0] % dp_n:
            bspec = None        # e.g. batch-1 long-context decode
    qt_specs = ShardedQTensor(
        in_codes=P(axis), out_codes=P(axis), stream_pos=P(axis),
        is_out=P(axis), scale_in=P(axis), scale_out=P(axis),
        shape=sqt.shape, bits_in=sqt.bits_in, bits_out=sqt.bits_out,
        subtile=sqt.subtile, shard_axis=sqt.shard_axis,
        n_shards=sqt.n_shards)

    if sqt.shard_axis == 1:
        def body(xl, q):
            return kops.qmm(xl, q.local(0), use_pallas=use_pallas)
        y = jax.shard_map(body, mesh=mesh,
                          in_specs=(P(bspec, None), qt_specs),
                          out_specs=P(bspec, axis), check_vma=False)(x2, sqt)
    else:
        def body(xl, q):
            yl = kops.qmm(xl, q.local(0), use_pallas=use_pallas)
            return jax.lax.psum(yl, axis)
        y = jax.shard_map(body, mesh=mesh,
                          in_specs=(P(bspec, axis), qt_specs),
                          out_specs=P(bspec, None), check_vma=False)(x2, sqt)
    return y.reshape(*lead, sqt.shape[1])
