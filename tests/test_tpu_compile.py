"""Compile-only checks of the serving kernels for a described TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a topology that is described, not present, and refuses what the chip
would refuse (BlockSpecs that break the (8, 128)-or-full-dim tiling rule,
VMEM overruns). Interpret-mode parity lives in
``test_paged_attention_kernel.py`` / ``test_kernels.py``; this file pins
that the same kernels, at StableLM-2-1.6B widths, are accepted by the
chip's compiler. Nothing runs: only shapes are passed.

The topology is described inside a module-scoped fixture (never at import)
so that only the worker that runs this file loads the TPU library.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

# StableLM-2-1.6B attention widths and the serving arena of the chip smoke
# run: 4 slots x 11 pages of 16 tokens (+ the null page)
N_HEADS = N_KV = 32
HEAD_DIM = 64
PAGE = 16
SLOTS, PAGES_PER_SEQ = 4, 11
N_PAGES = SLOTS * PAGES_PER_SEQ + 1
D_MODEL, D_FF = 2048, 5632


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described chip are written to the persistent cache
    # but can never be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("s", [1, 128], ids=["decode", "chunk128"])
@pytest.mark.parametrize("kv", ["fp32", "int8"])
def test_ragged_paged_attention_compiles(one_chip, s, kv):
    from repro.kernels.paged_attention import ragged_paged_attention

    kvd = N_KV * HEAD_DIM
    page_dt = jnp.float32 if kv == "fp32" else jnp.int8
    cache = {"k_pages": _sds(one_chip, (N_PAGES, PAGE, kvd), page_dt),
             "v_pages": _sds(one_chip, (N_PAGES, PAGE, kvd), page_dt),
             "block_tbl": _sds(one_chip, (SLOTS, PAGES_PER_SEQ), jnp.int32)}
    if kv == "int8":
        for name in ("k_scale_pages", "v_scale_pages"):
            cache[name] = _sds(one_chip, (N_PAGES, PAGE, N_KV),
                               jnp.bfloat16)
    q = _sds(one_chip, (SLOTS, s, N_HEADS, HEAD_DIM), jnp.float32)
    lens = _sds(one_chip, (SLOTS,), jnp.int32)

    def attn(q, cache, q_start, kv_len):
        return ragged_paged_attention(q, cache, q_start, kv_len, n_kv=N_KV,
                                      head_dim=HEAD_DIM, interpret=False)

    compiled = jax.jit(attn).lower(q, cache, lens, lens).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _qtensor_struct(sharding, k, n):
    from repro.core.qconfig import QMCConfig
    from repro.core.serving_quant import qtensor_struct
    qt = qtensor_struct(k, n, QMCConfig(rho=0.3, granularity="subtile"))
    return jax.tree_util.tree_map(
        lambda f: _sds(sharding, f.shape, f.dtype), qt)


@pytest.mark.parametrize("k,n", [(D_MODEL, D_FF), (D_FF, D_MODEL)],
                         ids=["up", "down"])
@pytest.mark.parametrize("kernel", ["decode", "colstrip"])
def test_qmm_compiles(one_chip, kernel, k, n):
    from repro.kernels.qmm import qmm_pallas, qmm_pallas_colstrip

    qt = _qtensor_struct(one_chip, k, n)
    if kernel == "decode":
        x = _sds(one_chip, (8, k), jnp.float32)

        def mm(x, qt):
            return qmm_pallas(x, qt, block_m=8, block_n=512,
                              interpret=False)
    else:
        x = _sds(one_chip, (128, k), jnp.float32)

        def mm(x, qt):
            return qmm_pallas_colstrip(x, qt, block_m=128, interpret=False)

    compiled = jax.jit(mm).lower(x, qt).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_code_plane_matmul_fuses_dequant(one_chip):
    """A code-plane weight at decode width: the dot's fusion reads the
    int8 codes itself and dequantizes in place, so no dense [K, N] f32
    weight is materialized per call."""
    from repro.core.qtensor import CodePlane
    from repro.models.layers import matmul_any

    plane = CodePlane(codes=_sds(one_chip, (D_MODEL, D_FF), jnp.int8),
                      scale_in=_sds(one_chip, (1, D_FF), jnp.float32),
                      scale_out=_sds(one_chip, (1, D_FF), jnp.float32))
    x = _sds(one_chip, (8, D_MODEL), jnp.float32)
    compiled = jax.jit(matmul_any).lower(x, plane).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < \
        D_MODEL * D_FF * 4
    # computations are separated by blank lines; the header names params
    dots = [c for c in compiled.as_text().split("\n\n")
            if " convolution(" in c]
    assert dots
    assert all(f"s8[{D_MODEL},{D_FF}]" in c.splitlines()[0] for c in dots)
