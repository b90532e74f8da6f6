"""Pallas kernel validation: shape/dtype sweeps vs the jnp oracles

(interpret=True executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.packing import pack_codes
from repro.core.qconfig import QMCConfig
from repro.core.qtensor import quantize_qtensor
from repro.kernels import ops
from repro.kernels.qmm import qmm_pallas
from repro.kernels.ref import qmm_ref, unpack3b_ref
from repro.kernels.unpack3b import unpack3b_pallas


@pytest.mark.parametrize("m,k,n", [(8, 128, 128), (128, 256, 128),
                                   (16, 128, 384), (256, 384, 256)])
@pytest.mark.parametrize("rho", [0.1, 0.3])
def test_qmm_shapes(m, k, n, rho):
    key = jax.random.PRNGKey(m * 7 + n)
    w = jax.random.t(key, df=3.0, shape=(k, n))
    x = jax.random.normal(jax.random.PRNGKey(1), (m, k))
    qt = quantize_qtensor(w, QMCConfig(rho=rho, granularity="subtile"))
    y_ref = qmm_ref(x, qt)
    y = qmm_pallas(x, qt, block_m=min(m, 128), interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_qmm_dtypes(dtype):
    w = jax.random.normal(jax.random.PRNGKey(0), (128, 128))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 128)).astype(dtype)
    qt = quantize_qtensor(w, QMCConfig(rho=0.25, granularity="subtile"))
    y = qmm_pallas(x, qt, block_m=8, interpret=True)
    y_ref = qmm_ref(x, qt)
    assert y.dtype == dtype
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               atol=3e-2, rtol=3e-2)


def test_qmm_extreme_rho():
    """rho=0 (all inliers) and rho~1 (all outliers) still work."""
    w = jax.random.normal(jax.random.PRNGKey(0), (128, 128))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 128))
    for rho in (0.0, 0.99):
        qt = quantize_qtensor(w, QMCConfig(rho=rho, granularity="subtile"))
        y = qmm_pallas(x, qt, block_m=8, interpret=True)
        np.testing.assert_allclose(np.asarray(y), np.asarray(qmm_ref(x, qt)),
                                   atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("n", [1024, 2048, 8192])
def test_unpack3b(n):
    rng = np.random.default_rng(n)
    codes = rng.integers(-4, 4, size=n)
    packed = pack_codes(codes, 3)
    out = unpack3b_pallas(jnp.asarray(packed), n, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), codes)
    np.testing.assert_array_equal(np.asarray(unpack3b_ref(
        jnp.asarray(packed), n)), codes)


def test_ops_dispatch_fallback():
    """ops.qmm falls back to the oracle for non-tileable shapes."""
    w = jax.random.normal(jax.random.PRNGKey(0), (96, 160))  # not 128-align
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 96))
    qt = quantize_qtensor(w, QMCConfig(rho=0.3, granularity="subtile",
                                       subtile=(8, 32)))
    y = ops.qmm(x, qt, use_pallas=True)   # silently uses ref path
    np.testing.assert_allclose(np.asarray(y), np.asarray(qmm_ref(x, qt)),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("backend,interpret,want", [
    ("cpu", None, True), ("cpu", True, True), ("cpu", False, False),
    ("tpu", None, False), ("tpu", False, False), ("tpu", True, None)])
def test_resolve_interpret_only_on_cpu(monkeypatch, backend, interpret,
                                       want):
    """Interpret mode is chosen only where the backend is the CPU; on any
    other backend asking for it raises instead of hiding the device."""
    from repro.kernels import resolve_interpret
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if want is None:
        with pytest.raises(ValueError):
            resolve_interpret(interpret)
    else:
        assert resolve_interpret(interpret) is want
