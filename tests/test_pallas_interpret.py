"""Pallas interpret-mode coverage for the TPU-gated solver paths.

The `pallas_level` / `pallas_fused` strategies are the TPU production path,
but CI has no TPU — without interpret-mode runs they would be
test-invisible.  This module drives the *strategy-level* kernel paths
(single + batched RHS, coarsened chains' ``fori_loop``-of-kernel-calls,
permuted packed variants with refresh, x64) explicitly under
``interpret=True`` and skips cleanly where a JAX build does not support
interpreting a construct, instead of failing the suite.

(The per-kernel shape sweeps live in ``test_kernels.py``; this file covers
the composition layers above them, which is where interpret-mode breakages
have actually appeared — e.g. the mixed int32/int64 ``pl.store`` index
under ``jax_enable_x64`` that this suite pinned down.)
"""
import numpy as np
import jax.numpy as jnp
import pytest

from jax import enable_x64
from repro.core import RewriteConfig, SpTRSV
from repro.core.csr import CSRMatrix
from repro.sparse import lung2_like, pathological

PALLAS_STRATEGIES = ["pallas_level", "pallas_fused"]


def _lung2():
    return lung2_like(scale=0.03, fat_levels=4, thin_run=6, dtype=np.float32)


def _interpret_build(L, **kw):
    """Build with interpret=True, skipping (not failing) when this JAX
    build cannot interpret the construct on CPU."""
    try:
        return SpTRSV.build(L, interpret=True, **kw)
    except (NotImplementedError, jnp.linalg.LinAlgError) as err:  # pragma: no cover
        pytest.skip(f"pallas interpret mode unsupported here: {err}")


def _solve(s, b):
    try:
        return np.asarray(s.solve(jnp.asarray(b)))
    except NotImplementedError as err:  # pragma: no cover
        pytest.skip(f"pallas interpret mode unsupported here: {err}")


@pytest.mark.parametrize("strategy", PALLAS_STRATEGIES)
@pytest.mark.parametrize("layout", ["permuted", "scatter"])
def test_interpret_single_and_batched(strategy, layout):
    L = _lung2()
    rng = np.random.default_rng(0)
    b = rng.standard_normal(L.n).astype(np.float32)
    B = rng.standard_normal((L.n, 4)).astype(np.float32)
    ref = np.asarray(SpTRSV.build(L, strategy="serial").solve(jnp.asarray(b)))
    s = _interpret_build(L, strategy=strategy, layout=layout)
    np.testing.assert_allclose(_solve(s, b), ref, rtol=2e-5, atol=2e-6)
    X = _solve(s, B)
    for j in range(4):
        rj = np.asarray(SpTRSV.build(L, strategy="serial").solve(
            jnp.asarray(B[:, j])))
        np.testing.assert_allclose(X[:, j], rj, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("strategy", PALLAS_STRATEGIES)
def test_interpret_coarsened_chain_and_rewrite(strategy):
    """Chains execute as a fori_loop whose body launches the kernel — the
    composition most likely to break in interpret mode."""
    L = _lung2()
    rng = np.random.default_rng(1)
    b = rng.standard_normal(L.n).astype(np.float32)
    ref = np.asarray(SpTRSV.build(L, strategy="serial").solve(jnp.asarray(b)))
    coarsen = True if strategy == "pallas_level" else None
    s = _interpret_build(L, strategy=strategy, coarsen=coarsen,
                         rewrite=RewriteConfig(thin_threshold=2))
    np.testing.assert_allclose(_solve(s, b), ref, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("strategy", PALLAS_STRATEGIES)
def test_interpret_x64(strategy):
    """Regression: x64 mode used to crash the fused kernel's pl.store with
    mixed int32/int64 dynamic-slice indices (found by the differential fuzz
    harness)."""
    L = pathological("arrow", n=72, seed=1)
    rng = np.random.default_rng(2)
    with enable_x64():
        b = rng.standard_normal(L.n)
        B = rng.standard_normal((L.n, 3))
        ref = np.linalg.solve(L.to_dense(), b)
        s = _interpret_build(L, strategy=strategy)
        np.testing.assert_allclose(_solve(s, b), ref, rtol=1e-11, atol=1e-12)
        X = _solve(s, B)
        np.testing.assert_allclose(
            X, np.linalg.solve(L.to_dense(), B), rtol=1e-11, atol=1e-12)


@pytest.mark.parametrize("strategy", PALLAS_STRATEGIES)
def test_interpret_refresh_hits_compiled_kernel(strategy):
    """Value-only refresh must reuse the interpret-compiled executor (same
    jit cache) — the packed pallas variants take runtime value buffers."""
    L = _lung2()
    rng = np.random.default_rng(3)
    b = rng.standard_normal(L.n).astype(np.float32)
    s = _interpret_build(L, strategy=strategy, layout="permuted")
    _solve(s, b)
    data2 = (L.data + 0.1 * rng.standard_normal(L.nnz)).astype(np.float32)
    data2[L.indptr[1:] - 1] += 3.0
    s.refresh(data2)
    fresh = _interpret_build(
        CSRMatrix(L.indptr, L.indices, data2, L.shape), strategy=strategy)
    np.testing.assert_allclose(_solve(s, b), _solve(fresh, b),
                               rtol=2e-6, atol=2e-6)
