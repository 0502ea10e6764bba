"""Oracle-equivalence of the dormant kernel packages (``spmv_ell``,
``trsm_block``) through the backend interface: both lowering families
(TPU Mosaic and pallas-triton) run under the pallas interpreter against the
packages' pure-jnp ``ref.py`` oracles and SciPy.
"""
import numpy as np
import pytest

import jax.numpy as jnp
import scipy.sparse as sp
import scipy.sparse.linalg as spla

from repro.kernels.spmv_ell import lowering_gpu as spmv_gpu
from repro.kernels.spmv_ell import lowering_tpu as spmv_tpu
from repro.kernels.spmv_ell.ops import make_spmv
from repro.kernels.spmv_ell.ref import spmv_ref
from repro.kernels.trsm_block import lowering_gpu as trsm_gpu
from repro.kernels.trsm_block import lowering_tpu as trsm_tpu
from repro.kernels.trsm_block.ops import make_block_solver
from repro.kernels.trsm_block.ref import block_apply_ref
from repro.sparse import banded_lower, random_lower

BACKENDS = ["interpret", "interpret:gpu"]


def _scipy(L):
    return sp.csr_matrix((L.data, L.indices, L.indptr), shape=L.shape)


# --------------------------------------------------------------------------
# spmv_ell
# --------------------------------------------------------------------------
@pytest.mark.parametrize("low", [spmv_tpu, spmv_gpu],
                         ids=["tpu_lowering", "gpu_lowering"])
def test_spmv_lowerings_match_ref(low):
    rng = np.random.default_rng(0)
    K, n_pad, m_pad = 5, 256, 384
    cols = rng.integers(0, m_pad, size=(K, n_pad)).astype(np.int32)
    vals = rng.standard_normal((K, n_pad)).astype(np.float32)
    v = rng.standard_normal(m_pad).astype(np.float32)
    y = np.asarray(low.spmv(jnp.asarray(v), jnp.asarray(cols),
                            jnp.asarray(vals), block=128, interpret=True))
    y_ref = np.asarray(spmv_ref(jnp.asarray(v), jnp.asarray(cols),
                                jnp.asarray(vals)))
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_make_spmv_matches_scipy(backend):
    rng = np.random.default_rng(1)
    L = random_lower(300, avg_offdiag=4.0, seed=7, dtype=np.float32)
    v = rng.standard_normal(L.n).astype(np.float32)
    y = np.asarray(make_spmv(L, backend=backend, block=128)(jnp.asarray(v)))
    np.testing.assert_allclose(y, _scipy(L) @ v, rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# trsm_block
# --------------------------------------------------------------------------
@pytest.mark.parametrize("low", [trsm_tpu, trsm_gpu],
                         ids=["tpu_lowering", "gpu_lowering"])
def test_block_apply_lowerings_match_ref(low):
    rng = np.random.default_rng(2)
    NB, T = 8, 128
    dinv = rng.standard_normal((NB, T, T)).astype(np.float32)
    rhs = rng.standard_normal((NB, T)).astype(np.float32)
    out = np.asarray(low.block_apply(jnp.asarray(dinv), jnp.asarray(rhs),
                                     batch_block=4, interpret=True))
    ref = np.asarray(block_apply_ref(jnp.asarray(dinv), jnp.asarray(rhs)))
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("make_L", [
    lambda: banded_lower(300, bandwidth=6, seed=3, dtype=np.float32),
    lambda: random_lower(300, avg_offdiag=3.0, seed=4, dtype=np.float32),
], ids=["banded", "random"])
def test_block_solver_matches_scipy(backend, make_L):
    rng = np.random.default_rng(5)
    L = make_L()
    b = rng.standard_normal(L.n).astype(np.float32)
    x = np.asarray(make_block_solver(L, T=128, backend=backend)(
        jnp.asarray(b)))
    x_ref = spla.spsolve_triangular(_scipy(L).tocsr(), b.astype(np.float64),
                                    lower=True)
    scale = max(np.abs(x_ref).max(), 1.0)
    np.testing.assert_allclose(x, x_ref, rtol=2e-4, atol=2e-4 * scale)


@pytest.mark.parametrize("path", ["pallas_tpu", "jnp"])
def test_block_apply_requests_full_f32_precision(path):
    """At default precision the TPU MXU rounds f32 matmul operands to bf16
    (a 2.9e-3 componentwise residual on a v5e band solve); both apply
    paths of the blocked executor must ask for HIGHEST."""
    import jax

    from repro.kernels.trsm_block.ops import make_block_apply

    if path == "jnp":
        fn = make_block_apply("interpret", kernel="jnp")
    else:
        def fn(d, r):
            return trsm_tpu.block_apply(d, r, batch_block=8, interpret=True)
    text = jax.jit(fn).lower(jnp.zeros((8, 128, 128)),
                             jnp.zeros((8, 128))).as_text()
    assert "HIGHEST" in text
