"""PCG + IC(0)/SpTRSV preconditioner integration."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pcg import make_ic_preconditioner, pcg
from repro.core.rewrite import RewriteConfig
from repro.sparse import ic0_factor, poisson2d


def test_pcg_converges_faster_with_sptrsv_preconditioner():
    A = poisson2d(24, 24, dtype=np.float32)
    L = ic0_factor(A)
    M = make_ic_preconditioner(L, rewrite=RewriteConfig(thin_threshold=4))
    b = jnp.asarray(np.random.default_rng(0).normal(size=A.n).astype(np.float32))
    plain = pcg(A, b, None, tol=1e-5, maxiter=1500)
    pre = pcg(A, b, M, tol=1e-5, maxiter=1500)
    assert pre.converged
    assert pre.iters < plain.iters, (pre.iters, plain.iters)
    x = np.asarray(pre.x, np.float64)
    r = np.asarray(b, np.float64) - A.astype(np.float64).matvec(x)
    assert np.linalg.norm(r) <= 1e-4 * np.linalg.norm(np.asarray(b))


def test_preconditioner_solve_exact_on_triangular_system():
    """(L Lᵀ)^{-1} applied to (L Lᵀ) v must give v back."""
    A = poisson2d(12, 12, dtype=np.float64)
    L = ic0_factor(A)
    M = make_ic_preconditioner(L, rewrite=None)
    rng = np.random.default_rng(1)
    v = rng.normal(size=A.n)
    Ld = L.to_dense()
    w = Ld @ (Ld.T @ v)
    got = np.asarray(M(jnp.asarray(w)))
    np.testing.assert_allclose(got, v, rtol=1e-4, atol=1e-5)  # f32 solves


# --------------------------------------------------------------------------
# regression: degenerate inputs must return well-formed results
# --------------------------------------------------------------------------
def test_pcg_maxiter_zero_returns_wellformed():
    """maxiter=0 used to crash with UnboundLocalError on `res`; it must
    return the initial iterate with a finite residual."""
    A = poisson2d(8, 8, dtype=np.float32)
    b = jnp.asarray(np.random.default_rng(0).normal(size=A.n).astype(np.float32))
    res = pcg(A, b, None, maxiter=0)
    assert not res.converged
    assert res.iters == 0
    assert np.isfinite(res.residual)
    assert np.isfinite(np.asarray(res.x)).all()


def test_pcg_zero_rhs_converges_immediately():
    """b = 0 used to make the tolerance test `res <= 0` (b_norm == 0) and
    spin to maxiter; x = 0 is exact and must converge in 0 iterations."""
    A = poisson2d(8, 8, dtype=np.float32)
    res = pcg(A, jnp.zeros(A.n, jnp.float32), None, maxiter=50)
    assert res.converged
    assert res.iters == 0
    assert res.residual == 0.0
    np.testing.assert_array_equal(np.asarray(res.x), 0.0)
    # with a preconditioner too (exercises M_inv on the zero residual path)
    L = ic0_factor(A)
    M = make_ic_preconditioner(L, rewrite=None)
    res_m = pcg(A, jnp.zeros(A.n, jnp.float32), M, maxiter=50)
    assert res_m.converged and np.isfinite(np.asarray(res_m.x)).all()


def test_pcg_breakdown_returns_wellformed():
    """Lanczos breakdown (pᵀAp = 0, e.g. A = 0): the unbatched path used to
    divide by zero and return NaN x with converged=False unset downstream;
    it must return the last finite iterate as a well-formed non-converged
    result — the same guard pcg_batched always had."""
    from repro.core import from_coo

    n = 8
    Z = from_coo([0], [0], [0.0], (n, n))   # all-zero SPD-shaped matrix
    res = pcg(Z, jnp.ones(n, jnp.float32), None, maxiter=10)
    assert not res.converged
    assert np.isfinite(np.asarray(res.x)).all()
    assert np.isfinite(res.residual)


def test_pcg_stall_window_stops_stagnation():
    """A rank-deficient preconditioner confines the search directions to a
    subspace: the residual component outside it can never shrink, so the
    iteration stagnates at a nonzero floor.  stall_window must cut the loop
    short as non-converged instead of burning all of maxiter (the
    iteration-control companion of the inexact sweeps preconditioner)."""
    A = poisson2d(8, 8, dtype=np.float32)
    b = jnp.asarray(np.random.default_rng(2).normal(size=A.n).astype(np.float32))
    mask = jnp.asarray((np.arange(A.n) % 2 == 0).astype(np.float32))
    frozen = pcg(A, b, lambda r: r * mask, tol=1e-6, maxiter=400,
                 stall_window=5)
    assert not frozen.converged
    assert frozen.iters < 400


def test_pcg_batched_maxiter_zero_and_zero_rhs():
    from repro.core.pcg import pcg_batched

    A = poisson2d(8, 8, dtype=np.float32)
    rng = np.random.default_rng(1)
    b = rng.normal(size=A.n).astype(np.float32)
    # maxiter=0: well-formed, nothing converged
    res0 = pcg_batched(A, jnp.stack([b, b], axis=1), None, maxiter=0)
    assert (~res0.converged).all()
    assert np.isfinite(res0.residual).all()
    assert np.isfinite(np.asarray(res0.x)).all()
    # mixed batch: a zero column converges in 0 iters without perturbing
    # the nonzero column, and produces no NaN
    B = np.stack([np.zeros_like(b), b], axis=1)
    res = pcg_batched(A, jnp.asarray(B), None, tol=1e-5, maxiter=300)
    assert res.converged.all()
    assert res.iters[0] == 0
    assert res.iters[1] > 0
    assert np.isfinite(np.asarray(res.x)).all()
    np.testing.assert_array_equal(np.asarray(res.x[:, 0]), 0.0)


def test_pcg_counts_two_readbacks_a_solve_and_two_an_iteration():
    """Every host read of a device value in ``pcg`` is counted: the first
    residual norm and ``||b||``, then ``p·Ap`` and ``||r||`` per iteration."""
    from repro.core import obs

    A = poisson2d(10, 10, dtype=np.float32)
    M = make_ic_preconditioner(ic0_factor(A), rewrite=None)
    b = jnp.asarray(np.random.default_rng(3).normal(size=A.n)
                    .astype(np.float32))
    before = obs.snapshot()
    res = pcg(A, b, M, tol=1e-6, maxiter=200)
    after = obs.snapshot()

    def delta(name):
        return after.get(name, 0) - before.get(name, 0)

    assert res.converged and res.iters > 0
    assert delta(obs.ITERATIONS) == res.iters
    assert delta(obs.READBACKS) == 2 * res.iters + 2


def _permuted(A):
    """``P A Pᵀ`` for a seeded random permutation ``P``: the same SPD
    M-matrix with its columns scattered off a few diagonals."""
    from repro.core.csr import from_coo

    perm = np.random.default_rng(9).permutation(A.n)
    inv = np.argsort(perm)
    rows = np.repeat(np.arange(A.n), A.row_nnz())
    return from_coo(inv[rows], inv[A.indices], A.data, A.shape)


@pytest.mark.parametrize("form", ["dia", "ell"])
def test_pcg_traces_its_spmv_once_per_shape(monkeypatch, form):
    """The SpMV is one jitted function over device arrays: solves on one
    operator, or on another of the same shape and pattern, trace it at
    most once between them, and a batched solve at most once more.  This
    holds for a stencil, which ``build_spmv`` stores by diagonals, and for a
    scattered pattern, which it stores as ELL.  The answers equal those of
    the SpMV built as a jitted closure over the host ELL arrays, a new
    program each solve."""
    import jax

    from repro.core import obs
    from repro.core import pcg as pcg_module
    from repro.core.codegen import Diagonals, build_ell, build_spmv, ell_spmv
    from repro.core.csr import CSRMatrix
    from repro.core.pcg import pcg_batched

    A = poisson2d(9, 11, dtype=np.float32)
    if form == "ell":
        A = _permuted(A)
    assert isinstance(build_spmv(A)[0], Diagonals) == (form == "dia")
    A2 = CSRMatrix(A.indptr, A.indices, 1.5 * A.data, A.shape)
    M = make_ic_preconditioner(ic0_factor(A), rewrite=None)
    rng = np.random.default_rng(4)
    b = jnp.asarray(rng.normal(size=A.n).astype(np.float32))
    b2 = jnp.asarray(rng.normal(size=A.n).astype(np.float32))
    B = jnp.asarray(rng.normal(size=(A.n, 3)).astype(np.float32))

    def solves():
        return [pcg(A, b, M, tol=1e-6), pcg(A, b2, M, tol=1e-6),
                pcg(A2, b, None, tol=1e-6)]

    def traces():
        return obs.snapshot().get(obs.MATVEC_TRACES, 0)

    start = traces()
    got = solves()
    after_pcg = traces()
    got.append(pcg_batched(A, B, M, tol=1e-6))
    assert after_pcg - start <= 1
    assert traces() - after_pcg <= 1
    after_all = traces()
    solves()
    pcg_batched(A, B, M, tol=1e-6)
    assert traces() == after_all              # nothing traced again

    def closure_matvec_of(M_, dtype):
        ell = build_ell(M_)
        return jax.jit(lambda v: ell_spmv(ell, v))

    monkeypatch.setattr(pcg_module, "_matvec_of", closure_matvec_of)
    want = solves() + [pcg_batched(A, B, M, tol=1e-6)]
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g.iters, w.iters)
        np.testing.assert_allclose(np.asarray(g.x), np.asarray(w.x),
                                   rtol=1e-6, atol=1e-6)
