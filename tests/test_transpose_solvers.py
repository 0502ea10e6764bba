"""First-class transpose solves (Lᵀ x = b): every strategy × rewrite ×
dtype × single/batched against a NumPy backward-substitution oracle, the
shared-analysis machinery (CSC view, reverse levels), and equivalence of the
shared-analysis IC preconditioner with the legacy reverse-permute
construction."""
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.core import RewriteConfig, SpTRSV, build_level_sets, rewrite_matrix
from repro.core.csr import from_coo
from repro.core.levels import compute_levels, compute_reverse_levels, compute_upper_levels
from repro.sparse import banded_lower, chain_matrix, lung2_like, random_lower

from test_property_solvers import _make_matrix, matrix_spec


def np_bsolve(L, b):
    """Backward-substitution oracle for Lᵀ x = b (host numpy, float64).

    Handles b of shape (n,) or (n, m)."""
    U = L.to_dense().T.astype(np.float64)
    n = U.shape[0]
    x = np.zeros(np.shape(b), dtype=np.float64)
    for i in range(n - 1, -1, -1):
        x[i] = (b[i] - U[i, i + 1:] @ x[i + 1:]) / U[i, i]
    return x


LOCAL_STRATEGIES = ["serial", "levelset", "levelset_unroll",
                    "pallas_level", "pallas_fused"]


# -- shared analysis building blocks ---------------------------------------
def test_transpose_and_csc_view_match_dense():
    L = random_lower(83, avg_offdiag=3.0, seed=9)
    Lt = L.transpose()
    np.testing.assert_allclose(Lt.to_dense(), L.to_dense().T)
    # upper factor stores the diagonal first in each row
    np.testing.assert_allclose(Lt.diagonal(first=True), L.diagonal())
    colptr, rows, vals = L.csc_view()
    np.testing.assert_array_equal(colptr, Lt.indptr)
    np.testing.assert_array_equal(rows, Lt.indices)
    np.testing.assert_array_equal(vals, Lt.data)


@pytest.mark.parametrize("kind", ["random", "banded", "chain", "lung2"])
def test_reverse_levels_derivations_agree(kind):
    L = _make_matrix(kind, 90, seed=17)
    levels = build_level_sets(L)
    loop = compute_reverse_levels(L)
    derived = compute_reverse_levels(L, levels)       # vectorized wavefront
    gathered = compute_upper_levels(L.transpose())    # gather over Lᵀ rows
    np.testing.assert_array_equal(derived, loop)
    np.testing.assert_array_equal(gathered, loop)
    # and they equal the legacy construction: forward levels of the
    # reverse-permuted transpose, mapped back through the permutation
    n = L.n
    rows = np.repeat(np.arange(n), L.row_nnz())
    Lt_rev = from_coo(n - 1 - L.indices, n - 1 - rows, L.data, (n, n))
    np.testing.assert_array_equal(compute_levels(Lt_rev)[::-1], loop)


def test_rewrite_upper_preserves_solution():
    """L'ᵀ x = E b must solve the same system as Lᵀ x = b."""
    L = lung2_like(scale=0.02, fat_levels=4, thin_run=6)
    Lt = L.transpose()
    res = rewrite_matrix(Lt, config=RewriteConfig(thin_threshold=3), upper=True)
    assert res.stats.levels_after < res.stats.levels_before
    rng = np.random.default_rng(3)
    b = rng.normal(size=L.n)
    x = np_bsolve(L, b)
    bp = res.E.matvec(b)
    # solve the rewritten upper system densely
    Up = res.L.to_dense()
    xp = np.linalg.solve(Up, bp)
    np.testing.assert_allclose(xp, x, rtol=1e-9, atol=1e-10)


# -- solver correctness ----------------------------------------------------
@given(matrix_spec())
@settings(max_examples=4, deadline=None)
def test_transpose_strategies_match_oracle_f32(spec):
    kind, n, seed = spec
    L = _make_matrix(kind, n, seed, dtype=np.float32)
    rng = np.random.default_rng(seed ^ 0xBACD)
    b = rng.normal(size=L.n).astype(np.float32)
    x_ref = np_bsolve(L.astype(np.float64), b.astype(np.float64))
    for strategy in LOCAL_STRATEGIES:
        for rewrite in (None, RewriteConfig(thin_threshold=3)):
            s = SpTRSV.build(L, strategy=strategy, transpose=True, rewrite=rewrite)
            assert s.transpose
            x = np.asarray(s.solve(jnp.asarray(b)))
            np.testing.assert_allclose(
                x, x_ref, rtol=2e-3, atol=2e-4,
                err_msg=f"{kind} n={n} seed={seed} {strategy} "
                        f"rewrite={rewrite is not None}")


@given(matrix_spec())
@settings(max_examples=2, deadline=None)
def test_transpose_strategies_match_oracle_f64(spec):
    from jax import enable_x64

    kind, n, seed = spec
    with enable_x64():
        L = _make_matrix(kind, n, seed, dtype=np.float64)
        rng = np.random.default_rng(seed ^ 0xD00D)
        b = rng.normal(size=L.n)
        x_ref = np_bsolve(L, b)
        for strategy in LOCAL_STRATEGIES:
            for rewrite in (None, RewriteConfig(thin_threshold=3)):
                s = SpTRSV.build(L, strategy=strategy, transpose=True,
                                 rewrite=rewrite)
                x = np.asarray(s.solve(jnp.asarray(b, dtype=jnp.float64)))
                np.testing.assert_allclose(
                    x, x_ref, rtol=1e-9, atol=1e-10,
                    err_msg=f"{kind} n={n} seed={seed} {strategy} "
                            f"rewrite={rewrite is not None}")


@pytest.mark.parametrize("strategy", LOCAL_STRATEGIES)
@pytest.mark.parametrize("rewrite", [None, RewriteConfig(thin_threshold=3)])
def test_transpose_batched_matches_columnwise(strategy, rewrite):
    L = lung2_like(scale=0.02, fat_levels=4, thin_run=6, dtype=np.float32)
    rng = np.random.default_rng(11)
    B = rng.normal(size=(L.n, 16)).astype(np.float32)
    s = SpTRSV.build(L, strategy=strategy, transpose=True, rewrite=rewrite)
    X = np.asarray(s.solve_batched(jnp.asarray(B)))
    assert X.shape == B.shape
    cols = np.stack(
        [np.asarray(s.solve(jnp.asarray(B[:, j]))) for j in range(B.shape[1])],
        axis=1)
    np.testing.assert_allclose(X, cols, rtol=1e-5, atol=1e-5)
    X_ref = np_bsolve(L.astype(np.float64), B.astype(np.float64))
    np.testing.assert_allclose(X, X_ref, rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("dist_strategy", ["all_gather", "psum"])
def test_transpose_distributed(dist_strategy):
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((8,), ("data",))
    L = random_lower(400, avg_offdiag=3.0, seed=4, dtype=np.float32)
    rng = np.random.default_rng(6)
    b = rng.normal(size=400).astype(np.float32)
    x_ref = np_bsolve(L.astype(np.float64), b.astype(np.float64))
    s = SpTRSV.build(L, strategy="distributed", transpose=True, mesh=mesh,
                     dist_strategy=dist_strategy,
                     rewrite=RewriteConfig(thin_threshold=4))
    x = np.asarray(s.solve(jnp.asarray(b)))
    np.testing.assert_allclose(x, x_ref, rtol=2e-3, atol=2e-4)
    B = rng.normal(size=(400, 8)).astype(np.float32)
    X = np.asarray(s.solve_batched(jnp.asarray(B)))
    np.testing.assert_allclose(
        X, np_bsolve(L.astype(np.float64), B.astype(np.float64)),
        rtol=2e-3, atol=2e-4)


def test_build_pair_shares_analysis_and_matches_separate_builds():
    L = banded_lower(150, bandwidth=5, fill=0.6, seed=8, dtype=np.float32)
    fwd, bwd = SpTRSV.build_pair(L, strategy="levelset")
    assert not fwd.transpose and bwd.transpose
    rng = np.random.default_rng(0)
    b = rng.normal(size=L.n).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(bwd.solve(jnp.asarray(b))),
        np.asarray(SpTRSV.build(L, transpose=True).solve(jnp.asarray(b))),
        rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(fwd.solve(jnp.asarray(b))),
        np.asarray(SpTRSV.build(L).solve(jnp.asarray(b))),
        rtol=1e-6, atol=1e-6)


# -- preconditioner equivalence --------------------------------------------
def test_shared_analysis_preconditioner_matches_reverse_permute_on_lung2():
    from repro.core.pcg import make_ic_preconditioner

    L = lung2_like(scale=0.02, fat_levels=4, thin_run=6, dtype=np.float32)
    rewrite = RewriteConfig(thin_threshold=2)

    # legacy construction: transpose + reverse-permute + second full build
    n = L.n
    rows = np.repeat(np.arange(n), L.row_nnz())
    Lt = from_coo(L.indices, rows, L.data, (n, n))
    rows_t = np.repeat(np.arange(n), Lt.row_nnz())
    Lt_rev = from_coo(n - 1 - rows_t, n - 1 - Lt.indices, Lt.data, (n, n))
    fwd = SpTRSV.build(L, rewrite=rewrite)
    bwd = SpTRSV.build(Lt_rev, rewrite=rewrite)

    def legacy(r):
        return bwd.solve(fwd.solve(r)[::-1])[::-1]

    shared = make_ic_preconditioner(L, rewrite=rewrite)
    rng = np.random.default_rng(5)
    r = jnp.asarray(rng.normal(size=n).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(shared(r)), np.asarray(legacy(r)), rtol=1e-4, atol=1e-5)
    # batched applies agree column-wise too
    R = jnp.asarray(rng.normal(size=(n, 4)).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(shared(R)), np.asarray(legacy(R)), rtol=1e-4, atol=1e-5)


# -- serving ---------------------------------------------------------------
def test_solve_engine_routes_transpose_requests():
    from repro.serve.engine import SolveEngine

    L = random_lower(120, avg_offdiag=3.0, seed=2, dtype=np.float32)
    fwd, bwd = SpTRSV.build_pair(L, strategy="levelset")
    eng = SolveEngine(fwd, bwd, max_batch=8)
    rng = np.random.default_rng(1)
    reqs = []
    for i in range(10):
        b = rng.normal(size=L.n).astype(np.float32)
        reqs.append((eng.submit(b, transpose=bool(i % 2)), b, bool(i % 2)))
    done = eng.run()
    assert done == 10 and eng.solved == 10
    from test_property_solvers import np_fsolve

    for req, b, transpose in reqs:
        assert req.done
        ref = (np_bsolve if transpose else np_fsolve)(
            L.astype(np.float64), b.astype(np.float64))
        np.testing.assert_allclose(req.x, ref, rtol=2e-3, atol=2e-4)


def test_solve_engine_rejects_transpose_without_solver():
    L = random_lower(30, seed=0, dtype=np.float32)
    from repro.serve.engine import SolveEngine

    eng = SolveEngine(SpTRSV.build(L))
    # a real ValueError, not an assert — asserts are stripped under
    # ``python -O`` and the request would strand in the queue unanswered
    with pytest.raises(ValueError, match="transpose"):
        eng.submit(np.zeros(L.n, np.float32), transpose=True)


# -- validation ------------------------------------------------------------
def test_validate_catches_malformed_row_beyond_spot_check():
    """A row with unsorted/duplicate columns past the old 64-row spot-check
    window must fail validation (it would corrupt _pack_rows' diag-last
    assumption silently)."""
    from repro.core.csr import CSRMatrix, from_dense

    L = random_lower(100, avg_offdiag=2.0, seed=1)
    L.validate()  # well-formed passes the full check
    bad_row = 80
    lo, hi = int(L.indptr[bad_row]), int(L.indptr[bad_row + 1])
    assert hi - lo >= 2, "need an off-diagonal entry to corrupt"
    indices = L.indices.copy()
    indices[lo], indices[hi - 1] = indices[hi - 1], indices[lo]  # unsort
    bad = CSRMatrix(L.indptr, indices, L.data, L.shape)
    with pytest.raises(AssertionError, match=f"row {bad_row}"):
        bad.validate()
