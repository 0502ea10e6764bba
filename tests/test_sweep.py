"""Sync-free speculative solve-then-correct executor (strategy="sweep").

The claims under test, in the order the module docstring makes them:
speculation is exact on diagonally-dominant systems (verified residual, no
fallback), the executor's program has no per-level loop/collective structure
at all, non-converged solves are corrected by the exact fallback
(oracle-equivalence), refresh re-packs the D + N value buffers without
re-tracing, the auto planner prices sweeps against level-set execution, and
the k-sweep inexact preconditioner keeps PCG convergent within 2x of the
exact preconditioner's iteration count.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax import enable_x64
from repro.core import RewriteConfig, SpTRSV, SweepConfig
from repro.core.csr import CSRMatrix
from repro.core.sweep import (
    build_sweep_layout,
    contraction_factor,
    default_residual_tol,
    planned_sweeps,
)
from repro.sparse import chain_matrix, ic0_factor, lung2_like, poisson2d


def _lung2(dtype=np.float64):
    return lung2_like(scale=0.05, fat_levels=6, thin_run=10, dtype=dtype)


def _oracle(L, b, transpose=False):
    A = L.to_dense()
    return np.linalg.solve(A.T if transpose else A, b)


# --------------------------------------------------------------------------
# speculation converges: oracle equivalence with zero fallbacks
# --------------------------------------------------------------------------
@pytest.mark.parametrize("transpose", [False, True])
@pytest.mark.parametrize("batch", [0, 3])
def test_sweep_exact_on_dominant_system(transpose, batch):
    """lung2-class (diagonally dominant, q ≈ 0.2): the k-sweep speculative
    solve must pass verification outright — componentwise-residual-exact
    with the fallback never firing."""
    with enable_x64():
        L = _lung2()
        rng = np.random.default_rng(0)
        b = rng.standard_normal((L.n, batch) if batch else L.n)
        s = SpTRSV.build(L, strategy="sweep", transpose=transpose)
        x = np.asarray(s.solve(jnp.asarray(b)))
        np.testing.assert_allclose(x, _oracle(L, b, transpose),
                                   rtol=1e-12, atol=1e-12)
        assert s.sweep_stats.solves == 1
        assert s.sweep_stats.fallback_solves == 0
        assert s.sweep_stats.last_residual_ratio <= \
            default_residual_tol(np.float64)


def test_sweep_scatter_layout_matches():
    with enable_x64():
        L = _lung2()
        b = np.random.default_rng(1).standard_normal(L.n)
        s = SpTRSV.build(L, strategy="sweep", layout="scatter")
        np.testing.assert_allclose(np.asarray(s.solve(jnp.asarray(b))),
                                   _oracle(L, b), rtol=1e-12, atol=1e-12)


def test_sweep_composes_with_rewrite():
    """Explicit rewrite: sweeps run on the rewritten system L' with the
    b' = E b transform applied upstream — same contract as every other
    executor."""
    with enable_x64():
        L = _lung2()
        b = np.random.default_rng(2).standard_normal(L.n)
        s = SpTRSV.build(L, strategy="sweep",
                         rewrite=RewriteConfig(thin_threshold=2))
        assert s.rewrite_result is not None
        np.testing.assert_allclose(np.asarray(s.solve(jnp.asarray(b))),
                                   _oracle(L, b), rtol=1e-11, atol=1e-11)


# --------------------------------------------------------------------------
# zero intra-solve barriers: program structure
# --------------------------------------------------------------------------
def test_sweep_jaxpr_has_no_level_structure():
    """The acceptance criterion stated structurally: the executor's jaxpr
    contains no loop or collective primitive — no while/scan/fori over
    levels, no per-segment anything.  Per-solve program shape is independent
    of the schedule's depth."""
    with enable_x64():
        L = _lung2()
        s = SpTRSV.build(L, strategy="sweep")
        b = jnp.asarray(np.random.default_rng(3).standard_normal(L.n))
        txt = str(jax.make_jaxpr(lambda bb, vv: s._sweep_exec(bb, vv))(
            b, s._values))
        for prim in ("while", "scan(", "fori", "all_gather", "psum",
                     "ppermute"):
            assert prim not in txt, f"found {prim!r} in sweep jaxpr"
        assert s.stats()["segments"] == 1
        assert s.schedule is None  # no level schedule was even built


def test_sweep_program_size_independent_of_depth():
    """Two chains, 4x apart in level count, produce sweep executors with the
    same number of jaxpr equations (same k) — per-solve cost decoupled from
    depth, which no level-set executor can do."""
    with enable_x64():
        sizes = []
        for n in (200, 800):
            C = chain_matrix(n)
            s = SpTRSV.build(C, strategy="sweep", sweep=SweepConfig(k=8))
            b = jnp.asarray(np.zeros(n))
            jaxpr = jax.make_jaxpr(lambda bb, vv: s._sweep_exec(bb, vv))(
                b, s._values)
            sizes.append(len(jaxpr.jaxpr.eqns))
        assert sizes[0] == sizes[1], sizes


# --------------------------------------------------------------------------
# solve-then-correct: fallback splices exact columns in
# --------------------------------------------------------------------------
def test_sweep_fallback_fires_and_corrects():
    """k=1 on a pure chain cannot converge (information travels one level
    per sweep); verification must reject it and the exact fallback must
    deliver the oracle answer anyway."""
    with enable_x64():
        C = chain_matrix(96)
        b = np.random.default_rng(4).standard_normal(96)
        s = SpTRSV.build(C, strategy="sweep", sweep=SweepConfig(k=1))
        x = np.asarray(s.solve(jnp.asarray(b)))
        np.testing.assert_allclose(x, _oracle(C, b), rtol=1e-12, atol=1e-12)
        assert s.sweep_stats.fallback_solves == 1
        assert s.sweep_stats.fallback_columns == 1
        assert s.sweep_stats.last_residual_ratio > \
            default_residual_tol(np.float64)


def test_sweep_fallback_splices_per_column():
    """Batched verification is per-column: converged speculative columns are
    kept, only offending columns are re-solved.  A zero RHS column converges
    after one sweep even on a chain; a random column does not."""
    with enable_x64():
        C = chain_matrix(96)
        rng = np.random.default_rng(5)
        B = np.stack([np.zeros(96), rng.standard_normal(96)], axis=1)
        s = SpTRSV.build(C, strategy="sweep", sweep=SweepConfig(k=1))
        X = np.asarray(s.solve(jnp.asarray(B)))
        np.testing.assert_allclose(X, _oracle(C, B), rtol=1e-12, atol=1e-12)
        assert s.sweep_stats.fallback_solves == 1
        assert s.sweep_stats.fallback_columns == 1  # only the random column


def test_sweep_fallback_strategy_is_configurable():
    with enable_x64():
        C = chain_matrix(64)
        b = np.random.default_rng(6).standard_normal(64)
        s = SpTRSV.build(C, strategy="sweep",
                         sweep=SweepConfig(k=1, fallback="serial"))
        np.testing.assert_allclose(np.asarray(s.solve(jnp.asarray(b))),
                                   _oracle(C, b), rtol=1e-12, atol=1e-12)
        assert s.sweep_stats.fallback_solves == 1


# --------------------------------------------------------------------------
# refresh: value-only re-pack, no re-trace, fallback stays in sync
# --------------------------------------------------------------------------
def test_sweep_refresh_matches_fresh_build():
    with enable_x64():
        L = _lung2()
        rng = np.random.default_rng(7)
        b = jnp.asarray(rng.standard_normal(L.n))
        s = SpTRSV.build(L, strategy="sweep")
        s.solve(b)
        data2 = L.data * (1.0 + 0.25 * rng.standard_normal(L.nnz))
        # keep diagonal dominance so speculation still converges
        s.refresh(data2)
        L2 = CSRMatrix(L.indptr, L.indices, data2, L.shape)
        np.testing.assert_allclose(np.asarray(s.solve(b)),
                                   _oracle(L2, np.asarray(b)),
                                   rtol=1e-11, atol=1e-11)
        assert s.sweep_stats.fallback_solves == 0


def test_sweep_refresh_does_not_retrace():
    with enable_x64():
        L = _lung2()
        s = SpTRSV.build(L, strategy="sweep")
        b = jnp.asarray(np.random.default_rng(8).standard_normal(L.n))
        s.solve(b)
        if not hasattr(s._sweep_exec, "_cache_size"):
            pytest.skip("jit cache introspection unavailable on this JAX")
        before = s._sweep_exec._cache_size()
        s.refresh(L.data * 1.5)
        s.solve(b)
        assert s._sweep_exec._cache_size() == before


def test_sweep_refresh_updates_lazy_fallback():
    """The exact fallback is built lazily; once built, a refresh must swap
    its values too — otherwise a later correction would solve against stale
    numbers."""
    with enable_x64():
        C = chain_matrix(64)
        rng = np.random.default_rng(9)
        b = rng.standard_normal(64)
        s = SpTRSV.build(C, strategy="sweep", sweep=SweepConfig(k=1))
        s.solve(jnp.asarray(b))          # fallback fires → built
        assert s.sweep_stats.fallback_solves == 1
        data2 = C.data * 3.0
        s.refresh(data2)
        C2 = CSRMatrix(C.indptr, C.indices, data2, C.shape)
        x = np.asarray(s.solve(jnp.asarray(b)))   # fallback fires again
        np.testing.assert_allclose(x, _oracle(C2, b), rtol=1e-12, atol=1e-12)
        assert s.sweep_stats.fallback_solves == 2


# --------------------------------------------------------------------------
# planner: sweeps priced against level-set from the depth/contraction profile
# --------------------------------------------------------------------------
def test_planner_picks_sweep_on_long_dominant_chain():
    """A long diagonally-dominant chain (q = 0.125): the serial scan pays
    O(n) latency-bound steps, level-set pays a barrier per level — the
    certified ~15-sweep speculative solve is modelled far cheaper than
    either, and the decision records the planned k."""
    with enable_x64():
        C = chain_matrix(4000)
        s = SpTRSV.build(C, strategy="auto")
        assert s.strategy == "sweep", s.plan.reason
        assert s.plan.sweep_k is not None and 1 <= s.plan.sweep_k <= 32
        assert "sweep" in s.plan.costs
        assert s.stats()["planned_sweeps"] == s.plan.sweep_k
        # the planner-chosen k must actually converge (no fallback)
        b = np.random.default_rng(10).standard_normal(C.n)
        x = np.asarray(s.solve(jnp.asarray(b)))
        np.testing.assert_allclose(x, _oracle(C, b), rtol=1e-12, atol=1e-12)
        assert s.sweep_stats.fallback_solves == 0


def test_planner_excludes_sweep_without_certified_convergence():
    """Non-dominant system (off-diagonal mass ≥ diagonal): no contraction
    certificate and depth exceeds the cap, so sweeps must not be priced —
    and sweep=False opts out even when they would be."""
    with enable_x64():
        # chain with off-diag 2.0 > diag 1.0: q = 2, depth = n > default cap
        n = 300
        rows = list(range(n)) + list(range(1, n))
        cols = list(range(n)) + list(range(n - 1))
        vals = [1.0] * n + [2.0] * (n - 1)
        from repro.core import from_coo
        C = from_coo(rows, cols, vals, (n, n))
        s = SpTRSV.build(C, strategy="auto")
        assert "sweep" not in s.plan.costs
        # opting out removes sweep from the candidate set on dominant input
        D = chain_matrix(4000)
        s2 = SpTRSV.build(D, strategy="auto", sweep=False)
        assert "sweep" not in s2.plan.costs


def test_planned_sweeps_bounds():
    # nilpotency bound: exact after depth sweeps regardless of contraction
    assert planned_sweeps(2.0, 5, 1e-14, 32) == 5
    # contraction improves on depth when it certifies an earlier stop
    # (⌈log(tol/256)/log q⌉ — margin for the initial-error constant)
    assert planned_sweeps(0.1, 500, 1e-14, 32) == 17
    # neither bound within the cap → no candidate
    assert planned_sweeps(0.99, 500, 1e-14, 32) is None
    assert planned_sweeps(2.0, 500, 1e-14, 32) is None


def test_contraction_factor_matches_dense():
    with enable_x64():
        L = _lung2()
        d = np.abs(np.diag(L.to_dense()))
        off = np.abs(L.to_dense()).sum(axis=1) - d
        np.testing.assert_allclose(contraction_factor(L), (off / d).max())
        # transpose storage reads the diagonal from the front of each row
        Lt = L.transpose()
        dt = np.abs(np.diag(Lt.to_dense()))
        offt = np.abs(Lt.to_dense()).sum(axis=1) - dt
        np.testing.assert_allclose(contraction_factor(Lt, upper=True),
                                   (offt / dt).max())


# --------------------------------------------------------------------------
# layout invariants
# --------------------------------------------------------------------------
def test_sweep_layout_roundtrip():
    """D + N split reassembles to the original matrix, forward and
    transpose."""
    with enable_x64():
        L = _lung2()
        for M, upper in ((L, False), (L.transpose(), True)):
            lay = build_sweep_layout(M, upper=upper)
            dense = np.zeros((M.n, M.n))
            for kk in range(lay.K):
                mask = lay.ell.val_src[kk] >= 0
                dense[np.nonzero(mask)[0],
                      lay.ell.cols[kk][mask]] += lay.ell.vals[kk][mask]
            dense[np.arange(M.n), np.arange(M.n)] += lay.diag
            np.testing.assert_allclose(dense, M.to_dense())


# --------------------------------------------------------------------------
# PCG with the k-sweep inexact preconditioner
# --------------------------------------------------------------------------
def test_pcg_inexact_sweep_preconditioner_within_2x():
    """Acceptance criterion: PCG with the k-sweep inexact M⁻¹ converges on
    the SPD suite within 2x the exact preconditioner's iterations."""
    from repro.core.pcg import make_ic_preconditioner, pcg

    A = poisson2d(24, 24, dtype=np.float32)
    L = ic0_factor(A)
    b = jnp.asarray(
        np.random.default_rng(0).normal(size=A.n).astype(np.float32))
    exact = pcg(A, b, make_ic_preconditioner(L, rewrite=None),
                tol=1e-5, maxiter=1500)
    inexact = pcg(A, b, make_ic_preconditioner(L, sweeps=8),
                  tol=1e-5, maxiter=1500, stall_window=25)
    assert exact.converged and inexact.converged
    assert inexact.iters <= 2 * exact.iters, (inexact.iters, exact.iters)
    x = np.asarray(inexact.x, np.float64)
    r = np.asarray(b, np.float64) - A.astype(np.float64).matvec(x)
    assert np.linalg.norm(r) <= 1e-4 * np.linalg.norm(np.asarray(b))


def test_pcg_inexact_sweep_preconditioner_batched():
    from repro.core.pcg import make_ic_preconditioner_batched, pcg_batched

    A = poisson2d(16, 16, dtype=np.float32)
    L = ic0_factor(A)
    B = jnp.asarray(
        np.random.default_rng(1).normal(size=(A.n, 3)).astype(np.float32))
    res = pcg_batched(A, B, make_ic_preconditioner_batched(L, sweeps=8),
                      tol=1e-5, maxiter=1500)
    assert res.converged.all()


def test_sweep_config_validation():
    with pytest.raises(AssertionError):
        SweepConfig(k=0)
    with pytest.raises(AssertionError):
        SweepConfig(fallback="auto")   # exact strategies only
    cfg = SweepConfig(k=4, fallback=None)
    assert dataclasses.replace(cfg, k=8).k == 8
