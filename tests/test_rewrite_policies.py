"""Criticality-guided selective rewriting + unified transform planner.

Covers the tentpole surfaces:

* the batched vectorized engine is decision- and pattern-identical to the
  seed dict-loop engine (values to fp tolerance), both triangles;
* ``policy="critical_path"`` targets only (near-)critical chain rows —
  strictly fewer rewrites/fill than ``thin`` when off-critical thin rows
  exist — and cuts the weighted critical path within the default budgets;
* per-row cost/benefit and pivot-skip counts are surfaced in RewriteStats;
* ``pivot_tol`` regression: an exactly-zero (or sub-tolerance) off-level
  pivot is skipped, leaving the row finite and solvable — no NaNs;
* array-form plans replay on new values (and refuse zero pivots);
* ``strategy="auto"`` prices rewrite vs coarsen vs both and records the
  transform on ``solver.plan``; explicit configs stay user directives.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from jax import enable_x64
from repro.core import (
    RewriteConfig,
    RewriteReplayError,
    SpTRSV,
    compute_criticality,
    from_dense,
    replay_rewrite_values,
    rewrite_matrix,
)
from repro.core.csr import CSRMatrix
from repro.core.levels import build_level_sets, build_reverse_level_sets
from repro.sparse import chain_matrix, lung2_like, pathological, random_lower


def np_fsolve(L, b):
    x = np.zeros(L.n)
    for i in range(L.n):
        c, v = L.row(i)
        x[i] = (b[i] - (v[:-1] * x[c[:-1]]).sum()) / v[-1]
    return x


def _lung2():
    return lung2_like(scale=0.05, fat_levels=6, thin_run=10, dtype=np.float64)


def _assert_same_rewrite(ra, rb):
    np.testing.assert_array_equal(ra.L.indptr, rb.L.indptr)
    np.testing.assert_array_equal(ra.L.indices, rb.L.indices)
    np.testing.assert_allclose(ra.L.data, rb.L.data, rtol=1e-12, atol=1e-14)
    np.testing.assert_array_equal(ra.E.indptr, rb.E.indptr)
    np.testing.assert_array_equal(ra.E.indices, rb.E.indices)
    np.testing.assert_allclose(ra.E.data, rb.E.data, rtol=1e-12, atol=1e-14)
    assert ra.stats.rows_rewritten == rb.stats.rows_rewritten
    assert ra.stats.eliminations == rb.stats.eliminations


# -------------------------------------------------------------------------
# engine equivalence
# -------------------------------------------------------------------------
@pytest.mark.parametrize("policy", ["thin", "critical_path"])
@pytest.mark.parametrize("mat", ["lung2", "random", "ladder"])
def test_vectorized_matches_loop(policy, mat):
    L = {"lung2": _lung2,
         "random": lambda: random_lower(200, avg_offdiag=3.0, seed=7),
         "ladder": lambda: pathological("singleton_ladder", n=120, seed=2)}[mat]()
    kw = dict(policy=policy, thin_threshold=3)
    rv = rewrite_matrix(L, config=RewriteConfig(engine="vectorized", **kw))
    rl = rewrite_matrix(L, config=RewriteConfig(engine="loop", **kw))
    _assert_same_rewrite(rv, rl)


def test_vectorized_matches_loop_upper():
    L = _lung2()
    U = L.transpose()
    levels = build_reverse_level_sets(L)
    rv = rewrite_matrix(U, levels, RewriteConfig(engine="vectorized"),
                        upper=True)
    rl = rewrite_matrix(U, levels, RewriteConfig(engine="loop"), upper=True)
    _assert_same_rewrite(rv, rl)


def test_engine_auto_uses_loop_for_original_rows():
    L = random_lower(60, seed=3)
    res = rewrite_matrix(
        L, config=RewriteConfig(thin_threshold=2, use_original_rows=True))
    assert res.plan.rounds is None      # loop engine — dict replay path
    with pytest.raises(ValueError, match="use_original_rows"):
        rewrite_matrix(L, config=RewriteConfig(
            use_original_rows=True, engine="vectorized"))


# -------------------------------------------------------------------------
# critical_path policy
# -------------------------------------------------------------------------
def _two_chain_matrix(pool=40, runs=10, depth=6, seed=0):
    """A level-0 pool feeding ``runs`` parallel pairs of chains per level
    band: a HEAVY chain (2 pool deps per row — on the weighted critical
    path) and a LIGHT chain (single dep — same levels, but its through-path
    weight is far below the critical path).  Thin-policy rewriting lifts
    both chains; criticality-guided rewriting must touch only the heavy
    ones."""
    rng = np.random.default_rng(seed)
    r, c, v = [], [], []

    def add(i, j, val):
        r.append(i), c.append(j), v.append(val)

    for p in range(pool):
        add(p, p, 4.0 + rng.random())
    i = pool
    for _ in range(runs):
        prev_a = prev_b = None
        for t in range(depth):
            a = i
            add(a, a, 4.0 + rng.random())
            for j in rng.choice(pool, size=2, replace=False):
                add(a, int(j), rng.normal() * 0.3)
            if prev_a is not None:
                add(a, prev_a, rng.normal() * 0.3)
            prev_a = a
            b = i + 1
            add(b, b, 4.0 + rng.random())
            add(b, prev_b if prev_b is not None
                else int(rng.integers(0, pool)), rng.normal() * 0.3)
            prev_b = b
            i += 2
    from repro.core import from_coo
    return from_coo(r, c, np.asarray(v), (i, i))


def test_critical_path_targets_fewer_rows_same_chain_cut():
    L = _two_chain_matrix()
    # both chains of a level band share a level => width 2*runs
    thin = rewrite_matrix(L, config=RewriteConfig(thin_threshold=20))
    crit = rewrite_matrix(L, config=RewriteConfig(policy="critical_path"))
    # both collapse the weighted critical path...
    assert crit.stats.critical_path_reduction >= 0.25
    assert thin.stats.critical_path_before == crit.stats.critical_path_before
    # ...but the criticality-guided policy touches strictly fewer rows and
    # pays strictly less fill (the off-critical chains stay untouched)
    assert crit.stats.rows_rewritten < thin.stats.rows_rewritten
    assert crit.stats.nnz_after <= thin.stats.nnz_after
    assert crit.stats.policy == "critical_path"
    # within the default fill budget
    assert crit.stats.nnz_after <= 2.0 * crit.stats.nnz_before
    # and still exact
    rng = np.random.default_rng(1)
    b = rng.standard_normal(L.n)
    np.testing.assert_allclose(
        np_fsolve(crit.L, crit.E.matvec(b)), np_fsolve(L, b),
        rtol=1e-9, atol=1e-11)


def test_criticality_membership_matches_definition():
    L = _lung2()
    levels = build_level_sets(L)
    crit = compute_criticality(L, levels)
    # brute-force weighted longest path on the dense DAG
    Ld = L.to_dense()
    w = crit.weights
    cp = np.zeros(L.n, dtype=np.int64)
    for i in range(L.n):
        deps = np.nonzero(Ld[i, :i])[0]
        cp[i] = w[i] + (cp[deps].max() if deps.size else 0)
    np.testing.assert_array_equal(crit.cp_in, cp)
    assert crit.critical_path == cp.max()
    # generic (no-levels) path agrees with the level-based fast path
    crit2 = compute_criticality(L)
    np.testing.assert_array_equal(crit2.cp_in, crit.cp_in)
    np.testing.assert_array_equal(crit2.cp_out, crit.cp_out)


def test_per_row_cost_benefit_surfaced():
    L = _lung2()
    res = rewrite_matrix(L, config=RewriteConfig(policy="critical_path"))
    s = res.stats
    assert s.rewritten_rows is not None and s.rewritten_rows.size == s.rows_rewritten
    assert s.row_fill.shape == s.rewritten_rows.shape
    assert s.row_benefit.shape == s.rewritten_rows.shape
    # fill sums to the global fill; benefit is nonnegative chain shortening
    assert int(s.row_fill.sum()) == s.nnz_after - s.nnz_before
    assert (s.row_benefit >= 0).all()
    assert s.row_benefit.max() > 0
    assert "critical path" in s.summary()


# -------------------------------------------------------------------------
# pivot_tol regression (exactly-zero / sub-tolerance off-level pivots)
# -------------------------------------------------------------------------
def test_zero_pivot_is_skipped_not_nan():
    # row 1 (thin level 1) stores an EXPLICIT zero diagonal (from_coo keeps
    # explicit zeros; from_dense would drop the entry); row 2 depends on it
    Ld = np.array([
        [1.0, 0.0, 0.0, 0.0],
        [0.5, 0.0, 0.0, 0.0],      # zero pivot
        [0.0, 0.7, 2.0, 0.0],
        [0.0, 0.0, 0.3, 3.0],
    ])
    from repro.core import from_coo
    rr, cc = np.nonzero(Ld + np.eye(4))   # include the zero diagonal slot
    L = from_coo(rr, cc, Ld[rr, cc], (4, 4))
    res = rewrite_matrix(L, config=RewriteConfig(thin_threshold=1))
    assert np.isfinite(res.L.data).all() and np.isfinite(res.E.data).all()
    # the elimination of dep 1 was skipped, surfaced in the stats...
    assert res.stats.eliminations_skipped >= 1
    # ...and row 2 still carries its dependency on row 1 (not dropped, not
    # poisoned): the transformed system is algebraically identical
    cols2, vals2 = res.L.row(2)
    assert 1 in cols2.tolist()
    np.testing.assert_allclose(res.E.to_dense() @ Ld, res.L.to_dense(),
                               rtol=1e-12, atol=1e-14)


@pytest.mark.parametrize("engine", ["vectorized", "loop"])
def test_tiny_pivot_under_tolerance_keeps_row_solvable(engine):
    rng = np.random.default_rng(5)
    n = 40
    Ld = np.eye(n) * (3.0 + rng.random(n))
    for i in range(1, n):
        Ld[i, i - 1] = 0.4
    Ld[7, 7] = 1e-12                # sub-tolerance pivot on the chain
    L = from_dense(Ld)
    cfg = RewriteConfig(thin_threshold=1, pivot_tol=1e-8, engine=engine,
                        max_fill_ratio=50.0)
    res = rewrite_matrix(L, config=cfg)
    assert res.stats.eliminations_skipped >= 1
    assert np.isfinite(res.L.data).all() and np.isfinite(res.E.data).all()
    b = rng.standard_normal(n)
    x = np_fsolve(res.L, res.E.matvec(b))
    np.testing.assert_allclose(x, np.linalg.solve(Ld, b), rtol=1e-6, atol=1e-9)


def test_solver_end_to_end_with_pivot_tol():
    L = _lung2()
    data = L.data.copy()
    # shrink one thin-level diagonal below tolerance
    levels = build_level_sets(L)
    thin_rows = np.nonzero((levels.counts <= 2)[levels.level]
                           & (levels.level > 0))[0]
    i = int(thin_rows[3])
    data[L.indptr[i + 1] - 1] = 1e-13
    L2 = CSRMatrix(L.indptr, L.indices, data, L.shape)
    with enable_x64():
        s = SpTRSV.build(L2, strategy="levelset",
                         rewrite=RewriteConfig(thin_threshold=2,
                                               pivot_tol=1e-8))
        assert s.rewrite_result.stats.eliminations_skipped >= 1
        b = np.random.default_rng(2).standard_normal(L.n)
        x = np.asarray(s.solve(jnp.asarray(b)))
        assert np.isfinite(x).all()
        np.testing.assert_allclose(x, np_fsolve(L2, b), rtol=1e-6, atol=1e-8)


# -------------------------------------------------------------------------
# array-form replay
# -------------------------------------------------------------------------
def test_array_plan_replays_and_refuses_zero_pivot():
    L = _lung2()
    res = rewrite_matrix(L, config=RewriteConfig(thin_threshold=2))
    assert res.plan.rounds is not None and len(res.plan.rounds) > 0
    assert res.plan.rows      # legacy summary still populated
    rng = np.random.default_rng(11)
    d2 = L.data + 0.05 * rng.standard_normal(L.nnz)
    d2[L.indptr[1:] - 1] += 2.0
    L2 = CSRMatrix(L.indptr, L.indices, d2, L.shape)
    lp, ed = replay_rewrite_values(L2, res.plan, res.L, res.E)
    fresh = rewrite_matrix(L2, config=RewriteConfig(thin_threshold=2))
    np.testing.assert_allclose(lp, fresh.L.data, rtol=1e-9, atol=1e-11)
    np.testing.assert_allclose(ed, fresh.E.data, rtol=1e-9, atol=1e-11)
    # zero out an eliminated pivot: the plan must refuse, not divide
    piv = int(res.plan.rounds[0].elim_piv[0])
    d3 = d2.copy()
    d3[L.indptr[piv + 1] - 1] = 0.0
    with pytest.raises(RewriteReplayError, match="zero pivot"):
        replay_rewrite_values(CSRMatrix(L.indptr, L.indices, d3, L.shape),
                              res.plan, res.L, res.E)


# -------------------------------------------------------------------------
# transform planner
# -------------------------------------------------------------------------
def test_auto_plans_rewrite_on_lung2():
    L = lung2_like(scale=0.05, fat_levels=6, thin_run=10, dtype=np.float32)
    s = SpTRSV.build(L, strategy="auto")
    assert s.plan.rewrite in ("thin", "critical_path")
    assert s.rewrite_result is not None
    assert s.rewrite_result.stats.policy == s.plan.rewrite
    # both transform families were actually priced
    assert any("+rewrite:" in k for k in s.plan.costs)
    assert any("+coarsen" in k for k in s.plan.costs)
    assert any(("+rewrite:" in k and "+coarsen" in k) for k in s.plan.costs)
    b = np.random.default_rng(0).standard_normal(L.n).astype(np.float32)
    ref = np.asarray(SpTRSV.build(L, strategy="serial").solve(jnp.asarray(b)))
    np.testing.assert_allclose(np.asarray(s.solve(jnp.asarray(b))), ref,
                               rtol=2e-5, atol=2e-6)
    st = s.stats()
    assert st["planned_transform"] == {"rewrite": s.plan.rewrite,
                                       "coarsen": s.plan.coarsen}
    assert st["rewrite_policy"] == s.plan.rewrite


def test_auto_skips_rewrite_candidates_for_chains_and_wavefronts():
    chain = SpTRSV.build(chain_matrix(2000), strategy="auto")
    assert chain.plan.rewrite is None
    assert not any("+rewrite:" in k for k in chain.plan.costs)
    wide = SpTRSV.build(random_lower(300, seed=1), strategy="auto")
    assert wide.plan.rewrite is None


def test_explicit_rewrite_is_a_user_directive():
    L = lung2_like(scale=0.05, fat_levels=6, thin_run=10, dtype=np.float32)
    cfg = RewriteConfig(thin_threshold=2, max_fill_ratio=1.2)
    s = SpTRSV.build(L, strategy="auto", rewrite=cfg)
    # planner did not price alternative policies — it took the directive
    assert s.plan.rewrite is None
    assert not any("+rewrite:" in k for k in s.plan.costs)
    assert s.rewrite_result is not None
    assert s.rewrite_result.stats.policy == "thin"


def test_planner_transform_composes_with_refresh_and_transpose():
    L = lung2_like(scale=0.05, fat_levels=6, thin_run=10, dtype=np.float32)
    rng = np.random.default_rng(4)
    b = rng.standard_normal(L.n).astype(np.float32)
    fwd, bwd = SpTRSV.build_pair(L, strategy="auto")
    assert fwd.plan is not None and bwd.plan is not None
    d2 = (L.data + 0.1 * rng.standard_normal(L.nnz)).astype(np.float32)
    d2[L.indptr[1:] - 1] += 3.0
    fwd.refresh(d2)
    bwd.refresh(d2)
    L2 = CSRMatrix(L.indptr, L.indices, d2, L.shape)
    rf = np.asarray(SpTRSV.build(L2, strategy="serial").solve(jnp.asarray(b)))
    rb = np.asarray(SpTRSV.build(L2, strategy="serial",
                                 transpose=True).solve(jnp.asarray(b)))
    np.testing.assert_allclose(np.asarray(fwd.solve(jnp.asarray(b))), rf,
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(bwd.solve(jnp.asarray(b))), rb,
                               rtol=2e-5, atol=2e-6)


def test_solve_engine_surfaces_transform_stats():
    from repro.serve import SolveEngine

    L = lung2_like(scale=0.04, fat_levels=5, thin_run=8, dtype=np.float32)
    eng = SolveEngine.from_matrix(L)
    st = eng.stats()
    assert st["forward"]["planned_transform"] is not None
    assert st["backward"] is not None
    assert st["queue_depth"] == 0
    b = np.random.default_rng(9).standard_normal(L.n).astype(np.float32)
    req = eng.submit(b)
    eng.run()
    ref = np.asarray(SpTRSV.build(L, strategy="serial").solve(jnp.asarray(b)))
    np.testing.assert_allclose(req.x, ref, rtol=2e-5, atol=2e-6)
    assert eng.stats()["solved"] == 1
