"""Supernodal/blocked solves: supernode detection, the blocked schedule,
both blocked executors (scatter + packed), planner integration, and the
stats surface.

Regression pins (ISSUE 8): ``lung2_like`` amalgamates to *nothing* (its thin
2-row chains never share structure with their neighbours), and the
``jagged_rows`` pathological pattern is all-singleton by construction — both
must report ``mean_block_size == 1.0`` and be excluded from the planner's
blocked candidacy, so adding the blocked executor cannot change any
previously-planned decision on lung2-class inputs.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from jax import enable_x64
from repro.core import SpTRSV, analyze
from repro.core.coarsen import blocked_candidate, build_block_schedule
from repro.core.levels import SupernodeConfig, Supernodes, detect_supernodes
from repro.sparse import pathological
from repro.sparse.generate import banded_lower, ic0_factor, lung2_like, poisson2d


def _oracle(L, b, transpose=False):
    A = L.to_dense()
    return np.linalg.solve(A.T if transpose else A, b)


def _check_partition(sn: Supernodes):
    """Structural invariants every detection result must satisfy."""
    assert sn.block_ptr[0] == 0 and sn.block_ptr[-1] == sn.n
    assert (np.diff(sn.block_ptr) >= 1).all()
    for b in range(sn.num_supernodes):
        lo, hi = sn.block_ptr[b], sn.block_ptr[b + 1]
        assert (sn.super_of_row[lo:hi] == b).all()
    assert sn.sizes().sum() == sn.n
    assert sn.max_block_size <= sn.config.max_block


# --------------------------------------------------------------------------
# detection
# --------------------------------------------------------------------------
def test_detection_dense_band_needs_relaxation():
    """Past its ramp-up triangle, a fully dense band has mismatch exactly 1
    between every adjacent row pair (the window slides by one), so exact
    matching (relax=0) merges only the leading bw+1 identical-structure rows
    and any relax >= 1/(bw+1) amalgamates the whole band."""
    L = banded_lower(256, bandwidth=16, fill=1.0, seed=0)
    strict = detect_supernodes(L, config=SupernodeConfig(relax=0.0))
    assert strict.num_supernodes == L.n - 16  # one 17-row ramp block
    assert strict.mean_block_size < 1.1
    relaxed = detect_supernodes(L, config=SupernodeConfig(relax=0.25))
    assert relaxed.mean_block_size > 8.0
    assert relaxed.dense_block_fraction > 0.9
    _check_partition(strict)
    _check_partition(relaxed)


def test_detection_max_block_cap():
    L = banded_lower(256, bandwidth=16, fill=1.0, seed=0)
    sn = detect_supernodes(L, config=SupernodeConfig(relax=0.25, max_block=8))
    assert sn.max_block_size <= 8
    assert sn.mean_block_size > 4.0
    _check_partition(sn)


def test_detection_upper_matches_transposed_lower():
    """Detecting on the upper factor (transpose solve) must find the same
    partition the lower factor does — the criterion is mirrored."""
    L = banded_lower(192, bandwidth=8, fill=1.0, seed=2)
    U = L.transpose()
    lo = detect_supernodes(L, upper=False)
    up = detect_supernodes(U, upper=True)
    np.testing.assert_array_equal(lo.block_ptr, up.block_ptr)


def test_detection_pins_lung2_all_singleton():
    """Regression pin: lung2-class inputs amalgamate to nothing, so the
    planner's blocked gate (mean block size >= 1.5) excludes them."""
    L = lung2_like(scale=0.02, seed=3)
    sn = detect_supernodes(L)
    assert sn.num_supernodes == L.n
    assert sn.mean_block_size == 1.0
    assert sn.dense_block_fraction == 0.0
    _check_partition(sn)


def test_detection_pins_jagged_rows_all_singleton():
    """Regression pin: the engineered no-amalgamatable pattern stays
    all-singleton even under a generous relaxation budget."""
    L = pathological("jagged_rows", n=96, seed=1)
    sn = detect_supernodes(L, config=SupernodeConfig(relax=0.5))
    assert sn.num_supernodes == L.n
    assert sn.mean_block_size == 1.0
    assert sn.dense_block_fraction == 0.0


# --------------------------------------------------------------------------
# analysis / stats surface
# --------------------------------------------------------------------------
def test_analysis_reports_supernode_metrics():
    L = banded_lower(128, bandwidth=8, fill=1.0, seed=0)
    a = analyze(L)
    rep = a.report()
    assert rep["supernode_count"] == a.supernodes.num_supernodes
    assert rep["supernode_count"] < L.n
    assert rep["mean_block_size"] > 1.5
    assert 0.0 < rep["dense_block_fraction"] <= 1.0

    a2 = analyze(lung2_like(scale=0.02, seed=3))
    rep2 = a2.report()
    assert rep2["supernode_count"] == a2.n
    assert rep2["mean_block_size"] == 1.0
    assert rep2["dense_block_fraction"] == 0.0


def test_solver_stats_expose_supernode_metrics():
    L = banded_lower(128, bandwidth=8, fill=1.0, seed=0)
    with enable_x64():
        s = SpTRSV.build(L, strategy="blocked")
        st = s.stats()
        assert st["supernode_count"] == s.supernodes.num_supernodes
        assert st["mean_block_size"] == s.supernodes.mean_block_size
        assert st["dense_block_fraction"] == s.supernodes.dense_block_fraction
        assert st["segments"] == s.block_schedule.num_segments
        # non-blocked solvers fall back to the analysis-level metrics
        s2 = SpTRSV.build(L, strategy="levelset")
        assert s2.stats()["supernode_count"] == st["supernode_count"]


# --------------------------------------------------------------------------
# block schedule
# --------------------------------------------------------------------------
def test_block_schedule_invariants():
    L = banded_lower(200, bandwidth=6, fill=0.9, seed=4)
    sn = detect_supernodes(L)
    bs = build_block_schedule(L, sn)
    perm = bs.perm()
    assert sorted(perm.tolist()) == list(range(L.n))
    assert bs.num_blocks == sn.num_supernodes
    assert bs.n == L.n and bs.nnz == L.nnz
    # every cross-block dependency points from an earlier super-level
    order = {b: lvl for b, lvl in enumerate(bs.level_of_block)}
    for i in range(L.n):
        cols, _ = L.row(i)
        for j in cols[cols < i]:
            if sn.super_of_row[j] != sn.super_of_row[i]:
                assert order[sn.super_of_row[j]] < order[sn.super_of_row[i]]
    cand = blocked_candidate(bs)
    assert cand.segments == bs.num_segments
    assert cand.panel_flops > 0 and cand.gemm_flops > 0


# --------------------------------------------------------------------------
# executors
# --------------------------------------------------------------------------
@pytest.mark.parametrize("layout", ["scatter", "permuted"])
@pytest.mark.parametrize("batch", [0, 3])
def test_blocked_executor_matches_oracle(layout, batch):
    L = banded_lower(150, bandwidth=6, fill=0.95, seed=1)
    rng = np.random.default_rng(7)
    b = rng.standard_normal((L.n, batch) if batch else L.n)
    with enable_x64():
        s = SpTRSV.build(L, strategy="blocked", layout=layout)
        x = np.asarray(s.solve(jnp.asarray(b)))
    np.testing.assert_allclose(x, _oracle(L, b), rtol=1e-12, atol=1e-12)


def test_blocked_build_pair_transpose():
    L = banded_lower(150, bandwidth=6, fill=0.95, seed=1)
    rng = np.random.default_rng(8)
    b = rng.standard_normal(L.n)
    with enable_x64():
        fwd, bwd = SpTRSV.build_pair(L, strategy="blocked", layout="permuted")
        np.testing.assert_allclose(np.asarray(fwd.solve(jnp.asarray(b))),
                                   _oracle(L, b), rtol=1e-12, atol=1e-12)
        np.testing.assert_allclose(np.asarray(bwd.solve(jnp.asarray(b))),
                                   _oracle(L, b, transpose=True),
                                   rtol=1e-12, atol=1e-12)
        assert bwd.transpose and bwd.supernodes is not None


def test_blocked_refresh_is_value_only_on_permuted():
    L = banded_lower(150, bandwidth=6, fill=0.95, seed=1)
    data2 = L.data * 1.3 + 0.01
    from repro.core import CSRMatrix
    L2 = CSRMatrix(L.indptr, L.indices, data2, L.shape)
    rng = np.random.default_rng(9)
    b = rng.standard_normal(L.n)
    with enable_x64():
        s = SpTRSV.build(L, strategy="blocked", layout="permuted")
        assert s.stats()["refreshable_in_place"]
        assert s.refresh(data2) is s
        np.testing.assert_allclose(np.asarray(s.solve(jnp.asarray(b))),
                                   _oracle(L2, b), rtol=1e-12, atol=1e-12)
        # scatter embeds values at trace time -> cold rebuild, same answer
        s2 = SpTRSV.build(L, strategy="blocked", layout="scatter")
        s2.refresh(data2)
        np.testing.assert_allclose(np.asarray(s2.solve(jnp.asarray(b))),
                                   _oracle(L2, b), rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("backend", ["interpret", "interpret:gpu"])
def test_blocked_pallas_kernel_both_families(backend):
    """``block_kernel="pallas"`` drives kernels/trsm_block through both
    lowering families under the interpreter.  The kernels accumulate in
    float32, so the tolerance is loose."""
    L = banded_lower(96, bandwidth=8, fill=1.0, seed=2)
    rng = np.random.default_rng(11)
    b = rng.standard_normal(L.n)
    with enable_x64():
        s = SpTRSV.build(L, strategy="blocked", layout="permuted",
                         block_kernel="pallas", backend=backend)
        x = np.asarray(s.solve(jnp.asarray(b)))
    np.testing.assert_allclose(x, _oracle(L, b), rtol=1e-4, atol=1e-4)


def test_blocked_composes_with_ic0():
    """The classic IC(0)-preconditioner workload end-to-end: factor a 2-D
    Poisson operator and run the blocked executor on the incomplete
    factor."""
    L = ic0_factor(poisson2d(10, 10))
    rng = np.random.default_rng(12)
    b = rng.standard_normal(L.n)
    with enable_x64():
        s = SpTRSV.build(L, strategy="blocked", layout="permuted")
        x = np.asarray(s.solve(jnp.asarray(b)))
    np.testing.assert_allclose(x, _oracle(L, b), rtol=1e-11, atol=1e-11)


def test_blocked_serves_through_solve_engine():
    from repro.serve import SolveEngine

    L = banded_lower(120, bandwidth=6, fill=0.95, seed=5)
    rng = np.random.default_rng(13)
    with enable_x64():
        eng = SolveEngine.from_matrix(L, strategy="blocked", layout="permuted")
        reqs = [eng.submit(rng.standard_normal(L.n)) for _ in range(3)]
        eng.run()
        for r in reqs:
            assert r.done
            np.testing.assert_allclose(r.x, _oracle(L, r.b),
                                       rtol=1e-11, atol=1e-11)


# --------------------------------------------------------------------------
# planner integration
# --------------------------------------------------------------------------
def test_auto_picks_blocked_on_dense_band():
    """Acceptance gate: on a dense banded factor the planner's calibrated
    gemm/trsm pricing must put the blocked executor below serial and every
    level-set candidate."""
    L = banded_lower(2048, bandwidth=24, fill=1.0, seed=1)
    with enable_x64():
        s = SpTRSV.build(L, strategy="auto")
        assert s.strategy == "blocked", s.plan.reason
        assert "blocked" in s.plan.reason
        rng = np.random.default_rng(14)
        b = rng.standard_normal(L.n)
        np.testing.assert_allclose(np.asarray(s.solve(jnp.asarray(b))),
                                   _oracle(L, b), rtol=1e-11, atol=1e-11)


def test_auto_unchanged_on_lung2_class():
    """Acceptance gate: lung2-class inputs are all-singleton, the blocked
    gate excludes them from candidacy, and the planner's decision is
    byte-identical to a build with supernodes disabled."""
    L = lung2_like(scale=0.02, seed=3)
    with enable_x64():
        s = SpTRSV.build(L, strategy="auto")
        baseline = SpTRSV.build(L, strategy="auto", supernodes=False)
        assert s.strategy == baseline.strategy
        assert s.plan.reason == baseline.plan.reason
        assert "blocked" not in s.plan.reason


def test_relax_knob_threads_through_build():
    L = banded_lower(128, bandwidth=8, fill=1.0, seed=0)
    with enable_x64():
        strict = SpTRSV.build(L, strategy="blocked",
                              supernodes=SupernodeConfig(relax=0.0))
        assert strict.supernodes.mean_block_size < 1.1
        relaxed = SpTRSV.build(L, strategy="blocked",
                               supernodes=SupernodeConfig(relax=0.25))
        assert relaxed.supernodes.mean_block_size > 1.5
        rng = np.random.default_rng(15)
        b = rng.standard_normal(L.n)
        np.testing.assert_allclose(np.asarray(strict.solve(jnp.asarray(b))),
                                   np.asarray(relaxed.solve(jnp.asarray(b))),
                                   rtol=1e-12, atol=1e-12)
