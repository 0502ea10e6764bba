"""Guarded execution layer: residual verification, iterative refinement,
breakdown policies, mixed precision, and the fault-injection matrix.

The fault matrix is the load-bearing part: every
:data:`repro.sparse.faults.VALUE_FAULTS` kind is pushed through
``refresh(..., validate=False)`` into guarded solvers of each strategy
family, and each configured ``on_breakdown`` policy must produce its
*configured* outcome — refine records the breakdown, fallback splices a
finite corrective answer, raise raises :class:`GuardBreakdownError` — not
merely "something happened".
"""
import logging

import numpy as np
import jax.numpy as jnp
import pytest

from jax import enable_x64
from repro.core import (
    GuardBreakdownError,
    GuardConfig,
    SpTRSV,
    repair_pivots,
    scan_values,
)
from repro.sparse import (
    diag_positions,
    inject_values,
    random_lower,
    wrong_pattern,
)

# serial on the permuted layout IS the packed-permuted executor; together
# with levelset / sweep / blocked this covers every executor family the
# acceptance matrix names.
GUARDED_STRATEGIES = ["serial", "levelset", "sweep", "blocked"]


def _mk(n=96, seed=5, m=4):
    L = random_lower(n=n, seed=seed)
    rng = np.random.default_rng(100 + seed)
    return L, rng.standard_normal((n, m))


def _dense_solve(L, B):
    return np.linalg.solve(L.to_dense(), B)


# --------------------------------------------------------------------------
# clean-path behaviour: verification passes, answers match the raw solver
# --------------------------------------------------------------------------
@pytest.mark.parametrize("strategy", GUARDED_STRATEGIES)
@pytest.mark.parametrize("layout", ["permuted", "scatter"])
def test_guard_exactness_on_clean_input(strategy, layout):
    """On a healthy factor the guard is an observer: the guarded answer
    equals the unguarded one bit-for-bit (zero refinement steps taken) and
    the solve verifies."""
    L, B = _mk()
    with enable_x64():
        plain = SpTRSV.build(L, strategy=strategy, layout=layout)
        guarded = SpTRSV.build(L, strategy=strategy, layout=layout, guard=True)
        xp = np.asarray(plain.solve(jnp.asarray(B)))
        xg = np.asarray(guarded.solve(jnp.asarray(B)))
        np.testing.assert_array_equal(xp, xg)
        st = guarded.guard.stats
        assert st.solves == 1 and st.verified == 1
        assert st.last_refine_steps == 0
        assert st.last_residual_ratio <= 128 * np.finfo(np.float64).eps


def test_guard_stats_surface_in_solver_stats():
    L, B = _mk()
    with enable_x64():
        s = SpTRSV.build(L, strategy="levelset", guard=True)
        s.solve(jnp.asarray(B))
        st = s.stats()
        assert st["guard_precision"] == "native"
        assert st["guard_refine_steps"] == 0
        assert st["guard_fallbacks"] == 0
        assert st["guard_pivot_alarms"] == 0
        assert st["guard_residual"] <= 128 * np.finfo(np.float64).eps
        assert st["guard"]["solves"] == 1 and st["guard"]["verified"] == 1
        # unguarded solvers expose the same keys as None (stable dashboards)
        un = SpTRSV.build(L, strategy="levelset").stats()
        assert un["guard"] is None and un["guard_precision"] is None


# --------------------------------------------------------------------------
# fault × policy matrix
# --------------------------------------------------------------------------
@pytest.mark.parametrize("strategy", GUARDED_STRATEGIES)
def test_fault_raise_policy_fires_at_refresh_scan(strategy):
    """Non-finite values and exactly-zero pivots are caught by the O(nnz)
    value scan the moment the faulted values arrive: under
    ``on_breakdown="raise"`` the refresh itself raises (after the swap —
    documented semantics), before any solve runs."""
    L, _ = _mk()
    with enable_x64():
        for kind in ("zero_pivot", "nan_slab", "inf_slab"):
            s = SpTRSV.build(L, strategy=strategy,
                             guard=GuardConfig(on_breakdown="raise"))
            bad = inject_values(L, kind, seed=7)
            with pytest.raises(GuardBreakdownError):
                s.refresh(bad, validate=False)
            assert s.guard.stats.raised == 1


@pytest.mark.parametrize("strategy", GUARDED_STRATEGIES)
def test_fault_raise_policy_fires_at_solve_time(strategy):
    """A subnormal pivot is finite and nonzero, so (at ``pivot_tol=0``) the
    value scan passes — the *residual check* must catch the resulting
    garbage and raise at solve time."""
    L, B = _mk()
    with enable_x64():
        s = SpTRSV.build(L, strategy=strategy,
                         guard=GuardConfig(on_breakdown="raise",
                                           refine_steps=1))
        s.refresh(inject_values(L, "tiny_pivot", seed=7), validate=False)
        with pytest.raises(GuardBreakdownError) as ei:
            s.solve(jnp.asarray(B))
        assert s.guard.stats.raised == 1
        assert ei.value.columns is not None and len(ei.value.columns) > 0


@pytest.mark.parametrize("strategy", GUARDED_STRATEGIES)
def test_fault_fallback_policy_zero_pivot(strategy):
    """Zero pivots + fallback: the scan alarms, the lazily built fallback
    (pivot-repaired) fires, and the answer is finite best-effort — the
    original system is singular, so verification cannot pass, but the
    breakdown is *recorded*, never silent."""
    L, B = _mk()
    with enable_x64():
        s = SpTRSV.build(L, strategy=strategy,
                         guard=GuardConfig(on_breakdown="fallback",
                                           refine_steps=1))
        s.refresh(inject_values(L, "zero_pivot", seed=7), validate=False)
        x = np.asarray(s.solve(jnp.asarray(B)))
        st = s.guard.stats
        assert np.isfinite(x).all()
        assert st.pivot_alarms >= 1
        assert st.fallback_solves == 1 and st.fallback_columns > 0
        assert st.breakdown_columns > 0  # singular original: recorded


@pytest.mark.parametrize("strategy", GUARDED_STRATEGIES)
def test_fault_fallback_policy_nan_slab(strategy):
    """A NaN slab poisons the primary solve; the fallback (NaN values
    zeroed, pivots floored by the repair) must return a finite spliced
    answer with the fallback accounted in stats."""
    L, B = _mk()
    with enable_x64():
        s = SpTRSV.build(L, strategy=strategy,
                         guard=GuardConfig(on_breakdown="fallback",
                                           refine_steps=1))
        s.refresh(inject_values(L, "nan_slab", seed=7), validate=False)
        x = np.asarray(s.solve(jnp.asarray(B)))
        st = s.guard.stats
        assert np.isfinite(x).all()
        assert st.pivot_alarms >= 1 and st.fallback_solves == 1


@pytest.mark.parametrize("strategy", GUARDED_STRATEGIES)
def test_fault_fallback_policy_tiny_pivot_with_pivot_tol(strategy):
    """With ``pivot_tol > 0`` the scan flags sub-tolerance pivots, so the
    fallback is built on *repaired* values (pivots floored) and produces a
    finite answer where the unrepaired factor overflows."""
    L, B = _mk()
    with enable_x64():
        s = SpTRSV.build(L, strategy=strategy,
                         guard=GuardConfig(on_breakdown="fallback",
                                           pivot_tol=1e-10, refine_steps=1))
        s.refresh(inject_values(L, "tiny_pivot", seed=7), validate=False)
        x = np.asarray(s.solve(jnp.asarray(B)))
        st = s.guard.stats
        assert np.isfinite(x).all()
        assert st.pivot_alarms >= 1 and st.fallback_solves == 1


@pytest.mark.parametrize("strategy", GUARDED_STRATEGIES)
def test_fault_refine_policy_is_best_effort(strategy):
    """``on_breakdown="refine"`` never raises and never falls back: a NaN
    slab yields a best-effort answer with the failing columns recorded in
    ``breakdown_columns`` (the healthy columns of the batch still refine —
    one poisoned RHS column must not stop the others)."""
    L, B = _mk()
    with enable_x64():
        s = SpTRSV.build(L, strategy=strategy,
                         guard=GuardConfig(on_breakdown="refine",
                                           refine_steps=1))
        s.refresh(inject_values(L, "nan_slab", seed=7), validate=False)
        s.solve(jnp.asarray(B))  # must not raise
        st = s.guard.stats
        assert st.breakdown_columns > 0
        assert st.fallback_solves == 0 and st.raised == 0


@pytest.mark.parametrize("strategy", ["levelset", "sweep"])
def test_fault_silent_corruption_is_verified_against_current_values(strategy):
    """``perturb_pivot`` and ``denormal_values`` produce *valid* (finite,
    nonzero-pivot) factors — refresh accepts them with ``validate=True``
    and the guard then verifies the solve against the CURRENT system: the
    guarded answer must satisfy the perturbed factor, not the stale one."""
    L, B = _mk()
    with enable_x64():
        for kind in ("perturb_pivot", "denormal_values"):
            s = SpTRSV.build(L, strategy=strategy, guard=True)
            bad = inject_values(L, kind, seed=7)
            s.refresh(bad)          # validate=True: these values are legal
            x = np.asarray(s.solve(jnp.asarray(B)))
            assert s.guard.stats.verified == 1
            L2 = type(L)(L.indptr, L.indices, bad, L.shape)
            np.testing.assert_allclose(x, _dense_solve(L2, B),
                                       rtol=1e-9, atol=1e-9)


# --------------------------------------------------------------------------
# refresh validation (satellite 1)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("layout", ["permuted", "scatter"])
def test_refresh_validation_rejects_broken_values(layout):
    """``refresh`` runs an O(nnz) finiteness + zero-pivot scan by default on
    BOTH layouts; ``validate=False`` admits the same payload (and leaves it
    to a guard, if any)."""
    L, B = _mk()
    with enable_x64():
        for kind in ("zero_pivot", "nan_slab", "inf_slab"):
            s = SpTRSV.build(L, strategy="levelset", layout=layout)
            bad = inject_values(L, kind, seed=7)
            with pytest.raises(ValueError, match="pass validate=False"):
                s.refresh(bad)
            # the rejected refresh must not have touched the live values
            np.testing.assert_allclose(
                np.asarray(s.solve(jnp.asarray(B))), _dense_solve(L, B),
                rtol=1e-10, atol=1e-10)
            s.refresh(bad, validate=False)   # explicitly admitted


@pytest.mark.parametrize("layout", ["permuted", "scatter"])
def test_refresh_validation_accepts_healthy_values(layout):
    L, B = _mk()
    with enable_x64():
        s = SpTRSV.build(L, strategy="levelset", layout=layout)
        good = inject_values(L, "perturb_pivot", seed=7)  # legal values
        s.refresh(good)
        L2 = type(L)(L.indptr, L.indices, good, L.shape)
        np.testing.assert_allclose(
            np.asarray(s.solve(jnp.asarray(B))), _dense_solve(L2, B),
            rtol=1e-9, atol=1e-9)


def test_refresh_rejects_wrong_pattern():
    L, _ = _mk()
    with enable_x64():
        s = SpTRSV.build(L, strategy="levelset", guard=True)
        with pytest.raises(ValueError):
            s.refresh(wrong_pattern(L))


# --------------------------------------------------------------------------
# serving-tier isolation (satellite 2)
# --------------------------------------------------------------------------
def test_engine_isolates_failing_requests():
    """One request whose solve raises (guarded ``on_breakdown="raise"`` with
    a NaN RHS) must not poison its micro-batch: co-batched healthy requests
    still get answers; the culprit carries the exception in ``error``."""
    from repro.serve import SolveEngine

    L, _ = _mk()
    with enable_x64():
        s = SpTRSV.build(L, strategy="levelset",
                         guard=GuardConfig(on_breakdown="raise",
                                           refine_steps=1))
        eng = SolveEngine(s, max_batch=8)
        rng = np.random.default_rng(3)
        good = [eng.submit(rng.standard_normal(L.n)) for _ in range(3)]
        bad_b = rng.standard_normal(L.n)
        bad_b[L.n // 2] = np.nan
        bad = eng.submit(bad_b)
        eng.run()
        for r in good:
            assert r.done and r.error is None
            np.testing.assert_allclose(
                r.x, _dense_solve(L, r.b), rtol=1e-9, atol=1e-9)
        assert bad.done and bad.x is None
        assert isinstance(bad.error, GuardBreakdownError)


def test_engine_refresh_forwards_validate():
    from repro.serve import SolveEngine

    L, _ = _mk()
    with enable_x64():
        s = SpTRSV.build(L, strategy="levelset",
                         guard=GuardConfig(on_breakdown="fallback",
                                           refine_steps=1))
        eng = SolveEngine(s, max_batch=4)
        bad = inject_values(L, "zero_pivot", seed=7)
        with pytest.raises(ValueError, match="pass validate=False"):
            eng.refresh(bad)
        eng.refresh(bad, validate=False)
        r = eng.submit(np.ones(L.n))
        eng.run()
        assert r.done and r.error is None and np.isfinite(r.x).all()
        assert s.guard.stats.fallback_solves >= 1


# --------------------------------------------------------------------------
# mixed precision
# --------------------------------------------------------------------------
def test_mixed_precision_recovers_fp64_accuracy():
    """bf16 value storage + fp32 accumulation + refinement against the fp64
    residual must land within the componentwise residual tolerance of a
    native fp64 solve — the acceptance bar of the guard benchmark."""
    L, B = _mk()
    with enable_x64():
        s = SpTRSV.build(L, strategy="levelset",
                         guard=GuardConfig(precision="mixed",
                                           refine_steps=4))
        x = np.asarray(s.solve(jnp.asarray(B)))
        st = s.guard.stats
        assert st.verified == 1
        assert st.last_residual_ratio <= 128 * np.finfo(np.float64).eps
        assert 1 <= st.last_refine_steps <= 4  # bf16 storage needs refining
        np.testing.assert_allclose(x, _dense_solve(L, B),
                                   rtol=1e-9, atol=1e-9)


def test_mixed_precision_build_pair_both_directions():
    L, B = _mk()
    with enable_x64():
        fwd, bwd = SpTRSV.build_pair(
            L, strategy="levelset",
            guard=GuardConfig(precision="mixed", refine_steps=4))
        y = np.asarray(fwd.solve(jnp.asarray(B)))
        z = np.asarray(bwd.solve(jnp.asarray(y)))
        ref = np.linalg.solve(L.to_dense().T, _dense_solve(L, B))
        np.testing.assert_allclose(z, ref, rtol=1e-8, atol=1e-8)
        assert fwd.guard.stats.verified == 1
        assert bwd.guard.stats.verified == 1


def test_mixed_precision_requires_permuted_runtime_buffers():
    L, _ = _mk()
    with pytest.raises(ValueError, match="mixed"):
        SpTRSV.build(L, strategy="levelset", layout="scatter",
                     guard=GuardConfig(precision="mixed"))


def test_planner_prices_mixed_precision():
    """``plan_strategy(..., precision="mixed")`` discounts every
    gather-bound term by the backend's ``mixed_gather_discount``: gather-
    bound candidates get cheaper, dispatch-bound ones (serial) do not, and
    the decision records the discount."""
    from repro.core.analysis import analyze
    from repro.core.coarsen import plan_strategy
    from repro.core.codegen import build_schedule
    from repro.core.levels import build_level_sets
    from repro.sparse import lung2_like

    L = lung2_like(scale=0.02, fat_levels=4, thin_run=6, dtype=np.float32)
    levels = build_level_sets(L)
    an = analyze(L, levels, upper=False)
    sched = build_schedule(L, levels, upper=False)
    nat = plan_strategy(an, sched, backend="tpu")
    mix = plan_strategy(an, sched, backend="tpu", precision="mixed")
    assert "precision=mixed" in mix.reason and "precision=mixed" not in nat.reason
    assert mix.costs["levelset"] < nat.costs["levelset"]
    assert mix.costs["serial"] == nat.costs["serial"]


# --------------------------------------------------------------------------
# helpers + config validation
# --------------------------------------------------------------------------
def test_scan_values_counts():
    L, _ = _mk()
    dpos = diag_positions(L)
    assert scan_values(L.data, dpos) == (0, 0)
    bad = inject_values(L, "zero_pivot", count=2, seed=7)
    assert scan_values(bad, dpos) == (0, 2)
    nan = inject_values(L, "nan_slab", slab=8, seed=7)
    nonfinite, _ = scan_values(nan, dpos)
    assert nonfinite == 8
    tiny = inject_values(L, "tiny_pivot", count=2, seed=7)
    assert scan_values(tiny, dpos) == (0, 0)          # finite + nonzero
    assert scan_values(tiny, dpos, pivot_tol=1e-10) == (0, 2)


def test_repair_pivots_floors_and_zeroes():
    L, _ = _mk()
    dpos = diag_positions(L)
    bad = inject_values(L, "zero_pivot", count=2, seed=7)
    bad[:4] = np.nan
    rep, n_rep = repair_pivots(bad, dpos)
    assert n_rep >= 2
    assert np.isfinite(rep).all()
    assert (np.abs(rep[dpos]) > 0).all()


def test_guard_config_validation():
    with pytest.raises(AssertionError):
        GuardConfig(on_breakdown="explode")
    with pytest.raises(AssertionError):
        GuardConfig(precision="fp8")
    with pytest.raises(AssertionError):
        GuardConfig(refine_steps=-1)
    with pytest.raises(AssertionError):
        GuardConfig(fallback="pallas_fused")   # not an exact host strategy
    with pytest.raises(AssertionError):
        GuardConfig(pivot_tol=-1e-3)


# --------------------------------------------------------------------------
# guarded preconditioner (tolerance-aware inexact mode)
# --------------------------------------------------------------------------
def test_pcg_with_guarded_preconditioner():
    """The tolerance-aware inexact mode: a guarded preconditioner with a
    loose residual_tol still drives PCG to convergence (flexible-PCG caveat
    covered by stall_window)."""
    from repro.core.pcg import make_ic_preconditioner, pcg
    from repro.sparse import ic0_factor, poisson2d

    with enable_x64():
        A = poisson2d(16, 16)
        Lf = ic0_factor(A)
        M = make_ic_preconditioner(
            Lf, guard=GuardConfig(residual_tol=1e-6, on_breakdown="refine"))
        b = jnp.asarray(np.random.default_rng(0).standard_normal(A.n))
        res = pcg(A, b, M, tol=1e-8, maxiter=400, stall_window=40)
        assert res.converged
