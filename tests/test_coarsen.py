"""Schedule coarsening + auto planner + scheduling-knob regression tests.

Covers the PR-3 tentpole and its bugfixes:

* coarsened schedules produce (near-)bit-identical solutions across
  strategy × rewrite × transpose × batch, checked against the uncoarsened
  executor at a few-ulp tolerance and against the serial oracle;
* the greedy cost model actually removes sync points on lung2-class level
  structure and refuses to pad fat wavefronts onto thin chains at scale;
* ``strategy="auto"`` builds on every matrix kind and records its decision;
* regression: ``bucket_pad_ratio`` reaches every schedule-consuming
  strategy (it was silently dropped for pallas_level / pallas_fused /
  distributed);
* regression: ``Schedule.padded_flops(unroll_threshold)`` counts unrolled
  slabs at their true nnz;
* regression: the distributed solver exchanges solved values only — row
  ids are static host-side constants (no per-level index all_gather) and
  ``collective_bytes`` skips replicated (coarsened) segments.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from jax import enable_x64
from repro.core import SpTRSV, RewriteConfig
from repro.core.calibrate import get_calibration
from repro.core.codegen import build_schedule, make_levelset_solver
from repro.core.coarsen import (
    CoarsenConfig,
    coarsen_schedule,
    coarsen_stats,
    plan_strategy,
    schedule_cost,
)
from repro.sparse import banded_lower, chain_matrix, lung2_like, random_lower


def _lung2():
    return lung2_like(scale=0.05, fat_levels=6, thin_run=10, dtype=np.float32)


def _oracle(L, b):
    """Host numpy forward-substitution (float64)."""
    Ld = L.to_dense().astype(np.float64)
    x = np.zeros(b.shape, dtype=np.float64)
    for i in range(L.n):
        x[i] = (b[i] - Ld[i, :i] @ x[:i]) / Ld[i, i]
    return x


# -------------------------------------------------------------------------
# coarsening mechanics
# -------------------------------------------------------------------------
def test_coarsen_reduces_segments_and_preserves_depth():
    sched = build_schedule(_lung2())
    co = coarsen_schedule(sched, CoarsenConfig())
    assert co.num_segments * 4 <= sched.num_segments  # >= 4x fewer barriers
    # every original wavefront is still swept exactly once, in order
    assert co.total_depth == sched.num_segments
    assert np.array_equal(
        np.concatenate([s.rows for s in co.slabs]),
        np.concatenate([s.rows for s in sched.slabs]),
    )
    st = coarsen_stats(sched, co)
    assert st.segment_reduction >= 4.0
    assert st.padded_flops_after >= st.padded_flops_before


def test_coarsen_is_idempotent_and_respects_max_depth():
    sched = build_schedule(_lung2())
    cfg = CoarsenConfig(max_depth=4)
    co = coarsen_schedule(sched, cfg)
    assert max(s.depth for s in co.slabs) <= 4
    again = coarsen_schedule(co, cfg)
    assert [s.depth for s in again.slabs] == [s.depth for s in co.slabs]


def test_coarsen_declines_fat_merges_at_scale():
    # full-width fat levels (few thousand rows) must never absorb thin runs:
    # padding every chained sub-step to the fat width dwarfs a saved barrier
    L = lung2_like(scale=0.5, fat_levels=4, thin_run=8, dtype=np.float32)
    co = coarsen_schedule(build_schedule(L), CoarsenConfig())
    for s in co.slabs:
        if s.depth > 1:
            assert max(s.sub_rows) <= 64, s.sub_rows  # chains stay thin
    # the 4 fat wavefronts survive as their own segments
    fat = [s for s in co.slabs if s.depth == 1 and s.R > 1000]
    assert len(fat) == 4


def test_schedule_cost_prefers_coarsened_on_thin_schedules():
    sched = build_schedule(_lung2())
    co = coarsen_schedule(sched, CoarsenConfig())
    assert schedule_cost(co) < schedule_cost(sched)


# -------------------------------------------------------------------------
# numerical equivalence: strategy × rewrite × transpose × batch
# -------------------------------------------------------------------------
COARSEN_STRATEGIES = ["levelset", "levelset_unroll", "pallas_level"]


@pytest.mark.parametrize("strategy", COARSEN_STRATEGIES)
@pytest.mark.parametrize("transpose", [False, True])
def test_coarsened_matches_uncoarsened_and_oracle(strategy, transpose):
    L64 = lung2_like(scale=0.05, fat_levels=6, thin_run=10)
    rng = np.random.default_rng(0)
    with enable_x64():
        for rewrite in (None, RewriteConfig(thin_threshold=2)):
            base = SpTRSV.build(L64, strategy=strategy, transpose=transpose,
                                rewrite=rewrite)
            co = SpTRSV.build(L64, strategy=strategy, transpose=transpose,
                              rewrite=rewrite, coarsen=True)
            # rewriting may already have emptied every mergeable thin level,
            # so only the unrewritten schedule must strictly shrink
            assert co.schedule.num_segments <= base.schedule.num_segments
            if rewrite is None:
                assert co.schedule.num_segments < base.schedule.num_segments
            for shape in ((L64.n,), (L64.n, 4)):
                b = rng.standard_normal(shape)
                xb = np.asarray(base.solve(jnp.asarray(b)))
                xc = np.asarray(co.solve(jnp.asarray(b)))
                # identical operand sets; XLA may re-contract the padded
                # reduction, so allow a few f64 ulp
                np.testing.assert_allclose(
                    xc, xb, rtol=1e-13, atol=1e-15,
                    err_msg=f"{strategy} transpose={transpose} "
                            f"rewrite={rewrite is not None} shape={shape}")
                if rewrite is None and not transpose and b.ndim == 1:
                    np.testing.assert_allclose(
                        xc, _oracle(L64, b), rtol=1e-9, atol=1e-11)


def test_coarsened_distributed_matches_serial():
    L = _lung2()
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("data",))
    b = np.random.default_rng(3).standard_normal(L.n).astype(np.float32)
    ref = np.asarray(SpTRSV.build(L, strategy="serial").solve(jnp.asarray(b)))
    for dist_strategy in ("all_gather", "psum"):
        s = SpTRSV.build(L, strategy="distributed", mesh=mesh, coarsen=True,
                         dist_strategy=dist_strategy)
        x = np.asarray(s.solve(jnp.asarray(b)))
        np.testing.assert_allclose(x, ref, rtol=2e-5, atol=2e-6)
        B = np.random.default_rng(4).standard_normal((L.n, 3)).astype(np.float32)
        X = np.asarray(s.solve(jnp.asarray(B)))
        for j in range(3):
            rj = np.asarray(SpTRSV.build(L, strategy="serial").solve(
                jnp.asarray(B[:, j])))
            np.testing.assert_allclose(X[:, j], rj, rtol=2e-5, atol=2e-6)


# -------------------------------------------------------------------------
# auto planner
# -------------------------------------------------------------------------
AUTO_MATRICES = [
    ("chain", lambda: chain_matrix(400)),
    ("random", lambda: random_lower(300, seed=1)),
    ("banded", lambda: banded_lower(256, bandwidth=8)),
    ("lung2", lambda: lung2_like(scale=0.05, fat_levels=6, thin_run=10)),
]


@pytest.mark.parametrize("kind,mk", AUTO_MATRICES)
def test_auto_builds_and_solves_everywhere(kind, mk):
    L = mk()
    rng = np.random.default_rng(7)
    with enable_x64():
        for transpose in (False, True):
            for rewrite in (None, RewriteConfig(thin_threshold=2)):
                s = SpTRSV.build(L, strategy="auto", transpose=transpose,
                                 rewrite=rewrite)
                assert s.plan is not None and s.strategy in (
                    "serial", "levelset", "levelset_unroll", "pallas_fused",
                    "sweep")
                assert s.strategy in s.plan.reason or s.plan.costs
                b = rng.standard_normal(L.n)
                x = np.asarray(s.solve(jnp.asarray(b)))
                ref = np.asarray(SpTRSV.build(
                    L, strategy="serial", transpose=transpose,
                    rewrite=rewrite).solve(jnp.asarray(b)))
                np.testing.assert_allclose(x, ref, rtol=1e-6, atol=1e-9)


def test_auto_picks_serial_for_chains_and_parallel_for_wavefronts():
    with enable_x64():
        # a pure chain is the worst case for level-set executors: the
        # planner must pick a barrier-free strategy — the certified sweep
        # when its convergence certificate holds, else the serial scan
        chain = SpTRSV.build(chain_matrix(2000), strategy="auto")
        assert chain.strategy in ("serial", "sweep"), chain.plan.reason
        # with sweeps opted out the original ordering claim still holds
        chain_ns = SpTRSV.build(chain_matrix(2000), strategy="auto",
                                sweep=False)
        assert chain_ns.strategy == "serial", chain_ns.plan.reason
        # wide wavefronts at a size where the serial scan's cache behavior
        # makes it clearly lose (measured ~5us/row at 33k rows vs ~60ns at
        # 1.5k — small systems legitimately go serial)
        wide = SpTRSV.build(random_lower(4000, avg_offdiag=3.0, seed=0),
                            strategy="auto")
        assert wide.strategy in ("levelset", "levelset_unroll"), wide.plan.reason


def test_auto_never_picks_pallas_on_cpu():
    # interpret-mode Pallas is a correctness harness, not an executor choice
    s = SpTRSV.build(_lung2(), strategy="auto")
    assert s.strategy != "pallas_fused"
    assert "pallas_fused" not in s.plan.costs  # gated, not just outscored


def test_auto_respects_coarsen_opt_out():
    s = SpTRSV.build(_lung2(), strategy="auto", coarsen=False)
    assert s.plan.coarsen is False
    if s.schedule is not None:
        assert all(sl.depth == 1 for sl in s.schedule.slabs)


def test_plan_strategy_gates_fused_on_backend_and_interpret():
    L = _lung2()
    sched = build_schedule(L)
    from repro.core import analyze
    an = analyze(L)
    d_cpu = plan_strategy(an, sched, backend="cpu", interpret=False)
    assert "pallas_fused" not in d_cpu.costs
    # interpret mode models nothing the cost formula describes — gated even
    # on a TPU backend
    d_interp = plan_strategy(an, sched, backend="tpu", interpret=True)
    assert "pallas_fused" not in d_interp.costs
    # compiled tpu: the shipped row gates fused out (Mosaic refuses its
    # gather); a row that admits it prices it
    d_tpu = plan_strategy(an, sched, backend="tpu", interpret=False)
    assert "pallas_fused" not in d_tpu.costs
    admit = dataclasses.replace(get_calibration("tpu"),
                                fused_max_rows=10 ** 9)
    d_admit = plan_strategy(an, sched, backend="tpu", interpret=False,
                            calibration=admit)
    assert "pallas_fused" in d_admit.costs


# -------------------------------------------------------------------------
# regression: bucket_pad_ratio reaches every schedule-consuming strategy
# -------------------------------------------------------------------------
def _bucket_matrix():
    # one wavefront with wildly uneven row widths => bucketing must split it
    n = 160
    rows, cols, vals = list(range(n)), list(range(n)), [4.0] * n
    rng = np.random.default_rng(0)
    for i in range(64, n):  # fat rows depend on many roots
        for j in rng.choice(48, size=24, replace=False):
            rows.append(i); cols.append(int(j)); vals.append(0.1)
    for i in range(48, 64):  # thin rows depend on one root
        rows.append(i); cols.append(i - 48); vals.append(0.1)
    from repro.core import from_coo
    return from_coo(rows, cols, np.asarray(vals, np.float32), (n, n))


@pytest.mark.parametrize(
    "strategy", ["levelset", "levelset_unroll", "pallas_level",
                 "pallas_fused", "distributed"])
def test_bucket_pad_ratio_reaches_every_strategy(strategy):
    L = _bucket_matrix()
    kw = {}
    if strategy == "distributed":
        kw["mesh"] = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("data",))
    plain = SpTRSV.build(L, strategy=strategy, **kw)
    split = SpTRSV.build(L, strategy=strategy, bucket_pad_ratio=1.5, **kw)
    # the bucketed slab split must show up in the schedule of EVERY strategy
    # (it used to be silently dropped for pallas_level/pallas_fused/distributed)
    assert split.schedule.num_segments > plain.schedule.num_segments
    assert split.schedule.padded_flops() < plain.schedule.padded_flops()
    b = np.random.default_rng(1).standard_normal(L.n).astype(np.float32)
    x = np.asarray(split.solve(jnp.asarray(b)))
    ref = np.asarray(SpTRSV.build(L, strategy="serial").solve(jnp.asarray(b)))
    np.testing.assert_allclose(x, ref, rtol=2e-5, atol=2e-6)


# -------------------------------------------------------------------------
# regression: padded_flops honors the unroll threshold
# -------------------------------------------------------------------------
def test_padded_flops_counts_unrolled_slabs_at_true_nnz():
    L = _lung2()
    sched = build_schedule(L)
    base = sched.padded_flops()
    unrolled = sched.padded_flops(unroll_threshold=2)
    assert unrolled < base
    # hand-count: thin (R<=2) slabs contribute 2*nnz + R, others 2*K*R + R
    expect = 0
    for s in sched.slabs:
        if s.R <= 2:
            expect += 2 * int(np.count_nonzero(s.vals)) + s.R
        else:
            expect += 2 * s.K * s.R + s.R
    assert unrolled == expect
    # coarsened chains execute depth uniform sub-steps — counted as such
    co = coarsen_schedule(sched, CoarsenConfig())
    expect_co = 0
    for s in co.slabs:
        if s.depth > 1:
            rmax = max(s.sub_rows)
            expect_co += s.depth * (2 * s.K * rmax + rmax)
        else:
            expect_co += 2 * s.K * s.R + s.R
    assert co.padded_flops() == expect_co


# -------------------------------------------------------------------------
# regression: distributed exchanges values only; bytes match the wire
# -------------------------------------------------------------------------
def test_distributed_no_index_collectives():
    from repro.core.dist import make_distributed_solver, shard_schedule

    L = _lung2()
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("data",))
    sched = build_schedule(L)
    dsched = shard_schedule(sched, 4)
    fn = make_distributed_solver(dsched, mesh, "data")
    jaxpr = str(jax.make_jaxpr(fn)(jnp.zeros((L.n,), jnp.float32)))
    # one value all_gather per sharded segment — and none for row ids
    # (primitive applications print as "all_gather[..."; the bare substring
    # also matches the all_gather_dimension= param, so anchor on the bracket)
    assert jaxpr.count("all_gather[") == dsched.num_collectives == sched.num_segments
    # with coarsening, replicated chains drop their collectives entirely
    co = coarsen_schedule(sched, CoarsenConfig())
    d_co = shard_schedule(co, 4)
    fn_co = make_distributed_solver(d_co, mesh, "data")
    jaxpr_co = str(jax.make_jaxpr(fn_co)(jnp.zeros((L.n,), jnp.float32)))
    assert jaxpr_co.count("all_gather[") == d_co.num_collectives < dsched.num_collectives


def test_collective_accounting_with_coarsening():
    from repro.core.dist import shard_schedule

    L = _lung2()
    sched = build_schedule(L)
    co = coarsen_schedule(sched, CoarsenConfig())
    d_plain = shard_schedule(sched, 4)
    d_co = shard_schedule(co, 4)
    assert d_plain.num_collectives == sched.num_segments
    assert d_co.num_collectives == sum(
        1 for s in co.slabs if s.depth == 1)
    # replicated chains move zero bytes; sharded segments count value payload
    expect = sum(r.size * 4 for r, rep in zip(d_co.rows, d_co.replicated)
                 if not rep)
    assert d_co.collective_bytes() == expect
    assert d_co.collective_bytes() <= d_plain.collective_bytes()
    assert d_co.collective_bytes(batch=8) == 8 * d_co.collective_bytes()


# -------------------------------------------------------------------------
# serve-engine plumbing
# -------------------------------------------------------------------------
def test_solve_engine_from_matrix_auto():
    from repro.serve.engine import SolveEngine

    L = _lung2()
    eng = SolveEngine.from_matrix(L)
    assert eng.solver.plan is not None and eng.solver_t is not None
    b = np.random.default_rng(5).standard_normal(L.n).astype(np.float32)
    r_f = eng.submit(b)
    r_b = eng.submit(b, transpose=True)
    eng.run()
    ref_f = np.asarray(SpTRSV.build(L, strategy="serial").solve(jnp.asarray(b)))
    ref_b = np.asarray(SpTRSV.build(L, strategy="serial",
                                    transpose=True).solve(jnp.asarray(b)))
    np.testing.assert_allclose(r_f.x, ref_f, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(r_b.x, ref_b, rtol=2e-5, atol=2e-6)
