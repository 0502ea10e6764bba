"""Public SpTRSV API — ties analysis, rewriting, and codegen together.

    solver = SpTRSV.build(L, strategy="levelset", rewrite=RewriteConfig())
    x = solver.solve(b)          # jit-compiled, matrix-specialized
    X = solver.solve(B)          # B: (n, m) — m systems in one pass

    bwd = SpTRSV.build(L, transpose=True)    # solves Lᵀ x = b
    fwd, bwd = SpTRSV.build_pair(L)          # both sweeps, one analysis

Every strategy solves one RHS ``b: (n,)`` or a multi-RHS batch
``B: (n, m)`` (m independent systems sharing L).  Batching amortizes the
per-level launch/synchronization cost over columns and widens the TPU lane
dimension from R to R*m, which is where thin levels (the paper's lung2
pathology) leave throughput on the table.

``transpose=True`` makes the solver execute the *backward* sweep
``Lᵀ x = b`` (the second half of every IC(0)/LU preconditioner apply).
The transpose DAG is the forward DAG with its edges reversed, so the
backward level sets are derived from the same symbolic analysis — no
reverse-permuted copy of the matrix, no second ``from_coo``; the backward
schedule packs columns of ``L`` (rows of ``L.transpose()``) into the same
ELL slabs every executor/kernel already consumes.

Strategy × capability matrix
----------------------------
=================  ==========  =========  =========  =========  =========  =========  ============
strategy           single RHS  batched    rewrite    transpose  coarsen    refresh    distributed
=================  ==========  =========  =========  =========  =========  =========  ============
serial             yes         yes        yes        yes        n/a        yes        no
levelset           yes         yes        yes        yes        yes        yes        no
levelset_unroll    yes         yes        yes        yes        yes        yes        no
pallas_level       yes         yes        yes        yes        yes        yes        no
pallas_fused       yes         yes        yes        yes        n/a (1 seg) yes       no
distributed        yes         yes        yes        yes        yes        yes        yes (mesh axis)
sweep              yes         yes        yes        yes        n/a (0 seg) yes       no
auto               transform planner: picks serial / levelset /
                   levelset_unroll / pallas_fused / sweep AND the matrix
                   transform (rewrite policy x coarsening) from one cost
                   model
=================  ==========  =========  =========  =========  =========  =========  ============

Transform planner (``strategy="auto"``)
---------------------------------------
``plan_strategy`` (:mod:`repro.core.coarsen`) prices *rewrite vs coarsen vs
both* with one launch-cost/padded-FLOP model: rewriting shortens the
dependency chain but adds fill and a per-solve RHS SpMV; coarsening removes
syncs but pads.  Candidate rewrites (``policy="thin"`` and
``policy="critical_path"``) are actually built — the vectorized rewrite
engine makes that a milliseconds-scale probe — and their schedules priced
like every other alternative.  The decision is recorded on ``solver.plan``
(:class:`repro.core.coarsen.PlanDecision`):

``plan.strategy``   executor chosen (``serial``/``levelset``/
                    ``levelset_unroll``/``pallas_fused``/``sweep``)
``plan.coarsen``    whether schedule coarsening is applied
``plan.rewrite``    winning rewrite-policy tag (``"thin"`` /
                    ``"critical_path"``) or ``None`` for no rewrite
``plan.sweep_k``    certified sweep count when the sync-free speculative
                    executor won (``plan.strategy == "sweep"``), else None.
                    Sweeps are priced against level-set execution from the
                    depth/contraction profile: ``k`` fused whole-matrix
                    updates + 1 verification pass vs. per-segment launch
                    cost — the sweeps-vs-levels decision.
``plan.costs``      modelled per-solve cost of every candidate, keyed
                    ``<strategy>[+rewrite:<tag>][+coarsen]`` (plus
                    ``sweep``)
``plan.reason``     human-readable audit line (also in ``stats()["plan"]``)

An explicit ``rewrite=RewriteConfig(...)`` is a user directive: the rewrite
is applied unconditionally and the planner only weighs strategy/coarsening
on the transformed system.  ``SolveEngine.from_matrix`` serves the planner
decision by default, and the chosen transform composes with permuted/packed
layout, transpose pairs, batching, and value-only refresh.

Permuted layout + value-only refresh (``layout=``, ``refresh``)
---------------------------------------------------------------
``layout="permuted"`` (default) executes in schedule-order permuted space:
each segment's rows are a contiguous slice of ``x̂`` (static-offset
``dynamic_update_slice`` writes, static RHS slices), ``b`` is permuted and
``x`` un-permuted exactly once at the boundary, and all slab values stream
from ONE packed flat buffer passed as a runtime jit argument.  Because the
values are arguments — not trace-time constants — ``solver.refresh(new_data)``
swaps in new values of the same sparsity pattern with one O(nnz) re-pack
and a jit cache hit: no level analysis, no re-trace, no re-compile.  That
is the dominant production pattern (numeric re-factorization between PCG /
Newton steps).  ``layout="scatter"`` keeps the legacy per-segment scatter
executors; refresh on it falls back to a cold rebuild.  ``solver.stats()``
reports the packed-buffer bytes, padding waste and permutation status.

Kernel backend (``backend=``)
-----------------------------
Pallas-backed strategies (``pallas_level`` / ``pallas_fused`` and the auto
planner's pricing) dispatch through :mod:`repro.kernels.backend`:
``backend=None`` (default) resolves from ``jax.default_backend()`` — ``tpu``
→ compiled Mosaic lowerings, ``gpu`` → compiled pallas-triton lowerings,
``cpu`` → the interpret backend (pallas has no CPU codegen).  Explicit specs
``"tpu"`` / ``"gpu"`` / ``"interpret"`` / ``"interpret:gpu"`` pin the
lowering family; the interpret variants run it under the pallas interpreter
(how CI exercises both families without hardware).  The planner prices
candidates from the backend's calibration row
(:mod:`repro.core.calibrate` — launch cost, gather throughput, lane width,
fused-dispatch shape).  The legacy ``interpret: bool`` knob remains as a
deprecated alias: ``interpret=True`` maps to the resolved platform's
interpret backend, ``interpret=False`` forces the compiled path.

Strategies
----------
``serial``         row-serial scan (paper Algorithm 1 — correctness baseline)
``levelset``       generated per-level vectorized segments (paper codegen)
``levelset_unroll``same, with tiny levels unrolled as constant-embedded code
``pallas_level``   per-level Pallas TPU kernel (kernels/sptrsv_level)
``pallas_fused``   whole solve in one Pallas kernel, x in VMEM (beyond-paper)
``distributed``    shard_map level solve over a mesh axis (one collective
                   per *segment* — rewriting and coarsening both reduce
                   collective count; a batch multiplies collective payload,
                   not count)
``sweep``          sync-free speculative solve-then-correct
                   (:mod:`repro.core.sweep`): k Jacobi-style triangular
                   sweeps ``x ← D⁻¹(b − N x)`` as ONE fused dispatch with
                   zero intra-solve barriers, componentwise residual
                   verification, exact-strategy fallback for non-converged
                   columns (``sweep=SweepConfig(k, residual_tol,
                   fallback)``).  The only executor whose per-solve cost is
                   independent of the level count.
``blocked``        supernodal/blocked solve: contiguous row runs with
                   (near-)identical column structure are amalgamated into
                   dense diagonal blocks (:func:`repro.core.levels.
                   detect_supernodes`, relaxation knob
                   ``supernodes=SupernodeConfig(relax=...)``); each
                   super-level applies the off-diagonal panel as one
                   gather/FMA pass and the inverted diagonal blocks as a
                   batched small-TRSM (``kernels/trsm_block``,
                   ``block_kernel="auto"|"pallas"|"jnp"``).  A scalar row
                   is just a 1×1 block, so the executor degrades
                   gracefully on unstructured factors.
``auto``           transform planner (:func:`repro.core.coarsen.plan_strategy`):
                   serial for chain-like DAGs, (coarsened) level-set
                   executors for wavefront-parallel matrices, the fused
                   Pallas kernel for VMEM-sized systems on a real TPU,
                   sync-free sweeps when the convergence model certifies a
                   cheap-enough sweep count, the blocked executor when
                   supernode amalgamation finds dense-enough diagonal
                   blocks (mean block size ≥ 1.5) and the calibrated
                   gemm/trsm rates price it below the level-set
                   candidates — and, for barrier-dominated
                   schedules, whether to rewrite the matrix first (``thin``
                   vs ``critical_path`` policy) under the same cost model.
                   The decision is recorded on ``solver.plan`` (see
                   "Transform planner" above).

Schedule coarsening (``coarsen=...``)
-------------------------------------
``coarsen=True`` (or a :class:`~repro.core.coarsen.CoarsenConfig`) merges
adjacent levels into super-level slabs under a launch-vs-padding cost model:
a lung2-class schedule drops from ~478 segments (sync points) to a few
dozen, with each merged slab executing its intra-slab dependency chain
back-to-back inside one segment.  Every row is computed from exactly the
same operands as uncoarsened (only zero padding is added), so results are
typically bit-identical and always within a few ulp — XLA may re-contract
the padded reduction (FMA/tree shape) when it recompiles the merged
segment.  ``strategy="auto"`` enables coarsening whenever the cost model
says it pays.

Batched quickstart (PCG with many right-hand sides)::

    from repro.core.pcg import make_ic_preconditioner_batched, pcg_batched
    M_inv = make_ic_preconditioner_batched(Lfactor, strategy="levelset")
    res = pcg_batched(A, B, M_inv)     # B: (n, m); res.x: (n, m)

Shared-analysis preconditioner quickstart (forward + backward sweep from one
analysis)::

    fwd, bwd = SpTRSV.build_pair(L, strategy="levelset",
                                 rewrite=RewriteConfig(thin_threshold=2))
    z = bwd.solve(fwd.solve(r))        # z = (L Lᵀ)^{-1} r
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from . import obs
from .analysis import MatrixAnalysis, analyze
from .coarsen import (
    SEGMENT_COST,
    BlockSchedule,
    CoarsenConfig,
    PlanDecision,
    RewriteCandidate,
    SweepCandidate,
    blocked_candidate,
    build_block_schedule,
    coarsen_schedule,
    plan_strategy,
    should_consider_rewrite,
)
from .codegen import (
    GATHER_UNROLL_MAX_K,
    Schedule,
    build_schedule,
    make_blocked_solver,
    make_levelset_solver,
    make_rhs_transform,
    make_serial_solver,
)
from .csr import CSRMatrix
from .levels import (
    LevelSets,
    SupernodeConfig,
    Supernodes,
    build_level_sets,
    build_reverse_level_sets,
    detect_supernodes,
)
from repro.kernels.backend import (
    KernelBackend,
    resolve_backend,
    warn_interpret_deprecated,
)
from .guard import GuardConfig, SolveGuard, scan_values
from .packed import (
    PackedStats,
    build_packed_blocked_layout,
    build_packed_layout,
    cast_value_buffers,
    ell_packed_stats,
    make_packed_blocked_solver,
    make_packed_levelset_solver,
    make_packed_rhs_transform,
    make_packed_serial_solver,
    pack_blocked_values,
    pack_values,
)
from .rewrite import (
    RewriteConfig,
    RewriteReplayError,
    RewriteResult,
    replay_rewrite_values,
    rewrite_matrix,
)
from .sweep import (
    SweepConfig,
    SweepStats,
    build_sweep_layout,
    contraction_factor,
    default_residual_tol,
    make_sweep_solver,
    pack_sweep_values,
    planned_sweeps,
)

__all__ = ["SpTRSV", "STRATEGIES", "LAYOUTS"]

logger = logging.getLogger(__name__)

STRATEGIES = (
    "serial",
    "levelset",
    "levelset_unroll",
    "pallas_level",
    "pallas_fused",
    "distributed",
    "sweep",
    "blocked",
    "auto",
)

# Execution-space layouts.  "permuted" (default) runs the whole solve in
# schedule-order permuted space with one packed streaming value buffer
# (:mod:`repro.core.packed`): contiguous dynamic-update-slice writes instead
# of per-segment row scatters, b permuted / x un-permuted exactly once at the
# API boundary, and value-only ``refresh`` without re-tracing.  "scatter" is
# the PR-3 layout (per-segment row-id scatters, values embedded as trace-time
# constants) — kept as the equivalence/benchmark baseline.
LAYOUTS = ("permuted", "scatter")


def _as_coarsen_config(coarsen) -> Optional[CoarsenConfig]:
    """Normalize the ``coarsen`` build knob: None/False → off, True → default
    config, a CoarsenConfig → itself."""
    if coarsen is None or coarsen is False:
        return None
    if coarsen is True:
        return CoarsenConfig()
    assert isinstance(coarsen, CoarsenConfig), coarsen
    return coarsen


def _as_supernode_config(supernodes) -> Optional[SupernodeConfig]:
    """Normalize the ``supernodes`` build knob: None/True → default
    detection config (``False`` additionally keeps the blocked executor out
    of the auto planner's candidate set), a SupernodeConfig → itself."""
    if supernodes is None or supernodes is True or supernodes is False:
        return SupernodeConfig()
    assert isinstance(supernodes, SupernodeConfig), supernodes
    return supernodes


def _as_guard_config(guard) -> Optional[GuardConfig]:
    """Normalize the ``guard`` build knob: None/False → unguarded, True →
    default :class:`repro.core.guard.GuardConfig`, a GuardConfig → itself."""
    if guard is None or guard is False:
        return None
    if guard is True:
        return GuardConfig()
    assert isinstance(guard, GuardConfig), guard
    return guard


def _as_sweep_config(sweep) -> Optional[SweepConfig]:
    """Normalize the ``sweep`` build knob: None/False → default off
    (``strategy="sweep"`` still gets a default config; ``False`` additionally
    keeps sweeps out of the auto planner's candidate set), True → default
    config, a SweepConfig → itself."""
    if sweep is None or sweep is False:
        return None
    if sweep is True:
        return SweepConfig()
    assert isinstance(sweep, SweepConfig), sweep
    return sweep


@dataclasses.dataclass
class _RefreshCtx:
    """Cached symbolic state for value-only refresh.

    ``source`` is the user's original factor (pattern reference for
    validating new values); ``values_map`` reorders its data into the solved
    system's storage (the CSC permutation for transpose solvers, identity
    otherwise); ``rewrite`` carries the replayable elimination plan and the
    cached L'/E patterns; ``repack``/``e_repack`` turn target-system data
    into the executor's runtime value buffers; ``rebuild`` is the cold
    fallback (scatter layout, or a rewrite plan that does not numerically
    transfer)."""

    source: CSRMatrix
    system: CSRMatrix
    values_map: Optional[np.ndarray]
    rewrite: Optional[RewriteResult]
    repack: Optional[Callable]
    e_repack: Optional[Callable]
    rebuild: Callable


@dataclasses.dataclass
class SpTRSV:
    """A matrix-specialized, jit-compiled triangular solver.

    ``transpose=True`` solvers execute the backward sweep ``Lᵀ x = b``; the
    executor machinery is identical — only the schedule (backward level sets,
    column-packed slabs) differs.

    ``layout="permuted"`` (default) executes in schedule-order permuted
    space with packed streaming value buffers and supports value-only
    :meth:`refresh`; ``layout="scatter"`` is the legacy per-segment
    row-scatter executor with values embedded as constants."""

    n: int
    strategy: str
    analysis: MatrixAnalysis
    schedule: Optional[Schedule]
    rewrite_result: Optional[RewriteResult]
    _solve_fn: Callable
    _rhs_fn: Optional[Callable]
    block_schedule: Optional[BlockSchedule] = None  # strategy="blocked" only
    supernodes: Optional[Supernodes] = None         # partition actually run
    transpose: bool = False
    plan: Optional[PlanDecision] = None   # set when strategy="auto" planned
    layout: str = "scatter"
    backend: str = "interpret"            # resolved kernel backend name
    packed_stats: Optional[PackedStats] = None
    sweep_stats: Optional[SweepStats] = None   # live, strategy="sweep" only
    guard: Optional[SolveGuard] = None    # guarded execution layer (guard=)
    _values: Optional[tuple] = None       # runtime value buffers (permuted)
    _e_values: Optional[jnp.ndarray] = None
    _refresh_ctx: Optional[_RefreshCtx] = None
    _sweep_exec: Optional[Callable] = None  # jitted barrier-free executor

    @staticmethod
    def build(
        L: CSRMatrix,
        *,
        strategy: str = "levelset",
        transpose: bool = False,
        rewrite: Optional[RewriteConfig] = None,
        unroll_threshold: int = 4,
        bucket_pad_ratio: float = 0.0,   # >1: split levels into nnz buckets
        coarsen=None,                    # True / CoarsenConfig: merge levels
        sweep=None,                      # True / SweepConfig: see below
        guard=None,                      # True / GuardConfig: see below
        supernodes=None,                 # SupernodeConfig / False: see below
        block_kernel: str = "auto",      # blocked apply: auto / pallas / jnp
        mesh=None,
        mesh_axis: str = "data",
        dist_strategy: str = "all_gather",
        backend=None,
        interpret: Optional[bool] = None,
        jit: bool = True,
        layout: str = "permuted",
        gather_unroll_max_k: int = GATHER_UNROLL_MAX_K,
    ) -> "SpTRSV":
        """Build a solver for ``L x = b`` (or ``Lᵀ x = b`` with
        ``transpose=True``).  ``L`` is always the lower-triangular factor.

        ``sweep`` configures the sync-free speculative executor
        (:class:`repro.core.sweep.SweepConfig` — sweep count ``k``,
        componentwise ``residual_tol``, exact ``fallback`` strategy).  With
        ``strategy="sweep"`` the config (default if omitted) drives the
        executor directly; with ``strategy="auto"`` it caps the sweep count
        the planner may certify (``sweep=False`` keeps sweeps out of the
        candidate set entirely).

        ``guard`` wraps the built solver in the guarded execution layer
        (``True`` or a :class:`repro.core.guard.GuardConfig`): every solve
        is verified with one fused componentwise residual pass against the
        ORIGINAL system, refined up to ``refine_steps`` times
        (``x += solve(r)``), and columns still above ``residual_tol``
        (default ``128·eps`` of the RHS dtype) are handled by
        ``on_breakdown`` — ``"refine"`` returns the best iterate and records
        the breakdown in ``stats()``, ``"fallback"`` re-solves the failed
        RHS columns with a lazily built exact solver (pivot-repaired when
        the build/refresh value scan tripped) and splices them in like the
        sweep executor's correction, ``"raise"`` raises
        :class:`repro.core.guard.GuardBreakdownError`.
        ``GuardConfig(precision="mixed")`` additionally stores the packed
        off-diagonal value buffer in bf16 (half the value-stream bytes) with
        the diagonal buffer in fp32, accumulates inner solves in fp32, and
        relies on refinement to recover fp64-class accuracy — requires
        ``layout="permuted"``.  Guard accounting (refinement steps taken,
        fallbacks fired, residual achieved, pivot alarms) lands in
        ``stats()`` under the ``guard_*`` keys.

        ``supernodes`` configures supernode amalgamation for the blocked
        (node-granular) executor — a
        :class:`repro.core.levels.SupernodeConfig` tunes the relaxation /
        block-size knobs, ``False`` keeps the blocked executor out of the
        auto planner's candidate set.  With ``strategy="blocked"`` each
        super-level runs as a batched dense diagonal-block apply (small
        TRSM via precomputed inverses) plus a padded ELL panel update;
        ``block_kernel`` picks the apply implementation (``"auto"`` —
        pallas on compiled tpu/gpu, ``dot_general`` elsewhere; ``"pallas"``
        / ``"jnp"`` force it).  A matrix with no amalgamatable rows
        degrades to all-singleton blocks — the scalar-row schedule.

        ``coarsen`` merges adjacent levels into super-level slabs under the
        :mod:`repro.core.coarsen` cost model (fewer segments / sync points;
        consumed by the levelset, pallas_level and distributed executors —
        serial has no segments and pallas_fused is already one segment).
        ``strategy="auto"`` lets the planner pick both the strategy and
        whether coarsening pays; the decision lands on ``solver.plan``.

        ``layout="permuted"`` (default) runs the solve in schedule-order
        permuted space (``b`` permuted in / ``x`` un-permuted out exactly
        once; contiguous slice writes per segment; one packed streaming
        value buffer) and enables :meth:`refresh`.  ``layout="scatter"``
        keeps the legacy per-segment scatter executors.

        ``gather_unroll_max_k`` bounds the batched per-k gather unrolling
        (see :data:`repro.core.codegen.GATHER_UNROLL_MAX_K`); wider slabs
        fall back to the fused 3-D gather and log the fallback."""
        assert L.is_lower_triangular(), "SpTRSV requires lower-triangular L with nonzero diagonal"
        if transpose:
            system, levels = L.transpose(), build_reverse_level_sets(L)
            values_map = np.argsort(L.indices, kind="stable")
        else:
            system, levels = L, build_level_sets(L)
            values_map = None
        return SpTRSV._build_system(
            system, levels, upper=transpose,
            strategy=strategy, rewrite=rewrite,
            unroll_threshold=unroll_threshold,
            bucket_pad_ratio=bucket_pad_ratio,
            coarsen=coarsen, sweep=sweep, guard=guard,
            supernodes=supernodes, block_kernel=block_kernel,
            mesh=mesh, mesh_axis=mesh_axis, dist_strategy=dist_strategy,
            backend=backend, interpret=interpret, jit=jit,
            layout=layout, gather_unroll_max_k=gather_unroll_max_k,
            source=L, values_map=values_map,
        )

    @staticmethod
    def build_cold(L: CSRMatrix, *, transpose_too: bool = False,
                   **build_kwargs) -> tuple["SpTRSV", Optional["SpTRSV"]]:
        """Cheapest-possible build for *cold* serving traffic: the
        row-serial scan executor, no planner probes, no rewrite candidates,
        no supernode detection, no schedule packing — just the O(nnz) level
        analysis and a ``lax.scan``.

        This is the path a :class:`repro.serve.SolverRegistry` uses to
        answer requests for a never-seen sparsity pattern *immediately*
        while the planned (``strategy="auto"``) build runs on a background
        worker; the serial solver is exact, refreshable (permuted layout
        keeps the scan operands as runtime buffers), and orders of
        magnitude cheaper to stand up than a planned build.

        Returns ``(forward, backward)`` — ``backward`` is ``None`` unless
        ``transpose_too=True`` (then both directions come from one shared
        analysis via :meth:`build_pair`).  Extra keyword arguments
        (``guard=``, ``backend=``, ...) pass through to the builder;
        ``strategy`` is pinned to ``"serial"``."""
        build_kwargs.pop("strategy", None)
        if transpose_too:
            return SpTRSV.build_pair(L, strategy="serial", **build_kwargs)
        return SpTRSV.build(L, strategy="serial", **build_kwargs), None

    @staticmethod
    def build_pair(L: CSRMatrix, **kwargs) -> tuple["SpTRSV", "SpTRSV"]:
        """Build ``(forward, backward)`` solvers — ``L y = b`` and
        ``Lᵀ z = y`` — from **one** shared symbolic analysis.

        The backward level sets are derived from the forward DAG arrays
        (:func:`repro.core.levels.compute_reverse_levels`) and the backward
        schedule is packed from an O(nnz) CSC view of ``L`` — the whole
        reverse-permute + second-analysis pipeline of the legacy
        preconditioner path is gone.  Accepts the same keyword arguments as
        :meth:`build` (except ``transpose``).  Both solvers support
        :meth:`refresh` against new values of ``L`` (the backward solver
        reorders them through the shared CSC map)."""
        assert "transpose" not in kwargs, "build_pair builds both directions"
        assert L.is_lower_triangular(), "SpTRSV requires lower-triangular L with nonzero diagonal"
        levels = build_level_sets(L)
        fwd = SpTRSV._build_system(L, levels, upper=False,
                                   source=L, values_map=None, **kwargs)
        # backward levels derived from the forward wavefronts — the shared
        # analysis; no second per-row DAG traversal
        bwd = SpTRSV._build_system(
            L.transpose(), build_reverse_level_sets(L, forward=levels),
            upper=True, source=L,
            values_map=np.argsort(L.indices, kind="stable"), **kwargs)
        return fwd, bwd

    @staticmethod
    def _build_system(
        system: CSRMatrix,
        levels: LevelSets,
        *,
        upper: bool,
        strategy: str = "levelset",
        rewrite: Optional[RewriteConfig] = None,
        unroll_threshold: int = 4,
        bucket_pad_ratio: float = 0.0,
        coarsen=None,
        sweep=None,
        guard=None,
        supernodes=None,
        block_kernel: str = "auto",
        mesh=None,
        mesh_axis: str = "data",
        dist_strategy: str = "all_gather",
        backend=None,
        interpret: Optional[bool] = None,
        jit: bool = True,
        layout: str = "permuted",
        gather_unroll_max_k: int = GATHER_UNROLL_MAX_K,
        source: Optional[CSRMatrix] = None,
        values_map: Optional[np.ndarray] = None,
    ) -> "SpTRSV":
        """Shared builder: ``system`` is the triangular matrix of the system
        actually solved (``L`` forward, ``L.transpose()`` backward) with its
        level sets already analyzed.  ``source``/``values_map`` record where
        the system's values came from (the user's factor and the data
        reordering into system storage) for :meth:`refresh`."""
        assert strategy in STRATEGIES, strategy
        assert layout in LAYOUTS, layout
        if interpret is not None and not isinstance(backend, KernelBackend):
            # internal recursion passes a resolved KernelBackend; only an
            # actual caller-supplied bool earns the deprecation notice
            warn_interpret_deprecated("SpTRSV.build")
        bk = resolve_backend(backend, interpret=interpret)
        strategy_arg = strategy
        build_kwargs = dict(
            upper=upper, strategy=strategy_arg, rewrite=rewrite,
            unroll_threshold=unroll_threshold,
            bucket_pad_ratio=bucket_pad_ratio, coarsen=coarsen, sweep=sweep,
            guard=guard, supernodes=supernodes, block_kernel=block_kernel,
            mesh=mesh, mesh_axis=mesh_axis, dist_strategy=dist_strategy,
            backend=bk, jit=jit, layout=layout,
            gather_unroll_max_k=gather_unroll_max_k,
        )
        if source is None:
            source, values_map = system, None
        analysis = analyze(system, levels, upper=upper)
        ccfg = _as_coarsen_config(coarsen)
        scfg = _as_sweep_config(sweep)
        gcfg = _as_guard_config(guard)
        if gcfg is not None and gcfg.precision == "mixed" \
                and layout != "permuted":
            raise ValueError(
                "guard precision='mixed' requires layout='permuted' — "
                "mixed storage lowers the runtime value buffers, and the "
                "scatter layout embeds values as trace-time constants")
        if strategy == "sweep" and scfg is None:
            scfg = SweepConfig()

        rres: Optional[RewriteResult] = None
        rhs_fn = None
        e_values = None
        e_repack = None
        target, target_levels = system, levels
        if rewrite is not None:
            # an explicit rewrite config is a user directive — applied
            # unconditionally; the auto planner then prices strategies on
            # the transformed system (and only weighs coarsening)
            rres = rewrite_matrix(system, levels, rewrite, upper=upper)
            target, target_levels = rres.L, rres.levels

        _memo: dict = {}

        def _schedule() -> Schedule:
            # every schedule-consuming strategy gets the bucketed slab split
            # (bucket_pad_ratio was silently dropped for pallas_*/distributed
            # before — schedules are executor-agnostic)
            if "base" not in _memo:
                _memo["base"] = build_schedule(
                    target, target_levels, upper=upper,
                    bucket_pad_ratio=bucket_pad_ratio)
            return _memo["base"]

        def _coarsened(cfg: CoarsenConfig) -> Schedule:
            if "coarse" not in _memo:
                _memo["coarse"] = coarsen_schedule(
                    _schedule(), cfg, unroll_threshold=unroll_threshold)
            return _memo["coarse"]

        sncfg = _as_supernode_config(supernodes)

        def _supernodes() -> Supernodes:
            # detection + packing run on the (possibly rewritten) target, so
            # blocked composes with an explicit rewrite directive like every
            # other executor
            if "sn" not in _memo:
                _memo["sn"] = detect_supernodes(target, upper=upper,
                                                config=sncfg)
            return _memo["sn"]

        def _block_schedule() -> BlockSchedule:
            if "blocked" not in _memo:
                _memo["blocked"] = build_block_schedule(
                    target, _supernodes(), upper=upper)
            return _memo["blocked"]

        plan: Optional[PlanDecision] = None
        if strategy == "auto":
            # let the planner weigh coarsening unless explicitly disabled
            plan_ccfg = ccfg if ccfg is not None else (
                None if coarsen is False else CoarsenConfig())
            # Price rewrite candidates (the transform planner): only when the
            # user left the rewrite choice open and the analysis says the
            # schedule is barrier-dominated enough for rewriting to plausibly
            # pay.  Candidates run the (vectorized, milliseconds-scale)
            # rewrite and schedule build so they are priced with the same
            # launch-cost/padded-FLOP model as everything else.
            cands: dict = {}
            cand_artifacts: dict = {}
            if rewrite is None and should_consider_rewrite(analysis):
                for policy in ("thin", "critical_path"):
                    cfg_r = RewriteConfig(policy=policy)
                    rr = rewrite_matrix(system, levels, cfg_r, upper=upper)
                    if rr.stats.rows_rewritten == 0:
                        continue
                    sched_r = build_schedule(
                        rr.L, rr.levels, upper=upper,
                        bucket_pad_ratio=bucket_pad_ratio)
                    co_r = (coarsen_schedule(sched_r, plan_ccfg,
                                             unroll_threshold=unroll_threshold)
                            if plan_ccfg is not None else None)
                    # per-solve price of b' = E b: one padded ELL SpMV plus
                    # one extra dispatch
                    k_e = int(np.diff(rr.E.indptr).max())
                    cands[policy] = RewriteCandidate(
                        schedule=sched_r, coarsened=co_r,
                        rhs_cost=2.0 * k_e * system.n + SEGMENT_COST)
                    cand_artifacts[policy] = (cfg_r, rr, sched_r, co_r)
            # Price the sync-free sweep executor when its convergence model
            # certifies a sweep count within the configured budget: exact
            # after depth sweeps (D⁻¹N nilpotent), earlier when the iteration
            # contracts (q = ‖D⁻¹N‖_∞ < 1).  ``sweep=False`` opts out.
            sweep_cand = None
            if sweep is not False:
                scfg0 = scfg if scfg is not None else SweepConfig()
                q = contraction_factor(target, upper=upper)
                tol = (scfg0.residual_tol if scfg0.residual_tol is not None
                       else default_residual_tol(target.dtype))
                k_plan = planned_sweeps(q, target_levels.num_levels, tol,
                                        scfg0.k)
                if k_plan is not None:
                    row_off = target.row_nnz() - 1
                    sweep_cand = SweepCandidate(
                        k=k_plan,
                        ell_k=max(int(row_off.max()) if row_off.size else 0,
                                  1),
                        n=target.n, contraction=q)
            # Price the blocked (supernodal) executor when amalgamation
            # finds substance: detection is a cheap O(nnz log nnz) probe,
            # but packing dense blocks is only worth the build cost when
            # rows actually merge.  ``supernodes=False`` opts out; an
            # all-singleton partition (mean block size 1) never competes —
            # it is the scalar schedule with extra reshapes.
            blocked_cand = None
            if supernodes is not False and _supernodes().mean_block_size >= 1.5:
                blocked_cand = blocked_candidate(_block_schedule())
            plan = plan_strategy(
                analysis, _schedule(),
                _coarsened(plan_ccfg) if plan_ccfg is not None else None,
                unroll_threshold=unroll_threshold, backend=bk,
                rewritten=cands or None, sweep=sweep_cand,
                blocked=blocked_cand,
                precision=gcfg.precision if gcfg is not None else "native")
            strategy = plan.strategy
            if strategy == "sweep":
                scfg = dataclasses.replace(
                    scfg if scfg is not None else SweepConfig(),
                    k=plan.sweep_k)
            if plan.rewrite is not None:
                # adopt the winning rewrite: its result and schedules were
                # already built for pricing — no recompute
                _, rres, sched_r, co_r = cand_artifacts[plan.rewrite]
                target, target_levels = rres.L, rres.levels
                _memo.clear()
                _memo["base"] = sched_r
                if co_r is not None:
                    _memo["coarse"] = co_r
            if ccfg is not None and strategy in ("levelset", "levelset_unroll"):
                # an explicit coarsen config is a user directive — coarsening
                # stays on even if the planner costed it out; record what
                # actually executes so solver.plan stays auditable
                plan = dataclasses.replace(plan, coarsen=True)
            elif plan.coarsen:
                ccfg = plan_ccfg

        if rres is not None and rres.stats.e_nnz_offdiag > 0:
            # the per-solve RHS transform b' = E b; skipped outright when E
            # is the identity (no rewrites survived the budgets) so no-op
            # transforms cost nothing per solve
            if layout == "permuted":
                rhs_fn, e_values, e_repack = make_packed_rhs_transform(rres)
            else:
                rhs_fn = make_rhs_transform(rres)

        def _maybe_coarsen(schedule: Schedule) -> Schedule:
            return _coarsened(ccfg) if ccfg is not None else schedule

        permuted = layout == "permuted"
        values: Optional[tuple] = None
        repack: Optional[Callable] = None
        packed_stats: Optional[PackedStats] = None
        schedule: Optional[Schedule] = None
        block_schedule: Optional[BlockSchedule] = None
        sweep_stats: Optional[SweepStats] = None
        sweep_exec: Optional[Callable] = None
        if strategy == "serial":
            if permuted:
                # no level segments to permute, but the scan operands become
                # runtime buffers so refresh skips the re-trace
                fn, values, repack = make_packed_serial_solver(
                    target, upper=upper)
                packed_stats = PackedStats(
                    permutation_applied=False,
                    value_bytes=sum(int(v.nbytes) for v in values),
                    index_bytes=0,
                    padded_value_bytes=0,
                    n_pad=system.n,
                    num_segments=1,
                )
            else:
                fn = make_serial_solver(target, upper=upper)
        elif strategy in ("levelset", "levelset_unroll"):
            schedule = _maybe_coarsen(_schedule())
            ut = unroll_threshold if strategy == "levelset_unroll" else 0
            if permuted:
                playout = build_packed_layout(schedule)
                fn = make_packed_levelset_solver(
                    playout, unroll_threshold=ut,
                    gather_unroll_max_k=gather_unroll_max_k)
                values = (jnp.asarray(playout.vals_flat),
                          jnp.asarray(playout.diag_flat))
                repack = lambda data, _pl=playout: tuple(  # noqa: E731
                    jnp.asarray(a) for a in pack_values(_pl, data))
                packed_stats = playout.stats()
            else:
                fn = make_levelset_solver(
                    schedule, unroll_threshold=ut,
                    gather_unroll_max_k=gather_unroll_max_k)
        elif strategy == "pallas_level":
            from repro.kernels.sptrsv_level import ops as level_ops

            schedule = _maybe_coarsen(_schedule())
            if permuted:
                fn, values, repack, playout = level_ops.make_packed_solver(
                    schedule, backend=bk)
                packed_stats = playout.stats()
            else:
                fn = level_ops.make_solver(schedule, backend=bk)
        elif strategy == "pallas_fused":
            from repro.kernels.sptrsv_fused import ops as fused_ops

            # fused is already a single segment; coarsening would only
            # re-partition its chunk walk, so the layout consumes sub-slabs
            schedule = _schedule()
            if permuted:
                fn, values, repack, flay = fused_ops.make_packed_solver(
                    schedule, backend=bk)
                packed_stats = PackedStats(
                    permutation_applied=True,
                    value_bytes=int(flay.vals.nbytes + flay.diag.nbytes),
                    index_bytes=int(flay.cols.nbytes),
                    padded_value_bytes=int(
                        ((flay.val_src < 0).sum() + (flay.diag_src < 0).sum())
                        * flay.vals.itemsize),
                    n_pad=flay.n_pad,
                    num_segments=1,
                )
            else:
                fn = fused_ops.make_solver(schedule, backend=bk)
        elif strategy == "distributed":
            from .dist import (
                build_packed_dist_layout,
                make_distributed_solver,
                make_packed_distributed_solver,
                shard_schedule,
            )

            assert mesh is not None, "distributed strategy needs a mesh"
            schedule = _maybe_coarsen(_schedule())
            ndev = int(np.prod([mesh.shape[a] for a in (mesh_axis,)]))
            if permuted:
                playout = build_packed_dist_layout(schedule, ndev)
                fn, values, repack = make_packed_distributed_solver(
                    playout, mesh, mesh_axis, strategy=dist_strategy,
                    gather_unroll_max_k=gather_unroll_max_k)
                packed_stats = playout.stats()
            else:
                dsched = shard_schedule(schedule, ndev)
                fn = make_distributed_solver(
                    dsched, mesh, mesh_axis, strategy=dist_strategy)
        elif strategy == "blocked":
            # node-granular (supernodal) executor: batched dense diagonal-
            # block apply + padded ELL panel update per super-level.  The
            # dense block inverses live in the runtime value buffers, so the
            # permuted layout refreshes value-only (re-gather + re-invert +
            # swap) with a jit cache hit.
            block_schedule = _block_schedule()
            if permuted:
                blay = build_packed_blocked_layout(block_schedule)
                fn = make_packed_blocked_solver(
                    blay, backend=bk, kernel=block_kernel,
                    gather_unroll_max_k=gather_unroll_max_k)
                values = pack_blocked_values(blay, target.data)
                repack = lambda data, _bl=blay: pack_blocked_values(  # noqa: E731
                    _bl, data)
                packed_stats = blay.stats()
            else:
                fn = make_blocked_solver(
                    block_schedule, backend=bk, kernel=block_kernel,
                    gather_unroll_max_k=gather_unroll_max_k)
        elif strategy == "sweep":
            # sync-free speculative solve-then-correct (repro.core.sweep):
            # whole-matrix D + N split, k fused sweeps, no schedule at all.
            # The exact-fallback solver is built lazily on first use — the
            # converged common case never pays its build.
            slayout = build_sweep_layout(target, upper=upper)
            cur_target = [target]
            fb_holder: dict = {}

            def _fallback():
                if "s" not in fb_holder:
                    fb_holder["s"] = SpTRSV._build_system(
                        cur_target[0], target_levels, upper=upper,
                        strategy=scfg.fallback, rewrite=None,
                        unroll_threshold=unroll_threshold,
                        bucket_pad_ratio=bucket_pad_ratio, coarsen=coarsen,
                        backend=bk, jit=jit, layout=layout,
                        gather_unroll_max_k=gather_unroll_max_k)
                return fb_holder["s"].solve

            fn, sweep_stats, sweep_exec = make_sweep_solver(
                slayout, scfg,
                fallback=_fallback if scfg.fallback is not None else None,
                jit=jit, runtime_values=permuted)
            if permuted:
                values = (jnp.asarray(slayout.ell.vals),
                          jnp.asarray(slayout.diag))

                def repack(target_data, _sl=slayout, _t=target):
                    # keep the lazily-built exact fallback numerically in
                    # sync with the refreshed values
                    cur_target[0] = CSRMatrix(
                        _t.indptr, _t.indices,
                        np.asarray(target_data).astype(_t.dtype, copy=False),
                        _t.shape)
                    if "s" in fb_holder:
                        fb_holder["s"].refresh(cur_target[0].data)
                    return pack_sweep_values(_sl, target_data)

                packed_stats = ell_packed_stats(
                    slayout.ell, slayout.diag, n=system.n)
        else:  # pragma: no cover
            raise ValueError(strategy)

        if gcfg is not None and gcfg.precision == "mixed":
            if values is None:
                raise ValueError(
                    f"guard precision='mixed' is not supported for "
                    f"strategy={strategy!r} (no runtime value buffers)")
            # bf16 off-diagonal stream + fp32 diagonal buffer; executors
            # cast to the RHS dtype at solve time, and the guard runs inner
            # solves in fp32 with fp64 refinement recovering full accuracy
            values = cast_value_buffers(values)
            if repack is not None:
                _repack_full = repack
                repack = lambda data: cast_value_buffers(  # noqa: E731
                    _repack_full(data))

        # jit the RHS transform b' = E b separately from the solve.  A
        # single jit over both lets XLA fuse the batched SpMV into the
        # per-level consumers and recompute it, a >10x slowdown at m=64 on
        # CPU; the extra dispatch costs microseconds.  The sweep wrapper is
        # a host function (verification readback + fallback dispatch) whose
        # pure executor is already jitted inside make_sweep_solver — an
        # outer jit would trace the data-dependent fallback branch away.
        solve_fn = fn if strategy == "sweep" else \
            (jax.jit(fn) if jit else fn)
        rhs_c = (jax.jit(rhs_fn) if jit else rhs_fn) if rhs_fn is not None \
            else None

        def _rebuild(data: np.ndarray) -> "SpTRSV":
            sys_data = data[values_map] if values_map is not None else data
            sys2 = CSRMatrix(system.indptr, system.indices,
                             sys_data.astype(system.dtype, copy=False),
                             system.shape)
            return SpTRSV._build_system(
                sys2, levels, source=CSRMatrix(
                    source.indptr, source.indices,
                    data.astype(source.dtype, copy=False), source.shape),
                values_map=values_map, **build_kwargs)

        ctx = _RefreshCtx(
            source=source, system=system, values_map=values_map,
            rewrite=rres, repack=repack, e_repack=e_repack,
            rebuild=_rebuild,
        )
        solver = SpTRSV(
            n=system.n,
            strategy=strategy,
            analysis=analysis,
            schedule=schedule,
            block_schedule=block_schedule,
            supernodes=(block_schedule.supernodes
                        if block_schedule is not None else None),
            rewrite_result=rres,
            _solve_fn=solve_fn,
            _rhs_fn=rhs_c,
            transpose=upper,
            plan=plan,
            layout=layout,
            backend=bk.name,
            packed_stats=packed_stats,
            sweep_stats=sweep_stats,
            _values=values,
            _e_values=e_values,
            _refresh_ctx=ctx,
            _sweep_exec=sweep_exec,
        )
        if gcfg is not None:
            # The guard verifies against the ORIGINAL (pre-rewrite) system —
            # end-to-end coverage of rewrite replay and E-SpMV fill — and its
            # exact fallback is built on that same system, so eliminated-
            # pivot divisions cannot poison the corrective path.  The inner
            # solve is the live pipeline (`_solve_raw` reads the current
            # value buffers), so refresh keeps the guard coherent.
            def _guard_fallback(data, _sys=system, _lv=levels):
                fb = SpTRSV._build_system(
                    CSRMatrix(_sys.indptr, _sys.indices,
                              np.asarray(data).astype(_sys.dtype, copy=False),
                              _sys.shape),
                    _lv, upper=upper, strategy=gcfg.fallback, rewrite=None,
                    unroll_threshold=unroll_threshold,
                    bucket_pad_ratio=bucket_pad_ratio,
                    backend=bk, jit=jit, layout=layout,
                    gather_unroll_max_k=gather_unroll_max_k)
                return fb.solve

            solver.guard = SolveGuard(
                system, upper=upper, config=gcfg,
                inner_solve=solver._solve_raw,
                fallback_builder=_guard_fallback, jit=jit)
        return solver

    @property
    def dtype(self) -> np.dtype:
        """Numeric dtype of the solved system's stored values — what batch
        buffers should be allocated in to hit the compiled executable's
        jit-cache bucket (see ``SolveEngine._solve_group``)."""
        if self._refresh_ctx is not None:
            return self._refresh_ctx.system.dtype
        return np.dtype(np.float64)

    @property
    def pattern_hash(self) -> Optional[str]:
        """Stable sparsity-pattern digest of the *source* factor this solver
        was built from (:meth:`CSRMatrix.pattern_hash`) — the registry key a
        serving tier routes same-pattern refreshes by.  ``None`` only for a
        solver built without refresh state."""
        if self._refresh_ctx is None:
            return None
        return self._refresh_ctx.source.pattern_hash()

    def solve(self, b: jnp.ndarray) -> jnp.ndarray:
        """Solve L x = b (or Lᵀ x = b for a ``transpose`` solver).  ``b``
        may be ``(n,)`` (one system) or ``(n, m)`` (m independent systems
        solved in one batched pass).  Each distinct batch width compiles
        once (shapes are trace-time constants — the executor is matrix-
        *and* batch-specialized).

        Permuted-layout solvers permute ``b`` and un-permute ``x`` exactly
        once inside the executor (two O(n) gathers at the API boundary —
        the price of contiguous per-segment reads/writes).

        Guarded solvers (``guard=``) route through
        :meth:`repro.core.guard.SolveGuard.solve`: the result is verified
        against the original system's componentwise residual, iteratively
        refined, and columns that stay above tolerance are handled by the
        configured ``on_breakdown`` policy (best-effort / exact per-column
        fallback / :class:`repro.core.guard.GuardBreakdownError`)."""
        with TraceAnnotation(obs.SOLVE):
            if b.ndim not in (1, 2) or b.shape[0] != self.n:
                raise ValueError(
                    f"b must be ({self.n},) or ({self.n}, m); got {b.shape}")
            if self.guard is not None:
                return self.guard.solve(b)
            return self._solve_raw(b)

    def _solve_raw(self, b: jnp.ndarray) -> jnp.ndarray:
        """The unguarded solve pipeline (RHS transform + executor) against
        the LIVE value buffers — what the guard wraps and refines."""
        if self._rhs_fn is not None:
            b = (self._rhs_fn(b, self._e_values)
                 if self._e_values is not None else self._rhs_fn(b))
        if self._values is not None:
            return self._solve_fn(b, self._values)
        return self._solve_fn(b)

    def solve_batched(self, B: jnp.ndarray) -> jnp.ndarray:
        """Explicitly-batched alias: ``B: (n, m)`` → ``X: (n, m)``.

        ``solve`` already dispatches on ndim; this entry point exists so
        call sites that *require* the multi-RHS path fail loudly when handed
        a single vector."""
        if B.ndim != 2:
            raise ValueError(f"solve_batched expects (n, m); got {B.shape}")
        return self.solve(B)

    def refresh(self, new_values, *, validate: bool = True) -> "SpTRSV":
        """Value-only numeric refresh: swap in new matrix **values** of the
        same sparsity pattern, reusing the whole cached symbolic state —
        level analysis, permutation, packed-buffer offsets, coarsening, the
        ``auto`` planner decision, and (crucially) the compiled executable.

        ``new_values`` is the new ``data`` array aligned with the original
        factor's CSR storage (or a :class:`CSRMatrix` with the identical
        pattern).  For transpose solvers the values are reordered through
        the cached CSC map; for rewritten solvers the recorded elimination
        plan is replayed numerically
        (:func:`repro.core.rewrite.replay_rewrite_values`) to produce new
        L'/E values in the cached patterns.  The executor's packed value
        buffers are then re-packed with one vectorized O(nnz) gather and
        swapped in — no re-trace, no re-compile; this is what a production
        PCG/IC server needs after each numeric re-factorization.

        Scatter-layout solvers (values embedded as trace-time constants)
        fall back to a cold rebuild, as does the rare case of a rewrite
        plan that does not numerically transfer (zero pivot / exact-zero
        cancellation in the *original* values).  Returns ``self``.

        ``validate`` (default on) runs a cheap O(nnz) value health scan —
        finiteness of every entry plus an exact-zero diagonal check — and
        raises ``ValueError`` on failure, because a refreshed executor would
        otherwise silently divide by zero or propagate NaN through the whole
        schedule.  ``validate=False`` skips the scan (e.g. to let a guarded
        solver's breakdown policy handle the bad values at solve time
        instead); a guarded solver additionally re-runs its own
        ``pivot_tol``-aware scan and re-packs its residual checker after
        every refresh."""
        ctx = self._refresh_ctx
        if ctx is None:
            raise ValueError("solver was built without refresh state")
        if isinstance(new_values, CSRMatrix):
            src = ctx.source
            if (new_values.nnz != src.nnz
                    or not np.array_equal(new_values.indptr, src.indptr)
                    or not np.array_equal(new_values.indices, src.indices)):
                raise ValueError(
                    "refresh requires the identical sparsity pattern; "
                    "rebuild for structural changes")
            data = np.asarray(new_values.data)
        else:
            data = np.asarray(new_values)
        if data.shape != ctx.source.data.shape:
            raise ValueError(
                f"new values must have shape {ctx.source.data.shape} "
                f"(one per stored nonzero); got {data.shape}")
        if validate:
            # O(nnz) health scan of the incoming values.  The source factor
            # is lower-triangular CSR with sorted columns, so its diagonal
            # is the last stored entry of every row.
            diag_idx = ctx.source.indptr[1:] - 1
            nonfinite, zero_piv = scan_values(data, diag_idx)
            if nonfinite or zero_piv:
                raise ValueError(
                    f"refresh: new values contain {nonfinite} non-finite "
                    f"entry(ies) and {zero_piv} zero/non-finite diagonal "
                    f"pivot(s); pass validate=False to accept them anyway "
                    f"(a guarded solver then applies its breakdown policy "
                    f"at solve time)")

        def _cold(reason: str) -> "SpTRSV":
            logger.warning("SpTRSV.refresh: %s — falling back to a cold "
                           "rebuild", reason)
            fresh = ctx.rebuild(data)
            self.__dict__.update(fresh.__dict__)
            return self

        if ctx.repack is None:
            return _cold(f"layout={self.layout!r} embeds values as "
                         "trace-time constants")
        sys_data = (data[ctx.values_map] if ctx.values_map is not None
                    else data).astype(ctx.system.dtype, copy=False)
        if ctx.rewrite is not None:
            system = CSRMatrix(ctx.system.indptr, ctx.system.indices,
                               sys_data, ctx.system.shape)
            try:
                target_data, e_data = replay_rewrite_values(
                    system, ctx.rewrite.plan, ctx.rewrite.L, ctx.rewrite.E)
            except RewriteReplayError as err:
                return _cold(f"rewrite plan did not transfer ({err})")
            if ctx.e_repack is not None:
                self._e_values = ctx.e_repack(e_data)
            self.rewrite_result = dataclasses.replace(
                ctx.rewrite,
                L=CSRMatrix(ctx.rewrite.L.indptr, ctx.rewrite.L.indices,
                            target_data, ctx.rewrite.L.shape),
                E=CSRMatrix(ctx.rewrite.E.indptr, ctx.rewrite.E.indices,
                            e_data, ctx.rewrite.E.shape))
        else:
            target_data = sys_data
        self._values = ctx.repack(target_data)
        # keep the cached source in sync so chained refreshes validate
        # against (and rebuild from) the latest values
        self._refresh_ctx = dataclasses.replace(
            ctx, source=CSRMatrix(ctx.source.indptr, ctx.source.indices,
                                  data, ctx.source.shape))
        if self.guard is not None:
            # re-pack the guard's full-precision residual buffers and re-run
            # its pivot_tol-aware value scan (breakdown policy applies)
            self.guard.refresh(sys_data)
        return self

    def stats(self) -> dict:
        """Execution-layout and schedule statistics, including the packed
        streaming-buffer bytes, padding waste, and whether the permuted
        layout is active — so benchmarks stop recomputing them ad hoc."""
        ps = self.packed_stats
        return {
            "strategy": self.strategy,
            "layout": self.layout,
            "backend": self.backend,
            "transpose": self.transpose,
            "n": self.n,
            "nnz": self.analysis.nnz,
            "segments": (self.schedule.num_segments
                         if self.schedule is not None
                         else self.block_schedule.num_segments
                         if self.block_schedule is not None else 1),
            "supernode_count": (self.supernodes.num_supernodes
                                if self.supernodes is not None
                                else self.analysis.supernode_count),
            "mean_block_size": (self.supernodes.mean_block_size
                                if self.supernodes is not None
                                else self.analysis.mean_block_size),
            "dense_block_fraction": (self.supernodes.dense_block_fraction
                                     if self.supernodes is not None
                                     else self.analysis.dense_block_fraction),
            "permutation_applied": bool(ps and ps.permutation_applied),
            "packed_value_bytes": ps.value_bytes if ps else None,
            "packed_index_bytes": ps.index_bytes if ps else None,
            # total resident packed-buffer footprint of this executor —
            # what a serving registry's byte budget charges per solver
            "packed_bytes": ((ps.value_bytes + ps.index_bytes)
                             if ps else None),
            "pattern_hash": self.pattern_hash,
            "padded_value_bytes": ps.padded_value_bytes if ps else None,
            "n_pad": ps.n_pad if ps else None,
            "refreshable_in_place": (self._refresh_ctx is not None
                                     and self._refresh_ctx.repack is not None),
            "rewrite": (self.rewrite_result.stats.summary()
                        if self.rewrite_result else None),
            "rewrite_policy": (self.rewrite_result.stats.policy
                               if self.rewrite_result else None),
            "critical_path_flops": self.analysis.critical_path_flops,
            "plan": self.plan.reason if self.plan else None,
            "planned_transform": (
                {"rewrite": self.plan.rewrite, "coarsen": self.plan.coarsen}
                if self.plan else None),
            "sweep": (self.sweep_stats.report()
                      if self.sweep_stats is not None else None),
            "planned_sweeps": self.plan.sweep_k if self.plan else None,
            # guarded-execution accounting (guard=GuardConfig(...)): the
            # full report plus the headline observables — refinement steps
            # taken, fallbacks fired, residual achieved, pivot alarms
            "guard": (self.guard.stats.report()
                      if self.guard is not None else None),
            "guard_precision": (self.guard.stats.precision
                                if self.guard is not None else None),
            "guard_refine_steps": (self.guard.stats.refine_steps_total
                                   if self.guard is not None else None),
            "guard_fallbacks": (self.guard.stats.fallback_solves
                                if self.guard is not None else None),
            "guard_residual": (self.guard.stats.last_residual_ratio
                               if self.guard is not None else None),
            "guard_pivot_alarms": (self.guard.stats.pivot_alarms
                                   if self.guard is not None else None),
        }
