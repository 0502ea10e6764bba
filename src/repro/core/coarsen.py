"""Synchronization-aware schedule coarsening + cost-model strategy planner.

The paper removes barriers by *rewriting equations* so thin levels empty
out.  This module applies the complementary lever (Böhnlein et al.,
arXiv:2503.05408): *merge* adjacent levels under a cost model instead of
changing the matrix.  A run of (mostly thin) levels becomes one **super-level
slab** carrying an intra-slab dependency chain (``LevelSlab.sub_rows``): the
sub-slabs execute back-to-back inside a single segment — one generated code
region / kernel launch / collective — so a lung2-class schedule collapses
from ~478 segments to a few dozen while the floating-point work per row is
**unchanged** (same gather-sum, same operands, same order; only zero padding
is added).  Results are typically bit-identical and always within a few ulp
of the uncoarsened executor — XLA may re-contract the zero-padded reduction
(FMA / tree shape) when compiling the merged segment.

Cost model
----------
Executing a slab costs ``segment_cost`` (launch + barrier + its share of XLA
program size / compile time, in FLOP-equivalents) plus its padded FLOPs.  A
merged group of ``d`` levels executes ``d`` uniform sub-steps padded to the
widest member — FLOP waste ``d*(2*Kmax*Rmax + Rmax) - sum_i work_i`` — but
pays ``segment_cost`` once instead of ``d`` times.  The greedy pass extends a
group while the waste stays below the segments saved.  Thin runs (R=2) merge
essentially for free; a fat wavefront next to a thin run is rejected because
padding every sub-step to the fat width would dwarf the saved barriers.

Strategy planner
----------------
:func:`plan_strategy` picks serial / levelset / levelset_unroll /
pallas_fused for ``SpTRSV.build(..., strategy="auto")`` from the
:class:`~repro.core.analysis.MatrixAnalysis` and schedule cost — chains go to
the ``lax.scan`` serial solver, level-parallel matrices to the (coarsened)
level-set executors, and to the fused kernel only where the calibration
row says a compiled fused dispatch exists (the GPU family today; the v5e
compiler refuses the TPU fused kernel).

Pricing is **backend-aware**: the model's coefficients (launch cost, gather
throughput, lane width, serial-scan cost, whether/how a fused single-dispatch
solve exists) come from the per-device calibration table in
:mod:`repro.core.calibrate`, keyed by the resolved
:class:`repro.kernels.backend.KernelBackend` — there are no hard-coded
platform checks in the planner.  The keys that differ across families:
``launch_cost`` (a GPU kernel launch is the barrier and is pricier than a
TPU grid step), ``gather_cost`` (relative padded-FLOP price),
``fused_max_rows`` (0 on cpu — pallas has no compiled CPU lowering; 0 on
TPU — Mosaic refuses the fused kernel's gather; GMEM-bounded on GPU) and
``fused_num_launches`` (``"one"`` TPU sequential grid vs ``"per_level"``
GPU span walk).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import numpy as np

from .analysis import MatrixAnalysis
from .calibrate import BackendCalibration, get_calibration
from .codegen import LevelSlab, Schedule, slab_padded_flops
from .csr import CSRMatrix
from .levels import Supernodes, _propagate_levels

__all__ = [
    "CoarsenConfig",
    "CoarsenStats",
    "coarsen_schedule",
    "coarsen_stats",
    "schedule_cost",
    "BlockSlab",
    "BlockSchedule",
    "build_block_schedule",
    "PlanDecision",
    "RewriteCandidate",
    "SweepCandidate",
    "BlockedCandidate",
    "blocked_candidate",
    "plan_strategy",
    "should_consider_rewrite",
    "SEGMENT_COST",
    "SUBSTEP_COST",
    "SERIAL_STEP_COST",
    "SERIAL_STEP_COST_SCALE",
]

# Cost of one barrier-separated segment, in FLOP-equivalents: dispatch of a
# gather/FMA/scatter group plus its share of program size.  Microseconds of
# launch/sync overhead at ~1 GFLOP/s effective SpTRSV throughput lands in the
# low thousands; the exact value only needs to separate "thin level" (work
# ~10 flops) from "fat level" (work >> segment_cost).
SEGMENT_COST = 4096.0

# Cost of one intra-chain sub-step (a fori_loop iteration: dynamic-slice of
# the stacked constants + the gather/FMA/scatter body).  Cheaper than a full
# segment — no barrier, no new program region — but not free; without this
# term the model would happily chain a fat wavefront onto a thin run and pay
# its padded width once per sub-step.
SUBSTEP_COST = SEGMENT_COST / 2

# Cost of one lax.scan step of the serial solver, FLOP-equivalents.  Rows of
# the serial scan are latency- not throughput-bound, and the measured
# per-row cost GROWS with n (the scan carries the whole x vector, so big
# systems fall out of cache): ~60ns/row at n=1.5k but ~5us/row at n=33k on
# CPU.  Modelled as base + scale*n per row — small systems legitimately
# solve fastest serially, large ones never do.
SERIAL_STEP_COST = 16.0
SERIAL_STEP_COST_SCALE = 0.06


@dataclasses.dataclass(frozen=True)
class CoarsenConfig:
    """Knobs of the coarsening cost model.

    ``max_depth``       longest intra-slab chain (bounds stacked-constant
                        memory ``d * K * Rmax`` and fori_loop trip count)
    ``max_chain_rows``  widest slab allowed inside a chain.  Chains exist to
                        absorb *thin* levels; a fat wavefront executes its
                        full width once per chained sub-step it rides along
                        with, which the flop terms under-bill when its K is
                        small (level-0 fat slabs have K=1), so wide slabs
                        always stand alone as plain parallel segments.
    ``segment_cost``    launch/sync/program-size cost per segment,
                        FLOP-equivalents (see :data:`SEGMENT_COST`)
    ``step_cost``       per-sub-step chain overhead (:data:`SUBSTEP_COST`)
    """

    max_depth: int = 32
    max_chain_rows: int = 128
    segment_cost: float = SEGMENT_COST
    step_cost: float = SUBSTEP_COST


@dataclasses.dataclass(frozen=True)
class CoarsenStats:
    segments_before: int
    segments_after: int
    padded_flops_before: int
    padded_flops_after: int

    @property
    def segment_reduction(self) -> float:
        return self.segments_before / max(self.segments_after, 1)

    def summary(self) -> str:
        return (
            f"segments {self.segments_before} -> {self.segments_after} "
            f"({self.segment_reduction:.1f}x fewer sync points), "
            f"padded FLOPs {self.padded_flops_before} -> "
            f"{self.padded_flops_after} "
            f"(+{100 * (self.padded_flops_after / max(self.padded_flops_before, 1) - 1):.1f}%)"
        )


def _slab_work(s: LevelSlab, unroll_threshold: int) -> float:
    """Executed FLOPs of one slab — the same per-slab formula
    ``Schedule.padded_flops`` sums, so merge decisions and planner costs
    can never drift apart."""
    return float(slab_padded_flops(s, unroll_threshold))


def _merge_group(group: list) -> LevelSlab:
    """Concatenate a group of plain slabs into one super-slab.  Sub-slab t
    keeps its exact packing (row order, values); only zero padding up to the
    group-wide K is added, so executors consume the identical operand sets
    the uncoarsened slabs would."""
    if len(group) == 1:
        return group[0]
    K = max(s.K for s in group)
    R = sum(s.R for s in group)
    rows = np.concatenate([s.rows for s in group]).astype(np.int32)
    diag = np.concatenate([s.diag for s in group])
    cols = np.zeros((K, R), dtype=np.int32)
    vals = np.zeros((K, R), dtype=group[0].vals.dtype)
    with_src = all(s.val_src is not None for s in group)
    val_src = np.full((K, R), -1, dtype=np.int64) if with_src else None
    diag_src = (np.concatenate([s.diag_src for s in group])
                if with_src else None)
    off = 0
    for s in group:
        cols[: s.K, off : off + s.R] = s.cols
        vals[: s.K, off : off + s.R] = s.vals
        if with_src:
            val_src[: s.K, off : off + s.R] = s.val_src
        off += s.R
    return LevelSlab(rows=rows, cols=cols, vals=vals, diag=diag,
                     sub_rows=tuple(s.R for s in group),
                     val_src=val_src, diag_src=diag_src)


def coarsen_schedule(
    schedule: Schedule,
    config: CoarsenConfig = CoarsenConfig(),
    *,
    unroll_threshold: int = 0,
) -> Schedule:
    """Greedy synchronization-aware level merging.

    Walks the slab sequence in order (any prefix-respecting grouping is
    correct: slab order is a topological order of the dependency DAG, and a
    chain over slabs that happen to be independent is merely conservative).
    A slab joins the open group iff the group's merged execution cost —
    ``d * (2*Kmax*Rmax + Rmax)`` for ``d`` uniform chained sub-steps — does
    not exceed executing it separately plus the ``segment_cost`` the merge
    saves.  Already-coarsened slabs pass through untouched (idempotent).
    """
    slabs = schedule.slabs
    if len(slabs) <= 1 or config.max_depth <= 1:
        return schedule
    out: list = []
    group: list = []
    g_kmax = g_rmax = 0

    def flush():
        nonlocal group, g_kmax, g_rmax
        if group:
            out.append(_merge_group(group))
        group, g_kmax, g_rmax = [], 0, 0

    for s in slabs:
        # pre-coarsened input and fat wavefronts stay their own segments
        if s.depth > 1 or s.R > config.max_chain_rows:
            flush()
            out.append(s)
            continue
        if group:
            d2 = len(group) + 1
            k2 = max(g_kmax, s.K)
            r2 = max(g_rmax, s.R)
            merged = d2 * (2 * k2 * r2 + r2 + config.step_cost)
            prev_merged = len(group) * (
                2 * g_kmax * g_rmax + g_rmax + config.step_cost)
            separate = prev_merged + _slab_work(s, unroll_threshold) \
                + config.segment_cost
            if d2 <= config.max_depth and merged <= separate:
                group.append(s)
                g_kmax, g_rmax = k2, r2
                continue
            flush()
        group = [s]
        g_kmax, g_rmax = s.K, s.R
    flush()
    return Schedule(n=schedule.n, slabs=out,
                    level_of_row=schedule.level_of_row, nnz=schedule.nnz)


def coarsen_stats(before: Schedule, after: Schedule,
                  unroll_threshold: int = 0) -> CoarsenStats:
    return CoarsenStats(
        segments_before=before.num_segments,
        segments_after=after.num_segments,
        padded_flops_before=before.padded_flops(unroll_threshold),
        padded_flops_after=after.padded_flops(unroll_threshold),
    )


# --------------------------------------------------------------------------
# Blocked (supernodal) schedule: the node-granular generalization
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class BlockSlab:
    """One super-level of the blocked schedule: ``B`` mutually independent
    supernodes executed as a batched dense diagonal-block apply plus a padded
    ELL panel update.

    Every block is padded to the level-wide ``T = max block size``; lanes are
    block-major (lane ``bi*T + t`` is row ``t`` of block ``bi``; padded lanes
    carry the sentinel row id ``n``).  The diagonal blocks are stored as
    *inverses* (``x_blk = D⁻¹ (b_blk − Panel · x_prev)``) so the solve is a
    batched GEMM rather than a per-block substitution; padded diagonal lanes
    hold an identity so the batched inverse is well-defined.

    ``blocks``    (B,) supernode ids
    ``rows``      (R,) original row ids, block-major, real rows only
    ``sizes``     (B,) rows per block
    ``dinv``      (B, T, T) float64 inverted diagonal blocks
    ``diag_src``  (B, T, T) int64 source position in ``L.data`` of each dense
                  in-block entry, −1 for structural zeros / padding — the
                  value-only ``refresh`` map for the dense blocks
    ``pad_eye``   (B, T, T) float64 identity on padded diagonal lanes (added
                  before every inversion, build and refresh alike)
    ``cols``      (K, B*T) int32 off-block dependency columns (0-padded)
    ``vals``      (K, B*T) off-block values
    ``val_src``   (K, B*T) int64 source positions in ``L.data``, −1 for pads
    ``lane_row``  (B*T,) int64 original row id per lane, ``n`` for padding
    """

    blocks: np.ndarray
    rows: np.ndarray
    sizes: np.ndarray
    dinv: np.ndarray
    diag_src: np.ndarray
    pad_eye: np.ndarray
    cols: np.ndarray
    vals: np.ndarray
    val_src: np.ndarray
    lane_row: np.ndarray

    @property
    def B(self) -> int:
        return self.dinv.shape[0]

    @property
    def T(self) -> int:
        return self.dinv.shape[1]

    @property
    def R(self) -> int:
        return len(self.rows)

    @property
    def K(self) -> int:
        return self.cols.shape[0]


@dataclasses.dataclass(frozen=True)
class BlockSchedule:
    """Supernodal (blocked) execution schedule: super-levels of dense
    diagonal blocks + off-diagonal panels.  The scalar-row level-set schedule
    is exactly this structure with every block of size 1 — node granularity
    is the only thing that changed."""

    n: int
    nnz: int
    slabs: tuple
    level_of_block: np.ndarray
    supernodes: Supernodes

    @property
    def num_segments(self) -> int:
        return len(self.slabs)

    @property
    def num_blocks(self) -> int:
        return sum(s.B for s in self.slabs)

    def perm(self) -> np.ndarray:
        """Original row id at each position of the blocked execution order
        (super-level by super-level, block-major)."""
        if not self.slabs:
            return np.zeros(0, dtype=np.int64)
        return np.concatenate([s.rows for s in self.slabs]).astype(np.int64)

    def panel_flops(self) -> int:
        """Padded FLOPs of the off-block panel updates (gather-sum over K
        ELL lanes + the RHS subtract)."""
        return sum(2 * s.K * s.B * s.T + s.B * s.T for s in self.slabs)

    def gemm_flops(self) -> int:
        """Dense FLOPs of the batched diagonal-block applies."""
        return sum(2 * s.T * s.T * s.B for s in self.slabs)


def build_block_schedule(
    M: CSRMatrix, supernodes: Supernodes, *, upper: bool = False
) -> BlockSchedule:
    """Build the blocked schedule of a triangular CSR from a supernode
    partition: level the *block-granular* dependency DAG (edge ``sb -> db``
    for any off-block entry coupling the two supernodes), then pack each
    super-level into a :class:`BlockSlab`.

    Correctness never depends on the partition — any contiguous run of rows
    is a valid block (its off-block dependencies are entirely outside the row
    span on the solved side) — so a degenerate all-singleton partition simply
    reproduces the scalar level-set structure with T=1 blocks."""
    n = M.n
    bp = supernodes.block_ptr
    block_of = supernodes.super_of_row
    nb = supernodes.num_supernodes
    indptr, indices, data = M.indptr, M.indices, M.data
    if nb == 0:
        return BlockSchedule(n=n, nnz=M.nnz, slabs=(),
                             level_of_block=np.zeros(0, np.int64),
                             supernodes=supernodes)
    row_of = np.repeat(np.arange(n, dtype=np.int64), M.row_nnz())
    strict = (indices > row_of) if upper else (indices < row_of)
    src_b = block_of[indices[strict]]
    dst_b = block_of[row_of[strict]]
    cross = src_b != dst_b
    edge_keys = np.unique(src_b[cross] * nb + dst_b[cross])
    blevel = _propagate_levels(nb, edge_keys // nb, edge_keys % nb)
    num_levels = int(blevel.max()) + 1 if nb else 0
    order = np.argsort(blevel, kind="stable")
    counts = np.bincount(blevel, minlength=num_levels)
    slabs = []
    off = 0
    for lv in range(num_levels):
        blocks = np.sort(order[off : off + int(counts[lv])])
        off += int(counts[lv])
        sizes = (bp[blocks + 1] - bp[blocks]).astype(np.int64)
        B = len(blocks)
        T = int(sizes.max())
        BT = B * T
        dense = np.zeros((B, T, T), np.float64)
        diag_src = np.full((B, T, T), -1, np.int64)
        pad_eye = np.zeros((B, T, T), np.float64)
        lane_row = np.full(BT, n, np.int64)
        offs = []           # (lane, off-block cols, off-block data positions)
        K = 1
        for bi, k in enumerate(blocks):
            r0, r1 = int(bp[k]), int(bp[k + 1])
            for t, r in enumerate(range(r0, r1)):
                lo, hi = int(indptr[r]), int(indptr[r + 1])
                c = indices[lo:hi]
                pos = np.arange(lo, hi, dtype=np.int64)
                inb = (c >= r0) & (c < r1)
                ci = c[inb] - r0
                dense[bi, t, ci] = data[lo:hi][inb]
                diag_src[bi, t, ci] = pos[inb]
                lane = bi * T + t
                lane_row[lane] = r
                cofs = c[~inb]
                offs.append((lane, cofs, pos[~inb]))
                K = max(K, len(cofs))
            for t in range(r1 - r0, T):
                pad_eye[bi, t, t] = 1.0
        # batched inversion in float64 — padded lanes are identity, so the
        # inverse exists whenever the diagonal does
        dinv = np.linalg.inv(dense + pad_eye)
        cols = np.zeros((K, BT), np.int32)
        vals = np.zeros((K, BT), dtype=M.data.dtype)
        val_src = np.full((K, BT), -1, np.int64)
        for lane, cofs, pofs in offs:
            kk = len(cofs)
            cols[:kk, lane] = cofs
            vals[:kk, lane] = data[pofs]
            val_src[:kk, lane] = pofs
        rows = np.concatenate(
            [np.arange(bp[k], bp[k + 1], dtype=np.int64) for k in blocks])
        slabs.append(BlockSlab(
            blocks=blocks, rows=rows, sizes=sizes, dinv=dinv,
            diag_src=diag_src, pad_eye=pad_eye, cols=cols, vals=vals,
            val_src=val_src, lane_row=lane_row))
    return BlockSchedule(n=n, nnz=M.nnz, slabs=tuple(slabs),
                         level_of_block=blevel, supernodes=supernodes)


# --------------------------------------------------------------------------
# Transform planner
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class PlanDecision:
    """Outcome of :func:`plan_strategy` — recorded on the built solver so
    ``strategy="auto"`` choices are auditable.

    ``strategy``  executor picked (serial / levelset / levelset_unroll /
                  pallas_fused / sweep / blocked)
    ``coarsen``   whether schedule coarsening is applied to the winner
    ``rewrite``   rewrite-policy tag ("thin" / "critical_path") when the
                  planner chose to transform the matrix first, else None
    ``costs``     every candidate's modelled per-solve cost; transform
                  combinations are keyed ``<strategy>+rewrite:<tag>+coarsen``
    ``sweep_k``   planned sweep count when the sync-free speculative
                  executor won (``strategy == "sweep"``), else None
    """

    strategy: str
    coarsen: bool
    reason: str
    costs: Dict[str, float]
    rewrite: Optional[str] = None
    sweep_k: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class RewriteCandidate:
    """A priced rewrite alternative handed to :func:`plan_strategy`: the
    schedule of the rewritten system L', its coarsened counterpart, and the
    modelled per-solve cost of the RHS transform ``b' = E b`` (one padded
    ELL SpMV plus one extra dispatch) — the fill-vs-parallelism price of the
    transformation."""

    schedule: Schedule
    coarsened: Optional[Schedule]
    rhs_cost: float


@dataclasses.dataclass(frozen=True)
class SweepCandidate:
    """A priced sync-free sweep alternative handed to :func:`plan_strategy`.

    ``k`` is the sweep count the convergence model certifies
    (:func:`repro.core.sweep.planned_sweeps` — ``min(depth, ⌈log tol /
    log q⌉)`` capped at the configured budget; the caller passes a candidate
    only when that bound lands).  ``ell_k`` is the off-diagonal ELL lane
    width of the whole-matrix ``D + N`` split, ``contraction`` the factor
    ``q = ‖D⁻¹N‖_∞`` — recorded so the decision's reason line is
    auditable."""

    k: int
    ell_k: int
    n: int
    contraction: float


@dataclasses.dataclass(frozen=True)
class BlockedCandidate:
    """A priced supernodal-blocked alternative handed to
    :func:`plan_strategy`, summarizing a built :class:`BlockSchedule`: one
    barrier per super-level, gathered panel FLOPs priced at ``gather_cost``,
    dense diagonal-block FLOPs at the (cheaper) ``gemm_cost``, and a fixed
    ``trsm_cost`` overhead per diagonal block."""

    segments: int
    panel_flops: int
    gemm_flops: int
    num_blocks: int
    supernode_count: int
    mean_block_size: float


def blocked_candidate(bsched: BlockSchedule) -> BlockedCandidate:
    """Pricing summary of a built blocked schedule."""
    sn = bsched.supernodes
    return BlockedCandidate(
        segments=bsched.num_segments,
        panel_flops=bsched.panel_flops(),
        gemm_flops=bsched.gemm_flops(),
        num_blocks=bsched.num_blocks,
        supernode_count=sn.num_supernodes,
        mean_block_size=sn.mean_block_size,
    )


def schedule_cost(schedule: Schedule, *, unroll_threshold: int = 0,
                  segment_cost: float = SEGMENT_COST,
                  step_cost: float = SUBSTEP_COST,
                  flop_cost: float = 1.0) -> float:
    """Modelled per-solve cost of a level-set schedule: executed (padded)
    FLOPs (scaled by the backend's relative ``flop_cost``), per-segment
    launch/sync overhead, and per-chain-sub-step loop overhead for coarsened
    slabs."""
    return (flop_cost * schedule.padded_flops(unroll_threshold)
            + segment_cost * schedule.num_segments
            + step_cost * (schedule.total_depth - schedule.num_segments))


# Spellings plan_strategy accepts for ``backend=`` beyond the calibration
# families themselves: jax platform aliases and the interpret backends
# (which execute on the host and are priced as cpu).
_CALIBRATION_KEY = {
    "cuda": "gpu",
    "rocm": "gpu",
    "interpret": "cpu",
    "interpret:tpu": "cpu",
    "interpret:gpu": "cpu",
}


def _plan_target(backend, interpret):
    """Resolve plan_strategy's ``backend=``/``interpret=`` knobs to
    ``(label, calibration_key, interpret_flag)``.

    ``backend`` may be a resolved :class:`~repro.kernels.backend.KernelBackend`
    (the solver path), a spec string (``cpu``/``tpu``/``gpu``/``cuda``/
    ``rocm``/``interpret``/``interpret:gpu``), or None — which reads
    ``jax.default_backend()``.  A ``cpu`` target is always *priced* as cpu
    even with ``interpret=False``: there is no compiled pallas path on a CPU
    host to price differently."""
    from repro.kernels.backend import KernelBackend

    if isinstance(backend, KernelBackend):
        return backend.name, backend.calibration_key, backend.interpret
    if backend is None:
        import jax

        backend = jax.default_backend()
    label = str(backend).lower()
    key = _CALIBRATION_KEY.get(label, label)
    if key not in ("cpu", "tpu", "gpu"):
        raise ValueError(
            f"unknown planner backend {backend!r}; expected a KernelBackend "
            f"or one of {sorted(('cpu', 'tpu', 'gpu', *_CALIBRATION_KEY))}")
    if interpret is None:
        # named hardware → its compiled lowerings; cpu → the interpreter
        # (the only way pallas executes there)
        interpret = key == "cpu"
    return label, key, interpret


def should_consider_rewrite(analysis: MatrixAnalysis) -> bool:
    """Gate for pricing rewrite candidates inside ``strategy="auto"``:
    equation rewriting targets barrier-dominated schedules with substantial
    thin-level content (the paper's lung2 pathology).  Chain-like matrices
    (levels ~ n) are excluded — the serial scan wins those outright and a
    speculative rewrite of a pure chain just burns fill budget — as are
    schedules too shallow to have barriers worth removing."""
    return (analysis.num_levels >= 8
            and analysis.num_levels <= 0.6 * analysis.n
            and analysis.thin_fraction_2 >= 0.25)


def plan_strategy(
    analysis: MatrixAnalysis,
    schedule: Schedule,
    coarsened: Optional[Schedule] = None,
    *,
    unroll_threshold: int = 4,
    segment_cost: Optional[float] = None,
    backend=None,
    interpret: Optional[bool] = None,
    calibration: Optional[BackendCalibration] = None,
    rewritten: Optional[Dict[str, RewriteCandidate]] = None,
    sweep: Optional[SweepCandidate] = None,
    blocked: Optional[BlockedCandidate] = None,
    precision: str = "native",
) -> PlanDecision:
    """Pick an execution strategy *and matrix transformation* from the
    analysis + schedule cost model.

    ``schedule`` is the uncoarsened schedule of the untransformed system;
    ``coarsened`` its coarsened counterpart when coarsening is on the table.
    ``rewritten`` maps rewrite-policy tags to priced
    :class:`RewriteCandidate` alternatives — rewriting shortens the chain
    (fewer segments on the rewritten schedule) but pays fill (that
    schedule's padded FLOPs) plus the per-solve RHS transform; coarsening
    removes syncs but pays padding.  ``sweep`` prices the sync-free
    speculative executor when its convergence model certifies a sweep count
    (see :class:`SweepCandidate`): ``k`` fused whole-matrix updates plus one
    verification pass, ONE dispatch total — the only candidate whose
    sync-point term does not scale with the level structure at all.  All
    combinations are priced with the same launch-cost/padded-FLOP model, so
    *rewrite vs coarsen vs both vs sweeps* is one ``min()`` over ``costs``.

    Pricing coefficients come from the per-backend calibration table
    (:mod:`repro.core.calibrate`), selected by ``backend`` — a resolved
    :class:`~repro.kernels.backend.KernelBackend`, a spec string, or None
    for ``jax.default_backend()``.  ``calibration`` overrides the table row
    (tests / measured micro-runs); an explicit ``segment_cost`` overrides
    just the launch-cost coefficient.  The fused kernel is a candidate only
    where the calibration says a compiled fused dispatch exists
    (``fused_max_rows > 0``, i.e. never on cpu) and the target is not the
    interpreter — interpret mode is a correctness harness, never a
    performance win; the cost below models the compiled kernel.

    ``precision="mixed"`` prices the guard's bf16-storage mode: every
    gather-bound term is scaled by the backend's ``mixed_gather_discount``
    (value-stream bytes halve; launch and dispatch terms do not), so the
    planner can shift toward gather-bound candidates when the caller
    requested mixed-precision execution.
    """
    backend, cal_key, interpret = _plan_target(backend, interpret)
    cal = calibration if calibration is not None         else get_calibration(cal_key)
    if precision == "mixed":
        # bf16 value storage (guard precision="mixed") halves the value-
        # stream bytes of every gather-bound term; the calibrated discount
        # reflects how much of the gather stream is values vs indices on
        # this backend.  Launch/TRSM/serial-step terms are unaffected —
        # mixed precision cheapens bandwidth, not dispatches.
        cal = dataclasses.replace(
            cal, gather_cost=cal.gather_cost * cal.mixed_gather_discount)
    seg_cost = cal.launch_cost if segment_cost is None else segment_cost

    costs: Dict[str, float] = {}
    # serial lax.scan: one segment, but every row is a latency-bound scan
    # step whose cost grows with the carried vector size.  Transforms never
    # help the scan (rewrite only adds work to it), so serial is priced on
    # the untransformed system only.
    costs["serial"] = analysis.solve_flops + analysis.n * (
        cal.serial_step_cost + cal.serial_step_cost_scale * analysis.n)

    def _levelset_costs(suffix: str, sched: Schedule,
                        co: Optional[Schedule], extra: float) -> None:
        costs[f"levelset{suffix}"] = extra + schedule_cost(
            sched, unroll_threshold=0, segment_cost=seg_cost,
            step_cost=cal.substep_cost, flop_cost=cal.gather_cost)
        costs[f"levelset_unroll{suffix}"] = extra + schedule_cost(
            sched, unroll_threshold=unroll_threshold,
            segment_cost=seg_cost, step_cost=cal.substep_cost,
            flop_cost=cal.gather_cost)
        if co is not None:
            costs[f"levelset{suffix}+coarsen"] = extra + schedule_cost(
                co, unroll_threshold=0, segment_cost=seg_cost,
                step_cost=cal.substep_cost, flop_cost=cal.gather_cost)
            costs[f"levelset_unroll{suffix}+coarsen"] = extra + schedule_cost(
                co, unroll_threshold=unroll_threshold,
                segment_cost=seg_cost, step_cost=cal.substep_cost,
                flop_cost=cal.gather_cost)

    def _fused_cost(suffix: str, sched: Schedule, extra: float) -> None:
        if interpret or analysis.n > cal.fused_max_rows:
            return
        # whole solve in one fused-layout dispatch: padded work bounded by
        # the widest slab's K over all (lane-padded) rows.  The launch term
        # is calibration-shaped: one sequential-grid dispatch on TPU, one
        # launch per wavefront span on GPU.
        kmax = max((s.K for s in sched.slabs), default=1)
        lane = max(cal.lane_width, 1)
        n_pad = -(-analysis.n // lane) * lane
        launches = (sched.total_depth
                    if cal.fused_num_launches == "per_level" else 1)
        costs[f"pallas_fused{suffix}"] = (
            extra + cal.gather_cost * (2 * kmax * n_pad + analysis.n)
            + seg_cost * launches)

    _levelset_costs("", schedule, coarsened, 0.0)
    _fused_cost("", schedule, 0.0)
    for tag, cand in (rewritten or {}).items():
        _levelset_costs(f"+rewrite:{tag}", cand.schedule, cand.coarsened,
                        cand.rhs_cost)
        _fused_cost(f"+rewrite:{tag}", cand.schedule, cand.rhs_cost)
    if sweep is not None:
        # k sweeps + 1 verification pass, each one fused ELL gather-sum over
        # all rows (2*K*n FMA-ish flops + n divides), one dispatch total.
        # The verification readback is the solve's single sync point.
        costs["sweep"] = cal.gather_cost * (sweep.k + 1) * (
            2 * sweep.ell_k * sweep.n + sweep.n) + seg_cost
    if blocked is not None:
        # one barrier per super-level; panel updates are gathered ELL work,
        # diagonal-block applies are contiguous dense flops at the backend's
        # gemm price plus a fixed per-block dispatch overhead
        costs["blocked"] = (
            seg_cost * blocked.segments
            + cal.gather_cost * blocked.panel_flops
            + cal.gemm_cost * blocked.gemm_flops
            + cal.trsm_cost * blocked.num_blocks)

    best = min(costs, key=costs.get)
    parts = best.split("+")
    strategy = parts[0]
    rewrite_tag = next((p[len("rewrite:"):] for p in parts
                        if p.startswith("rewrite:")), None)
    decision = PlanDecision(
        strategy=strategy,
        coarsen="coarsen" in parts,
        rewrite=rewrite_tag,
        sweep_k=sweep.k if (sweep is not None and strategy == "sweep")
        else None,
        reason=(
            # critical_fraction is deliberately NOT formatted here: it is a
            # lazy O(num_levels) computation and the reason line is built on
            # every auto plan, chains included
            f"min modelled cost {costs[best]:.0f} among "
            + ", ".join(f"{k}={v:.0f}" for k, v in sorted(costs.items()))
            + f" (n={analysis.n}, levels={analysis.num_levels}, "
            f"thin_fraction={analysis.thin_fraction_2:.2f}, backend={backend}"
            + (f", precision=mixed(gather x{cal.mixed_gather_discount:g})"
               if precision == "mixed" else "")
            + ")"
        ),
        costs=costs,
    )
    return decision
