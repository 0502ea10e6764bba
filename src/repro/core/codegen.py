"""Specialized code generation (paper §IV), adapted to TPU/JAX.

The paper's code generator emits per-level C functions with the matrix
structure *embedded as constants* (no indirect indexing for rewritten rows).
The TPU analogue: we generate, per matrix, a specialized executor whose
XLA/Mosaic program bakes the level structure in at trace time:

* each level is packed into an ELL *slab* — rows sorted by nnz, dependency
  columns/values padded to the level's max row width, stored transposed
  ``(K, R)`` so the row dimension maps to TPU lanes;
* fat levels execute as vectorized gather/FMA/reduce segments (one per level
  — the generated "function per level");
* tiny levels (``R <= unroll_threshold``) are unrolled into scalar ops with
  literal indices and values — the paper's constant-embedding, verbatim;
* the slab index arrays are closure constants, so XLA sees them as literals.

Executors produced here are pure JAX; the Pallas kernels in
:mod:`repro.kernels` consume the same :class:`Schedule`.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import obs
from .csr import CSRMatrix
from .levels import LevelSets, build_level_sets, compute_upper_levels
from .rewrite import RewriteResult

__all__ = [
    "LevelSlab",
    "Schedule",
    "EllMatrix",
    "Diagonals",
    "GATHER_UNROLL_MAX_K",
    "build_schedule",
    "build_ell",
    "build_offdiag_ell",
    "build_spmv",
    "slab_padded_flops",
    "stack_sub_slabs",
    "serial_arrays",
    "make_serial_solver",
    "make_levelset_solver",
    "make_blocked_solver",
    "make_rhs_transform",
    "ell_spmv",
    "spmv",
]

logger = logging.getLogger(__name__)

# Batched gathers are unrolled over the ELL width K into K two-dimensional
# row gathers (see _gather_sum) — ~50x faster on CPU than one (K, R, m)
# gather.  Past this width the unrolled program would bloat compile time, so
# _gather_sum falls back to the single fused 3-D gather (and logs it).
GATHER_UNROLL_MAX_K = 32


# --------------------------------------------------------------------------
# Packed structures
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class LevelSlab:
    """One level's rows in padded ELL form, transposed for TPU lanes.

    ``rows`` (R,) row ids;  ``cols``/``vals`` (K, R) with zero-padding
    (col 0 / val 0.0 is a safe no-op gather);  ``diag`` (R,).

    ``sub_rows`` is the slab's intra-slab dependency chain (schedule
    coarsening, :mod:`repro.core.coarsen`): when non-empty it partitions the
    R rows into consecutive *sub-slabs* that must execute back-to-back in
    order — sub-slab ``t`` may depend on rows of sub-slabs ``< t`` — but the
    whole chain forms **one** segment: a single barrier/launch/collective
    covers all of it.  An empty tuple means the classic one-level slab (all
    rows mutually independent).

    ``val_src``/``diag_src`` map each packed value back to its index in the
    source matrix's ``data`` array (-1 for zero padding).  They are the
    symbolic side of value-only numeric refresh (:meth:`SpTRSV.refresh`):
    re-packing a slab for new values with the same sparsity pattern is one
    vectorized gather instead of a re-analysis.
    """

    rows: np.ndarray
    cols: np.ndarray
    vals: np.ndarray
    diag: np.ndarray
    sub_rows: tuple = ()
    val_src: Optional[np.ndarray] = None   # (K, R) int64, -1 = padding
    diag_src: Optional[np.ndarray] = None  # (R,) int64

    @property
    def R(self) -> int:
        return self.rows.shape[0]

    @property
    def K(self) -> int:
        return self.cols.shape[0]

    @property
    def depth(self) -> int:
        """Length of the intra-slab dependency chain (1 = plain level)."""
        return len(self.sub_rows) if self.sub_rows else 1

    def sub_slabs(self):
        """Iterate the chain as plain (depth-1) :class:`LevelSlab` views —
        consumers that need per-wavefront slabs (fused layout, replicated
        distributed execution) remain agnostic to coarsening."""
        if self.depth == 1:
            yield dataclasses.replace(self, sub_rows=())
            return
        off = 0
        for r in self.sub_rows:
            yield LevelSlab(
                rows=self.rows[off : off + r],
                cols=self.cols[:, off : off + r],
                vals=self.vals[:, off : off + r],
                diag=self.diag[off : off + r],
                val_src=None if self.val_src is None
                else self.val_src[:, off : off + r],
                diag_src=None if self.diag_src is None
                else self.diag_src[off : off + r],
            )
            off += r


@dataclasses.dataclass(frozen=True)
class Schedule:
    """Level-set execution schedule for a (possibly rewritten) matrix."""

    n: int
    slabs: List[LevelSlab]
    level_of_row: np.ndarray
    nnz: int

    @property
    def num_levels(self) -> int:
        return len(self.slabs)

    @property
    def num_segments(self) -> int:
        """Barrier-separated execution units.  Every slab — coarsened or not
        — is one segment: one generated code region, one kernel launch, one
        collective.  This is the schedule's synchronization-point count."""
        return len(self.slabs)

    @property
    def total_depth(self) -> int:
        """Sum of intra-slab chain depths = wavefront count actually swept
        (equals the level count of the uncoarsened schedule)."""
        return sum(s.depth for s in self.slabs)

    def perm(self) -> np.ndarray:
        """Schedule-order row permutation: ``perm[p]`` = original row id at
        permuted position ``p``.  Each segment's output rows are a
        *contiguous* slice of the permuted space (see :func:`row_offsets`),
        which is what lets the permuted-space executors replace per-segment
        row scatters with ``lax.dynamic_update_slice``.  Concatenating slab
        row arrays is exact because every row appears in exactly one slab
        and slabs execute in this order."""
        if not self.slabs:
            return np.zeros(0, dtype=np.int64)
        return np.concatenate([s.rows for s in self.slabs]).astype(np.int64)

    def row_offsets(self) -> np.ndarray:
        """(num_segments + 1,) permuted-space start offset of each segment:
        segment ``i`` owns positions ``[row_offsets[i], row_offsets[i+1])``."""
        return np.concatenate(
            [[0], np.cumsum([s.R for s in self.slabs])]).astype(np.int64)

    def padded_flops(self, unroll_threshold: int = 0) -> int:
        """FLOPs actually executed including padding waste (load-balance
        metric — the TPU analogue of idle cores).

        ``unroll_threshold``: plain slabs with that few rows execute as
        constant-embedded scalar code (``_apply_slab_unrolled``) which skips
        zero padding entirely, so they count at their true nnz — without this
        the ``auto`` planner would charge unrolled thin levels for padding
        they never execute.  Coarsened slabs execute ``depth`` uniform
        sub-steps padded to the widest sub-slab."""
        return sum(slab_padded_flops(s, unroll_threshold) for s in self.slabs)


def slab_padded_flops(s: LevelSlab, unroll_threshold: int = 0) -> int:
    """Executed FLOPs of one slab as the executors actually run it: chains
    do ``depth`` uniform sub-steps padded to the widest sub-slab, unrolled
    slabs skip zero padding (true nnz), plain slabs pay the full ELL pad.
    The single source of the per-slab cost — both ``Schedule.padded_flops``
    and the coarsening/planner cost model sum this."""
    if s.depth > 1:
        rmax = max(s.sub_rows)
        return s.depth * (2 * s.K * rmax + rmax)
    if s.R <= unroll_threshold:
        return 2 * int(np.count_nonzero(s.vals)) + s.R
    return 2 * s.K * s.R + s.R


@dataclasses.dataclass(frozen=True)
class EllMatrix:
    """Whole-matrix ELL (used for the RHS operator E and for SpMV).

    ``val_src`` (optional) maps each packed value to its index in the source
    matrix's ``data`` array (-1 padding) — the refresh map for re-packing
    new values of the same pattern in one vectorized gather."""

    cols: np.ndarray  # (K, n)
    vals: np.ndarray  # (K, n)
    val_src: Optional[np.ndarray] = None  # (K, n) int64, -1 = padding

    @property
    def K(self) -> int:
        return self.cols.shape[0]


def _pack_rows(
    L: CSRMatrix, rows: np.ndarray, sort_by_nnz: bool, *, diag_first: bool = False
) -> LevelSlab:
    """Pack the given rows into one ELL slab.

    ``diag_first=False`` assumes lower-triangular storage (diagonal last in
    each row, the forward-solve layout); ``diag_first=True`` assumes
    upper-triangular storage (diagonal first — rows of ``L.transpose()``,
    i.e. columns of ``L``, the backward-solve layout).  Either way the slab
    comes out identical in shape, so every executor downstream is
    direction-agnostic."""
    row_nnz = L.indptr[rows + 1] - L.indptr[rows] - 1  # off-diagonal count
    if sort_by_nnz and rows.size > 1:
        order = np.argsort(row_nnz, kind="stable")
        rows = rows[order]
        row_nnz = row_nnz[order]
    K = max(int(row_nnz.max()) if rows.size else 0, 1)
    R = rows.size
    cols = np.zeros((K, R), dtype=np.int32)
    vals = np.zeros((K, R), dtype=L.dtype)
    diag = np.empty((R,), dtype=L.dtype)
    val_src = np.full((K, R), -1, dtype=np.int64)
    diag_src = np.empty((R,), dtype=np.int64)
    for r, i in enumerate(rows):
        lo, hi = int(L.indptr[int(i)]), int(L.indptr[int(i) + 1])
        c, v = L.indices[lo:hi], L.data[lo:hi]
        if diag_first:
            diag[r] = v[0]
            diag_src[r] = lo
            c, v = c[1:], v[1:]
            src = np.arange(lo + 1, hi, dtype=np.int64)
        else:
            diag[r] = v[-1]
            diag_src[r] = hi - 1
            c, v = c[:-1], v[:-1]
            src = np.arange(lo, hi - 1, dtype=np.int64)
        k = c.size
        cols[:k, r] = c
        vals[:k, r] = v
        val_src[:k, r] = src
    return LevelSlab(rows=rows.astype(np.int32), cols=cols, vals=vals,
                     diag=diag, val_src=val_src, diag_src=diag_src)


def build_schedule(
    L: CSRMatrix,
    levels: Optional[LevelSets] = None,
    *,
    sort_by_nnz: bool = True,
    bucket_pad_ratio: float = 0.0,
    upper: bool = False,
) -> Schedule:
    """Pack each level into ELL slabs.

    ``bucket_pad_ratio`` > 1 splits a level into several slabs so that within
    a slab ``max_nnz <= ratio * max(min_nnz, 1)`` — the paper's "multiple
    functions per thick level", applied to padding: after equation rewriting,
    rewritten rows carry fill-in and a single max-width slab pays their K for
    every native row (measured 3.5x serial slowdown on lung2-like before this
    split; §Perf solver iteration 1).  Slabs of one level stay mutually
    independent — only level boundaries synchronize.

    ``upper=True`` packs an upper-triangular matrix (diagonal stored first
    per row) over its backward-substitution levels — the transpose-solve
    schedule.  Pass ``L.transpose()`` (whose rows are columns of ``L``) plus
    the reverse level sets derived from the forward analysis; the resulting
    slabs feed the *same* executors/kernels as forward schedules.
    """
    if levels is None:
        level = compute_upper_levels(L) if upper else None
        levels = build_level_sets(L, level=level)
    slabs = []
    for rows in levels.rows:
        if bucket_pad_ratio and bucket_pad_ratio > 1.0 and rows.size > 1:
            nnz = L.indptr[rows + 1] - L.indptr[rows] - 1
            order = np.argsort(nnz, kind="stable")
            rows_sorted = rows[order]
            nnz_sorted = nnz[order]
            start = 0
            while start < rows_sorted.size:
                kmin = max(int(nnz_sorted[start]), 1)
                end = int(np.searchsorted(
                    nnz_sorted, kmin * bucket_pad_ratio, side="right"))
                end = max(end, start + 1)
                slabs.append(_pack_rows(L, np.sort(rows_sorted[start:end]),
                                        sort_by_nnz, diag_first=upper))
                start = end
        else:
            slabs.append(_pack_rows(L, rows, sort_by_nnz, diag_first=upper))
    return Schedule(n=L.n, slabs=slabs, level_of_row=levels.level, nnz=L.nnz)


def build_ell(M: CSRMatrix) -> EllMatrix:
    """Whole matrix (diagonal included) as ELL, transposed (K, n), with the
    value-source map recorded for value-only refresh."""
    row_nnz = M.row_nnz()
    K = max(int(row_nnz.max()), 1)
    cols = np.zeros((K, M.n), dtype=np.int32)
    vals = np.zeros((K, M.n), dtype=M.dtype)
    val_src = np.full((K, M.n), -1, dtype=np.int64)
    # entry e of row i goes to slot e - indptr[i] of column i
    src = np.arange(M.nnz, dtype=np.int64)
    row = np.repeat(np.arange(M.n), row_nnz)
    slot = src - np.repeat(M.indptr[:-1], row_nnz)
    cols[slot, row] = M.indices
    vals[slot, row] = M.data
    val_src[slot, row] = src
    return EllMatrix(cols=cols, vals=vals, val_src=val_src)


def build_offdiag_ell(M: CSRMatrix, *, upper: bool = False):
    """Split a triangular matrix into its strictly-triangular ELL part ``N``
    and diagonal ``D`` — the ``L = D + N`` decomposition the sync-free sweep
    executor iterates on (:mod:`repro.core.sweep`).

    Returns ``(ell, diag, diag_src)``: ``ell`` is the off-diagonal part as a
    transposed ``(K, n)`` :class:`EllMatrix` with its value-source map
    recorded, ``diag`` the ``(n,)`` diagonal, ``diag_src`` its indices into
    ``M.data`` — so a value-only refresh re-packs both with one masked
    gather.  ``upper=True`` reads upper-triangular storage (diagonal first
    per row, e.g. ``L.transpose()``)."""
    row_nnz = M.row_nnz() - 1
    K = max(int(row_nnz.max()) if row_nnz.size else 0, 1)
    cols = np.zeros((K, M.n), dtype=np.int32)
    vals = np.zeros((K, M.n), dtype=M.dtype)
    val_src = np.full((K, M.n), -1, dtype=np.int64)
    # entry e of row i goes to slot e - indptr[i] (- 1 past a leading
    # diagonal) of column i; the diagonal entries are left out
    src = np.arange(M.nnz, dtype=np.int64)
    row = np.repeat(np.arange(M.n), M.row_nnz())
    start = M.indptr[:-1][row]
    slot = src - start - (1 if upper else 0)
    off = src != (start if upper else M.indptr[1:][row] - 1)
    cols[slot[off], row[off]] = M.indices[off]
    vals[slot[off], row[off]] = M.data[off]
    val_src[slot[off], row[off]] = src[off]
    diag = M.diagonal(first=upper)
    diag_src = (M.indptr[:-1] if upper else M.indptr[1:] - 1).astype(np.int64)
    return EllMatrix(cols=cols, vals=vals, val_src=val_src), diag, diag_src


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class Diagonals:
    """The pattern of a matrix stored by diagonals (DIA): the ascending
    ``col − row`` offsets of its stored diagonals.  A static pytree, so a
    jitted :func:`spmv` keys its cache on the offsets and takes only the
    values as an argument."""

    offsets: tuple


def build_spmv(M: CSRMatrix) -> tuple:
    """``M`` as ``(pattern, vals)`` for :func:`spmv`, in whichever of two
    formats stores fewer bytes.

    DIA — pattern a :class:`Diagonals`, ``vals`` ``(ndiag, rows)``, zero
    where a row lacks a diagonal — when ``ndiag · b ≤ K · (b + 4)`` for
    ``b``-byte values, ``K`` the ELL width and 4 the bytes of ELL's int32
    column: a stencil's few offsets.  Otherwise ELL as :func:`build_ell`
    packs it, pattern the ``(K, rows)`` columns.  Offsets ascend, as each
    row's columns do, so both formats visit a row's terms in one order.
    Each build counts its stored entries as ``spmv.dia_entries`` or
    ``spmv.ell_entries`` (:mod:`repro.core.obs`)."""
    rows, cols = M.shape
    row_nnz = M.row_nnz()
    K = max(int(row_nnz.max(initial=0)), 1)
    row = np.repeat(np.arange(rows, dtype=np.int64), row_nnz)
    # col - row, shifted by rows - 1 to count from 0
    off = M.indices - row + (rows - 1)
    present = np.flatnonzero(np.bincount(off, minlength=rows + cols - 1))
    b = M.dtype.itemsize
    if present.size * b <= K * (b + 4):
        slot = np.zeros(rows + cols - 1, dtype=np.int64)
        slot[present] = np.arange(present.size)
        vals = np.zeros((present.size, rows), dtype=M.dtype)
        vals[slot[off], row] = M.data
        obs.count(obs.SPMV_DIA_ENTRIES, vals.size)
        return Diagonals(tuple((present - (rows - 1)).tolist())), vals
    ell = build_ell(M)
    obs.count(obs.SPMV_ELL_ENTRIES, ell.vals.size)
    return ell.cols, ell.vals


# --------------------------------------------------------------------------
# Executors (pure JAX)
#
# Every executor accepts either a single RHS ``(n,)`` or a multi-RHS batch
# ``(n, m)`` (columns are independent systems L x_j = b_j).  The batch axis
# rides along as a trailing dimension of the solution vector, so a slab's
# gather/FMA/reduce becomes ``(K, R, m)`` and the TPU lane dimension is
# ``R * m`` instead of ``R`` — thin levels no longer underfeed the lanes.
# --------------------------------------------------------------------------
def _coef(a: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Broadcast a per-row coefficient array over the batch axis of x (a
    no-op for single-RHS solves)."""
    return a if x.ndim == 1 else a[..., None]


def _gather_sum(
    vals: jnp.ndarray,
    cols: jnp.ndarray,
    x: jnp.ndarray,
    *,
    unroll_max_k: int = GATHER_UNROLL_MAX_K,
) -> jnp.ndarray:
    """``sum_k vals[k] * x[cols[k]]`` over the static ELL width K.

    Single-RHS stays the paper's fused one-gather + reduce.  Batched x
    ``(n, m)`` instead unrolls the K axis into K row-gathers of ``(R, m)``:
    XLA's CPU gather of (K, R, m) row slices runs ~50x slower per element
    than the same work as K two-dimensional gathers.  Slabs wider than
    ``unroll_max_k`` (default :data:`GATHER_UNROLL_MAX_K`) fall back to the
    fused 3-D gather — correct but slower; the fallback is logged at trace
    time so wide-slab batched solves are diagnosable."""
    if x.ndim == 1 or cols.shape[0] > unroll_max_k:
        if x.ndim > 1:
            logger.debug(
                "_gather_sum: K=%d > unroll_max_k=%d — falling back to the "
                "fused 3-D gather for this batched slab (slower on CPU)",
                cols.shape[0], unroll_max_k,
            )
        # single RHS, or rows wide enough that unrolling K gathers would
        # bloat the program: one fused gather + reduce
        return jnp.sum(_coef(vals, x) * x[cols], axis=0)
    acc = vals[0][:, None] * x[cols[0]]
    for k in range(1, cols.shape[0]):
        acc = acc + vals[k][:, None] * x[cols[k]]
    return acc


def _diagonal_sum(vals: jnp.ndarray, offsets: tuple,
                  x: jnp.ndarray) -> jnp.ndarray:
    """``y[i] = sum_d vals[d, i] * x[i + offsets[d]]``: each term a static
    slice of ``x`` zero-padded along axis 0, so no index array and no
    gather; ``x`` ``(cols,)`` or ``(cols, m)``."""
    rows = vals.shape[1]
    if not offsets:
        return jnp.zeros((rows,) + x.shape[1:], x.dtype)
    lo = max(0, -offsets[0])
    hi = max(0, offsets[-1] + rows - x.shape[0])
    xp = jnp.pad(x, [(lo, hi)] + [(0, 0)] * (x.ndim - 1))
    acc = None
    for d, off in enumerate(offsets):
        term = _coef(vals[d], x) * xp[lo + off:lo + off + rows]
        acc = term if acc is None else acc + term
    return acc


def spmv(pattern, vals: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """``y = M x`` for ``M`` as :func:`build_spmv` stores it: shifted slices
    for DIA, a gather for ELL.  ``x`` may be ``(cols,)`` or batched
    ``(cols, m)``."""
    if isinstance(pattern, Diagonals):
        return _diagonal_sum(vals, pattern.offsets, x)
    return _gather_sum(vals, pattern, x)


def ell_spmv(ell: EllMatrix, v: jnp.ndarray) -> jnp.ndarray:
    """y = M v for ELL-packed M.  Fully parallel (one gather + reduce per
    ELL slot).  ``v`` may be ``(n,)`` or batched ``(n, m)`` (one SpMV per
    column)."""
    cols = jnp.asarray(ell.cols)
    vals = jnp.asarray(ell.vals, dtype=v.dtype)
    return _gather_sum(vals, cols, v)


def serial_arrays(L: CSRMatrix, *, upper: bool = False):
    """Row-major serial-scan arrays plus their refresh source maps.

    Returns ``(cols (n, K), vals (n, K), diag (n,), val_src (n, K),
    diag_src (n,), order (n,))`` — ``order`` is the scan order (reversed for
    backward substitution).  ``val_src``/``diag_src`` index ``L.data``
    (-1 = padding), so a value-only refresh re-packs the scan operands with
    one vectorized gather."""
    row_nnz = L.row_nnz() - 1
    K = max(int(row_nnz.max()), 1)
    n = L.n
    cols = np.zeros((n, K), dtype=np.int32)
    vals = np.zeros((n, K), dtype=L.dtype)
    val_src = np.full((n, K), -1, dtype=np.int64)
    for i in range(n):
        lo, hi = int(L.indptr[i]), int(L.indptr[i + 1])
        k = hi - lo - 1
        if upper:
            cols[i, :k] = L.indices[lo + 1 : hi]
            vals[i, :k] = L.data[lo + 1 : hi]
            val_src[i, :k] = np.arange(lo + 1, hi, dtype=np.int64)
        else:
            cols[i, :k] = L.indices[lo : hi - 1]
            vals[i, :k] = L.data[lo : hi - 1]
            val_src[i, :k] = np.arange(lo, hi - 1, dtype=np.int64)
    diag = L.diagonal(first=upper)
    diag_src = (L.indptr[:-1] if upper else L.indptr[1:] - 1).astype(np.int64)
    order = np.arange(n, dtype=np.int32)
    if upper:
        order = order[::-1]
    return cols, vals, diag, val_src, diag_src, order


def make_serial_solver(
    L: CSRMatrix, *, upper: bool = False
) -> Callable[[jnp.ndarray], jnp.ndarray]:
    """Algorithm 1 of the paper: row-serial substitution as a ``lax.scan``
    over rows (the paper's serial baseline).  ``b`` may be ``(n,)`` or
    ``(n, m)``; the scan carries all columns at once.

    ``upper=True`` takes an upper-triangular matrix (diagonal first per row,
    e.g. ``L.transpose()``) and scans rows in *reverse* order — backward
    substitution for the transpose solve ``Lᵀ x = b``."""
    cols, vals, diag, _, _, order = serial_arrays(L, upper=upper)
    cols_d = jnp.asarray(cols[order])
    vals_d = jnp.asarray(vals[order])
    diag_d = jnp.asarray(diag[order])
    idx = jnp.asarray(order)

    def solve(b: jnp.ndarray) -> jnp.ndarray:
        dt = b.dtype
        vals_l = vals_d.astype(dt)
        diag_l = diag_d.astype(dt)

        def body(x, inp):
            c, v, d, bi, i = inp
            s = jnp.sum(_coef(v, x) * x[c], axis=0)
            xi = (bi - s) / d
            x = x.at[i].set(xi)
            return x, ()

        x0 = jnp.zeros(b.shape, dtype=dt)
        x, _ = jax.lax.scan(body, x0, (cols_d, vals_l, diag_l, b[idx], idx))
        return x

    return solve


def _apply_slab(
    x: jnp.ndarray, b: jnp.ndarray, slab: LevelSlab,
    unroll_max_k: int = GATHER_UNROLL_MAX_K,
) -> jnp.ndarray:
    """One level as a vectorized gather/FMA/reduce segment.  For batched
    solves the gather is ``(K, R, m)`` and the reduce yields ``(R, m)``."""
    cols = jnp.asarray(slab.cols)
    vals = jnp.asarray(slab.vals, dtype=x.dtype)
    rows = jnp.asarray(slab.rows)
    diag = jnp.asarray(slab.diag, dtype=x.dtype)
    s = _gather_sum(vals, cols, x, unroll_max_k=unroll_max_k)  # (R,) or (R, m)
    xl = (b[rows] - s) / _coef(diag, x)
    return x.at[rows].set(xl)


def _apply_slab_unrolled(x: jnp.ndarray, b: jnp.ndarray, slab: LevelSlab) -> jnp.ndarray:
    """Tiny level unrolled with literal indices/values — the generated-code
    path of the paper (Fig. 4): no indirect indexing, constants embedded.
    Batched solves broadcast naturally: each scalar op becomes an (m,)
    vector op over the RHS columns."""
    new_vals = []
    for r in range(slab.R):
        i = int(slab.rows[r])
        s = b[i]
        for k in range(slab.K):
            v = float(slab.vals[k, r])
            if v != 0.0:
                s = s - v * x[int(slab.cols[k, r])]
        new_vals.append(s / float(slab.diag[r]))
    rows = jnp.asarray(slab.rows.astype(np.int32))
    return x.at[rows].set(jnp.stack(new_vals).astype(x.dtype))


def stack_sub_slabs(slab: LevelSlab, n: int, *, with_src: bool = False):
    """Uniform stacked arrays for a coarsened slab's chain: every sub-slab
    zero-padded to the widest one so the chain can run as ONE ``fori_loop``
    (one XLA while op — segment count and program size independent of depth).

    Returns ``(rows, cols, vals, diag)`` of shapes ``(d, Rmax)``,
    ``(d, K, Rmax)``, ``(d, K, Rmax)``, ``(d, Rmax)``.  Padding rows carry
    the sentinel id ``n`` (they read ``b_ext[n] = 0``, divide by diag 1, and
    scatter into the scratch slot ``n`` — never read back, masked off at the
    end of the solve).  ``with_src=True`` appends the stacked
    ``(val_src, diag_src)`` refresh maps (-1 padding)."""
    d = slab.depth
    rmax = max(slab.sub_rows) if slab.sub_rows else slab.R
    rows = np.full((d, rmax), n, dtype=np.int32)
    cols = np.zeros((d, slab.K, rmax), dtype=np.int32)
    vals = np.zeros((d, slab.K, rmax), dtype=slab.vals.dtype)
    diag = np.ones((d, rmax), dtype=slab.diag.dtype)
    val_src = np.full((d, slab.K, rmax), -1, dtype=np.int64)
    diag_src = np.full((d, rmax), -1, dtype=np.int64)
    for t, sub in enumerate(slab.sub_slabs()):
        rows[t, : sub.R] = sub.rows
        cols[t, :, : sub.R] = sub.cols
        vals[t, :, : sub.R] = sub.vals
        diag[t, : sub.R] = sub.diag
        if with_src and sub.val_src is not None:
            val_src[t, :, : sub.R] = sub.val_src
            diag_src[t, : sub.R] = sub.diag_src
    if with_src:
        return rows, cols, vals, diag, val_src, diag_src
    return rows, cols, vals, diag


def _apply_slab_chain(
    x: jnp.ndarray, b_ext: jnp.ndarray, slab: LevelSlab, n: int,
    unroll_max_k: int = GATHER_UNROLL_MAX_K,
) -> jnp.ndarray:
    """A coarsened slab: ``depth`` dependent sub-slabs executed back-to-back
    inside one segment — a single ``fori_loop`` over the stacked uniform
    sub-arrays, so the XLA program holds one gather/FMA/scatter body per
    *super*-level instead of one per level.  ``x`` is ``(n+1, [m])`` with the
    scratch slot last; ``b_ext`` is b with a zero appended."""
    rows_h, cols_h, vals_h, diag_h = stack_sub_slabs(slab, n)
    rows_s = jnp.asarray(rows_h)
    cols_s = jnp.asarray(cols_h)
    vals_s = jnp.asarray(vals_h, dtype=x.dtype)
    diag_s = jnp.asarray(diag_h, dtype=x.dtype)

    def body(t, xc):
        s = _gather_sum(vals_s[t], cols_s[t], xc, unroll_max_k=unroll_max_k)
        xl = (b_ext[rows_s[t]] - s) / _coef(diag_s[t], xc)
        return xc.at[rows_s[t]].set(xl)

    return jax.lax.fori_loop(0, slab.depth, body, x)


def make_levelset_solver(
    schedule: Schedule,
    *,
    unroll_threshold: int = 0,
    gather_unroll_max_k: int = GATHER_UNROLL_MAX_K,
) -> Callable[[jnp.ndarray], jnp.ndarray]:
    """Level-set executor: one generated segment per level (paper's
    function-per-level), executed in level order.  ``unroll_threshold`` > 0
    additionally unrolls levels with that few rows into constant-embedded
    scalar code.  ``b`` may be ``(n,)`` or ``(n, m)``.

    Coarsened slabs (``depth > 1``, see :mod:`repro.core.coarsen`) execute
    their sub-slab chain as one ``fori_loop`` segment; the solution vector
    gains a scratch slot ``n`` for their pad rows (sliced off on return).
    Chained slabs are never unrolled — their rows are not mutually
    independent.  ``gather_unroll_max_k`` bounds the batched per-k gather
    unrolling of :func:`_gather_sum` (wider slabs fall back to the fused
    3-D gather, logged at trace time)."""
    n = schedule.n
    chained = any(s.depth > 1 for s in schedule.slabs)

    def solve(b: jnp.ndarray) -> jnp.ndarray:
        ext = 1 if chained else 0
        x = jnp.zeros((n + ext,) + b.shape[1:], dtype=b.dtype)
        if chained:
            b_ext = jnp.concatenate(
                [b, jnp.zeros((1,) + b.shape[1:], dtype=b.dtype)])
        for slab in schedule.slabs:
            if slab.depth > 1:
                x = _apply_slab_chain(x, b_ext, slab, n, gather_unroll_max_k)
            elif slab.R <= unroll_threshold:
                x = _apply_slab_unrolled(x, b, slab)
            else:
                x = _apply_slab(x, b, slab, gather_unroll_max_k)
        return x[:n] if chained else x

    return solve


def make_blocked_solver(
    bsched,
    *,
    backend=None,
    kernel: str = "auto",
    gather_unroll_max_k: int = GATHER_UNROLL_MAX_K,
) -> Callable[[jnp.ndarray], jnp.ndarray]:
    """Blocked (supernodal) executor over a
    :class:`~repro.core.coarsen.BlockSchedule`, scatter layout: per
    super-level one padded ELL panel gather-sum (the off-block update) and
    one batched dense diagonal-block apply

        x_blk = D⁻¹_blk (b_blk − Panel · x_prev)

    through :func:`repro.kernels.trsm_block.ops.make_block_apply` — the
    batched-TRSM step of the supernodal decomposition.  ``b`` may be
    ``(n,)`` or ``(n, m)``.  Lanes are block-major with sentinel row ``n``
    for padding, so ``x`` carries one scratch slot (sliced off on return);
    scalar rows are simply T=1 blocks — the same code path."""
    from repro.kernels.trsm_block.ops import make_block_apply

    apply_blocks = make_block_apply(backend, kernel=kernel)
    n = bsched.n

    def solve(b: jnp.ndarray) -> jnp.ndarray:
        dt = b.dtype
        b_ext = jnp.concatenate(
            [b, jnp.zeros((1,) + b.shape[1:], dtype=dt)])
        x = jnp.zeros((n + 1,) + b.shape[1:], dtype=dt)
        for slab in bsched.slabs:
            lane = jnp.asarray(slab.lane_row)
            s = _gather_sum(jnp.asarray(slab.vals, dt),
                            jnp.asarray(slab.cols), x,
                            unroll_max_k=gather_unroll_max_k)
            rhs = b_ext[lane] - s                       # (B*T[, m])
            rhs = rhs.reshape((slab.B, slab.T) + b.shape[1:])
            xb = apply_blocks(jnp.asarray(slab.dinv, dt), rhs)
            x = x.at[lane].set(
                xb.reshape((slab.B * slab.T,) + b.shape[1:]))
            x = x.at[n].set(jnp.zeros(b.shape[1:], dtype=dt))
        return x[:n]

    return solve


def make_rhs_transform(res: RewriteResult) -> Optional[Callable]:
    """b' = E b — the per-solve RHS update of the rewriting method, as one
    fully-parallel ELL SpMV.  For a batch ``B: (n, m)`` this is a single
    batched SpMV ``B' = E B`` (not m separate ones).  Returns ``None`` when
    E is the identity (no rewrites survived the budgets)."""
    if res.stats.e_nnz_offdiag == 0:
        return None
    ell = build_ell(res.E)

    def transform(b: jnp.ndarray) -> jnp.ndarray:
        return ell_spmv(ell, b)

    return transform
