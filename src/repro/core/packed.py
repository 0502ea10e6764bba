"""Permuted-space packed execution + value-only numeric refresh.

PR-3 removed most synchronization points (a lung2-class schedule runs as
~58 segments instead of ~493); what remains on the hot path is *memory
irregularity inside each segment* — every segment scatters its solved rows
into ``x`` at arbitrary ids and gathers ``b`` the same way — plus
build/compile time when the same sparsity pattern is re-solved with new
values (the dominant case in iterative workloads: each numeric
re-factorization of a PCG/IC server changes values, never structure).

This module addresses both:

**Permuted space.**  The slab order of a :class:`~repro.core.codegen.Schedule`
already visits every row exactly once, so it defines a row permutation
``perm`` (:meth:`Schedule.perm`) under which each segment's output rows are a
*contiguous slice*.  Executors here run entirely in that space: ``b`` is
permuted once at entry (``b̂ = b[perm]``), every segment reads its RHS with a
static slice and writes its solution with ``lax.dynamic_update_slice`` — no
per-segment scatter/gather of row ids — and ``x`` is un-permuted once at exit
(``x = x̂[pos]``).  ELL dependency columns are remapped to permuted positions
once at build.  (This generalizes the fused Pallas kernel's level-order
layout trick to *every* executor.)

**One packed streaming buffer.**  All per-segment ``vals`` slabs are packed
into one flat buffer with static offsets (same for ``diag`` and the column
indices), and the value buffers are passed to the jitted executor as
*runtime arguments* rather than trace-time constants.  XLA holds one
streaming input instead of ~58 embedded constants, and — the refresh payoff —
new values with the same pattern reuse the compiled executable outright:
``SpTRSV.refresh`` re-packs the buffers with one vectorized gather
(:func:`pack_values`, O(nnz)) and swaps them in.  No level analysis, no
re-trace, no re-compile.

Padding discipline: a segment may write its full padded width ``R_pad``;
padding lanes compute finite garbage (val 0 / diag 1) that lands *forward* —
on positions whose owning segment has not yet executed and always overwrites
them before any consumer reads them — so only writes past position ``n``
need scratch, provided by the ``n_pad - n`` tail.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import obs
from .codegen import (
    GATHER_UNROLL_MAX_K,
    Schedule,
    _coef,
    _gather_sum,
    build_ell,
    serial_arrays,
    stack_sub_slabs,
)
from .csr import CSRMatrix
from .rewrite import RewriteResult

__all__ = [
    "PackedSegment",
    "PackedLayout",
    "PackedStats",
    "PackedBlockSegment",
    "PackedBlockedLayout",
    "build_packed_layout",
    "build_packed_blocked_layout",
    "ell_packed_stats",
    "gather_src",
    "pack_values",
    "pack_blocked_values",
    "cast_value_buffers",
    "MIXED_VALS_DTYPE",
    "MIXED_DIAG_DTYPE",
    "make_packed_levelset_solver",
    "make_packed_blocked_solver",
    "make_packed_serial_solver",
    "make_packed_rhs_transform",
]


@dataclasses.dataclass(frozen=True)
class PackedSegment:
    """Geometry of one segment inside the packed flat buffers.

    ``off`` is the segment's first position in permuted space; its rows own
    positions ``[off, off + R)``.  ``R_pad`` is the padded lane width the
    executor computes/writes (equals ``R`` unless an executor-specific row
    alignment was requested).  Chains (``depth > 1``) store the stacked
    uniform sub-slab arrays ``(d, K, R_pad)``; ``sub_offs`` are the
    per-sub-slab permuted-space offsets driving the ``fori_loop``."""

    kind: str                 # "plain" | "chain"
    off: int
    R: int
    R_pad: int
    K: int
    depth: int
    val_off: int
    col_off: int
    diag_off: int
    sub_offs: Optional[np.ndarray] = None  # (depth,) int64, chains only
    block_rows: int = 0       # pallas row-block size (0 = not a kernel path)

    @property
    def val_size(self) -> int:
        return self.depth * self.K * self.R_pad

    @property
    def diag_size(self) -> int:
        return self.depth * self.R_pad


@dataclasses.dataclass(frozen=True)
class PackedStats:
    """Byte-level accounting of a packed layout — surfaced by
    ``SpTRSV.stats()`` so benchmarks stop recomputing it ad hoc."""

    permutation_applied: bool
    value_bytes: int          # packed vals + diag buffers
    index_bytes: int          # packed column-position buffer
    padded_value_bytes: int   # zero-padding share of value_bytes
    n_pad: int                # permuted vector length incl. scratch tail
    num_segments: int

    def report(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class PackedLayout:
    """Permuted-space packed form of a :class:`Schedule`.

    ``perm[p]`` = original row at permuted position ``p``; ``pos[i]`` =
    position of original row ``i``.  ``cols_flat`` holds *positions* (already
    remapped through ``pos``).  ``vals_src``/``diag_src`` map every packed
    value back into the target matrix's ``data`` array (-1 = padding) — the
    refresh maps consumed by :func:`pack_values`."""

    n: int
    n_pad: int
    nnz: int
    perm: np.ndarray
    pos: np.ndarray
    segments: tuple
    cols_flat: np.ndarray
    vals_flat: np.ndarray
    diag_flat: np.ndarray
    vals_src: np.ndarray
    diag_src: np.ndarray

    def stats(self) -> PackedStats:
        item = self.vals_flat.itemsize
        pad = int((self.vals_src < 0).sum() + (self.diag_src < 0).sum())
        return PackedStats(
            permutation_applied=True,
            value_bytes=self.vals_flat.nbytes + self.diag_flat.nbytes,
            index_bytes=self.cols_flat.nbytes,
            padded_value_bytes=pad * item,
            n_pad=self.n_pad,
            num_segments=len(self.segments),
        )


def build_packed_layout(
    schedule: Schedule,
    *,
    pad_rows: Optional[Callable[[int], int]] = None,
    pad_chain_rows: Optional[Callable[[int], int]] = None,
    block_rows_for: Optional[Callable[[int], int]] = None,
) -> PackedLayout:
    """Lower a schedule into the permuted-space packed layout.

    ``pad_rows(R) -> R_pad`` lets kernel executors request row alignment
    (TPU lane multiples, mesh-axis divisibility); default is no padding.
    ``pad_chain_rows`` applies to the widest sub-slab of a chain (defaults
    to ``pad_rows``).  ``block_rows_for(R_pad)`` records a per-segment
    kernel block size for Pallas executors."""
    pad_rows = pad_rows or (lambda r: r)
    pad_chain_rows = pad_chain_rows or pad_rows
    n = schedule.n
    perm = schedule.perm()
    assert perm.size == n, (perm.size, n)
    pos = np.empty(n, dtype=np.int64)
    pos[perm] = np.arange(n, dtype=np.int64)
    pos32 = pos.astype(np.int32)

    segments = []
    cols_b, vals_b, diag_b, vsrc_b, dsrc_b = [], [], [], [], []
    off = voff = doff = 0
    write_end_max = n
    dtype = schedule.slabs[0].vals.dtype if schedule.slabs else np.float64
    for slab in schedule.slabs:
        R = slab.R
        if R == 0:
            continue
        if slab.depth > 1:
            _, cols_s, vals_s, diag_s, vsrc_s, dsrc_s = stack_sub_slabs(
                slab, n, with_src=True)
            d, K, rmax = cols_s.shape
            Rp = int(pad_chain_rows(rmax))
            cols_p = np.zeros((d, K, Rp), dtype=np.int32)
            cols_p[:, :, :rmax] = pos32[cols_s]
            vals_p = np.zeros((d, K, Rp), dtype=vals_s.dtype)
            vals_p[:, :, :rmax] = vals_s
            diag_p = np.ones((d, Rp), dtype=diag_s.dtype)
            diag_p[:, :rmax] = diag_s
            vsrc_p = np.full((d, K, Rp), -1, dtype=np.int64)
            vsrc_p[:, :, :rmax] = vsrc_s
            dsrc_p = np.full((d, Rp), -1, dtype=np.int64)
            dsrc_p[:, :rmax] = dsrc_s
            sub_offs = off + np.concatenate(
                [[0], np.cumsum(slab.sub_rows[:-1])]).astype(np.int64)
            write_end = int(sub_offs[-1]) + Rp
            seg = PackedSegment(
                kind="chain", off=off, R=R, R_pad=Rp, K=K, depth=d,
                val_off=voff, col_off=voff, diag_off=doff, sub_offs=sub_offs,
                block_rows=block_rows_for(Rp) if block_rows_for else 0)
        else:
            K = slab.K
            Rp = int(pad_rows(R))
            cols_p = np.zeros((K, Rp), dtype=np.int32)
            cols_p[:, :R] = pos32[slab.cols]
            vals_p = np.zeros((K, Rp), dtype=slab.vals.dtype)
            vals_p[:, :R] = slab.vals
            diag_p = np.ones((Rp,), dtype=slab.diag.dtype)
            diag_p[:R] = slab.diag
            vsrc_p = np.full((K, Rp), -1, dtype=np.int64)
            dsrc_p = np.full((Rp,), -1, dtype=np.int64)
            if slab.val_src is not None:
                vsrc_p[:, :R] = slab.val_src
                dsrc_p[:R] = slab.diag_src
            write_end = off + Rp
            seg = PackedSegment(
                kind="plain", off=off, R=R, R_pad=Rp, K=K, depth=1,
                val_off=voff, col_off=voff, diag_off=doff,
                block_rows=block_rows_for(Rp) if block_rows_for else 0)
        segments.append(seg)
        cols_b.append(cols_p.ravel())
        vals_b.append(vals_p.ravel())
        diag_b.append(diag_p.ravel())
        vsrc_b.append(vsrc_p.ravel())
        dsrc_b.append(dsrc_p.ravel())
        write_end_max = max(write_end_max, write_end)
        off += R
        voff += seg.val_size
        doff += seg.diag_size
    assert off == n, (off, n)

    def cat(blocks, dt):
        return (np.concatenate(blocks).astype(dt, copy=False) if blocks
                else np.zeros(0, dtype=dt))

    return PackedLayout(
        n=n, n_pad=write_end_max, nnz=schedule.nnz,
        perm=perm, pos=pos,
        segments=tuple(segments),
        cols_flat=cat(cols_b, np.int32),
        vals_flat=cat(vals_b, dtype),
        diag_flat=cat(diag_b, dtype),
        vals_src=cat(vsrc_b, np.int64),
        diag_src=cat(dsrc_b, np.int64),
    )


def ell_packed_stats(ell, diag: np.ndarray, *, n: int) -> PackedStats:
    """:class:`PackedStats` for a whole-matrix ELL layout (the ``sweep``
    executor's ``D + N`` split): one segment, no permutation, padding share
    read off the value-source map."""
    pad = int((ell.val_src < 0).sum())
    return PackedStats(
        permutation_applied=False,
        value_bytes=ell.vals.nbytes + diag.nbytes,
        index_bytes=ell.cols.nbytes,
        padded_value_bytes=pad * ell.vals.itemsize,
        n_pad=n,
        num_segments=1,
    )


def gather_src(data: np.ndarray, src: np.ndarray, fill, dtype) -> np.ndarray:
    """Masked source-map gather: ``out[i] = data[src[i]]`` where ``src >= 0``
    and ``fill`` at padding slots (``src < 0``).  The single re-pack idiom
    every refresh path shares (flat slabs, serial scan operands, the E
    operator, the fused layout)."""
    data = np.asarray(data)
    out = np.where(src >= 0, data[np.clip(src, 0, None)], fill)
    return out.astype(dtype, copy=False)


def pack_values(layout: PackedLayout, data: np.ndarray):
    """Re-pack the flat value buffers for new ``data`` of the same pattern —
    the numeric-refresh hot path: two vectorized gathers, O(nnz + padding),
    no analysis, no executor rebuild."""
    return (gather_src(data, layout.vals_src, 0.0, layout.vals_flat.dtype),
            gather_src(data, layout.diag_src, 1.0, layout.diag_flat.dtype))


# Mixed-precision storage dtypes (guard ``precision="mixed"``): bf16 for the
# large off-diagonal/panel stream, fp32 for the diagonal / inverted-diagonal
# buffer.  The diagonal stays fp32 because the refinement error-iteration
# matrix (A − Ã)Ã⁻¹ has the relative diagonal storage error on ITS diagonal
# — bf16 diagonals stall refinement near 4e-3/step while fp32 diagonals
# contract ~1e-3–1e-4/step; the diagonal is O(n) of O(nnz) bytes, so the
# saving lives in the off-diagonal stream either way.
MIXED_VALS_DTYPE = jnp.bfloat16
MIXED_DIAG_DTYPE = jnp.float32


def cast_value_buffers(values, *, vals_dtype=MIXED_VALS_DTYPE,
                       diag_dtype=MIXED_DIAG_DTYPE):
    """Lower a packed runtime value tuple to mixed-precision storage: the
    first buffer (off-diagonal / panel values — the O(nnz) stream) to
    ``vals_dtype``, every remaining buffer (diagonal, inverted diagonal
    blocks) to ``diag_dtype``.  Works for every permuted-layout executor —
    they all pass ``(offdiag_buffer, diag_buffer)`` 2-tuples and cast to the
    RHS dtype at solve time."""
    vals, *rest = values
    return (jnp.asarray(vals).astype(vals_dtype),
            *(jnp.asarray(r).astype(diag_dtype) for r in rest))


# --------------------------------------------------------------------------
# Permuted-space executors (pure JAX)
# --------------------------------------------------------------------------
def _slice_seg(flat, start, size):
    return jax.lax.slice_in_dim(flat, start, start + size)


def _plain_segment(x, bhat, seg, cols_flat, vf, df, gk):
    K, Rp = seg.K, seg.R_pad
    cols = _slice_seg(cols_flat, seg.col_off, K * Rp).reshape(K, Rp)
    vals = _slice_seg(vf, seg.val_off, K * Rp).reshape(K, Rp)
    diag = _slice_seg(df, seg.diag_off, Rp)
    s = _gather_sum(vals, cols, x, unroll_max_k=gk)
    bw = jax.lax.slice_in_dim(bhat, seg.off, seg.off + Rp)
    xl = (bw - s) / _coef(diag, x)
    return jax.lax.dynamic_update_slice_in_dim(x, xl, seg.off, 0)


def _chain_segment(x, bhat, seg, cols_flat, vf, df, gk):
    d, K, Rp = seg.depth, seg.K, seg.R_pad
    cols = _slice_seg(cols_flat, seg.col_off, d * K * Rp).reshape(d, K, Rp)
    vals = _slice_seg(vf, seg.val_off, d * K * Rp).reshape(d, K, Rp)
    diag = _slice_seg(df, seg.diag_off, d * Rp).reshape(d, Rp)
    sub = jnp.asarray(seg.sub_offs)

    def body(t, xc):
        s = _gather_sum(vals[t], cols[t], xc, unroll_max_k=gk)
        o = sub[t]
        bw = jax.lax.dynamic_slice_in_dim(bhat, o, Rp)
        xl = (bw - s) / _coef(diag[t], xc)
        return jax.lax.dynamic_update_slice_in_dim(xc, xl, o, 0)

    return jax.lax.fori_loop(0, d, body, x)


def _unrolled_segment(x, bhat, seg, layout, vf, df):
    """Tiny segment as generated scalar code — the paper's constant-embedded
    path, adapted to refresh: column *positions* stay literal constants, the
    values are scalar reads of the runtime buffer at literal offsets, so the
    unrolled program survives a value swap without re-tracing."""
    K, Rp, R = seg.K, seg.R_pad, seg.R
    cols = layout.cols_flat[seg.col_off: seg.col_off + K * Rp].reshape(K, Rp)
    nz = layout.vals_src[seg.val_off: seg.val_off + K * Rp].reshape(K, Rp) >= 0
    outs = []
    for r in range(R):
        s = bhat[seg.off + r]
        for k in range(K):
            if nz[k, r]:
                s = s - vf[seg.val_off + k * Rp + r] * x[int(cols[k, r])]
        outs.append(s / df[seg.diag_off + r])
    xl = jnp.stack(outs)
    return jax.lax.dynamic_update_slice_in_dim(x, xl, seg.off, 0)


def make_packed_levelset_solver(
    layout: PackedLayout,
    *,
    unroll_threshold: int = 0,
    gather_unroll_max_k: int = GATHER_UNROLL_MAX_K,
):
    """Permuted-space level-set executor.

    Returns ``solve(b, values)`` with ``values = (vals_flat, diag_flat)`` as
    runtime buffers (see module docstring).  ``b`` may be ``(n,)`` or
    ``(n, m)``; the permute/un-permute happens exactly once at the
    boundaries regardless of segment count.  Their ops carry the scope
    ``sptrsv.permute``, each segment's ``sptrsv.segment``
    (:mod:`repro.core.obs`)."""
    n, n_pad = layout.n, layout.n_pad
    cols_flat = jnp.asarray(layout.cols_flat)
    perm = jnp.asarray(layout.perm)
    pos = jnp.asarray(layout.pos)

    def solve(b: jnp.ndarray, values) -> jnp.ndarray:
        vals_flat, diag_flat = values
        dt = b.dtype
        vf = vals_flat.astype(dt)
        df = diag_flat.astype(dt)
        with jax.named_scope(obs.PERMUTE):
            bhat = b[perm]
            if n_pad > n:
                bhat = jnp.concatenate(
                    [bhat, jnp.zeros((n_pad - n,) + b.shape[1:], dt)])
        x = jnp.zeros((n_pad,) + b.shape[1:], dt)
        for seg in layout.segments:
            with jax.named_scope(obs.SEGMENT):
                if seg.kind == "chain":
                    x = _chain_segment(x, bhat, seg, cols_flat, vf, df,
                                       gather_unroll_max_k)
                elif seg.R <= unroll_threshold:
                    x = _unrolled_segment(x, bhat, seg, layout, vf, df)
                else:
                    x = _plain_segment(x, bhat, seg, cols_flat, vf, df,
                                       gather_unroll_max_k)
        with jax.named_scope(obs.PERMUTE):
            return x[pos]

    return solve


# --------------------------------------------------------------------------
# Blocked (supernodal) packed layout
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class PackedBlockSegment:
    """Geometry of one super-level inside the packed blocked buffers.

    The segment's real rows own permuted positions ``[off, off + R)``; its
    lane space is ``B * T`` block-major lanes, of which ``lane_idx`` are the
    real ones (the rest are padding).  ``val_off`` indexes the flat panel
    buffers (``K * B * T`` entries), ``dinv_off`` the flat dense-block
    buffers (``B * T * T`` entries)."""

    off: int
    R: int
    B: int
    T: int
    K: int
    val_off: int
    dinv_off: int
    lane_idx: np.ndarray      # (R,) int32


@dataclasses.dataclass(frozen=True)
class PackedBlockedLayout:
    """Permuted-space packed form of a
    :class:`~repro.core.coarsen.BlockSchedule`.

    Same contract as :class:`PackedLayout`: ``cols_flat`` holds permuted
    *positions*; ``vals_src`` (panel values) and ``diag_src`` (dense
    diagonal-block entries) map every packed value back into the target
    matrix's ``data`` array (−1 = padding / structural zero), so
    :func:`pack_blocked_values` re-packs both runtime buffers — including
    the batched block re-inversion — from new values alone.  ``pad_eye_flat``
    is the identity padding added before every inversion."""

    n: int
    nnz: int
    perm: np.ndarray
    pos: np.ndarray
    segments: tuple
    cols_flat: np.ndarray
    vals_flat: np.ndarray
    vals_src: np.ndarray
    dinv_flat: np.ndarray     # float64 inverted blocks, concatenated raveled
    diag_src: np.ndarray      # int64, aligned with dinv_flat
    pad_eye_flat: np.ndarray  # float64, aligned with dinv_flat

    def stats(self) -> PackedStats:
        item = self.vals_flat.itemsize
        pad = int((self.vals_src < 0).sum() + (self.diag_src < 0).sum())
        return PackedStats(
            permutation_applied=True,
            value_bytes=self.vals_flat.nbytes + self.dinv_flat.nbytes,
            index_bytes=self.cols_flat.nbytes,
            padded_value_bytes=pad * item,
            n_pad=self.n,
            num_segments=len(self.segments),
        )


def build_packed_blocked_layout(bsched) -> PackedBlockedLayout:
    """Lower a blocked schedule into permuted-space flat buffers: the
    blocked execution order (super-level by super-level, block-major)
    defines ``perm``; panel columns are remapped to positions once here."""
    n = bsched.n
    perm = bsched.perm()
    assert perm.size == n, (perm.size, n)
    pos = np.empty(n, dtype=np.int64)
    pos[perm] = np.arange(n, dtype=np.int64)
    pos32 = pos.astype(np.int32)

    segments = []
    cols_b, vals_b, vsrc_b, dinv_b, dsrc_b, eye_b = [], [], [], [], [], []
    off = voff = doff = 0
    dtype = (bsched.slabs[0].vals.dtype if bsched.slabs else np.float64)
    for slab in bsched.slabs:
        B, T, K, R = slab.B, slab.T, slab.K, slab.R
        lane_idx = np.nonzero(slab.lane_row < n)[0].astype(np.int32)
        segments.append(PackedBlockSegment(
            off=off, R=R, B=B, T=T, K=K, val_off=voff, dinv_off=doff,
            lane_idx=lane_idx))
        # padded panel lanes keep column 0 -> position pos[0]: its value is
        # 0 and x starts zero-filled, so the gather is a no-op everywhere
        cols_b.append(pos32[slab.cols].ravel())
        vals_b.append(slab.vals.ravel())
        vsrc_b.append(slab.val_src.ravel())
        dinv_b.append(slab.dinv.ravel())
        dsrc_b.append(slab.diag_src.ravel())
        eye_b.append(slab.pad_eye.ravel())
        off += R
        voff += K * B * T
        doff += B * T * T
    assert off == n, (off, n)

    def cat(blocks, dt):
        return (np.concatenate(blocks).astype(dt, copy=False) if blocks
                else np.zeros(0, dtype=dt))

    return PackedBlockedLayout(
        n=n, nnz=bsched.nnz, perm=perm, pos=pos, segments=tuple(segments),
        cols_flat=cat(cols_b, np.int32),
        vals_flat=cat(vals_b, dtype),
        vals_src=cat(vsrc_b, np.int64),
        dinv_flat=cat(dinv_b, np.float64),
        diag_src=cat(dsrc_b, np.int64),
        pad_eye_flat=cat(eye_b, np.float64),
    )


def pack_blocked_values(layout: PackedBlockedLayout, data: np.ndarray):
    """Re-pack the blocked runtime buffers for new ``data`` of the same
    pattern: one vectorized gather for the panel values, one gather +
    identity padding + batched ``np.linalg.inv`` (float64, host-side) for
    the dense diagonal blocks.  O(nnz + Σ B·T³) with no analysis and no
    executor re-trace — the compiled solve is reused outright."""
    vals = gather_src(data, layout.vals_src, 0.0, layout.vals_flat.dtype)
    dense = (gather_src(data, layout.diag_src, 0.0, np.float64)
             + layout.pad_eye_flat)
    dinv = np.empty_like(layout.dinv_flat)
    for seg in layout.segments:
        size = seg.B * seg.T * seg.T
        blk = dense[seg.dinv_off : seg.dinv_off + size].reshape(
            seg.B, seg.T, seg.T)
        try:
            inv = np.linalg.inv(blk)
        except np.linalg.LinAlgError:
            # A singular/non-finite diagonal block (zero pivot admitted via
            # refresh(validate=False)) must not abort the re-pack: invert
            # the healthy blocks, poison the broken ones with NaN so the
            # solve produces NaN rows a guarded solver's breakdown policy
            # can see and handle.
            inv = np.empty_like(blk)
            for i in range(blk.shape[0]):
                try:
                    inv[i] = np.linalg.inv(blk[i])
                except np.linalg.LinAlgError:
                    inv[i] = np.nan
        dinv[seg.dinv_off : seg.dinv_off + size] = inv.ravel()
    return jnp.asarray(vals), jnp.asarray(dinv)


def make_packed_blocked_solver(
    layout: PackedBlockedLayout,
    *,
    backend=None,
    kernel: str = "auto",
    gather_unroll_max_k: int = GATHER_UNROLL_MAX_K,
):
    """Permuted-space blocked (supernodal) executor.

    Returns ``solve(b, values)`` with ``values = (vals_flat, dinv_flat)`` as
    runtime buffers (from :func:`pack_blocked_values`).  Per super-level:
    one panel gather-sum, one batched dense diagonal-block apply
    (:func:`repro.kernels.trsm_block.ops.make_block_apply`), one contiguous
    ``dynamic_update_slice`` write.  ``b`` may be ``(n,)`` or ``(n, m)``."""
    from repro.kernels.trsm_block.ops import make_block_apply

    apply_blocks = make_block_apply(backend, kernel=kernel)
    n = layout.n
    cols_flat = jnp.asarray(layout.cols_flat)
    perm = jnp.asarray(layout.perm)
    pos = jnp.asarray(layout.pos)

    def solve(b: jnp.ndarray, values) -> jnp.ndarray:
        vals_flat, dinv_flat = values
        dt = b.dtype
        vf = vals_flat.astype(dt)
        dvf = dinv_flat.astype(dt)
        bhat = b[perm]
        x = jnp.zeros((n,) + b.shape[1:], dt)
        for seg in layout.segments:
            BT = seg.B * seg.T
            cols = _slice_seg(cols_flat, seg.val_off, seg.K * BT).reshape(
                seg.K, BT)
            vals = _slice_seg(vf, seg.val_off, seg.K * BT).reshape(
                seg.K, BT)
            s = _gather_sum(vals, cols, x, unroll_max_k=gather_unroll_max_k)
            bw = jax.lax.slice_in_dim(bhat, seg.off, seg.off + seg.R)
            lane = jnp.asarray(seg.lane_idx)
            rhs = jnp.zeros((BT,) + b.shape[1:], dt).at[lane].set(bw) - s
            dinv = _slice_seg(dvf, seg.dinv_off, BT * seg.T).reshape(
                seg.B, seg.T, seg.T)
            xb = apply_blocks(dinv, rhs.reshape((seg.B, seg.T) + b.shape[1:]))
            xl = xb.reshape((BT,) + b.shape[1:])[lane]
            x = jax.lax.dynamic_update_slice_in_dim(x, xl, seg.off, 0)
        return x[pos]

    return solve


def make_packed_serial_solver(L: CSRMatrix, *, upper: bool = False):
    """Serial ``lax.scan`` solver with the scan operands as runtime buffers.

    Returns ``(solve(b, values), values0, repack)`` — ``repack(new_data)``
    rebuilds ``values`` for new matrix values of the same pattern (the
    serial strategy has no permuted space to exploit, but refresh must not
    re-trace its scan either)."""
    cols, vals, diag, val_src, diag_src, order = serial_arrays(L, upper=upper)
    cols_d = jnp.asarray(cols[order])
    idx = jnp.asarray(order)

    def repack(data: np.ndarray):
        v = gather_src(data, val_src, 0.0, vals.dtype)
        d = np.asarray(data)[diag_src].astype(diag.dtype, copy=False)
        return jnp.asarray(v[order]), jnp.asarray(d[order])

    values0 = (jnp.asarray(vals[order]), jnp.asarray(diag[order]))

    def solve(b: jnp.ndarray, values) -> jnp.ndarray:
        vals_o, diag_o = values
        dt = b.dtype
        vals_l = vals_o.astype(dt)
        diag_l = diag_o.astype(dt)

        def body(x, inp):
            c, v, d, bi, i = inp
            s = jnp.sum(_coef(v, x) * x[c], axis=0)
            x = x.at[i].set((bi - s) / d)
            return x, ()

        x0 = jnp.zeros(b.shape, dtype=dt)
        x, _ = jax.lax.scan(body, x0, (cols_d, vals_l, diag_l, b[idx], idx))
        return x

    return solve, values0, repack


def make_packed_rhs_transform(res: RewriteResult):
    """``b' = E b`` with the ELL values as a runtime buffer.

    Returns ``(transform(b, e_vals), e_vals0, repack)`` where
    ``repack(e_data)`` re-packs new E values (from
    :func:`repro.core.rewrite.replay_rewrite_values`) into the buffer.
    When E is the identity (no rewrites survived the budgets) returns
    ``(None, None, None)`` — a no-op SpMV would still cost a dispatch and a
    packed buffer per solve."""
    if res.stats.e_nnz_offdiag == 0:
        return None, None, None
    ell = build_ell(res.E)
    cols = jnp.asarray(ell.cols)
    src = ell.val_src

    def transform(b: jnp.ndarray, e_vals: jnp.ndarray) -> jnp.ndarray:
        return _gather_sum(e_vals.astype(b.dtype), cols, b)

    def repack(e_data: np.ndarray):
        return jnp.asarray(gather_src(e_data, src, 0.0, ell.vals.dtype))

    return transform, jnp.asarray(ell.vals), repack
