"""Backend-dispatched wrapper: pack a Schedule into the fused level-order
layout and solve it — one sequential-grid ``pallas_call`` on the TPU
backend, a level-scheduled launch walk of the same layout on the GPU
backend (see the lowering modules).

Direction-agnostic: backward (transpose) schedules permute rows by *reverse*
level order, so all dependency positions still precede their consumers in
the grid walk; padding slots gather val-0 entries against the zero-initialized
VMEM scratch and contribute nothing."""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax.numpy as jnp
import numpy as np

from repro.core.codegen import Schedule
from repro.kernels.backend import refuse_mosaic_gather, resolve_backend

from . import lowering_gpu, lowering_tpu

__all__ = ["FusedLayout", "build_layout", "make_solver", "make_packed_solver",
           "select_lowering"]


def select_lowering(backend=None):
    """Lowering module for a backend spec — the single dispatch point the
    backend-matrix CI job asserts on."""
    bk = resolve_backend(backend)
    return lowering_gpu if bk.platform == "gpu" else lowering_tpu


@dataclasses.dataclass(frozen=True)
class FusedLayout:
    """Level-order permuted ELL layout with chunk-aligned level boundaries.

    ``perm_rows[p]`` = original row at position p (pad -> n).
    ``pos[i]``       = position of original row i.
    ``cols``         (K, n_pad) dependency *positions* (pad: points at a
                     pad position whose value is always 0).
    ``val_src``/``diag_src`` map packed values back to the source matrix's
    ``data`` indices (-1 padding) — the value-only refresh maps.
    ``spans``        chunk-aligned ``(offset, padded_rows)`` of each
                     wavefront — the launch boundaries of the GPU
                     (level-scheduled) lowering; the TPU grid walk ignores
                     them.
    """

    n: int
    n_pad: int
    chunk: int
    K: int
    perm_rows: np.ndarray
    pos: np.ndarray
    cols: np.ndarray
    vals: np.ndarray
    diag: np.ndarray
    val_src: Optional[np.ndarray] = None
    diag_src: Optional[np.ndarray] = None
    spans: tuple = ()

    @property
    def padded_flops(self) -> int:
        return 2 * self.K * self.n_pad + self.n_pad


def build_layout(schedule: Schedule, chunk: int = 512) -> FusedLayout:
    n = schedule.n
    # A coarsened slab's sub-slabs are NOT mutually independent, so the
    # chunk walk must keep every wavefront in its own chunk-aligned span —
    # expand chains back to their sub-slabs (the fused solve is already a
    # single segment; coarsening has nothing left to merge here).
    slabs = [sub for slab in schedule.slabs for sub in slab.sub_slabs()]
    K = max(s.K for s in slabs)
    # positions: wavefronts in order, each padded to a chunk multiple
    spans = []
    off = 0
    for slab in slabs:
        r_pad = int(np.ceil(slab.R / chunk) * chunk)
        spans.append((off, r_pad))
        off += r_pad
    n_pad = off
    perm_rows = np.full((n_pad,), n, dtype=np.int32)
    pos = np.zeros((n + 1,), dtype=np.int64)
    for (o, _), slab in zip(spans, slabs):
        perm_rows[o : o + slab.R] = slab.rows
        pos[slab.rows] = np.arange(o, o + slab.R)
    pos[n] = n_pad - 1  # scratch row maps to the last pad position

    val_dtype = slabs[0].vals.dtype
    cols = np.zeros((K, n_pad), dtype=np.int32)
    vals = np.zeros((K, n_pad), dtype=val_dtype)
    diag = np.ones((n_pad,), dtype=val_dtype)
    val_src = np.full((K, n_pad), -1, dtype=np.int64)
    diag_src = np.full((n_pad,), -1, dtype=np.int64)
    for (o, _), slab in zip(spans, slabs):
        k = slab.K
        # remap dependency columns (original row ids) to positions
        cols[:k, o : o + slab.R] = pos[slab.cols]
        vals[:k, o : o + slab.R] = slab.vals
        diag[o : o + slab.R] = slab.diag
        if slab.val_src is not None:
            val_src[:k, o : o + slab.R] = slab.val_src
            diag_src[o : o + slab.R] = slab.diag_src
    return FusedLayout(
        n=n, n_pad=n_pad, chunk=chunk, K=K,
        perm_rows=perm_rows, pos=pos, cols=cols, vals=vals, diag=diag,
        val_src=val_src, diag_src=diag_src,
        spans=tuple((int(o), int(rp)) for o, rp in spans),
    )


def make_solver(
    schedule: Schedule,
    *,
    backend=None,
    interpret: Optional[bool] = None,
    chunk: int = 512,
) -> Callable[[jnp.ndarray], jnp.ndarray]:
    bk = resolve_backend(backend, interpret=interpret)
    refuse_mosaic_gather(bk, "sptrsv_fused")
    low = select_lowering(bk)
    lay = build_layout(schedule, chunk)
    perm_rows = jnp.asarray(lay.perm_rows)
    pos = jnp.asarray(lay.pos[: lay.n])
    cols = jnp.asarray(lay.cols)
    vals = jnp.asarray(lay.vals)
    diag = jnp.asarray(lay.diag)

    kw = {"spans": lay.spans} if bk.platform == "gpu" else {}

    def solve(b: jnp.ndarray) -> jnp.ndarray:
        """b: (n,) or (n, m) — one fused dispatch either way (TPU: one
        sequential-grid kernel; GPU: one launch per wavefront span)."""
        dt = b.dtype
        kern = low.fused_solve_batched if b.ndim == 2 else low.fused_solve
        b_ext = jnp.concatenate([b, jnp.zeros((1,) + b.shape[1:], dt)])
        bl_perm = b_ext[perm_rows]  # pad rows -> b_ext[n] = 0
        xp = kern(
            bl_perm, cols, vals.astype(dt), diag.astype(dt),
            chunk=lay.chunk, interpret=bk.interpret, **kw,
        )
        return xp[pos]

    return solve


def make_packed_solver(
    schedule: Schedule,
    *,
    backend=None,
    interpret: Optional[bool] = None,
    chunk: int = 512,
):
    """Refresh-capable fused solver: identical kernel and layout to
    :func:`make_solver` (the fused kernel already executes in permuted
    space), but the packed ``vals``/``diag`` buffers ride as runtime
    arguments so a value-only refresh swaps them without re-tracing.

    Returns ``(solve(b, values), values0, repack, layout)``."""
    bk = resolve_backend(backend, interpret=interpret)
    refuse_mosaic_gather(bk, "sptrsv_fused")
    low = select_lowering(bk)
    lay = build_layout(schedule, chunk)
    perm_rows = jnp.asarray(lay.perm_rows)
    pos = jnp.asarray(lay.pos[: lay.n])
    cols = jnp.asarray(lay.cols)
    values0 = (jnp.asarray(lay.vals), jnp.asarray(lay.diag))
    vsrc, dsrc = lay.val_src, lay.diag_src

    def repack(target_data):
        from repro.core.packed import gather_src

        return (jnp.asarray(gather_src(target_data, vsrc, 0.0, lay.vals.dtype)),
                jnp.asarray(gather_src(target_data, dsrc, 1.0, lay.diag.dtype)))

    kw = {"spans": lay.spans} if bk.platform == "gpu" else {}

    def solve(b: jnp.ndarray, values) -> jnp.ndarray:
        """b: (n,) or (n, m) — one fused dispatch either way."""
        vals, diag = values
        dt = b.dtype
        kern = low.fused_solve_batched if b.ndim == 2 else low.fused_solve
        b_ext = jnp.concatenate([b, jnp.zeros((1,) + b.shape[1:], dt)])
        bl_perm = b_ext[perm_rows]  # pad rows -> b_ext[n] = 0
        xp = kern(
            bl_perm, cols, vals.astype(dt), diag.astype(dt),
            chunk=lay.chunk, interpret=bk.interpret, **kw,
        )
        return xp[pos]

    return solve, values0, repack, lay
