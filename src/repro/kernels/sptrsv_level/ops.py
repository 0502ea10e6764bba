"""Backend-dispatched wrapper: whole-matrix level-set solve using the level
kernel.

``make_solver(schedule, backend=...)`` packs the schedule once (the packing
is lowering-agnostic) and dispatches each segment to the selected backend's
level kernel — TPU Mosaic (:mod:`.lowering_tpu`), pallas-triton
(:mod:`.lowering_gpu`), or either under the pallas interpreter
(``backend="interpret"`` / ``"interpret:gpu"``).

Direction-agnostic: a backward (transpose) :class:`Schedule` — column-packed
slabs over reverse level sets — runs through the same kernels; nothing here
assumes which triangle the slabs came from.

Coarsened schedules (slabs with ``depth > 1``, :mod:`repro.core.coarsen`)
execute the intra-slab chain as ONE ``fori_loop`` whose body launches the
level kernel on a uniform stacked sub-slab — the XLA program holds one
kernel call per *super*-level instead of one per level, so program size and
trace/compile time stop scaling with the level count."""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.codegen import Schedule, stack_sub_slabs
from repro.core.packed import build_packed_layout, pack_values
from repro.kernels.backend import refuse_mosaic_gather, resolve_backend

from . import lowering_gpu, lowering_tpu

__all__ = ["make_solver", "make_packed_solver", "select_lowering"]


def select_lowering(backend=None):
    """Lowering module for a backend spec — the single dispatch point the
    backend-matrix CI job asserts on."""
    bk = resolve_backend(backend)
    return lowering_gpu if bk.platform == "gpu" else lowering_tpu


def _ceil_to(v: int, m: int) -> int:
    return int(np.ceil(v / m) * m) if v else m


def make_solver(
    schedule: Schedule,
    *,
    backend=None,
    interpret: Optional[bool] = None,
    block_rows: int = 512,
) -> Callable[[jnp.ndarray], jnp.ndarray]:
    """Build solve(b) that runs one Pallas kernel per segment (one per level,
    or one per coarsened chain via ``fori_loop``) on the given backend.
    ``interpret`` is the deprecated boolean alias (see
    :func:`repro.kernels.backend.resolve_backend`)."""
    bk = resolve_backend(backend, interpret=interpret)
    refuse_mosaic_gather(bk, "sptrsv_level")
    low = select_lowering(bk)
    n = schedule.n
    n_pad = _ceil_to(n + 1, 128)
    packed = []
    for slab in schedule.slabs:
        if slab.depth > 1:
            # chain: stack sub-slabs to a uniform (d, K, R_pad) block so one
            # fori_loop'd kernel call covers the whole segment
            rows_s, cols_s, vals_s, diag_s = stack_sub_slabs(slab, n)
            rmax = rows_s.shape[1]
            R_pad = _ceil_to(rmax, block_rows if rmax > block_rows // 4 else 128)
            br = min(block_rows, R_pad)
            d = slab.depth
            rows = np.full((d, R_pad), n, dtype=np.int32)
            rows[:, :rmax] = rows_s
            cols = np.zeros((d, slab.K, R_pad), np.int32)
            cols[:, :, :rmax] = cols_s
            vals = np.zeros((d, slab.K, R_pad), slab.vals.dtype)
            vals[:, :, :rmax] = vals_s
            diag = np.ones((d, R_pad), slab.diag.dtype)
            diag[:, :rmax] = diag_s
        else:
            R_pad = _ceil_to(slab.R, block_rows if slab.R > block_rows // 4 else 128)
            br = min(block_rows, R_pad)
            rows = np.full((R_pad,), n, dtype=np.int32)
            rows[: slab.R] = slab.rows
            cols = np.zeros((slab.K, R_pad), np.int32)
            cols[:, : slab.R] = slab.cols
            # keep the matrix dtype — hard-coding f32 here would silently
            # truncate f64 factors at pack time
            vals = np.zeros((slab.K, R_pad), slab.vals.dtype)
            vals[:, : slab.R] = slab.vals
            diag = np.ones((R_pad,), slab.diag.dtype)
            diag[: slab.R] = slab.diag
        packed.append(
            (
                slab.depth,
                jnp.asarray(rows),
                jnp.asarray(cols),
                jnp.asarray(vals),
                jnp.asarray(diag),
                br,
            )
        )

    def solve(b: jnp.ndarray) -> jnp.ndarray:
        """b: (n,) or (n, m) — batched RHS solve all columns in one pass."""
        dt = b.dtype
        kern = (low.level_solve_blocks_batched if b.ndim == 2
                else low.level_solve_blocks)
        b_ext = jnp.concatenate([b, jnp.zeros((1,) + b.shape[1:], dt)])
        x = jnp.zeros((n_pad,) + b.shape[1:], dt)

        def step(x, rows, cols, vals, diag, br):
            bl = b_ext[jnp.minimum(rows, n)]
            xl = kern(
                x, bl, cols, vals.astype(dt), diag.astype(dt),
                block_rows=br, interpret=bk.interpret,
            )
            x = x.at[rows].set(xl)
            return x.at[n].set(0.0)  # pad rows target the scratch slot

        for depth, rows, cols, vals, diag, br in packed:
            if depth == 1:
                x = step(x, rows, cols, vals, diag, br)
            else:
                x = jax.lax.fori_loop(
                    0, depth,
                    lambda t, xc, _r=rows, _c=cols, _v=vals, _d=diag, _br=br:
                        step(xc, _r[t], _c[t], _v[t], _d[t], _br),
                    x,
                )
        return x[:n]

    return solve


def make_packed_solver(
    schedule: Schedule,
    *,
    backend=None,
    interpret: Optional[bool] = None,
    block_rows: int = 512,
):
    """Permuted-space packed variant: one kernel call per segment, but the
    level's solution lands with a contiguous ``dynamic_update_slice`` at a
    static offset instead of a row-id scatter, ``b`` is permuted once at
    entry, and the slab values stream from one flat runtime buffer (so
    ``SpTRSV.refresh`` swaps values without re-tracing any kernel).

    Returns ``(solve(b, values), values0, repack, layout)``."""
    bk = resolve_backend(backend, interpret=interpret)
    refuse_mosaic_gather(bk, "sptrsv_level")
    low = select_lowering(bk)

    def _pad(r):
        return _ceil_to(r, block_rows if r > block_rows // 4 else 128)

    layout = build_packed_layout(
        schedule, pad_rows=_pad, pad_chain_rows=_pad,
        block_rows_for=lambda rp: min(block_rows, rp))
    n, n_pad = layout.n, layout.n_pad
    n_x = _ceil_to(n_pad, 128)
    cols_flat = jnp.asarray(layout.cols_flat)
    perm = jnp.asarray(layout.perm)
    pos = jnp.asarray(layout.pos)
    values0 = (jnp.asarray(layout.vals_flat), jnp.asarray(layout.diag_flat))

    def repack(target_data):
        vf, df = pack_values(layout, target_data)
        return jnp.asarray(vf), jnp.asarray(df)

    def solve(b: jnp.ndarray, values) -> jnp.ndarray:
        """b: (n,) or (n, m) — batched RHS solve all columns in one pass."""
        vals_flat, diag_flat = values
        dt = b.dtype
        vf = vals_flat.astype(dt)
        df = diag_flat.astype(dt)
        kern = (low.level_solve_blocks_batched if b.ndim == 2
                else low.level_solve_blocks)
        bhat = b[perm]
        if n_pad > n:
            bhat = jnp.concatenate(
                [bhat, jnp.zeros((n_pad - n,) + b.shape[1:], dt)])
        x = jnp.zeros((n_x,) + b.shape[1:], dt)
        for seg in layout.segments:
            K, Rp, br = seg.K, seg.R_pad, seg.block_rows
            if seg.kind == "chain":
                d = seg.depth
                cols_c = jax.lax.slice_in_dim(
                    cols_flat, seg.col_off, seg.col_off + d * K * Rp
                ).reshape(d, K, Rp)
                vals_c = jax.lax.slice_in_dim(
                    vf, seg.val_off, seg.val_off + d * K * Rp
                ).reshape(d, K, Rp)
                diag_c = jax.lax.slice_in_dim(
                    df, seg.diag_off, seg.diag_off + d * Rp).reshape(d, Rp)
                sub = jnp.asarray(seg.sub_offs)

                def body(t, xc, _c=cols_c, _v=vals_c, _d=diag_c, _sub=sub,
                         _Rp=Rp, _br=br):
                    o = _sub[t]
                    bw = jax.lax.dynamic_slice_in_dim(bhat, o, _Rp)
                    xl = kern(xc, bw, _c[t], _v[t], _d[t],
                              block_rows=_br, interpret=bk.interpret)
                    return jax.lax.dynamic_update_slice_in_dim(xc, xl, o, 0)

                x = jax.lax.fori_loop(0, d, body, x)
            else:
                cols_s = jax.lax.slice_in_dim(
                    cols_flat, seg.col_off, seg.col_off + K * Rp).reshape(K, Rp)
                vals_s = jax.lax.slice_in_dim(
                    vf, seg.val_off, seg.val_off + K * Rp).reshape(K, Rp)
                diag_s = jax.lax.slice_in_dim(
                    df, seg.diag_off, seg.diag_off + Rp)
                bw = jax.lax.slice_in_dim(bhat, seg.off, seg.off + Rp)
                xl = kern(x, bw, cols_s, vals_s, diag_s,
                          block_rows=br, interpret=bk.interpret)
                x = jax.lax.dynamic_update_slice_in_dim(x, xl, seg.off, 0)
        return x[pos]

    return solve, values0, repack, layout
