"""Distributed SpTRSV over a mesh axis (beyond-paper, required at scale).

Rows of each segment are sharded across the ``data`` axis with ``shard_map``.
After a segment solves its rows, the newly computed ``x`` entries are
exchanged.  On a pod, **each segment boundary is one collective** — the
direct analogue of the paper's per-level CPU barrier.  Equation rewriting
reduces the number of levels and schedule coarsening merges the survivors,
so both shrink the collective count; §Perf of EXPERIMENTS.md measures
exactly this.

Two exchange strategies (hillclimb pair):

* ``psum``       — naive: every device scatters its solved rows into an
                   n-vector of zeros and a full ``psum`` combines them.
                   Bytes/segment = O(n).  Paper-faithful port of "barrier".
* ``all_gather`` — each device contributes only its R/ndev solved values;
                   bytes/segment = O(R_segment).  The optimized schedule.

Row ids are static host-known constants, so only solved *values* ever move
on the wire: the full row order each device needs after the exchange is
precomputed host-side in :func:`shard_schedule` (a ring ``all_gather(tiled)``
of contiguous row shards reproduces the slab's own row array), and each
device slices its shard out of the replicated constant with
``lax.axis_index`` — there is no runtime collective over index arrays.

Coarsened slabs (``depth > 1``, :mod:`repro.core.coarsen`) execute
**replicated**: every device redundantly computes the whole intra-slab chain
(thin levels are latency-bound, so the redundant FLOPs are noise) and the
solution stays consistent on all devices with **zero** collectives for those
slabs — a run of thin levels that used to cost one collective per level now
costs none.

Transpose solves (``SpTRSV.build(L, transpose=True, strategy="distributed")``)
flow through unchanged: a backward :class:`Schedule` packs columns of L over
the reverse level sets, and sharding/collectives are schedule-agnostic —
the collective count equals the number of *sharded backward segments*.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from .codegen import GATHER_UNROLL_MAX_K, Schedule, _gather_sum, stack_sub_slabs
from .packed import PackedLayout, build_packed_layout, pack_values

__all__ = [
    "DistributedSchedule",
    "shard_schedule",
    "make_distributed_solver",
    "build_packed_dist_layout",
    "make_packed_distributed_solver",
]


@dataclasses.dataclass(frozen=True)
class DistributedSchedule:
    """Per-segment slabs.

    Sharded segments are padded so the row dimension splits evenly over the
    mesh axis; padding rows are no-ops (col 0 / val 0 / diag 1) writing to
    the scratch slot ``n`` of the x vector (length n+1).  Replicated
    segments (coarsened chains) hold the uniform *stacked* sub-slab arrays
    of :func:`repro.core.codegen.stack_sub_slabs` — ``rows (d, Rmax)``,
    ``cols/vals (d, K, Rmax)``, ``diag (d, Rmax)`` — executed as one
    ``fori_loop`` per chain, same as the levelset/pallas executors, so the
    traced program holds one body per chain rather than one per wavefront.
    ``rows`` of a sharded segment is the **full** row order — the host-side
    precomputed gather order; devices never exchange indices.
    """

    n: int
    ndev: int
    rows: List[np.ndarray]   # (R_pad,) sharded / (d, Rmax) replicated; pad -> n
    cols: List[np.ndarray]   # (K, R_pad) sharded / (d, K, Rmax) replicated
    vals: List[np.ndarray]
    diag: List[np.ndarray]
    replicated: List[bool]   # True: executed redundantly, no collective

    @property
    def num_levels(self) -> int:
        return len(self.rows)

    @property
    def num_collectives(self) -> int:
        """Collectives per solve — sharded segments only (replicated chains
        exchange nothing; row ids never move)."""
        return sum(not r for r in self.replicated)

    def collective_bytes(self, itemsize: int = 4, strategy: str = "all_gather",
                         batch: int = 1) -> int:
        """Predicted on-wire bytes per solve (per device, ring all-gather):
        the §Roofline collective term for the distributed solver.  Counts
        what actually moves: solved values of *sharded* segments only —
        replicated segments exchange nothing, and row ids are static
        host-side constants (they used to ride an extra runtime
        ``all_gather`` per level).  A batched solve multiplies the payload
        by ``batch`` but keeps the collective *count* fixed — latency-bound
        thin levels amortize over columns."""
        if strategy == "psum":
            return self.num_collectives * 2 * (self.n + 1) * batch * itemsize
        return sum(r.size * batch * itemsize
                   for r, rep in zip(self.rows, self.replicated) if not rep)


def _pad_to(x: np.ndarray, size: int, fill) -> np.ndarray:
    pad = size - x.shape[-1]
    if pad == 0:
        return x
    width = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
    return np.pad(x, width, constant_values=fill)


def shard_schedule(schedule: Schedule, ndev: int) -> DistributedSchedule:
    rows, cols, vals, diag, replicated = [], [], [], [], []
    for slab in schedule.slabs:
        if slab.depth > 1:
            # coarsened chain: replicated execution (stacked uniform
            # sub-slabs, fori_loop'd per device), no exchange
            r_s, c_s, v_s, d_s = stack_sub_slabs(slab, schedule.n)
            rows.append(r_s)
            cols.append(c_s)
            vals.append(v_s)
            diag.append(d_s)
            replicated.append(True)
            continue
        rpad = int(np.ceil(slab.R / ndev) * ndev)
        rows.append(_pad_to(slab.rows.astype(np.int32), rpad, schedule.n))
        cols.append(_pad_to(slab.cols, rpad, 0))
        vals.append(_pad_to(slab.vals, rpad, 0.0))
        diag.append(_pad_to(slab.diag, rpad, 1.0))
        replicated.append(False)
    return DistributedSchedule(
        n=schedule.n, ndev=ndev, rows=rows, cols=cols, vals=vals, diag=diag,
        replicated=replicated,
    )


def make_distributed_solver(
    dsched: DistributedSchedule,
    mesh: Mesh,
    axis: str = "data",
    *,
    strategy: str = "all_gather",
) -> Callable[[jnp.ndarray], jnp.ndarray]:
    """Build a jit-able distributed level-set solve(b) over ``mesh[axis]``.

    x is replicated (n+1, scratch slot last); per sharded segment each
    device solves an R/ndev shard of rows and the solved values are
    exchanged — values only: the device's row shard is a static slice
    (``lax.axis_index``) of the replicated host-precomputed row order, and
    the post-exchange scatter uses that same constant, so the index
    ``all_gather`` that used to run every level of every solve is gone.
    Replicated (coarsened) segments run their whole chain on every device
    with no collective at all.

    ``b`` may be ``(n,)`` or batched ``(n, m)``: the batch axis rides through
    the shard_map region unsharded (columns are independent systems), so the
    per-segment collective moves ``R * m`` values instead of ``R`` — the
    collective *count* (the paper's barrier analogue) is unchanged while the
    per-solve payload amortizes over the batch.
    """
    assert strategy in ("all_gather", "psum")
    n = dsched.n
    ndev = dsched.ndev
    # Per-segment constants, device-side.  Sharded segments split their slabs
    # over the axis; rows stay replicated everywhere (static gather order).
    cols_d = [jnp.asarray(c) for c in dsched.cols]
    vals_d = [jnp.asarray(v) for v in dsched.vals]
    diag_d = [jnp.asarray(d) for d in dsched.diag]
    rows_d = [jnp.asarray(r) for r in dsched.rows]
    rep = list(dsched.replicated)

    in_specs = (
        P(),  # b (replicated)
        [P() if r else P(None, axis) for r in rep],  # cols (K, R)
        [P() if r else P(None, axis) for r in rep],  # vals
        [P() if r else P(axis) for r in rep],        # diag
        [P()] * dsched.num_levels,                   # rows: always replicated
    )

    def _solve(b, cols, vals, diag, rows):
        dt = b.dtype
        batched = b.ndim == 2
        bx = jnp.concatenate([b, jnp.zeros((1,) + b.shape[1:], dt)])  # scratch
        x = jnp.zeros((n + 1,) + b.shape[1:], dt)
        me = jax.lax.axis_index(axis)
        for lv in range(len(cols)):
            v = vals[lv].astype(dt)
            d = diag[lv].astype(dt)
            if rep[lv]:
                # coarsened chain, replicated on every device: one fori_loop
                # over the stacked sub-slabs (deterministic => consistent x,
                # no exchange; pad rows write the scratch slot n) — the
                # traced program holds one body per chain, not one per level
                def chain_body(t, xc, _r=rows[lv], _c=cols[lv], _v=v, _d=d):
                    d_t = _d[t][:, None] if batched else _d[t]
                    s = _gather_sum(_v[t], _c[t], xc)
                    return xc.at[_r[t]].set((bx[_r[t]] - s) / d_t)

                x = jax.lax.fori_loop(0, rows[lv].shape[0], chain_body, x)
                x = x.at[n].set(0.0)
                continue
            if batched:
                d = d[:, None]
            shard = rows[lv].shape[0] // ndev
            rows_me = jax.lax.dynamic_slice_in_dim(rows[lv], me * shard, shard)
            s = _gather_sum(v, cols[lv], x)             # (R/ndev[, m])
            xl = (bx[rows_me] - s) / d
            if strategy == "all_gather":
                # values only; the gathered row order is the replicated
                # constant rows[lv] (host-precomputed)
                xg = jax.lax.all_gather(xl, axis, tiled=True)        # (R[, m])
                x = x.at[rows[lv]].set(xg)
            else:  # psum: full-vector exchange — the naive barrier port
                contrib = jnp.zeros_like(x).at[rows_me].set(xl)
                x = x + jax.lax.psum(contrib, axis)
            x = x.at[n].set(0.0)  # clear pad-row scratch writes
        return x[:n]

    fn = shard_map(
        _solve,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=P(),
        check_vma=False,
    )

    def solve(b: jnp.ndarray) -> jnp.ndarray:
        return fn(b, cols_d, vals_d, diag_d, rows_d)

    return solve


# ==========================================================================
# Permuted-space packed distributed solver (refresh-capable)
# ==========================================================================
def build_packed_dist_layout(schedule: Schedule, ndev: int) -> PackedLayout:
    """Packed layout whose sharded segments are row-padded to a multiple of
    the mesh axis size (chains execute replicated and need no alignment)."""
    return build_packed_layout(
        schedule,
        pad_rows=lambda r: int(np.ceil(r / ndev) * ndev),
        pad_chain_rows=lambda r: r,
    )


def make_packed_distributed_solver(
    layout: PackedLayout,
    mesh: Mesh,
    axis: str = "data",
    *,
    strategy: str = "all_gather",
    gather_unroll_max_k: int = GATHER_UNROLL_MAX_K,
):
    """Permuted-space distributed solve over ``mesh[axis]``.

    Identical exchange structure to :func:`make_distributed_solver` — one
    value ``all_gather`` (or ``psum``) per *sharded* segment, replicated
    chains exchange nothing — but executed in permuted space: ``b`` is
    permuted once on entry, each device solves a contiguous shard of its
    segment's positions, and the gathered window lands with one
    ``dynamic_update_slice`` at a static offset (the per-segment row-id
    scatter and its replicated row-order constants are gone entirely).

    Returns ``(solve(b, values), values0, repack)``: the per-segment value
    arrays ride as runtime arguments, so ``SpTRSV.refresh`` swaps them
    (via ``repack(new_target_data)``) without re-tracing the shard_map."""
    assert strategy in ("all_gather", "psum")
    n, n_pad = layout.n, layout.n_pad
    ndev = int(np.prod([mesh.shape[a] for a in (axis,)]))
    segs = layout.segments

    def _seg_slices(flat, kind):
        """Per-segment views of one flat buffer, honoring that buffer's own
        offset field (``val_off``/``col_off``/``diag_off``)."""
        out = []
        for s in segs:
            if kind == "diag":
                a = flat[s.diag_off: s.diag_off + s.diag_size]
                shape = (s.depth, s.R_pad) if s.kind == "chain" else (s.R_pad,)
            else:
                off = s.val_off if kind == "val" else s.col_off
                a = flat[off: off + s.val_size]
                shape = ((s.depth, s.K, s.R_pad) if s.kind == "chain"
                         else (s.K, s.R_pad))
            out.append(a.reshape(shape))
        return out

    def _seg_arrays(vals_flat, diag_flat):
        return (_seg_slices(vals_flat, "val"), _seg_slices(diag_flat, "diag"))

    rep = [s.kind == "chain" for s in segs]
    slab_specs = tuple(P() if r else P(None, axis) for r in rep)
    diag_specs = tuple(P() if r else P(axis) for r in rep)
    in_specs = (
        P(),                                              # b (replicated)
        P(),                                              # perm
        P(),                                              # pos
        slab_specs,                                       # vals
        diag_specs,                                       # diag
        slab_specs,                                       # cols (positions)
    )

    def _place(arrays, specs):
        # buffers live where the shard_map reads them: each device holds
        # its shard (replicated chains: a copy), never only devices[0]
        return tuple(jax.device_put(a, NamedSharding(mesh, sp))
                     for a, sp in zip(arrays, specs))

    def _place_values(vals_flat, diag_flat):
        vs, ds = _seg_arrays(vals_flat, diag_flat)
        return _place(vs, slab_specs), _place(ds, diag_specs)

    values0 = _place_values(layout.vals_flat, layout.diag_flat)
    cols_d = _place(_seg_slices(layout.cols_flat, "col"), slab_specs)
    perm_d = jnp.asarray(layout.perm)
    pos_d = jnp.asarray(layout.pos)

    def repack(target_data: np.ndarray):
        return _place_values(*pack_values(layout, target_data))

    def _solve(b, perm, pos, vals_t, diag_t, cols_t):
        dt = b.dtype
        batched = b.ndim == 2
        bhat = b[perm]
        if n_pad > n:
            bhat = jnp.concatenate(
                [bhat, jnp.zeros((n_pad - n,) + b.shape[1:], dt)])
        x = jnp.zeros((n_pad,) + b.shape[1:], dt)
        me = jax.lax.axis_index(axis)
        for i, seg in enumerate(segs):
            v = vals_t[i].astype(dt)
            d = diag_t[i].astype(dt)
            c = cols_t[i]
            if rep[i]:
                # coarsened chain, replicated on every device: deterministic
                # => consistent x, zero collectives (pad lanes write forward
                # into positions their owners overwrite before any read)
                sub = jnp.asarray(seg.sub_offs)
                Rp = seg.R_pad

                def chain_body(t, xc, _c=c, _v=v, _d=d, _sub=sub, _Rp=Rp):
                    s = _gather_sum(_v[t], _c[t], xc,
                                    unroll_max_k=gather_unroll_max_k)
                    o = _sub[t]
                    bw = jax.lax.dynamic_slice_in_dim(bhat, o, _Rp)
                    dd = _d[t][:, None] if batched else _d[t]
                    xl = (bw - s) / dd
                    return jax.lax.dynamic_update_slice_in_dim(xc, xl, o, 0)

                x = jax.lax.fori_loop(0, seg.depth, chain_body, x)
                continue
            shard = seg.R_pad // ndev
            if batched:
                d = d[:, None]
            s = _gather_sum(v, c, x, unroll_max_k=gather_unroll_max_k)
            bw = jax.lax.dynamic_slice_in_dim(bhat, seg.off + me * shard, shard)
            xl = (bw - s) / d
            if strategy == "all_gather":
                # values only, in position order — the gathered window IS
                # the segment's contiguous permuted-space slice
                win = jax.lax.all_gather(xl, axis, tiled=True)  # (R_pad[, m])
            else:  # psum: full-vector exchange — the naive barrier port
                lane = me * shard + jnp.arange(shard)
                mask = lane < seg.R
                xl = jnp.where(mask[:, None] if batched else mask, xl, 0)
                contrib = jnp.zeros_like(x)
                contrib = jax.lax.dynamic_update_slice_in_dim(
                    contrib, xl, seg.off + me * shard, 0)
                summed = jax.lax.psum(contrib, axis)
                win = jax.lax.slice_in_dim(
                    summed, seg.off, seg.off + seg.R_pad)
            x = jax.lax.dynamic_update_slice_in_dim(x, win, seg.off, 0)
        return x[pos]

    fn = shard_map(
        _solve,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=P(),
        check_vma=False,
    )

    def solve(b: jnp.ndarray, values) -> jnp.ndarray:
        vals_t, diag_t = values
        return fn(b, perm_d, pos_d, vals_t, diag_t, cols_d)

    return solve, values0, repack
