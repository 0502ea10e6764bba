"""GPU (pallas-triton) lowering of the fused SpTRSV layout.

A GPU grid gives no cross-block ordering guarantee, so the TPU trick —
one ``pallas_call`` whose sequential (``ARBITRARY``) grid walks chunks in
dependence order with ``x`` resident in VMEM — does not port.  What ports
is the layout: rows stay in **level-order permutation** with chunk-aligned
wavefront spans and dependency columns pre-remapped to positions, and the
executor walks the spans with one pallas-triton launch per wavefront
(the CSR level-scheduled shape of SNIPPETS.md Snippet 1 and of cuSPARSE's
``csrsv2``: kernel-launch boundaries are the only synchronization, all
thread blocks inside a launch are independent).

Because every span is a contiguous position range, each launch's solution
lands with a static-offset ``dynamic_update_slice`` — the same no-scatter
property the TPU grid walk has — and the flat ``cols``/``vals``/``diag``
buffers are sliced per span at trace time, so the value-only refresh path
(buffers as runtime jit arguments) works unchanged.

The per-span compute kernel is exactly the GPU level kernel
(:mod:`repro.kernels.sptrsv_level.lowering_gpu`): gather loads from the
GMEM-resident permuted solution, unrolled static-K FMA, one divide.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.sptrsv_level.lowering_gpu import (
    level_solve_blocks,
    level_solve_blocks_batched,
)

__all__ = ["fused_solve", "fused_solve_batched"]


@functools.partial(jax.jit, static_argnames=("chunk", "spans", "interpret"))
def fused_solve(
    bl_perm: jnp.ndarray,   # (n_pad,) b in level-order positions
    cols: jnp.ndarray,      # (K, n_pad) deps remapped to positions
    vals: jnp.ndarray,      # (K, n_pad)
    diag: jnp.ndarray,      # (n_pad,)
    *,
    chunk: int = 512,
    spans: tuple = (),      # ((off, r_pad), ...) chunk-aligned wavefronts
    interpret: bool,
) -> jnp.ndarray:
    """Level-scheduled walk of the fused layout; one launch per wavefront."""
    K, n_pad = cols.shape
    assert spans, "GPU fused lowering needs the layout's wavefront spans"
    x = jnp.zeros((n_pad,), bl_perm.dtype)
    for off, rp in spans:
        bl_s = lax.slice_in_dim(bl_perm, off, off + rp)
        cols_s = lax.slice(cols, (0, off), (K, off + rp))
        vals_s = lax.slice(vals, (0, off), (K, off + rp))
        diag_s = lax.slice_in_dim(diag, off, off + rp)
        xl = level_solve_blocks(
            x, bl_s, cols_s, vals_s, diag_s,
            block_rows=min(chunk, rp), interpret=interpret,
        )
        x = lax.dynamic_update_slice_in_dim(x, xl, off, 0)
    return x


@functools.partial(jax.jit, static_argnames=("chunk", "spans", "interpret"))
def fused_solve_batched(
    bl_perm: jnp.ndarray,   # (n_pad, m) b in level-order positions
    cols: jnp.ndarray,      # (K, n_pad) deps remapped to positions
    vals: jnp.ndarray,      # (K, n_pad)
    diag: jnp.ndarray,      # (n_pad,)
    *,
    chunk: int = 512,
    spans: tuple = (),
    interpret: bool,
) -> jnp.ndarray:
    """Multi-RHS level-scheduled walk; the batch rides the lane dimension of
    every per-wavefront launch."""
    K, n_pad = cols.shape
    assert spans, "GPU fused lowering needs the layout's wavefront spans"
    m = bl_perm.shape[1]
    x = jnp.zeros((n_pad, m), bl_perm.dtype)
    for off, rp in spans:
        bl_s = lax.slice(bl_perm, (off, 0), (off + rp, m))
        cols_s = lax.slice(cols, (0, off), (K, off + rp))
        vals_s = lax.slice(vals, (0, off), (K, off + rp))
        diag_s = lax.slice_in_dim(diag, off, off + rp)
        xl = level_solve_blocks_batched(
            x, bl_s, cols_s, vals_s, diag_s,
            block_rows=min(chunk, rp), interpret=interpret,
        )
        x = lax.dynamic_update_slice(x, xl, (off, 0))
    return x
