"""TPU (Mosaic) lowering: one SpTRSV level (wavefront) in ELL-slab form.

This is the ``platform="tpu"`` implementation behind
:mod:`repro.kernels.backend`; the pallas-triton twin with the same entry
points lives in :mod:`.lowering_gpu`.

The level's rows are independent, so the kernel is a vectorized
gather / FMA / reduce / divide over a ``(K, R)`` slab:

    s[r]  = sum_k vals[k, r] * x[cols[k, r]]
    xl[r] = (bl[r] - s[r]) / diag[r]

Tiling: the row dimension R maps to TPU lanes; the grid walks row blocks of
``block_rows`` (multiple of 128).  The full (padded) ``x`` vector is resident
in VMEM for every block — n up to ~3M rows fits the ~16 MiB VMEM budget at
f32.  The K loop is unrolled at trace time (K is a per-level compile-time
constant — the "generated code" is specialized per level, exactly like the
paper's per-level functions).

TPU lowering note: the v5e Mosaic compiler refuses this kernel.  It lowers
a gather only when the indices have the operand's own 2-D shape, and
``jnp.take`` over the whole VMEM-resident ``x`` is not that
(``NotImplementedError: Only 2D gather is supported``), so a compiled
``tpu`` backend is refused at build
(:func:`repro.kernels.backend.refuse_mosaic_gather`).  The interpret
backend runs it on the host.  The scatter of solved values back into x
happens *outside* the kernel (x.at[rows].set) where XLA handles it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = [
    "level_kernel",
    "level_solve_blocks",
    "level_kernel_batched",
    "level_solve_blocks_batched",
]


def level_kernel(x_ref, bl_ref, cols_ref, vals_ref, diag_ref, out_ref):
    """One (K, BR) slab block.  x_ref: full padded x in VMEM."""
    x = x_ref[...]
    acc = bl_ref[...]
    K = cols_ref.shape[0]
    for k in range(K):  # unrolled: K is static per level
        acc = acc - vals_ref[k, :] * jnp.take(x, cols_ref[k, :], mode="clip")
    out_ref[...] = acc / diag_ref[...]


def level_kernel_batched(x_ref, bl_ref, cols_ref, vals_ref, diag_ref, out_ref):
    """Multi-RHS variant: x_ref (n_pad, m), bl/out (BR, m), cols/vals (K, BR).

    The row gather pulls whole (m,) solution rows, so the innermost (lane)
    dimension is the batch — thin levels stop underfeeding the vector unit
    once m reaches the lane width."""
    x = x_ref[...]                       # (n_pad, m)
    acc = bl_ref[...]                    # (BR, m)
    K = cols_ref.shape[0]
    for k in range(K):  # unrolled: K is static per level
        dep = jnp.take(x, cols_ref[k, :], axis=0, mode="clip")  # (BR, m)
        acc = acc - vals_ref[k, :][:, None] * dep
    out_ref[...] = acc / diag_ref[...][:, None]


@functools.partial(
    jax.jit, static_argnames=("block_rows", "interpret")
)
def level_solve_blocks(
    x_pad: jnp.ndarray,    # (n_pad,) current solution incl. scratch slot
    bl: jnp.ndarray,       # (R_pad,) b gathered at the level's rows
    cols: jnp.ndarray,     # (K, R_pad) int32
    vals: jnp.ndarray,     # (K, R_pad)
    diag: jnp.ndarray,     # (R_pad,)
    *,
    block_rows: int = 512,
    interpret: bool,
) -> jnp.ndarray:
    """Solve one level; returns xl (R_pad,)."""
    K, R = cols.shape
    assert R % block_rows == 0, (R, block_rows)
    n_pad = x_pad.shape[0]
    grid = (R // block_rows,)
    return pl.pallas_call(
        level_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_pad,), lambda i: (0,)),            # x: full
            pl.BlockSpec((block_rows,), lambda i: (i,)),       # bl
            pl.BlockSpec((K, block_rows), lambda i: (0, i)),   # cols
            pl.BlockSpec((K, block_rows), lambda i: (0, i)),   # vals
            pl.BlockSpec((block_rows,), lambda i: (i,)),       # diag
        ],
        out_specs=pl.BlockSpec((block_rows,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((R,), x_pad.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL,),  # blocks of a level are independent
        ),
        interpret=interpret,
        name="sptrsv_level",
    )(x_pad, bl, cols, vals, diag)


@functools.partial(
    jax.jit, static_argnames=("block_rows", "interpret")
)
def level_solve_blocks_batched(
    x_pad: jnp.ndarray,    # (n_pad, m) current solution incl. scratch row
    bl: jnp.ndarray,       # (R_pad, m) b gathered at the level's rows
    cols: jnp.ndarray,     # (K, R_pad) int32
    vals: jnp.ndarray,     # (K, R_pad)
    diag: jnp.ndarray,     # (R_pad,)
    *,
    block_rows: int = 512,
    interpret: bool,
) -> jnp.ndarray:
    """Solve one level for m RHS columns at once; returns xl (R_pad, m)."""
    K, R = cols.shape
    assert R % block_rows == 0, (R, block_rows)
    n_pad, m = x_pad.shape
    grid = (R // block_rows,)
    return pl.pallas_call(
        level_kernel_batched,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_pad, m), lambda i: (0, 0)),            # x: full
            pl.BlockSpec((block_rows, m), lambda i: (i, 0)),       # bl
            pl.BlockSpec((K, block_rows), lambda i: (0, i)),       # cols
            pl.BlockSpec((K, block_rows), lambda i: (0, i)),       # vals
            pl.BlockSpec((block_rows,), lambda i: (i,)),           # diag
        ],
        out_specs=pl.BlockSpec((block_rows, m), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, m), x_pad.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL,),  # blocks of a level are independent
        ),
        interpret=interpret,
        name="sptrsv_level_batched",
    )(x_pad, bl, cols, vals, diag)
