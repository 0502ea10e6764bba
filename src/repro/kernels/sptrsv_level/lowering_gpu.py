"""GPU (pallas-triton) lowering: one SpTRSV level in ELL-slab form.

Same entry points and semantics as :mod:`.lowering_tpu`, with the memory
model a Triton SpTRSV actually uses (the CSR level-scheduled shape of the
SNIPPETS.md Snippet 1 exemplar and cuSPARSE's level-scheduled solve):

* ``x`` is **not** staged into on-chip memory — it stays a global-memory
  operand and each dependency is a gather **load** (ref indexing with an
  int32 index vector → per-lane pointer arithmetic in Triton), because a
  GPU has no VMEM-sized scratch to hold a whole solution vector;
* the grid maps row blocks of the level to thread blocks (one
  ``program_id`` axis, all blocks independent — level scheduling provides
  the only synchronization, between kernel launches);
* the K loop is unrolled at trace time exactly like the TPU lowering — K
  is a per-level compile-time constant, the "generated code" is
  specialized per level.

Block sizes should be powers of two for the real Triton lowering
(``tl.arange`` constraint); the shared padding helper in ``ops.py`` already
rounds row blocks to 128-multiples, which CI exercises through the
interpret backend (``backend="interpret:gpu"``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = [
    "level_kernel",
    "level_solve_blocks",
    "level_kernel_batched",
    "level_solve_blocks_batched",
]


def level_kernel(x_ref, bl_ref, cols_ref, vals_ref, diag_ref, out_ref):
    """One (K, BR) slab block; x_ref: full solution vector in GMEM."""
    acc = bl_ref[...]
    K = cols_ref.shape[0]
    for k in range(K):  # unrolled: K is static per level
        acc = acc - vals_ref[k, :] * x_ref[cols_ref[k, :]]
    out_ref[...] = acc / diag_ref[...]


def level_kernel_batched(x_ref, bl_ref, cols_ref, vals_ref, diag_ref, out_ref):
    """Multi-RHS variant: x_ref (n_pad, m) in GMEM, bl/out (BR, m).

    The gather pulls whole (m,) solution rows via a broadcast 2-D index
    load — rows from the ELL columns, all m batch columns per row."""
    acc = bl_ref[...]                    # (BR, m)
    K, _ = cols_ref.shape
    m = bl_ref.shape[1]
    batch_ix = jnp.arange(m, dtype=jnp.int32)[None, :]
    for k in range(K):  # unrolled: K is static per level
        dep = x_ref[cols_ref[k, :][:, None], batch_ix]  # (BR, m)
        acc = acc - vals_ref[k, :][:, None] * dep
    out_ref[...] = acc / diag_ref[...][:, None]


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
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
    """Solve one level; returns xl (R_pad,).  Same contract as the TPU
    lowering — ops-layer packing is backend-agnostic."""
    K, R = cols.shape
    assert R % block_rows == 0, (R, block_rows)
    n_pad = x_pad.shape[0]
    grid = (R // block_rows,)
    return pl.pallas_call(
        level_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_pad,), lambda i: (0,)),            # x: full, GMEM
            pl.BlockSpec((block_rows,), lambda i: (i,)),       # bl
            pl.BlockSpec((K, block_rows), lambda i: (0, i)),   # cols
            pl.BlockSpec((K, block_rows), lambda i: (0, i)),   # vals
            pl.BlockSpec((block_rows,), lambda i: (i,)),       # diag
        ],
        out_specs=pl.BlockSpec((block_rows,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((R,), x_pad.dtype),
        interpret=interpret,
        name="sptrsv_level_gpu",
    )(x_pad, bl, cols, vals, diag)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
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
        interpret=interpret,
        name="sptrsv_level_batched_gpu",
    )(x_pad, bl, cols, vals, diag)
