"""TPU (Mosaic) lowering: the ENTIRE SpTRSV in one ``pallas_call``.

This is the ``platform="tpu"`` implementation behind
:mod:`repro.kernels.backend`; the pallas-triton twin (level-scheduled
launches over the same fused layout — a GPU has no sequential grid to ride)
lives in :mod:`.lowering_gpu`.

This is the TPU-native analogue of the paper's synchronization-barrier
removal, taken to its limit: instead of one kernel launch (CPU: one barrier)
per level, the whole solve is a single kernel whose grid walks fixed-size
row *chunks* in level order.  TPU grid steps with ``ARBITRARY`` dimension
semantics execute **sequentially on one core**, which is exactly the
dependence order we need — cross-level ordering is enforced by the grid, and
``x`` never leaves VMEM.

Layout trick that removes dynamic scatter: rows are stored in **level-order
permutation**.  Chunk ``c`` writes positions ``[c*C, (c+1)*C)`` of the
permuted solution — a contiguous dynamic-offset store (supported) instead of
an arbitrary scatter (not supported).  Dependency columns are remapped to
positions, so gathers read the same permuted vector.  Chunks never straddle a
level boundary (codegen pads), so every gather hits positions written by
earlier grid steps.

VMEM working set: x_perm scratch (n_pad f32) + one (K, C) cols/vals block +
three (C,) vectors.

The v5e Mosaic compiler refuses both kernels: it lowers a gather only when
the indices have the operand's own 2-D shape, and ``jnp.take`` over the
whole scratch is not that (``NotImplementedError: Only 2D gather is
supported``; batched: ``ValueError: Shape mismatch in input, indices and
output``).  A compiled ``tpu`` backend is refused at build
(:func:`repro.kernels.backend.refuse_mosaic_gather`); the interpret
backend runs the kernels on the host.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["fused_kernel", "fused_solve", "fused_kernel_batched",
           "fused_solve_batched"]


def _chunk_start(c, C):
    """Dynamic store offset in the platform's default integer dtype.
    ``program_id`` is int32; with jax_enable_x64 the other index components
    of a multi-axis ref store default to int64, and interpret-mode
    ``dynamic_slice`` rejects mixed index dtypes."""
    return (c * C).astype(jnp.asarray(0).dtype)


def fused_kernel(bl_ref, cols_ref, vals_ref, diag_ref, out_ref, x_scr):
    """Grid step = one chunk of C rows inside a single level.

    bl/diag: (C,), cols/vals: (K, C); out: (n_pad,) written incrementally;
    x_scr: (n_pad,) VMEM scratch holding the permuted solution so far.
    """
    c = pl.program_id(0)
    C = bl_ref.shape[0]

    @pl.when(c == 0)
    def _init():
        x_scr[...] = jnp.zeros_like(x_scr)

    x = x_scr[...]
    acc = bl_ref[...]
    K = cols_ref.shape[0]
    for k in range(K):  # unrolled; K static (matrix-specialized program)
        acc = acc - vals_ref[k, :] * jnp.take(x, cols_ref[k, :], mode="clip")
    xl = acc / diag_ref[...]
    # contiguous dynamic-offset store — no scatter needed
    start = _chunk_start(c, C)
    x_scr[pl.ds(start, C)] = xl
    out_ref[pl.ds(start, C)] = xl


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def fused_solve(
    bl_perm: jnp.ndarray,   # (n_pad,) b in level-order positions
    cols: jnp.ndarray,      # (K, n_pad) deps remapped to positions
    vals: jnp.ndarray,      # (K, n_pad)
    diag: jnp.ndarray,      # (n_pad,)
    *,
    chunk: int = 512,
    interpret: bool,
) -> jnp.ndarray:
    K, n_pad = cols.shape
    assert n_pad % chunk == 0
    grid = (n_pad // chunk,)
    return pl.pallas_call(
        fused_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((chunk,), lambda c: (c,)),      # bl
            pl.BlockSpec((K, chunk), lambda c: (0, c)),  # cols
            pl.BlockSpec((K, chunk), lambda c: (0, c)),  # vals
            pl.BlockSpec((chunk,), lambda c: (c,)),      # diag
        ],
        # full-length output; each step stores its chunk
        out_specs=pl.BlockSpec((n_pad,), lambda c: (0,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), bl_perm.dtype),
        scratch_shapes=[pltpu.VMEM((n_pad,), bl_perm.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.ARBITRARY,),  # sequential grid = dep order
        ),
        interpret=interpret,
        name="sptrsv_fused",
    )(bl_perm, cols, vals, diag)


def fused_kernel_batched(bl_ref, cols_ref, vals_ref, diag_ref, out_ref, x_scr):
    """Multi-RHS grid step: one chunk of C rows × all m columns.

    bl: (C, m), cols/vals: (K, C), diag: (C,); out/x_scr: (n_pad, m).
    Same contiguous-store layout trick as the single-RHS kernel — the chunk
    writes rows [c*C, (c+1)*C) of the permuted solution, now as a (C, m)
    block whose minor (lane) dimension is the batch."""
    c = pl.program_id(0)
    C = bl_ref.shape[0]

    @pl.when(c == 0)
    def _init():
        x_scr[...] = jnp.zeros_like(x_scr)

    x = x_scr[...]                      # (n_pad, m)
    acc = bl_ref[...]                   # (C, m)
    K = cols_ref.shape[0]
    for k in range(K):  # unrolled; K static (matrix-specialized program)
        dep = jnp.take(x, cols_ref[k, :], axis=0, mode="clip")  # (C, m)
        acc = acc - vals_ref[k, :][:, None] * dep
    xl = acc / diag_ref[...][:, None]
    # contiguous dynamic-offset store along rows — no scatter needed
    start = _chunk_start(c, C)
    x_scr[pl.ds(start, C), :] = xl
    out_ref[pl.ds(start, C), :] = xl


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def fused_solve_batched(
    bl_perm: jnp.ndarray,   # (n_pad, m) b in level-order positions
    cols: jnp.ndarray,      # (K, n_pad) deps remapped to positions
    vals: jnp.ndarray,      # (K, n_pad)
    diag: jnp.ndarray,      # (n_pad,)
    *,
    chunk: int = 512,
    interpret: bool,
) -> jnp.ndarray:
    K, n_pad = cols.shape
    m = bl_perm.shape[1]
    assert n_pad % chunk == 0
    grid = (n_pad // chunk,)
    return pl.pallas_call(
        fused_kernel_batched,
        grid=grid,
        in_specs=[
            pl.BlockSpec((chunk, m), lambda c: (c, 0)),  # bl
            pl.BlockSpec((K, chunk), lambda c: (0, c)),  # cols
            pl.BlockSpec((K, chunk), lambda c: (0, c)),  # vals
            pl.BlockSpec((chunk,), lambda c: (c,)),      # diag
        ],
        # full-length output; each step stores its chunk of rows
        out_specs=pl.BlockSpec((n_pad, m), lambda c: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, m), bl_perm.dtype),
        scratch_shapes=[pltpu.VMEM((n_pad, m), bl_perm.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.ARBITRARY,),  # sequential grid = dep order
        ),
        interpret=interpret,
        name="sptrsv_fused_batched",
    )(bl_perm, cols, vals, diag)
