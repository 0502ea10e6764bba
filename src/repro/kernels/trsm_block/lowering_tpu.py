"""Pallas TPU kernel: batched dense diagonal-block apply for block SpTRSV.

The paper lists "forming dense blocks to improve the locality" (ref [22]:
dense BLAS on off-diagonal blocks) as a planned optimization.  On TPU the
profitable mapping is the MXU: diagonal blocks of size T are inverted once at
preprocessing time, and the solve applies

    x_blk = Dinv_blk @ (b_blk - s_blk)

as a batched (T, T) @ (T,) product.  The kernel computes a batch of such
products per grid step (one (BB, T, T) tile), keeping everything in VMEM and
feeding the MXU with T=128-aligned tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["block_apply_kernel", "block_apply"]


def block_apply_kernel(dinv_ref, rhs_ref, out_ref):
    """dinv: (BB, T, T), rhs: (BB, T) -> out: (BB, T)."""
    d = dinv_ref[...]
    r = rhs_ref[...]
    # batched matvec on the MXU: (BB, T, T) @ (BB, T, 1).  HIGHEST: the
    # MXU's default f32 precision rounds operands to bf16, which left a
    # componentwise residual of 2.9e-3 on a v5e band solve
    out_ref[...] = jax.lax.dot_general(
        d, r[..., None],
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )[..., 0].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("batch_block", "interpret"))
def block_apply(
    dinv: jnp.ndarray,  # (NB, T, T) precomputed block inverses
    rhs: jnp.ndarray,   # (NB, T)
    *,
    batch_block: int = 8,
    interpret: bool,
) -> jnp.ndarray:
    NB, T, _ = dinv.shape
    assert NB % batch_block == 0, (NB, batch_block)
    return pl.pallas_call(
        block_apply_kernel,
        grid=(NB // batch_block,),
        in_specs=[
            pl.BlockSpec((batch_block, T, T), lambda i: (i, 0, 0)),
            pl.BlockSpec((batch_block, T), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((batch_block, T), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((NB, T), rhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL,),
        ),
        interpret=interpret,
        name="trsm_block_apply",
    )(dinv, rhs)
