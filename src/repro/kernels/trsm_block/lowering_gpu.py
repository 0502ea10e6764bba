"""GPU (pallas-triton) lowering: batched dense diagonal-block apply.

Twin of :mod:`.lowering_tpu` with the Mosaic-isms removed: the batched
matvec maps to tensor-core ``dot`` instead of the MXU, the grid is an
ordinary parallel launch, and there are no TPU compiler params.  Same
signature, tiling, and padding contract.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["block_apply_kernel", "block_apply"]


def block_apply_kernel(dinv_ref, rhs_ref, out_ref):
    """dinv: (BB, T, T), rhs: (BB, T) -> out: (BB, T)."""
    d = dinv_ref[...]
    r = rhs_ref[...]
    out_ref[...] = jax.lax.dot_general(
        d, r[..., None],
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
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
        interpret=interpret,
        name="trsm_block_apply_gpu",
    )(dinv, rhs)
