"""GPU (pallas-triton) lowering: ELL SpMV  y = M v.

Twin of :mod:`.lowering_tpu` with the Mosaic-isms removed: gather loads
(ref indexing with an index array) from the GMEM-resident input vector
replace ``jnp.take`` over a VMEM-resident copy, the grid is an ordinary
parallel launch (SpMV has no cross-block dependence), and there are no
TPU compiler params.  Same signature, layout, and padding contract.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["spmv_kernel", "spmv"]


def spmv_kernel(v_ref, cols_ref, vals_ref, out_ref):
    K, C = cols_ref.shape
    acc = jnp.zeros((C,), out_ref.dtype)
    for k in range(K):
        acc = acc + vals_ref[k, :] * v_ref[cols_ref[k, :]]
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def spmv(
    v_pad: jnp.ndarray,   # (m_pad,) input vector, padded
    cols: jnp.ndarray,    # (K, n_pad)
    vals: jnp.ndarray,    # (K, n_pad)
    *,
    block: int = 1024,
    interpret: bool,
) -> jnp.ndarray:
    K, n_pad = cols.shape
    assert n_pad % block == 0, (n_pad, block)
    m_pad = v_pad.shape[0]
    return pl.pallas_call(
        spmv_kernel,
        grid=(n_pad // block,),
        in_specs=[
            pl.BlockSpec((m_pad,), lambda i: (0,)),
            pl.BlockSpec((K, block), lambda i: (0, i)),
            pl.BlockSpec((K, block), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), v_pad.dtype),
        interpret=interpret,
        name="spmv_ell_gpu",
    )(v_pad, cols, vals)
