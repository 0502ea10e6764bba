"""Pallas TPU kernel: ELL SpMV  y = M v  (transposed slab layout (K, n)).

Used for (a) the rewriting method's per-solve RHS update ``b' = E b`` — one
fully parallel pass, and (b) matvecs in the iterative-solver examples.

Grid walks column blocks of the slab (rows of y); ``v`` is VMEM-resident in
full.  Memory-bound: bytes = (2*K*n)*4 slab + n*4 in/out; the K loop is
unrolled (K static — matrix-specialized program).

The v5e Mosaic compiler refuses the ``jnp.take`` over the whole ``v``
(``NotImplementedError: Only 2D gather is supported``); a compiled ``tpu``
backend is refused at build
(:func:`repro.kernels.backend.refuse_mosaic_gather`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["spmv_kernel", "spmv"]


def spmv_kernel(v_ref, cols_ref, vals_ref, out_ref):
    v = v_ref[...]
    K, C = cols_ref.shape
    acc = jnp.zeros((C,), v.dtype)
    for k in range(K):
        acc = acc + vals_ref[k, :] * jnp.take(v, cols_ref[k, :], mode="clip")
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
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL,),
        ),
        interpret=interpret,
        name="spmv_ell",
    )(v_pad, cols, vals)
