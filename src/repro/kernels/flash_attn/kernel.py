"""Pallas TPU flash attention (forward).

The §Perf memory profiles show attention score tiles as the dominant HBM
traffic once sharding is fixed (pure-XLA attention materializes every
(bq, bk) block).  This kernel keeps the online-softmax state — acc (bq, hd),
m, l (bq,) — in VMEM scratch across the KV grid dimension, so score tiles
never touch HBM: per (batch·head, q-block), HBM traffic is q + streamed
k/v + one output write.

Grid: ``(BH, n_q, n_kv)`` with ``dimension_semantics=(parallel, parallel,
arbitrary)`` — the last (KV) dimension iterates sequentially per TPU core,
which is what makes scratch-carried accumulation legal.  Masking (causal /
sliding window) is applied from global block coordinates; fully-masked
trailing blocks are skipped with ``pl.when`` (they still occupy grid steps —
block-skipping via scalar-prefetch ragged grids is the known follow-up).

MXU alignment: bq, bk multiples of 128; hd padded to 128 by ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["flash_fwd"]

NEG_INF = -1e30


def _fa_kernel(spec_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
               *, bq: int, bk: int, causal: bool, window: int, scale: float):
    qb = pl.program_id(1)
    kb = pl.program_id(2)
    n_kv = pl.num_programs(2)
    seq_off_q = qb * bq
    seq_off_k = kb * bk

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # static-ish skip: with causal masking, blocks fully above the diagonal
    # contribute nothing
    q_pos = seq_off_q + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = seq_off_k + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    live = True
    if causal:
        live = seq_off_k <= seq_off_q + bq - 1
    if window > 0:
        live = jnp.logical_and(live, seq_off_k + bk - 1 > seq_off_q - window)

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32)          # (bq, hd)
        k = k_ref[0].astype(jnp.float32)          # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        mask = spec_ref[0] > 0                    # (1,) valid-length flag mode
        del mask
        ok = k_pos < spec_ref[0]                  # valid key positions
        if causal:
            ok = jnp.logical_and(ok, k_pos <= q_pos)
        if window > 0:
            ok = jnp.logical_and(ok, k_pos > q_pos - window)
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jax.lax.dot(p.astype(v.dtype), v))
        m_ref[...] = m_new

    @pl.when(kb == n_kv - 1)
    def _fin():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"))
def flash_fwd(
    q: jnp.ndarray,          # (BH, Sq, hd)
    k: jnp.ndarray,          # (BH, Sk, hd)
    v: jnp.ndarray,          # (BH, Sk, hd)
    valid_len: jnp.ndarray,  # (1,) int32 — number of valid key positions
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool,
) -> jnp.ndarray:
    BH, Sq, hd = q.shape
    Sk = k.shape[1]
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk)
    grid = (BH, Sq // block_q, Sk // block_k)
    kernel = functools.partial(
        _fa_kernel, bq=block_q, bk=block_k, causal=causal, window=window,
        scale=hd ** -0.5)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda b, i, j: (0,),
                         memory_space=pltpu.SMEM),             # valid_len
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),   # acc
            pltpu.VMEM((block_q,), jnp.float32),      # m
            pltpu.VMEM((block_q,), jnp.float32),      # l
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL,
                                 pltpu.ARBITRARY),
        ),
        interpret=interpret,
        name="flash_attn_fwd",
    )(valid_len, q, k, v)
