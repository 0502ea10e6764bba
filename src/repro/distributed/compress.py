"""Gradient compression: int8 quantize -> all_reduce -> dequantize, with
error-feedback residual (1-bit-Adam-style EF so compression error does not
accumulate as bias).

At 512 chips the cross-pod gradient all-reduce is the only collective on the
slow inter-pod links; int8 cuts its wire bytes 4x vs f32 (2x vs bf16) at the
cost of one extra abs-max pass.  Selectable per-run (``--compress-grads``),
measured in EXPERIMENTS.md §Perf (multipod hillclimb).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

__all__ = ["CompressionState", "compressed_allreduce", "make_compressed_grad_fn"]


@dataclasses.dataclass
class CompressionState:
    residual: Any           # error-feedback residual, like grads (f32)

    @staticmethod
    def init(grads_like):
        return CompressionState(
            jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads_like))


def _quant(g: jnp.ndarray):
    amax = jnp.max(jnp.abs(g))
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    return q, scale


def compressed_allreduce(g: jnp.ndarray, residual: jnp.ndarray, axis: str):
    """One tensor: EF-int8 psum over ``axis`` (inside shard_map)."""
    g = g.astype(jnp.float32) + residual
    q, scale = _quant(g)
    deq = q.astype(jnp.float32) * scale
    new_residual = g - deq
    # int8 values psum directly (sum of int8 fits s32); scales psum'd too —
    # per-peer scales differ, so sum(q_i * s_i) != s * sum(q_i).  We trade
    # exactness for wire bytes: send q (1B) + scale (4B/tensor) and let each
    # peer reconstruct with a shared max-scale.  Error lands in EF residual.
    smax = jax.lax.pmax(scale, axis)
    q_rescaled = jnp.round(deq / smax).astype(jnp.int32)
    total = jax.lax.psum(q_rescaled, axis).astype(jnp.float32) * smax
    n = jax.lax.psum(jnp.ones((), jnp.float32), axis)
    return total / n, new_residual


def make_compressed_grad_fn(mesh, axis: str = "pod"):
    """Tree-level wrapper: all-reduce grads over ``axis`` with EF-int8.
    Used when the training step keeps grads sharded per-pod and performs the
    cross-pod reduction explicitly (shard_map region)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def reduce_tree(grads, state: CompressionState):
        def one(g, r):
            spec = P(*([None] * g.ndim))
            f = shard_map(
                lambda gg, rr: compressed_allreduce(gg, rr, axis),
                mesh=mesh, in_specs=(spec, spec), out_specs=(spec, spec),
                check_vma=False)
            return f(g, r)

        out = jax.tree.map(one, grads, state.residual)
        new_g = jax.tree.map(lambda t: t[0], out,
                             is_leaf=lambda t: isinstance(t, tuple))
        new_r = jax.tree.map(lambda t: t[1], out,
                             is_leaf=lambda t: isinstance(t, tuple))
        return new_g, CompressionState(new_r)

    return reduce_tree
