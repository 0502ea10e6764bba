"""GPipe-style pipeline parallelism with ``shard_map`` + ``ppermute``.

The production meshes assigned to this paper carry (pod, data, model) axes —
no pipe axis — so PP ships as an optional feature (off by default), validated
on small virtual meshes by tests.  Schedule: GPipe with M microbatches over
P stages; bubble fraction (P-1)/(M+P-1).

Implementation: every device holds one stage's params.  The microbatch
stream rotates through stages with ``ppermute``; each device applies its
stage to whatever activation it currently holds.  After M+P-1 ticks all
microbatches passed all stages.  Activations for the backward pass come from
``jax.vjp`` inside the stage (XLA keeps them live per-stage — stage-local
rematerialization is the standard follow-up, hooked via ``remat_stage``).
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["gpipe_stage_fn", "make_gpipe"]


def gpipe_stage_fn(stage_apply: Callable, num_stages: int, axis: str,
                   *, remat_stage: bool = True):
    """Build the shard_map body: (stage_params, microbatches) -> outputs.

    ``stage_apply(params, x)``: one stage on one microbatch.
    Microbatch tensor: (M, mb, ...) sharded so each device sees all M.
    """
    apply = jax.checkpoint(stage_apply) if remat_stage else stage_apply

    def body(params, mbs):
        # params: this device's stage slice — shard_map keeps the sharded
        # leading axis at local size 1; squeeze it.  mbs: (M, mb, d) replicated
        params = jax.tree.map(lambda a: a[0], params)
        stage = jax.lax.axis_index(axis)
        M = mbs.shape[0]
        T = M + num_stages - 1
        mb_shape = mbs.shape[1:]

        def tick(carry, t):
            buf, outs = carry     # buf: activation currently held (mb, d)
            # stage 0 injects microbatch t (if any)
            inject = jnp.where(t < M, t, M - 1)
            x_in = jnp.where(stage == 0, mbs[inject], buf)
            y = apply(params, x_in)
            # mark validity: stage s works on microbatch t-s
            valid = (t - stage >= 0) & (t - stage < M)
            y = jnp.where(valid, y, buf)
            # last stage emits finished microbatch
            out_idx = jnp.where(t - (num_stages - 1) >= 0, t - (num_stages - 1), 0)
            emit = (stage == num_stages - 1) & valid
            outs = outs.at[out_idx].set(jnp.where(emit, y, outs[out_idx]))
            # rotate activations downstream
            perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]
            buf = jax.lax.ppermute(y, axis, perm)
            return (buf, outs), None

        buf0 = jnp.zeros(mb_shape, mbs.dtype)
        outs0 = jnp.zeros((M,) + mb_shape, mbs.dtype)
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0), jnp.arange(T))
        # outputs live on the last stage; broadcast so every device returns them
        outs = jax.lax.psum(
            jnp.where(stage == num_stages - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    return body


def make_gpipe(stage_apply: Callable, mesh: Mesh, axis: str = "pipe",
               *, num_stages: int | None = None, remat_stage: bool = True):
    """stage_params (P, ...) + microbatches (M, mb, d) -> outputs (M, mb, d)."""
    P_ = num_stages or int(mesh.shape[axis])
    body = gpipe_stage_fn(stage_apply, P_, axis, remat_stage=remat_stage)
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P()),       # stage params sharded; microbatches repl.
        out_specs=P(),
        check_vma=False,
    )
    return fn
