"""Mixture-of-Experts with expert parallelism (EP).

Two execution paths sharing one sort-based dispatch (no S×E×C one-hot —
token→capacity-slot packing is computed with an argsort + cummax-free
position-in-run trick, so the dispatch buffers are O(E·C·D)):

* ``ep_shard_map``: production path.  Experts are sharded over the ``model``
  mesh axis; tokens are exchanged with two ``all_to_all``s (dispatch +
  return).  Expert weights arrive FSDP-sharded over ``data`` and are
  all-gathered inside the block (the per-layer FSDP gather).
* ``dense local``: no-mesh fallback used by CPU smoke tests — identical
  math, no collectives.

Router: softmax top-k with load-balance auxiliary loss (Switch-style).
llama4-scout adds a shared (always-on) expert; arctic adds a parallel dense
residual MLP — both are plain MLPs applied outside the EP region.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from .config import ModelConfig
from .layers import dense, init_dense, init_mlp, init_rms_norm, mlp_apply, rms_norm

__all__ = ["init_moe", "moe_apply"]


def init_moe(key, cfg: ModelConfig) -> dict:
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 6)
    p = {
        "ln": init_rms_norm(D),
        "router": init_dense(ks[0], D, E),
        "wi": jax.random.normal(ks[1], (E, D, F), jnp.float32) * D ** -0.5,
        "wg": jax.random.normal(ks[2], (E, D, F), jnp.float32) * D ** -0.5,
        "wo": jax.random.normal(ks[3], (E, F, D), jnp.float32) * F ** -0.5,
    }
    if cfg.shared_expert:
        p["shared"] = init_mlp(ks[4], cfg)
    if cfg.moe_dense_residual:
        p["dense_mlp"] = init_mlp(ks[5], cfg)
    return p


def _capacity(tokens: int, cfg: ModelConfig) -> int:
    c = int(np.ceil(tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor))
    return max(8, -(-c // 8) * 8)


def _dispatch_indices(eid: jnp.ndarray, capacity: int):
    """eid: (N,) expert id per (token, choice).  Returns (slot, kept):
    slot[n] in [0, capacity] — capacity == dropped (overflow) sentinel."""
    N = eid.shape[0]
    order = jnp.argsort(eid)                       # stable
    se = eid[order]
    first = jnp.searchsorted(se, se, side="left")  # start of each run
    pos = jnp.arange(N) - first                    # position within expert run
    slot_sorted = jnp.where(pos < capacity, pos, capacity)
    slot = jnp.zeros((N,), jnp.int32).at[order].set(slot_sorted.astype(jnp.int32))
    return slot


def _expert_ffn(x: jnp.ndarray, wi, wg, wo, dtype) -> jnp.ndarray:
    """x: (E, C, D) -> (E, C, D); batched over experts (feeds the MXU)."""
    h = jnp.einsum("ecd,edf->ecf", x, wg.astype(dtype))
    u = jnp.einsum("ecd,edf->ecf", x, wi.astype(dtype))
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, wo.astype(dtype))


def _moe_local(params, cfg: ModelConfig, x2: jnp.ndarray):
    """Dense fallback: x2 (T, D) local tokens, full expert weights."""
    T, D = x2.shape
    E, k = cfg.n_experts, cfg.top_k
    C = _capacity(T, cfg)
    dt = x2.dtype
    logits = dense(params["router"], x2).astype(jnp.float32)   # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eid = jax.lax.top_k(probs, k)                         # (T, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    eflat = eid.reshape(-1)                                     # (T*k,)
    slot = _dispatch_indices(eflat, C)                          # (T*k,)
    src = jnp.repeat(jnp.arange(T), k)
    buf = jnp.zeros((E, C + 1, D), dt).at[eflat, slot].set(x2[src])
    y_buf = _expert_ffn(buf[:, :C], params["wi"], params["wg"], params["wo"], dt)
    y_buf = jnp.concatenate([y_buf, jnp.zeros((E, 1, D), dt)], axis=1)
    y = y_buf[eflat, slot] * gate.reshape(-1)[:, None].astype(dt)
    y = jnp.zeros((T, D), dt).at[src].add(y)

    # Switch aux loss: E * sum_e f_e * p_e
    me = probs.mean(0)
    ce = jnp.zeros((E,), jnp.float32).at[eflat].add(1.0) / (T * k)
    aux = E * jnp.sum(me * ce)
    return y, aux


def moe_apply(
    params: dict,
    cfg: ModelConfig,
    x: jnp.ndarray,                     # (B, S, D)
    *,
    mesh: Optional[Mesh] = None,
    dp_axes: tuple = ("data",),
    ep_axis: str = "model",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (x + moe_out [+ shared/dense residual], aux_loss)."""
    B, S, D = x.shape
    h = rms_norm(params["ln"], x)

    if mesh is None or ep_axis not in mesh.axis_names:
        y, aux = _moe_local(params, cfg, h.reshape(B * S, D))
        y = y.reshape(B, S, D)
    else:
        E = cfg.n_experts
        ep = int(mesh.shape[ep_axis])
        fsdp = "data" if "data" in mesh.axis_names else dp_axes[0]
        dp_spec = P(dp_axes if len(dp_axes) > 1 else dp_axes[0], None, None)
        w_spec = P(ep_axis, fsdp, None)
        ndp = int(np.prod([mesh.shape[a] for a in dp_axes]))
        T_loc = (B // ndp) * S
        C = _capacity(T_loc, cfg)

        @partial(
            shard_map, mesh=mesh,
            in_specs=(dp_spec, P(None, None), w_spec, w_spec, w_spec),
            out_specs=(dp_spec, P()),
            check_vma=False,
        )
        def ep_block(hl, router_w, wi, wg, wo):
            dt = hl.dtype
            Bl, Sl, _ = hl.shape
            x2 = hl.reshape(Bl * Sl, D)
            T = Bl * Sl
            k = cfg.top_k
            # FSDP all-gather of this layer's expert shards (over data axis)
            wi = jax.lax.all_gather(wi, fsdp, axis=1, tiled=True)
            wg = jax.lax.all_gather(wg, fsdp, axis=1, tiled=True)
            wo = jax.lax.all_gather(wo, fsdp, axis=1, tiled=True)

            logits = (x2 @ router_w.astype(dt)).astype(jnp.float32)
            probs = jax.nn.softmax(logits, axis=-1)
            gate, eid = jax.lax.top_k(probs, k)
            gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
            eflat = eid.reshape(-1)
            slot = _dispatch_indices(eflat, C)
            src = jnp.repeat(jnp.arange(T), k)
            buf = jnp.zeros((E, C + 1, D), dt).at[eflat, slot].set(x2[src])
            buf = buf[:, :C]                                   # (E, C, D)
            # dispatch: split experts across EP peers, collect their tokens
            recv = jax.lax.all_to_all(buf, ep_axis, split_axis=0,
                                      concat_axis=1, tiled=True)
            y_loc = _expert_ffn(recv, wi, wg, wo, dt)          # (E/ep, ep*C, D)
            back = jax.lax.all_to_all(y_loc, ep_axis, split_axis=1,
                                      concat_axis=0, tiled=True)
            back = jnp.concatenate([back, jnp.zeros((E, 1, D), dt)], axis=1)
            y = back[eflat, slot] * gate.reshape(-1)[:, None].astype(dt)
            y = jnp.zeros((T, D), dt).at[src].add(y).reshape(Bl, Sl, D)

            me = probs.mean(0)
            ce = jnp.zeros((E,), jnp.float32).at[eflat].add(1.0) / (T * k)
            aux = E * jnp.sum(me * ce)
            aux = jax.lax.pmean(aux, dp_axes)
            aux = jax.lax.pmean(aux, ep_axis)   # identical on all; keep replicated
            return y, aux

        y, aux = ep_block(h, params["router"]["w"], params["wi"],
                          params["wg"], params["wo"])

    out = x + y
    if "shared" in params:          # llama4: always-on shared expert
        out = out + mlp_apply(params["shared"], h, residual=False)
    if "dense_mlp" in params:       # arctic: parallel dense residual MLP
        out = out + mlp_apply(params["dense_mlp"], h, residual=False)
    return out, aux
