"""Jit'd wrapper: (B, S, H, hd) GQA layout -> padded MHA kernel call."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from .kernel import flash_fwd

__all__ = ["flash_attention_kernel"]


def _ceil_to(v: int, m: int) -> int:
    return max(-(-v // m) * m, m)


def flash_attention_kernel(
    q: jnp.ndarray,           # (B, Sq, Hq, hd)
    k: jnp.ndarray,           # (B, Sk, Hkv, hd)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool,
) -> jnp.ndarray:
    B, Sq, Hq, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    g = Hq // Hkv
    if g > 1:                 # GQA: repeat KV heads for the MHA kernel
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    Sq_p, Sk_p = _ceil_to(Sq, block_q), _ceil_to(Sk, block_k)
    hd_p = _ceil_to(hd, 128)
    qp = jnp.pad(q, ((0, 0), (0, Sq_p - Sq), (0, 0), (0, hd_p - hd)))
    kp = jnp.pad(k, ((0, 0), (0, Sk_p - Sk), (0, 0), (0, hd_p - hd)))
    vp = jnp.pad(v, ((0, 0), (0, Sk_p - Sk), (0, 0), (0, hd_p - hd)))
    # rescale: padding hd changes the kernel's hd**-0.5
    qp = qp * jnp.asarray((hd_p / hd) ** 0.5, qp.dtype)

    def bh(x, S):
        return x.transpose(0, 2, 1, 3).reshape(B * Hq, S, hd_p)

    o = flash_fwd(bh(qp, Sq_p), bh(kp, Sk_p), bh(vp, Sk_p),
                  jnp.asarray([Sk], jnp.int32),
                  causal=causal, window=window,
                  block_q=block_q, block_k=block_k, interpret=interpret)
    o = o.reshape(B, Hq, Sq_p, hd_p).transpose(0, 2, 1, 3)
    return o[:, :Sq, :, :hd]
