"""Jit'd wrapper: block-dense SpTRSV path.

Partitions the matrix into contiguous row blocks of size T; diagonal T×T
blocks are densified and inverted at preprocessing (host), off-block
dependencies stay in ELL slabs.  Solve walks blocks sequentially:

    s_blk  = ELL_offblock @ x          (gather/FMA — spmv-style)
    x_blk  = Dinv_blk @ (b_blk - s_blk)   (MXU kernel)

Profitable when the matrix has dense-ish diagonal blocks (banded /
reordered matrices — the paper's ref [22] scenario).
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.csr import CSRMatrix
from repro.kernels.backend import resolve_backend

from . import lowering_gpu, lowering_tpu

__all__ = ["make_block_apply", "make_block_solver", "select_lowering"]


def select_lowering(backend=None):
    """Lowering module for a backend spec — the single dispatch point the
    backend-matrix CI job asserts on."""
    bk = resolve_backend(backend)
    return lowering_gpu if bk.platform == "gpu" else lowering_tpu


def _dot_apply(dinv: jnp.ndarray, rhs: jnp.ndarray) -> jnp.ndarray:
    """Reference batched block apply: (B, T, T) @ (B, T[, m]) via
    ``dot_general``, accumulating in the RHS dtype (float64-exact under
    x64 — the interpret/CPU path the differential fuzz relies on).
    ``HIGHEST`` keeps f32 operands out of the TPU MXU's default bf16
    rounding."""
    r = rhs[..., None] if rhs.ndim == 2 else rhs
    out = jax.lax.dot_general(
        dinv.astype(rhs.dtype), r,
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=rhs.dtype,
    )
    return out[..., 0] if rhs.ndim == 2 else out


def make_block_apply(backend=None, *, kernel: str = "auto",
                     batch_block: int = 8) -> Callable:
    """Batched diagonal-block apply ``(B, T, T) × (B, T[, m]) -> (B, T[, m])``
    for the blocked (supernodal) executors.

    ``kernel`` picks the implementation:

    * ``"auto"``   — the pallas lowering on compiled tpu/gpu backends, the
      ``dot_general`` path under the interpreter / on CPU (the pallas
      interpreter is a correctness harness, far too slow for a hot loop);
    * ``"pallas"`` — force the backend's pallas lowering (interpret-mode
      backends run it under the interpreter — the CI path that exercises
      both lowering families);
    * ``"jnp"``    — force the ``dot_general`` path.

    The pallas kernels are single-vector ``(NB, T)``; batched RHS always
    takes the ``dot_general`` path.  ``NB`` is padded up to a
    ``batch_block`` multiple with identity blocks / zero rows to satisfy the
    kernel's grid, and the pad is sliced off the result.  The kernels
    accumulate in float32 — fine for f32 solves; float64 pipelines should
    keep ``kernel="auto"``/``"jnp"`` off-hardware."""
    assert kernel in ("auto", "pallas", "jnp"), kernel
    bk = resolve_backend(backend)
    use_pallas = kernel == "pallas" or (kernel == "auto" and not bk.interpret)
    low = select_lowering(bk)

    def apply(dinv: jnp.ndarray, rhs: jnp.ndarray) -> jnp.ndarray:
        if not use_pallas or rhs.ndim != 2:
            return _dot_apply(dinv, rhs)
        B, T = rhs.shape
        bb = min(batch_block, B) if B else 1
        B_pad = -(-B // bb) * bb
        if B_pad != B:
            pad = B_pad - B
            dinv = jnp.concatenate(
                [dinv, jnp.broadcast_to(jnp.eye(T, dtype=dinv.dtype),
                                        (pad, T, T))])
            rhs = jnp.concatenate([rhs, jnp.zeros((pad, T), rhs.dtype)])
        out = low.block_apply(dinv.astype(rhs.dtype), rhs,
                              batch_block=bb, interpret=bk.interpret)
        return out[:B]

    return apply


def make_block_solver(
    L: CSRMatrix,
    *,
    T: int = 128,
    backend=None,
    interpret: Optional[bool] = None,
) -> Callable[[jnp.ndarray], jnp.ndarray]:
    bk = resolve_backend(backend, interpret=interpret)
    low = select_lowering(bk)
    n = L.n
    nb = int(np.ceil(n / T))
    n_pad = nb * T
    dense = np.zeros((nb, T, T), np.float64)
    # off-block deps in ELL per block row
    off_cols, off_vals, maxk = [], [], 1
    for b in range(nb):
        oc, ov = [], []
        for r in range(b * T, min((b + 1) * T, n)):
            c, v = L.row(r)
            inblk = c >= b * T
            dense[b, r - b * T, c[inblk] - b * T] = v[inblk]
            oc.append(c[~inblk])
            ov.append(v[~inblk])
        k = max((len(x) for x in oc), default=0)
        maxk = max(maxk, k)
        off_cols.append(oc)
        off_vals.append(ov)
    for b in range(nb):  # pad rows beyond n: identity
        for r in range(T):
            if b * T + r >= n:
                dense[b, r, r] = 1.0
    dinv = np.stack([np.linalg.inv(dense[b]) for b in range(nb)])
    cols = np.zeros((nb, maxk, T), np.int32)
    vals = np.zeros((nb, maxk, T), np.float32)
    for b in range(nb):
        for r, (oc, ov) in enumerate(zip(off_cols[b], off_vals[b])):
            cols[b, : len(oc), r] = oc
            vals[b, : len(ov), r] = ov
    dinv_d = jnp.asarray(dinv.astype(np.float32))
    cols_d = jnp.asarray(cols)
    vals_d = jnp.asarray(vals)

    def solve(b_vec: jnp.ndarray) -> jnp.ndarray:
        dt = b_vec.dtype
        bp = jnp.zeros((n_pad,), dt).at[:n].set(b_vec)
        x = jnp.zeros((n_pad,), dt)
        for blk in range(nb):
            s = jnp.sum(vals_d[blk].astype(dt) * x[cols_d[blk]], axis=0)  # (T,)
            rhs = (bp[blk * T : (blk + 1) * T] - s)[None, :]  # (1, T)
            xb = low.block_apply(
                dinv_d[blk][None].astype(dt), rhs, batch_block=1,
                interpret=bk.interpret,
            )[0]
            x = x.at[blk * T : (blk + 1) * T].set(xb)
        return x[:n]

    return solve
