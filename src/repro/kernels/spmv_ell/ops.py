"""Backend-dispatched wrapper: CSR -> padded ELL, then the Pallas SpMV on
the selected backend (TPU Mosaic, pallas-triton, or either interpreted)."""
from __future__ import annotations

from typing import Callable, Optional

import jax.numpy as jnp
import numpy as np

from repro.core.codegen import build_ell
from repro.core.csr import CSRMatrix
from repro.kernels.backend import refuse_mosaic_gather, resolve_backend

from . import lowering_gpu, lowering_tpu

__all__ = ["make_spmv", "select_lowering"]


def select_lowering(backend=None):
    """Lowering module for a backend spec — the single dispatch point the
    backend-matrix CI job asserts on."""
    bk = resolve_backend(backend)
    return lowering_gpu if bk.platform == "gpu" else lowering_tpu


def _ceil_to(v: int, m: int) -> int:
    return max(int(np.ceil(v / m) * m), m)


def make_spmv(
    M: CSRMatrix,
    *,
    backend=None,
    interpret: Optional[bool] = None,
    block: int = 1024,
) -> Callable[[jnp.ndarray], jnp.ndarray]:
    bk = resolve_backend(backend, interpret=interpret)
    refuse_mosaic_gather(bk, "spmv_ell")
    low = select_lowering(bk)
    ell = build_ell(M)
    n = M.n
    n_pad = _ceil_to(n, block)
    m_pad = _ceil_to(M.shape[1], 128)
    cols = np.zeros((ell.K, n_pad), np.int32)
    cols[:, :n] = ell.cols
    vals = np.zeros((ell.K, n_pad), np.float32)
    vals[:, :n] = ell.vals
    cols_d, vals_d = jnp.asarray(cols), jnp.asarray(vals)

    def matvec(v: jnp.ndarray) -> jnp.ndarray:
        dt = v.dtype
        v_pad = jnp.zeros((m_pad,), dt).at[: v.shape[0]].set(v)
        y = low.spmv(v_pad, cols_d, vals_d.astype(dt), block=block,
                     interpret=bk.interpret)
        return y[:n]

    return matvec
