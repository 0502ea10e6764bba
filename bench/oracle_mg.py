"""The float64 check of HPCG's preconditioner: one V-cycle in SciPy,
independent of the program.

CG converges to the same ``x`` under any symmetric positive definite
preconditioner, so the true residual of its answers cannot tell HPCG's
V-cycle from one that drops a smoother, a level or a term: such a V-cycle
only costs iterations, and a solve with it still reaches ``tol``.  HPCG's own
validation checks its optimized preconditioner against the reference one;
here the system's ``M⁻¹ r`` on one vector ``r`` of the run is held to
``ComputeMG_ref`` computed in float64 on the same ``r``:

* ``vcycle_error``: ``||z − z_ref|| / ||z_ref||``.

``z_ref`` follows the HPCG 3.1 reference code term by term: from ``x = 0``
one SYMGS (``ComputeSYMGS_ref``: the forward sweep solves
``(D + L) x¹ = r − U x⁰``, the backward sweep ``(D + U) x² = r − L x¹``,
each a SciPy ``spsolve_triangular``), the restriction of the whole
``r − A x`` by injection at ``f2c``, the coarser level, the prolongation
``x[f2c] += x_c`` and one more SYMGS; the coarsest level one SYMGS.  An f32
V-cycle reads a small multiple of eps(f32) = 1.19e-7, a bf16 one a multiple
of eps(bf16) = 7.8e-3.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp
from scipy.sparse.linalg import spsolve_triangular


def _symgs(A, r: np.ndarray, x0: np.ndarray) -> np.ndarray:
    lower, upper = sp.tril(A, format="csr"), sp.triu(A, format="csr")
    strict_lower, strict_upper = sp.tril(A, -1), sp.triu(A, 1)
    x1 = spsolve_triangular(lower, r - strict_upper @ x0, lower=True)
    return spsolve_triangular(upper, r - strict_lower @ x1, lower=False)


def vcycle(levels: list, f2c: list, r: np.ndarray) -> np.ndarray:
    """``ComputeMG_ref``'s ``z = M⁻¹ r`` in float64 over ``levels`` (the
    finest first, each a ``bench.sparse.Csr``) and the injection maps."""
    A = levels[0].scipy()
    r = np.asarray(r, np.float64)
    x = _symgs(A, r, np.zeros_like(r))
    if not f2c:
        return x
    rc = (r - A @ x)[f2c[0]]
    x[f2c[0]] += vcycle(levels[1:], f2c[1:], rc)
    return _symgs(A, r, x)


def vcycle_errors(levels: list, f2c: list, r: np.ndarray,
                  z: np.ndarray) -> dict:
    """``vcycle_error`` of ``z`` as the V-cycle's answer to ``r``; ``inf``
    when ``z`` is not finite or misshapen."""
    z = np.asarray(z, np.float64)
    if z.shape != np.shape(r) or not np.isfinite(z).all():
        return {"vcycle_error": float("inf")}
    want = vcycle(levels, f2c, r)
    return {"vcycle_error": float(np.linalg.norm(z - want)
                                  / np.linalg.norm(want))}
