"""The float64 oracle that decides ``correct``: host-side SciPy, independent
of the program.

Copied from the checks the repository's chip smoke run made (its
``check_solve`` and its PCG true-residual check) so that a change to the
program cannot move the yardstick.  The limits each number is held to live
in ``bench/workloads/<cell>.json``, with the readings they were set from.

Triangular solve, two numbers per answer:

* ``residual``: the componentwise (Oettli-Prager) backward error
  ``max_i |b - A x|_i / (|A| |x| + |b|)_i``.  Substitution is componentwise
  backward stable, ``|dL| <= gamma_k |L|`` with ``gamma_k ~ k eps / 2`` for
  ``k`` terms in a row (Higham, Accuracy and Stability of Numerical
  Algorithms, Thm 8.5), so an f32 solve reads a small multiple of
  eps(f32) = 1.19e-7 whatever the conditioning, and a bf16 one a multiple of
  eps(bf16) = 7.8e-3.
* ``forward_error``: ``max |x - x_ref| / max |x_ref|`` against SciPy's
  float64 ``spsolve_triangular``.

PCG: ``true_residual``, the relative residual ``||b - A x|| / ||b||`` of the
returned ``x`` in float64.  PCG stops on its recursive residual; in f32 the
true one drifts from it, so the limit on the true residual sits above the
stopping tolerance by what sound runs read.
"""
from __future__ import annotations

import numpy as np
from scipy.sparse.linalg import spsolve_triangular

from bench.sparse import Csr


def solve_errors(L: Csr, b: np.ndarray, x: np.ndarray, *,
                 transpose: bool) -> dict:
    """``residual`` and ``forward_error`` of ``x`` as an answer to
    ``L x = b`` (``Lᵀ x = b`` with ``transpose``), worst over the columns.
    A non-finite or misshapen answer reads ``inf``."""
    A = L.scipy()
    if transpose:
        A = A.T.tocsr()
    x = np.asarray(x, np.float64)
    b = np.asarray(b, np.float64)
    if x.shape != b.shape or not np.isfinite(x).all():
        return {"residual": float("inf"), "forward_error": float("inf")}
    r = np.abs(b - A @ x)
    denom = abs(A) @ np.abs(x) + np.abs(b)
    omega = float(np.max(np.where(denom > 0, r / np.where(denom > 0, denom, 1),
                                  r)))
    x_ref = spsolve_triangular(A, b, lower=not transpose)
    fwd = float(np.max(np.abs(x - x_ref)) / max(np.max(np.abs(x_ref)), 1e-300))
    return {"residual": omega, "forward_error": fwd}


def pcg_errors(A: Csr, b: np.ndarray, x: np.ndarray) -> dict:
    """``true_residual`` of ``x`` as an answer to ``A x = b``; ``inf`` when
    ``x`` is not finite or misshapen."""
    x = np.asarray(x, np.float64)
    b = np.asarray(b, np.float64)
    if x.shape != b.shape or not np.isfinite(x).all():
        return {"true_residual": float("inf")}
    r = b - A.scipy() @ x
    return {"true_residual": float(np.linalg.norm(r) / np.linalg.norm(b))}


def worst(readings: list) -> dict:
    """Largest reading of each number over a list of answers."""
    out: dict = {}
    for rd in readings:
        for k, v in rd.items():
            out[k] = max(out.get(k, 0.0), v)
    return out


def judge(readings: dict, limits: dict) -> tuple:
    """``(ok, checks)``: each limited number beside its limit, in the order
    of ``limits``; a number the run did not read fails."""
    checks, ok = {}, True
    for name, limit in limits.items():
        value = readings.get(name, float("inf"))
        checks[name] = {"value": value, "limit": limit}
        ok = ok and value <= limit
    return ok, checks
