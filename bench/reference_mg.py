"""Plain reference of HPCG's multigrid-preconditioned CG in JAX, independent
of the program, in any dtype.

It follows the HPCG 3.1 reference code term by term and uses none of the
program's shortcuts.  ``A = D + L + U`` on each level.  SYMGS
(``ComputeSYMGS_ref``) from ``x⁰``: the forward sweep solves
``(D + L) x¹ = r − U x⁰``, the backward sweep ``(D + U) x² = r − L x¹``,
each right-hand side with a full SpMV of its triangle, each sweep the
level-scheduled substitution of ``bench/reference.py`` on ``tril(A)`` (the
backward one as its transpose, ``D + U`` for a symmetric ``A``).  The
V-cycle (``ComputeMG_ref``) starts from ``x = 0``, runs one SYMGS, restricts
the whole ``r − A x`` by injection at ``f2c``, recurses, prolongs
``x[f2c] += x_c`` and runs one more SYMGS; the coarsest level one SYMGS.
CG starts from ``x = 0`` and stops when ``||r|| <= tol ||b||`` or after
``maxiter`` iterations, all in one ``lax.while_loop``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.generators.hpcg_27pt import lower
from bench.reference import _forward, _padded_levels, _transposed
from bench.sparse import Csr


def _ell(A: Csr, keep: np.ndarray, dtype) -> tuple:
    """The entries ``keep`` of ``A`` as row-major ELL ``(cols, vals)``,
    padded with column 0 and value 0."""
    r, c, v = A.rows()[keep], A.indices[keep], A.data[keep]
    counts = np.bincount(r, minlength=A.n)
    k = max(int(counts.max()), 1)
    pos = np.arange(r.size) - (np.cumsum(counts) - counts)[r]
    cols = np.zeros((A.n, k), dtype=np.int32)
    vals = np.zeros((A.n, k))
    cols[r, pos] = c
    vals[r, pos] = v
    return jnp.asarray(cols), jnp.asarray(vals, dtype)


def _spmv(ell: tuple, v):
    cols, vals = ell
    return jnp.sum(vals * v[cols], axis=1)


def _level(A: Csr, f2c, dtype) -> dict:
    """One level's arrays: both sweeps' padded levels, ``A`` and its strict
    triangles as ELL, and the injection map (None on the coarsest)."""
    rows, L = A.rows(), lower(A)
    return {"fwd": _padded_levels(L, False, dtype),
            "bwd": _padded_levels(L, True, dtype),
            "a": _ell(A, np.ones(A.nnz, dtype=bool), dtype),
            "strict_lower": _ell(A, A.indices < rows, dtype),
            "strict_upper": _ell(A, A.indices > rows, dtype),
            "f2c": None if f2c is None else jnp.asarray(f2c, jnp.int32)}


def _symgs(lv: dict, r, x0):
    x1 = _forward(lv["fwd"], (r - _spmv(lv["strict_upper"], x0))[:, None])
    rhs = r - _spmv(lv["strict_lower"], x1[:, 0])
    return _transposed(lv["bwd"], rhs[:, None])[:, 0]


def _vcycle(lvs: list, depth: int, r):
    lv = lvs[depth]
    x = _symgs(lv, r, jnp.zeros_like(r))
    if lv["f2c"] is None:
        return x
    rc = (r - _spmv(lv["a"], x))[lv["f2c"]]
    x = x.at[lv["f2c"]].add(_vcycle(lvs, depth + 1, rc))
    return _symgs(lv, r, x)


def _mgcg(lvs: list, b, tol, cap):
    stop = tol * jnp.linalg.norm(b)
    z = _vcycle(lvs, 0, b)
    state = (jnp.zeros_like(b), b, z, jnp.vdot(b, z), jnp.linalg.norm(b), 0)

    def cond(s):
        return (s[4] > stop) & (s[5] < cap)

    def body(s):
        x, r, p, rz, _, it = s
        ap = _spmv(lvs[0]["a"], p)
        alpha = rz / jnp.vdot(p, ap)
        x = x + alpha * p
        r = r - alpha * ap
        z = _vcycle(lvs, 0, r)
        rz_new = jnp.vdot(r, z)
        p = z + (rz_new / rz) * p
        return (x, r, p, rz_new, jnp.linalg.norm(r), it + 1)

    x, _, _, _, rnorm, it = jax.lax.while_loop(cond, body, state)
    return x, it, rnorm <= stop


class ReferenceMGCG:
    """CG with HPCG's V-cycle as ``M^{-1}`` on ``levels`` (the finest first)
    and the injection maps ``f2c``, every vector and operation in ``dtype``.
    ``solve(b, maxiter=None) -> (x, iterations, converged)``;
    ``precondition(r)`` is one V-cycle.  The levels' arrays are arguments
    of the jitted programs, not constants in them."""

    def __init__(self, levels: list, f2c: list, *, tol: float, maxiter: int,
                 dtype):
        self.dtype, self.tol, self.maxiter = jnp.dtype(dtype), tol, maxiter
        self._lvs = [_level(A, f2c[i] if i < len(f2c) else None, self.dtype)
                     for i, A in enumerate(levels)]
        self._fn = jax.jit(_mgcg)
        self._vcycle = jax.jit(lambda lvs, r: _vcycle(lvs, 0, r))

    def solve(self, b, maxiter: int | None = None):
        x, it, ok = self._fn(self._lvs, jnp.asarray(b, self.dtype), self.tol,
                             maxiter or self.maxiter)
        return x, int(it), bool(ok)

    def precondition(self, r):
        return self._vcycle(self._lvs, jnp.asarray(r, self.dtype))


def as_program(dtype):
    """The reference in ``dtype``, shaped as the ``mgcg`` driver's system
    under test (its ``program=`` argument).  Built once per hierarchy of
    sizes and kept: every seed of a cell solves the same matrices."""
    built: dict = {}

    def program(levels, f2c, *, tol, maxiter):
        key = (tuple((A.n, A.nnz) for A in levels), tol, maxiter)
        if key not in built:
            built[key] = ReferenceMGCG(levels, f2c, tol=tol, maxiter=maxiter,
                                       dtype=dtype)
        return built[key]

    return program
