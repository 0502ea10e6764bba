"""Plain reference solvers in JAX, independent of the program, in any dtype.

They serve as the control that ``correct`` has to reject: put in the
program's place and run in bfloat16, the precision below the float32 the
configurations state, their answers must fail the oracle's limits.  Run in
float32 they are a second witness beside the float64 oracle.

The triangular solve is the textbook level-scheduled substitution: rows
grouped by level (longest path in the dependency graph), one ``lax.scan``
step per level, every level padded to the widest one.  The forward solve
gathers ``x`` along the rows of ``L``; the transpose solve scatters each
solved row's contribution along the same rows (right-looking), so both read
``L`` in its CSR order and neither transposes it.  PCG is the textbook
recurrence in one ``lax.while_loop`` with that solve pair as ``M^{-1}``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.sparse import Csr


def _levels(L: Csr, transpose: bool) -> np.ndarray:
    """Level of every row: forward, a row waits for the rows its
    off-diagonals name; transposed, a row ``j`` waits for every row whose
    off-diagonals name ``j``."""
    indptr, indices = L.indptr.tolist(), L.indices.tolist()
    level = [0] * L.n
    if not transpose:
        for i in range(L.n):
            lv = 0
            for p in range(indptr[i], indptr[i + 1] - 1):
                lv = max(lv, level[indices[p]] + 1)
            level[i] = lv
    else:
        for i in range(L.n - 1, -1, -1):
            nxt = level[i] + 1
            for p in range(indptr[i], indptr[i + 1] - 1):
                j = indices[p]
                if level[j] < nxt:
                    level[j] = nxt
    return np.asarray(level, dtype=np.int64)


def _padded_levels(L: Csr, transpose: bool, dtype):
    """Per level, padded to the widest: row ids, the off-diagonal columns
    and values of each row of ``L``, and its diagonal.  Padding points at
    the scratch slot ``n`` with value 0 and diagonal 1."""
    n = L.n
    level = _levels(L, transpose)
    nlev = int(level.max()) + 1
    order = np.argsort(level, kind="stable")
    counts = np.bincount(level, minlength=nlev)
    width = int(counts.max())
    row_nnz = np.diff(L.indptr) - 1                  # off-diagonals per row
    k = max(int(row_nnz.max()), 1)
    slot = np.arange(n) - np.repeat(np.cumsum(counts) - counts, counts)
    rows = np.full((nlev, width), n, dtype=np.int32)
    rows[level[order], slot] = order
    cols = np.full((n + 1, k), n, dtype=np.int32)
    vals = np.zeros((n + 1, k), dtype=np.float64)
    r = L.rows()
    off = L.indices != r
    pos = np.arange(L.nnz) - L.indptr[r]
    cols[r[off], pos[off]] = L.indices[off]
    vals[r[off], pos[off]] = L.data[off]
    diag = np.ones(n + 1)
    diag[:n] = L.data[L.indptr[1:] - 1]
    return (jnp.asarray(rows), jnp.asarray(cols[rows]),
            jnp.asarray(vals[rows], dtype), jnp.asarray(diag[rows], dtype))


def _forward(levels, b):
    rows, cols, vals, diag = levels
    n, m = b.shape
    bx = jnp.concatenate([b, jnp.zeros((1, m), b.dtype)])

    def step(x, lv):
        r, c, v, d = lv
        acc = jnp.sum(v[:, :, None] * x[c], axis=1)
        return x.at[r].set((bx[r] - acc) / d[:, None]), None

    x, _ = jax.lax.scan(step, jnp.zeros_like(bx), (rows, cols, vals, diag))
    return x[:n]


def _transposed(levels, b):
    rows, cols, vals, diag = levels
    n, m = b.shape
    bx = jnp.concatenate([b, jnp.zeros((1, m), b.dtype)])

    def step(carry, lv):
        x, acc = carry
        r, c, v, d = lv
        xr = (bx[r] - acc[r]) / d[:, None]
        acc = acc.at[c].add(v[:, :, None] * xr[:, None, :])
        return (x.at[r].set(xr), acc), None

    (x, _), _ = jax.lax.scan(step, (jnp.zeros_like(bx), jnp.zeros_like(bx)),
                             (rows, cols, vals, diag))
    return x[:n]


class ReferenceSolver:
    """``L x = b`` (``Lᵀ x = b`` with ``transpose``) computed in ``dtype``;
    ``solve`` takes ``(n,)`` or ``(n, m)`` and answers in ``dtype``."""

    def __init__(self, L: Csr, *, transpose: bool, dtype):
        self.dtype = jnp.dtype(dtype)
        levels = _padded_levels(L, transpose, self.dtype)
        kernel = _transposed if transpose else _forward
        self._fn = jax.jit(lambda b: kernel(levels, b))

    def solve(self, b):
        b = jnp.asarray(b, self.dtype)
        x = self._fn(b.reshape(b.shape[0], -1))
        return x.reshape(b.shape)


class ReferencePCG:
    """PCG on SPD ``A`` with ``M^{-1} = (L Lᵀ)^{-1}``, every vector and
    operation in ``dtype``; stops when ``||r|| <= tol ||b||`` or after
    ``maxiter`` iterations.  ``solve(b) -> (x, iterations, converged)``."""

    def __init__(self, A: Csr, L: Csr, *, tol: float, maxiter: int, dtype):
        self.dtype = jnp.dtype(dtype)
        fwd = _padded_levels(L, False, self.dtype)
        bwd = _padded_levels(L, True, self.dtype)
        r = A.rows()
        pos = np.arange(A.nnz) - A.indptr[r]
        k = int(np.diff(A.indptr).max())
        a_cols = np.zeros((A.n, k), dtype=np.int32)
        a_vals = np.zeros((A.n, k))
        a_cols[r, pos] = A.indices
        a_vals[r, pos] = A.data
        a_cols, a_vals = jnp.asarray(a_cols), jnp.asarray(a_vals, self.dtype)

        def matvec(v):
            return jnp.sum(a_vals * v[a_cols], axis=1)

        def precond(v):
            return _transposed(bwd, _forward(fwd, v[:, None]))[:, 0]

        def run(b):
            stop = tol * jnp.linalg.norm(b)
            x = jnp.zeros_like(b)
            z = precond(b)
            state = (x, b, z, jnp.vdot(b, z), jnp.linalg.norm(b), 0)

            def cond(s):
                return (s[4] > stop) & (s[5] < maxiter)

            def body(s):
                x, r, p, rz, _, it = s
                ap = matvec(p)
                alpha = rz / jnp.vdot(p, ap)
                x = x + alpha * p
                r = r - alpha * ap
                z = precond(r)
                rz_new = jnp.vdot(r, z)
                p = z + (rz_new / rz) * p
                return (x, r, p, rz_new, jnp.linalg.norm(r), it + 1)

            x, _, _, _, rnorm, it = jax.lax.while_loop(cond, body, state)
            return x, it, rnorm <= stop

        self._fn = jax.jit(run)

    def solve(self, b):
        x, it, ok = self._fn(jnp.asarray(b, self.dtype))
        return x, int(it), bool(ok)


def as_program(driver: str, dtype):
    """The reference in ``dtype``, shaped as the system under test of the
    driver named ``driver`` (its ``program=`` argument)."""
    if driver == "pcg":
        return functools.partial(ReferencePCG, dtype=dtype)
    return functools.partial(ReferenceSolver, dtype=dtype)
