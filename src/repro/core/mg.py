"""HPCG's multigrid preconditioner: a V-cycle whose symmetric Gauss–Seidel
smoother runs its sweeps as SpTRSV (HPCG 3.1 reference code,
``ComputeMG_ref`` and ``ComputeSYMGS_ref``).

Split ``A = D + L + U`` (diagonal, strictly lower, strictly upper).  In
natural row order one SYMGS from ``x⁰`` is

    forward   ``(D + L) x¹ = r − U x⁰``
    backward  ``(D + U) x² = r − L x¹``

so its sweeps are a forward solve with ``tril(A) = D + L`` and a transpose
solve with ``tril(A)ᵀ``, which is ``D + U`` because ``A`` is symmetric: one
``SpTRSV.build_pair(tril(A))`` per level serves both.

Two exact identities keep the SpMVs around the sweeps small; both are
algebra and drop no term:

- from the forward equation, ``r − L x¹ = U x⁰ + D x¹``.  So a SYMGS needs
  one strictly-upper SpMV ``c = U x⁰``, used by both sweeps, and from
  ``x⁰ = 0`` (every pre-smoother, and the coarsest level) it needs none;
- the restriction needs ``r − A x`` only at the rows that ``f2c`` injects
  from, so it multiplies by the rows of ``A`` at ``f2c``: an eighth of
  ``A``.

The V-cycle sets ``x = 0`` on each level and runs one SYMGS, restricts
``r − A x`` by injection, recurses, prolongs ``x[f2c] += x_c`` and runs one
more SYMGS; the coarsest level runs one SYMGS only.  It reads nothing back
to the host: its programs are enqueued one after another.  Each apply is an
``mg.vcycle`` span and counts one ``mg.vcycles``; each program it enqueues
counts one ``mg.dispatches``.  The SYMGS's SpMV and vector updates carry the
device scope ``mg.smooth``, the restriction and prolongation
``mg.transfer`` (:mod:`repro.core.obs`); the solvers keep their own.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from . import obs
from .codegen import build_ell, build_spmv
from .csr import CSRMatrix
from .pcg import _matvec
from .solver import SpTRSV

__all__ = ["MGLevel", "MGPreconditioner", "make_mg_preconditioner"]


def _triangle(A: CSRMatrix, *, upper: bool,
              strict: bool = False) -> CSRMatrix:
    """``triu(A)`` or ``tril(A)``, diagonal included unless ``strict``."""
    rows = np.repeat(np.arange(A.n, dtype=np.int64), A.row_nnz())
    side = A.indices - rows if upper else rows - A.indices
    keep = side > 0 if strict else side >= 0
    indptr = np.zeros(A.n + 1, dtype=np.int64)
    np.cumsum(np.bincount(rows[keep], minlength=A.n), out=indptr[1:])
    return CSRMatrix(indptr, A.indices[keep], A.data[keep], A.shape)


def _rows(A: CSRMatrix, idx: np.ndarray) -> CSRMatrix:
    """The rows ``idx`` of ``A``, as a ``(len(idx), n)`` matrix."""
    counts = A.row_nnz()[idx]
    indptr = np.zeros(idx.size + 1, dtype=np.int64)
    np.cumsum(counts, out=indptr[1:])
    src = (np.repeat(A.indptr[idx] - indptr[:-1], counts)
           + np.arange(indptr[-1], dtype=np.int64))
    return CSRMatrix(indptr, A.indices[src], A.data[src],
                     (idx.size, A.shape[1]))


def _on_device(ell) -> tuple:
    return jax.device_put(ell.cols), jax.device_put(ell.vals)


@jax.jit
def _diag_times(d, x1):
    """The backward right-hand side ``D x¹`` of a SYMGS from ``x⁰ = 0``."""
    with jax.named_scope(obs.MG_SMOOTH):
        return d.astype(x1.dtype) * x1


@jax.jit
def _upper_rhs(pattern, vals, x0, r):
    """``c = U x⁰`` and the forward right-hand side ``r − c``."""
    with jax.named_scope(obs.MG_SMOOTH):
        c = _matvec(pattern, vals.astype(x0.dtype), x0)
        return c, r - c


@jax.jit
def _backward_rhs(c, d, x1):
    """The backward right-hand side ``U x⁰ + D x¹``."""
    with jax.named_scope(obs.MG_SMOOTH):
        return c + d.astype(x1.dtype) * x1


@jax.jit
def _restrict(cols, vals, f2c, r, x):
    """``(r − A x)[f2c]`` from the rows of ``A`` at ``f2c``."""
    with jax.named_scope(obs.MG_TRANSFER):
        return r[f2c] - _matvec(cols, vals.astype(x.dtype), x)


@jax.jit
def _prolong(f2c, x, xc):
    """``x[f2c] += x_c``."""
    with jax.named_scope(obs.MG_TRANSFER):
        return x.at[f2c].add(xc)


def _run(fn, *args):
    """Enqueue one program, counted."""
    obs.count(obs.MG_DISPATCHES)
    return fn(*args)


@dataclasses.dataclass(frozen=True)
class MGLevel:
    """One level's operators, resident on the device.

    ``fwd`` solves ``(D + L) x = b``, ``bwd`` ``(D + U) x = b``; ``diag`` is
    ``D``; ``upper`` is ``U`` as ``build_spmv`` stores it, ``(pattern,
    vals)``: by diagonals for a stencil; ``f2c`` the fine rows the next
    level injects from and ``rows_at_f2c`` those rows of ``A`` as
    transposed ELL ``(cols, vals)``, both None on the coarsest level."""

    fwd: SpTRSV
    bwd: SpTRSV
    diag: jnp.ndarray
    upper: tuple
    f2c: Optional[jnp.ndarray]
    rows_at_f2c: Optional[tuple]

    @property
    def sweeps(self) -> int:
        """Solves each of ``fwd`` and ``bwd`` runs in one V-cycle."""
        return 1 if self.f2c is None else 2

    def symgs(self, r: jnp.ndarray,
              x0: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        """One symmetric Gauss–Seidel step from ``x0`` (None: ``x⁰ = 0``,
        which needs no SpMV)."""
        if x0 is None:
            rhs = _run(_diag_times, self.diag, _run(self.fwd.solve, r))
        else:
            c, rhs = _run(_upper_rhs, *self.upper, x0, r)
            x1 = _run(self.fwd.solve, rhs)
            rhs = _run(_backward_rhs, c, self.diag, x1)
        return _run(self.bwd.solve, rhs)


class MGPreconditioner:
    """``z = M⁻¹ r``: one V-cycle over ``levels`` (0 the finest), for
    ``pcg(A, b, M_inv=...)``; ``r`` is ``(n,)``."""

    def __init__(self, levels: Sequence[MGLevel]):
        self.levels = tuple(levels)

    def __call__(self, r: jnp.ndarray) -> jnp.ndarray:
        obs.count(obs.MG_VCYCLES)
        with TraceAnnotation(obs.MG_VCYCLE):
            return self._cycle(0, r)

    def _cycle(self, depth: int, r: jnp.ndarray) -> jnp.ndarray:
        lv = self.levels[depth]
        x = lv.symgs(r)
        if lv.f2c is None:
            return x
        rc = _run(_restrict, *lv.rows_at_f2c, lv.f2c, r, x)
        x = _run(_prolong, lv.f2c, x, self._cycle(depth + 1, rc))
        return lv.symgs(r, x)


def make_mg_preconditioner(As: Sequence[CSRMatrix],
                           f2c: Sequence[np.ndarray], *,
                           strategy: str = "auto") -> MGPreconditioner:
    """HPCG's V-cycle over the operators ``As`` (0 the finest; each
    symmetric, with its diagonal stored) and the injection maps ``f2c``
    (``f2c[l][i]`` the row of level ``l`` that row ``i`` of level ``l + 1``
    injects from), as :func:`repro.sparse.hpcg_hierarchy` returns them.

    Each level builds ``SpTRSV.build_pair(tril(A), strategy=strategy,
    rewrite=None)`` and puts ``D``, ``U`` (in the format ``build_spmv``
    picks) and the rows of ``A`` at ``f2c`` on the device once."""
    if len(f2c) != len(As) - 1:
        raise ValueError(f"need one f2c map between each pair of levels: "
                         f"{len(As)} levels, {len(f2c)} maps")
    levels = []
    for depth, A in enumerate(As):
        if A.shape[0] != A.shape[1]:
            raise ValueError(f"level {depth} operator is not square: "
                             f"{A.shape}")
        lower = _triangle(A, upper=False)
        fwd, bwd = SpTRSV.build_pair(lower, strategy=strategy, rewrite=None)
        upper = build_spmv(_triangle(A, upper=True, strict=True))
        inject = rows = None
        if depth < len(f2c):
            idx = np.asarray(f2c[depth], dtype=np.int64)
            if (idx.shape != (As[depth + 1].n,) or idx.min() < 0
                    or idx.max() >= A.n):
                raise ValueError(
                    f"f2c[{depth}] must hold {As[depth + 1].n} rows of "
                    f"level {depth} (0 to {A.n - 1})")
            inject = jax.device_put(idx.astype(np.int32))
            rows = _on_device(build_ell(_rows(A, idx)))
        levels.append(MGLevel(fwd=fwd, bwd=bwd,
                              diag=jax.device_put(lower.diagonal()),
                              upper=jax.device_put(upper), f2c=inject,
                              rows_at_f2c=rows))
    return MGPreconditioner(levels)
