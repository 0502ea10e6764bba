"""Preconditioned conjugate gradients with an IC(0)/SpTRSV preconditioner —
the classic workload SpTRSV sits inside (paper §I: "the building block for
several numerical solutions").

``M^{-1} r`` = two triangular solves with the incomplete-Cholesky factor,
each executed by the matrix-specialized (optionally rewritten) level-set
solver.  The backward sweep ``Lᵀ z = y`` is a first-class transpose solve
(``SpTRSV.build_pair``): its level sets are derived from the *same* forward
DAG analysis, so one symbolic analysis serves both sweeps — no transposed
copy, no reverse-permutation, no second analysis pipeline.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from . import obs
from .codegen import build_spmv, spmv
from .csr import CSRMatrix
from .rewrite import RewriteConfig
from .solver import SpTRSV

__all__ = [
    "PCGResult",
    "BatchedPCGResult",
    "make_ic_preconditioner",
    "make_ic_preconditioner_batched",
    "pcg",
    "pcg_batched",
]


@dataclasses.dataclass
class PCGResult:
    x: jnp.ndarray
    iters: int
    residual: float
    converged: bool


@dataclasses.dataclass
class BatchedPCGResult:
    """m independent PCG solves sharing one matrix/preconditioner build.

    ``x`` (n, m); ``iters``/``residual``/``converged`` are per-column —
    iteration count is where each column first hit tolerance (maxiter if
    it never did)."""

    x: jnp.ndarray
    iters: np.ndarray          # (m,) int
    residual: np.ndarray       # (m,) float
    converged: np.ndarray      # (m,) bool


def make_ic_preconditioner(
    L: CSRMatrix,
    *,
    strategy: str = "levelset",
    rewrite: Optional[RewriteConfig] = RewriteConfig(thin_threshold=2),
    sweeps: Optional[int] = None,
    sweep_tol: Optional[float] = None,
    backend=None,
    guard=None,
) -> Callable[[jnp.ndarray], jnp.ndarray]:
    """Given lower factor L (A ≈ L Lᵀ) build z = (L Lᵀ)^{-1} r.

    Exactly **one** level-set analysis serves both sweeps: the backward
    solver's level sets are the forward DAG's reverse levels and its slabs
    are packed from an O(nnz) CSC view of ``L`` (``SpTRSV.build_pair``).
    The legacy construction — transpose + reverse-permute + a second full
    ``SpTRSV.build`` — is benchmarked against this one in
    ``benchmarks/preconditioner.py``.

    ``sweeps=k`` switches to the **inexact** stale-synchronous mode: each
    triangular solve becomes ``k`` sync-free Jacobi sweeps
    (:mod:`repro.core.sweep`, ``fallback=None`` — no verification, no
    correction, ONE fused dispatch per apply).  A k-sweep apply is a *fixed
    linear operator* — the same truncated Neumann polynomial of ``L``
    every call — so standard (non-flexible) PCG remains valid with it; an
    inexact ``M⁻¹`` only needs to stay a contraction, not an exact solve.
    Pair it with ``pcg(..., stall_window=...)`` so iteration control notices
    if ``k`` was chosen too small to keep helping.  ``sweep_tol`` is
    accepted for config symmetry but only matters if verification is
    re-enabled.  ``rewrite`` is ignored in sweep mode — the sweeps consume
    the factor directly and an RHS transform would add a dispatch to the
    apply for nothing.

    ``guard`` (``True`` or a :class:`repro.core.guard.GuardConfig`) wraps
    both sweeps in the guarded execution layer.  The **tolerance-aware
    inexact** mode is ``GuardConfig(residual_tol=τ, on_breakdown="refine")``
    with a loose ``τ``: each apply is verified and refined only *up to* the
    requested tolerance — cheaper than an exact solve, but never the silent
    garbage an unverified inexact apply can produce (zero extra inner solves
    when the tolerance already holds).  Because the refinement count may
    vary call-to-call, a guarded ``M⁻¹`` with loose ``τ`` is no longer a
    strictly fixed linear operator — pair it with ``pcg(...,
    stall_window=...)`` just like the sweep mode."""
    if sweeps is not None:
        from .sweep import SweepConfig

        fwd, bwd = SpTRSV.build_pair(
            L, strategy="sweep", rewrite=None, backend=backend,
            sweep=SweepConfig(k=sweeps, residual_tol=sweep_tol,
                              fallback=None),
            guard=guard)
    else:
        fwd, bwd = SpTRSV.build_pair(L, strategy=strategy, rewrite=rewrite,
                                     backend=backend, guard=guard)

    def apply(r: jnp.ndarray) -> jnp.ndarray:
        return bwd.solve(fwd.solve(r))

    return apply


def make_ic_preconditioner_batched(
    L: CSRMatrix,
    *,
    strategy: str = "levelset",
    rewrite: Optional[RewriteConfig] = RewriteConfig(thin_threshold=2),
    sweeps: Optional[int] = None,
    sweep_tol: Optional[float] = None,
    backend=None,
    guard=None,
) -> Callable[[jnp.ndarray], jnp.ndarray]:
    """Batched z = (L Lᵀ)^{-1} R for R: (n, m).

    The executors are batch-polymorphic, so this *is*
    :func:`make_ic_preconditioner` — both triangular solves (forward and
    transpose) operate column-wise on (n, m) arrays.  Kept as a named entry
    point so batched PCG call sites read explicitly and stay stable if the
    single-RHS path ever specializes."""
    return make_ic_preconditioner(L, strategy=strategy, rewrite=rewrite,
                                  sweeps=sweeps, sweep_tol=sweep_tol,
                                  backend=backend, guard=guard)


@jax.jit
def _matvec(pattern, vals: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """``A v`` for ``A`` as :func:`~repro.core.codegen.build_spmv` stores
    it (DIA or ELL), ``v`` (n,) or (n, m).

    The values, and ELL's columns, are arguments, not constants baked into
    the program, so jit's cache keys on shapes, dtypes and DIA's offsets
    alone: every operator of one shape and pattern shares one trace, and a
    solve after the first traces nothing.  Each trace counts one
    ``pcg.matvec_traces``."""
    obs.count(obs.MATVEC_TRACES)
    return spmv(pattern, vals, v)


def _matvec_of(A: CSRMatrix, dtype) -> Callable[[jnp.ndarray], jnp.ndarray]:
    """``v -> A v``: ``A`` in the format :func:`build_spmv` picks, put on
    the device once, values in the solve's ``dtype``, bound to the shared
    :func:`_matvec`."""
    pattern, vals = build_spmv(A)
    return functools.partial(_matvec, jax.device_put(pattern),
                             jnp.asarray(vals, dtype=dtype))


def _read(v) -> float:
    """One host read of a device value: a ``pcg.readback`` span, counted."""
    obs.count(obs.READBACKS)
    with TraceAnnotation(obs.PCG_READBACK):
        return float(v)


def pcg(A: CSRMatrix, b: jnp.ndarray,
        M_inv: Optional[Callable] = None,
        *, tol: float = 1e-8, maxiter: int = 500,
        stall_window: int = 0) -> PCGResult:
    """Standard PCG on SPD A (host loop; each iteration jit-executed).

    ``stall_window`` (0 = off) enables tolerance-aware iteration control for
    inexact preconditioners (``make_ic_preconditioner(..., sweeps=k)``): if
    the residual norm fails to improve on its running best for that many
    consecutive iterations, the loop stops and returns the best-so-far
    iterate as non-converged instead of burning the rest of ``maxiter`` on a
    stagnated recurrence — the signature that ``k`` sweeps stopped being a
    useful contraction at the requested ``tol``.

    Each host read of a device value is a ``pcg.readback`` span and one
    ``pcg.readbacks`` count (:mod:`repro.core.obs`): two before the loop and
    two per iteration."""
    with TraceAnnotation(obs.PCG_SETUP):
        x = jnp.zeros_like(b)
        matvec = _matvec_of(A, x.dtype)
        r = b - matvec(x)
        # Initialize the residual before the loop (maxiter=0 must return a
        # well-formed result, not hit an unbound `res`), and guard b_norm == 0
        # the same way pcg_batched does — otherwise b = 0 makes the tolerance
        # test `res <= 0`, which never fires despite x = 0 being exact.
        res = _read(jnp.linalg.norm(r))
        b_norm = _read(jnp.linalg.norm(b))
        if b_norm == 0.0:
            b_norm = 1.0
        if res <= tol * b_norm:
            return PCGResult(x, 0, res, True)
        z = M_inv(r) if M_inv else r
        p = z
        rz = jnp.vdot(r, z)
    best_res = res
    stall = 0
    for it in range(maxiter):
        with TraceAnnotation(obs.PCG_ITER):
            obs.count(obs.ITERATIONS)
            Ap = matvec(p)
            pap = jnp.vdot(p, Ap)
            if _read(pap) == 0.0:
                # Lanczos breakdown (p in the null space of the Krylov
                # recurrence, e.g. A = 0 or an indefinite M).  pcg_batched
                # guards this division; the unbatched path silently produced
                # NaN x with converged=False unset.  Return the last finite
                # iterate as a well-formed non-converged result.
                return PCGResult(x, it, res, False)
            alpha = rz / pap
            x = x + alpha * p
            r = r - alpha * Ap
            res = _read(jnp.linalg.norm(r))
            if res <= tol * b_norm:
                return PCGResult(x, it + 1, res, True)
            if stall_window > 0:
                if res < 0.999 * best_res:
                    best_res, stall = res, 0
                else:
                    stall += 1
                    if stall >= stall_window:
                        return PCGResult(x, it + 1, res, False)
            z = M_inv(r) if M_inv else r
            rz_new = jnp.vdot(r, z)
            p = z + (rz_new / rz) * p
            rz = rz_new
    return PCGResult(x, maxiter, res, False)


def pcg_batched(A: CSRMatrix, B: jnp.ndarray,
                M_inv: Optional[Callable] = None,
                *, tol: float = 1e-8,
                maxiter: int = 500) -> BatchedPCGResult:
    """m independent PCG solves A x_j = B[:, j], advanced in lockstep.

    One batched SpMV and one batched preconditioner apply (two multi-RHS
    SpTRSVs) per iteration serve *all* columns — the analysis/rewriting cost
    and every kernel launch amortize over the batch, which is the workload
    the paper's specialization story targets (same L, many b).  Per-column
    α/β keep the recurrences mathematically identical to m separate runs;
    converged columns freeze (masked updates) so late columns can keep
    iterating without perturbing early ones.
    """
    assert B.ndim == 2, f"pcg_batched expects B: (n, m); got {B.shape}"
    m = B.shape[1]
    X = jnp.zeros_like(B)
    matvec = _matvec_of(A, X.dtype)
    R = B - matvec(X)
    Z = M_inv(R) if M_inv else R
    P = Z
    rz = jnp.sum(R * Z, axis=0)                      # (m,)
    b_norm = np.asarray(jnp.linalg.norm(B, axis=0))  # (m,)
    b_norm = np.where(b_norm == 0.0, 1.0, b_norm)
    iters = np.full((m,), maxiter, dtype=np.int64)
    done = np.zeros((m,), dtype=bool)
    res = np.asarray(jnp.linalg.norm(R, axis=0))
    # columns already at tolerance (e.g. zero RHS) converge in 0 iterations
    done |= res <= tol * b_norm
    iters[done] = 0
    for it in range(maxiter):
        if done.all():
            break
        AP = matvec(P)
        pap = jnp.sum(P * AP, axis=0)
        active = jnp.asarray(~done)
        # frozen columns get α = 0 (their P may be degenerate — guard the
        # division as well so no NaN leaks into X via 0 * inf)
        alpha = jnp.where(active, rz / jnp.where(pap == 0, 1.0, pap), 0.0)
        X = X + alpha[None, :] * P
        R = R - alpha[None, :] * AP
        res = np.asarray(jnp.linalg.norm(R, axis=0))
        newly = (~done) & (res <= tol * b_norm)
        iters[newly] = it + 1
        done |= newly
        if done.all():
            break
        Z = M_inv(R) if M_inv else R
        rz_new = jnp.sum(R * Z, axis=0)
        beta = jnp.where(jnp.asarray(~done), rz_new / jnp.where(rz == 0, 1.0, rz), 0.0)
        P = Z + beta[None, :] * P
        rz = rz_new
    return BatchedPCGResult(
        x=X, iters=iters, residual=res, converged=done.copy())
