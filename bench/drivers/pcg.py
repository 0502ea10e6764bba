"""Whole preconditioned-CG solves, back to back, each on a fresh seeded
right-hand side ``b = A x*``.

Traffic keys: ``tol`` and ``maxiter`` (handed to the solver), ``pool``
(right-hand sides kept on the device and cycled through, more than a window
completes), ``warmup_solves`` and ``trace_solves``.  Each ``x*`` is a fresh N(0, I)
field drawn from the run's seed.  The window runs whole solves until
``seconds`` have passed and ends on a solve boundary; every answer of the
window is checked.

The program's entry is ``SpTRSV.build_pair(L, strategy="auto",
rewrite=None)`` composed as ``make_ic_preconditioner`` composes it (one
forward and one transpose triangular solve per apply), and ``pcg``.  The
driver builds the pair itself so that the planner's counts are read from
the two solvers it holds.
"""
from __future__ import annotations

import time

import numpy as np

from bench import harness, oracle
from bench.sparse import Csr


class Program:
    """The system under test: the repository's IC(0)-preconditioned CG."""

    def __init__(self, A: Csr, L: Csr, *, tol: float, maxiter: int):
        from repro.core import CSRMatrix, SpTRSV

        def csr(M):
            return CSRMatrix(M.indptr, M.indices, M.data, (M.n, M.n))

        self.A, self.tol, self.maxiter = csr(A), tol, maxiter
        self.fwd, self.bwd = SpTRSV.build_pair(csr(L), strategy="auto",
                                               rewrite=None)

    def precond(self, r):
        return self.bwd.solve(self.fwd.solve(r))

    def solve(self, b):
        from repro.core import pcg as pcg_module

        res = pcg_module.pcg(self.A, b, self.precond, tol=self.tol,
                             maxiter=self.maxiter)
        return res.x, res.iters, res.converged

    def layer_objects(self) -> list:
        """The preconditioner's forward and transpose solvers."""
        return [self.fwd, self.bwd]


def right_hand_sides(A: Csr, seed: int, count: int) -> list:
    """``count`` right-hand sides ``b = A x*``, each ``x*`` a fresh N(0, I)
    field from ``seed``; computed in float64, rounded to ``A``'s dtype."""
    gen = harness.rng(seed, 1)
    A64 = A.scipy()
    return [(A64 @ gen.standard_normal(A.n)).astype(A.data.dtype)
            for _ in range(count)]


def run(cell, matrices: dict, seed: int, seconds: float, trace_dir, t0: float,
        program=None) -> dict:
    """Build, warm up, run whole solves for the window, check every answer.
    ``program(A, L, tol=..., maxiter=...)`` replaces :class:`Program`."""
    import jax

    tr = cell.traffic
    A, L = matrices["A"], matrices["L"]
    t = time.perf_counter()
    sut = (program or Program)(A, L, tol=tr["tol"], maxiter=tr["maxiter"])
    build_s = time.perf_counter() - t

    pool_host = right_hand_sides(A, seed, tr["pool"] + tr["warmup_solves"])
    pool = [jax.device_put(b) for b in pool_host]
    jax.block_until_ready(pool)
    t = time.perf_counter()
    for i in range(tr["warmup_solves"]):
        jax.block_until_ready(sut.solve(pool[tr["pool"] + i])[0])
    warmup_s = time.perf_counter() - t

    counter = harness.CompileCounter()
    answers, iters, times, failed = [], [], [], 0
    setup_s = time.perf_counter() - t0
    with counter.active(), harness.traced_window(trace_dir):
        start = time.perf_counter()
        end = start
        i = 0
        while (i < tr["trace_solves"] if trace_dir is not None
               else end - start < seconds):
            b = pool[i % tr["pool"]]
            if trace_dir is not None:
                with jax.profiler.TraceAnnotation("bench.pcg_solve"):
                    x, it, ok = sut.solve(b)
                    jax.block_until_ready(x)
            else:
                x, it, ok = sut.solve(b)
                jax.block_until_ready(x)
            t = time.perf_counter()
            times.append(t - end)
            end = t
            answers.append((i, x))
            iters.append(int(it))
            failed += not ok
            i += 1
    window_s = end - start
    peak = harness.memory_peak_bytes()

    readings = [oracle.pcg_errors(A, pool_host[j % tr["pool"]], np.asarray(x))
                for j, x in answers]
    return {
        "end_to_end": {"setup_s": setup_s, "pcg_s": window_s / len(answers)},
        "attempted": len(answers), "failed": failed,
        "readings": oracle.worst(readings), "checked": len(readings),
        "compiles": counter.report(), "memory_peak_bytes": peak,
        "window": {"calls": len(answers), "seconds": window_s,
                   "iterations": iters, "solve_s": times},
        "layer": {"objects": sut.layer_objects()
                  if hasattr(sut, "layer_objects") else [],
                  "build_s": build_s, "warmup_s": warmup_s,
                  "calls": len(answers), "pcg_iters": iters,
                  "n": A.n, "nnz": L.nnz},
    }
