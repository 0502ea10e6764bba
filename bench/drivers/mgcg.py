"""Whole multigrid-preconditioned CG solves, back to back, each on a fresh
seeded right-hand side ``b = A x*``: HPCG's solver.

Traffic keys: ``tol`` and ``maxiter`` (handed to the solver), ``pool``
(right-hand sides kept on the device and cycled through), ``warmup_solves``
solves capped at ``warmup_iters`` iterations, which enqueue every program
once, and ``trace_solves``.  Each ``x*`` is a fresh N(0, I) field drawn from
the run's seed.  The window runs whole solves until ``seconds`` have passed
and ends on a solve boundary.  ``setup_s`` is as ``bench/drivers/pcg.py``
defines it; ``pcg_s`` is the window's time over the CG iterations of its
solves, seconds an iteration.  HPCG times fixed work (sets of 50
iterations); a solve to ``tol`` takes 21-29 iterations as the seeded
right-hand side falls, and a window holds only two or three solves, so time
a solve would follow the seed more than the system.

Every answer of the window is checked (``true_residual``), and so is the
preconditioner itself, since CG reaches ``tol`` under a wrong V-cycle too:
after the window the system's ``M⁻¹`` is applied to a fresh N(0, I) vector
from the seed and held to HPCG's V-cycle in float64 (``vcycle_error``,
``bench/oracle_mg.py``).  White noise carries every frequency, so the
coarse levels' smooth corrections weigh in; a right-hand side ``A x*`` is
mostly rough and would hide them.

The program's entry is ``make_mg_preconditioner`` over every level's
operator and injection map, which builds ``SpTRSV.build_pair(tril(A),
strategy="auto", rewrite=None)`` per level, and ``pcg`` with it as
``M_inv``.
"""
from __future__ import annotations

import time

import numpy as np

from bench import harness, oracle, oracle_mg
from bench.drivers.pcg import right_hand_sides
from bench.sparse import Csr


class Program:
    """The system under test: the repository's MG-preconditioned CG."""

    def __init__(self, levels: list, f2c: list, *, tol: float,
                 maxiter: int):
        from repro.core import CSRMatrix, make_mg_preconditioner

        ops = [CSRMatrix(M.indptr, M.indices, M.data, (M.n, M.n))
               for M in levels]
        self.A, self.tol, self.maxiter = ops[0], tol, maxiter
        self.mg = make_mg_preconditioner(ops, f2c, strategy="auto")

    def solve(self, b, maxiter: int | None = None):
        from repro.core import pcg as pcg_module

        res = pcg_module.pcg(self.A, b, self.mg, tol=self.tol,
                             maxiter=maxiter or self.maxiter)
        return res.x, res.iters, res.converged

    def precondition(self, r):
        """``M⁻¹ r``: the V-cycle ``pcg`` applies."""
        return self.mg(r)

    def layer_objects(self) -> list:
        """``(solver, sweeps)``: each level's forward and transpose solvers,
        with the solves each runs in one V-cycle."""
        return [(s, lv.sweeps) for lv in self.mg.levels
                for s in (lv.fwd, lv.bwd)]


def level_sizes(levels: list, f2c: list) -> list:
    """Per level, the sizes the roofline's bytes are computed from: ``n``,
    ``nnz`` of the operator and of its lower triangle, and ``nnz`` of its
    rows that the next level injects from (0 on the coarsest)."""
    out = []
    for i, A in enumerate(levels):
        row_nnz = np.diff(A.indptr)
        out.append({"n": A.n, "nnz": A.nnz,
                    "nnz_L": int((A.indices <= A.rows()).sum()),
                    "nnz_f2c_rows": int(row_nnz[f2c[i]].sum())
                    if i < len(f2c) else 0})
    return out


def run(cell, matrices: dict, seed: int, seconds: float, trace_dir, t0: float,
        program=None) -> dict:
    """Build, warm up, run whole solves for the window, check every answer
    and one V-cycle.  ``program(levels, f2c, tol=..., maxiter=...)``
    replaces :class:`Program`; like it, it has ``solve`` and
    ``precondition``."""
    import jax
    import jax.numpy as jnp

    tr = cell.traffic
    A: Csr = matrices["A"]
    t = time.perf_counter()
    sut = (program or Program)(matrices["levels"], matrices["f2c"],
                               tol=tr["tol"], maxiter=tr["maxiter"])
    build_s = time.perf_counter() - t

    pool_host = right_hand_sides(A, seed, tr["pool"] + tr["warmup_solves"])
    pool = [jax.device_put(b) for b in pool_host]
    jax.block_until_ready(pool)
    t = time.perf_counter()
    for i in range(tr["warmup_solves"]):
        jax.block_until_ready(
            sut.solve(pool[tr["pool"] + i], maxiter=tr["warmup_iters"])[0])
    # A solve cut at maxiter has enqueued one more V-cycle than its x needs;
    # the device runs programs in the order they were enqueued, so waiting
    # for one more keeps that work out of the window (and out of its trace,
    # where its programs would have no enqueue inside the trace).
    jax.block_until_ready(jnp.zeros(()) + 1)
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
    probe = harness.rng(seed, 2).standard_normal(A.n).astype(A.data.dtype)
    readings.append(oracle_mg.vcycle_errors(
        matrices["levels"], matrices["f2c"], probe,
        np.asarray(sut.precondition(jax.device_put(probe)))))
    return {
        "end_to_end": {"setup_s": setup_s, "pcg_s": window_s / sum(iters)},
        "attempted": len(answers), "failed": failed,
        "readings": oracle.worst(readings), "checked": len(readings),
        "compiles": counter.report(), "memory_peak_bytes": peak,
        "window": {"calls": len(answers), "seconds": window_s,
                   "iterations": iters, "solve_s": times},
        "layer": {"objects": sut.layer_objects()
                  if hasattr(sut, "layer_objects") else [],
                  "build_s": build_s, "warmup_s": warmup_s,
                  "calls": len(answers), "pcg_iters": iters,
                  "mg_levels": level_sizes(matrices["levels"],
                                           matrices["f2c"]),
                  "n": A.n, "nnz": matrices["L"].nnz,
                  "value_bytes": A.data.dtype.itemsize},
    }
