"""Speculative sweep benchmark: sync points, solve time, residual quality.

The level-set executor — even coarsened — pays one host/device barrier per
schedule segment.  The ``sweep`` strategy replaces the whole dependency
schedule with k data-parallel Jacobi sweeps ``x <- D^{-1}(b - N x)`` over
*all* rows: zero intra-solve barriers, one residual-verification readback
per solve, and an exact fallback for the (certified-away) non-converged
case.  On a lung2-class matrix that trades ~hundreds of barrier-separated
segments for a single fused region.

Reported per configuration:

* ``sync_points``   barriers per solve (schedule segments; 1 for sweep —
  the verification readback)
* ``build_s``       analysis + trace + compile time
* ``solve_s``       median per-solve wall time
* ``max_err``       vs the row-serial oracle solve
* ``residual``      sweep's componentwise residual ratio vs its tolerance

``--smoke`` runs a scaled-down matrix and *asserts* the PR-6 acceptance
criteria: >= 5x fewer sync points than the coarsened level-set schedule,
residual within the verification tolerance, and zero fallback solves — a
CI guard against convergence or certification regressions the unit tests
cannot see.  ``--json PATH`` writes the result dict for artifact diffing.

Usage::

    python -m benchmarks.sweep                               # lung2-scale
    python -m benchmarks.sweep --smoke --json BENCH_sweep.json   # CI
"""
from __future__ import annotations

import argparse
import time

import jax.numpy as jnp
import numpy as np

from repro.core import SpTRSV
from repro.core.sweep import default_residual_tol
from repro.sparse import lung2_like

try:  # runnable both as `python -m benchmarks.sweep` and as a file
    from .common import emit, flush_csv, timeit, write_bench_json
except ImportError:  # pragma: no cover
    from common import emit, flush_csv, timeit, write_bench_json


def run(*, smoke: bool = False, json_path: str = ""):
    print("== sweep: speculative solve-then-correct vs level-set ==")
    if smoke:
        L = lung2_like(scale=0.05, fat_levels=6, thin_run=10, dtype=np.float32)
        iters, warmup = 10, 2
    else:
        L = lung2_like(scale=1.0, dtype=np.float32)
        iters, warmup = 5, 2
    emit("sweep.rows", L.n)
    emit("sweep.nnz", L.nnz)

    rng = np.random.default_rng(0)
    b = jnp.asarray(rng.standard_normal(L.n).astype(np.float32))
    oracle = np.asarray(SpTRSV.build(L, strategy="serial").solve(b))
    results: dict = {"rows": L.n, "nnz": L.nnz}

    # coarsened level-set baseline: one barrier per schedule segment
    t0 = time.perf_counter()
    s_ls = SpTRSV.build(L, strategy="levelset", coarsen=True)
    s_ls.solve(b).block_until_ready()
    ls_build = time.perf_counter() - t0
    ls_sync = s_ls.schedule.num_segments
    ls_solve = timeit(s_ls.solve, b, iters=iters, warmup=warmup)
    ls_err = float(np.abs(np.asarray(s_ls.solve(b)) - oracle).max())
    emit("sweep.levelset.sync_points", ls_sync)
    emit("sweep.levelset.build_s", round(ls_build, 4), "s")
    emit("sweep.levelset.solve_s", f"{ls_solve:.3e}", "s")
    emit("sweep.levelset.max_err", f"{ls_err:.2e}")
    results["levelset"] = dict(sync_points=ls_sync, build_s=ls_build,
                               solve_s=ls_solve, err=ls_err)

    # speculative sweep: zero intra-solve barriers, one verification readback
    t0 = time.perf_counter()
    s_sw = SpTRSV.build(L, strategy="sweep")
    s_sw.solve(b).block_until_ready()
    sw_build = time.perf_counter() - t0
    sw_solve = timeit(s_sw.solve, b, iters=iters, warmup=warmup)
    sw_err = float(np.abs(np.asarray(s_sw.solve(b)) - oracle).max())
    st = s_sw.sweep_stats
    tol = default_residual_tol(L.dtype)
    sw_sync = 1  # the verification readback; the k sweeps share one region
    emit("sweep.sweep.sync_points", sw_sync)
    emit("sweep.sweep.k", st.k)
    emit("sweep.sweep.build_s", round(sw_build, 4), "s")
    emit("sweep.sweep.solve_s", f"{sw_solve:.3e}", "s")
    emit("sweep.sweep.max_err", f"{sw_err:.2e}")
    emit("sweep.sweep.residual_ratio", f"{st.last_residual_ratio:.2e}",
         tol=f"{tol:.2e}")
    emit("sweep.sweep.fallback_solves", st.fallback_solves)
    results["sweep"] = dict(sync_points=sw_sync, k=st.k, build_s=sw_build,
                            solve_s=sw_solve, err=sw_err,
                            residual_ratio=st.last_residual_ratio,
                            residual_tol=tol,
                            fallback_solves=st.fallback_solves)

    ratio = ls_sync / sw_sync
    emit("sweep.sync_reduction", round(ratio, 1), "x")
    emit("sweep.solve_speedup", round(ls_solve / sw_solve, 3), "x")
    results["sync_reduction"] = ratio
    results["solve_speedup"] = ls_solve / sw_solve

    # auto planner on the same matrix: record what it picked and why
    s_auto = SpTRSV.build(L, strategy="auto")
    err_auto = float(np.abs(np.asarray(s_auto.solve(b)) - oracle).max())
    emit("sweep.auto.strategy", s_auto.strategy,
         planned_sweeps=s_auto.plan.sweep_k)
    emit("sweep.auto.max_err", f"{err_auto:.2e}")
    results["auto"] = dict(strategy=s_auto.strategy,
                           planned_sweeps=s_auto.plan.sweep_k, err=err_auto)

    if smoke:
        # PR-6 acceptance: the speculative path must beat the coarsened
        # schedule on sync points by >= 5x on a lung2-class matrix, stay
        # within its own verification tolerance (so no solve ever falls
        # back), and match the oracle to fp tolerance.
        assert ratio >= 5.0, f"sync reduction {ratio:.1f}x < 5x"
        assert st.last_residual_ratio <= tol, (
            f"residual {st.last_residual_ratio:.2e} > tol {tol:.2e}")
        assert st.fallback_solves == 0, st.report()
        assert sw_err < 1e-4, sw_err
        assert err_auto < 1e-4, err_auto
        print("  smoke assertions passed "
              f"({ratio:.0f}x fewer sync points, residual "
              f"{st.last_residual_ratio:.1e} <= {tol:.1e}, 0 fallbacks)")

    if json_path:
        write_bench_json(json_path, "sweep", results,
                         n=results["rows"], nnz=results["nnz"])
    return results


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small matrix + acceptance assertions (CI)")
    ap.add_argument("--json", default="", help="write results JSON here")
    ap.add_argument("--csv", default="")
    args = ap.parse_args()
    run(smoke=args.smoke, json_path=args.json)
    if args.csv:
        flush_csv(args.csv)
