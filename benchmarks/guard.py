"""Guarded execution benchmark: verification overhead, mixed-precision
refinement quality, and the breakdown machinery under injected faults.

The guard layer (``SpTRSV.build(..., guard=...)``) adds exactly one fused
componentwise residual pass + one ratio readback per solve.  This benchmark
prices that guarantee and checks the two claims the robustness PR makes:

* **Overhead** — a guarded fp64 solve on a lung2-class factor costs at most
  a few percent over the unguarded solve (the residual pass is one ELL
  SpMV against hundreds of barrier-separated level launches);
* **Mixed precision** — bf16 value storage + fp32 accumulation + iterative
  refinement against the fp64 residual recovers fp64-class componentwise
  accuracy (``<= 128·eps(fp64)``) within a small, fixed number of
  refinement steps.

``--smoke`` asserts both (guarded fp64 overhead <= 1.15x unguarded;
bf16+refine residual within ``128·eps(fp64)`` in <= 3 steps) plus that the
fallback breakdown path actually fires under an injected zero pivot — the
CI tie-in for the fault harness.  ``--json PATH`` writes the shared-schema
perf-trajectory artifact.

Usage::

    python -m benchmarks.guard                              # lung2-scale
    python -m benchmarks.guard --smoke --json BENCH_guard.json   # CI
"""
from __future__ import annotations

import argparse
import time

import jax.numpy as jnp
import numpy as np

from jax import enable_x64
from repro.core import GuardConfig, SpTRSV
from repro.core.sweep import default_residual_tol
from repro.sparse import inject_values, lung2_like

try:  # runnable both as `python -m benchmarks.guard` and as a file
    from .common import emit, flush_csv, timeit, write_bench_json
except ImportError:  # pragma: no cover
    from common import emit, flush_csv, timeit, write_bench_json

MAX_OVERHEAD = 1.15
MAX_REFINE_STEPS = 3


def run(*, smoke: bool = False, json_path: str = ""):
    print("== guard: verified execution overhead + mixed-precision refine ==")
    with enable_x64():
        if smoke:
            # Deep level structure (~1.1k levels) like real lung2: the solve
            # is launch-bound, the residual check is one fused SpMV — the
            # regime the overhead bound is a claim about.
            L = lung2_like(scale=0.05, fat_levels=20, thin_run=60,
                           dtype=np.float64)
            iters, warmup = 10, 3
        else:
            L = lung2_like(scale=1.0, dtype=np.float64)
            iters, warmup = 5, 2
        emit("guard.rows", L.n)
        emit("guard.nnz", L.nnz)
        tol = default_residual_tol(np.float64)
        emit("guard.residual_tol", f"{tol:.2e}")
        results: dict = {"rows": L.n, "nnz": L.nnz, "residual_tol": tol}

        rng = np.random.default_rng(0)
        b = jnp.asarray(rng.standard_normal(L.n))

        # -- unguarded fp64 baseline ------------------------------------
        t0 = time.perf_counter()
        s_plain = SpTRSV.build(L, strategy="levelset")
        s_plain.solve(b).block_until_ready()
        plain_build = time.perf_counter() - t0
        plain_solve = timeit(s_plain.solve, b, iters=iters, warmup=warmup)
        emit("guard.unguarded.build_s", round(plain_build, 4), "s")
        emit("guard.unguarded.solve_s", f"{plain_solve:.3e}", "s")
        results["unguarded"] = dict(build_s=plain_build, solve_s=plain_solve)

        # -- guarded fp64: one residual pass + one readback per solve ----
        t0 = time.perf_counter()
        s_g = SpTRSV.build(L, strategy="levelset", guard=True)
        np.asarray(s_g.solve(b))  # guard solve returns post-readback
        g_build = time.perf_counter() - t0
        g_solve = timeit(s_g.solve, b, iters=iters, warmup=warmup)
        st = s_g.guard.stats
        overhead = g_solve / plain_solve
        emit("guard.guarded.build_s", round(g_build, 4), "s")
        emit("guard.guarded.solve_s", f"{g_solve:.3e}", "s")
        emit("guard.guarded.overhead", round(overhead, 3), "x")
        emit("guard.guarded.residual_ratio", f"{st.last_residual_ratio:.2e}",
             tol=f"{tol:.2e}")
        emit("guard.guarded.refine_steps", st.last_refine_steps)
        results["guarded"] = dict(
            build_s=g_build, solve_s=g_solve, overhead=overhead,
            residual_ratio=st.last_residual_ratio,
            refine_steps=st.last_refine_steps, verified=st.verified)

        # -- mixed precision: bf16 values + fp32 accum + fp64 refinement -
        t0 = time.perf_counter()
        s_mx = SpTRSV.build(
            L, strategy="levelset",
            guard=GuardConfig(precision="mixed",
                              refine_steps=MAX_REFINE_STEPS))
        np.asarray(s_mx.solve(b))
        mx_build = time.perf_counter() - t0
        mx_solve = timeit(s_mx.solve, b, iters=iters, warmup=warmup)
        stm = s_mx.guard.stats
        emit("guard.mixed.build_s", round(mx_build, 4), "s")
        emit("guard.mixed.solve_s", f"{mx_solve:.3e}", "s")
        emit("guard.mixed.residual_ratio", f"{stm.last_residual_ratio:.2e}",
             tol=f"{tol:.2e}")
        emit("guard.mixed.refine_steps", stm.last_refine_steps,
             max=MAX_REFINE_STEPS)
        emit("guard.mixed.verified", stm.verified)
        results["mixed"] = dict(
            build_s=mx_build, solve_s=mx_solve,
            residual_ratio=stm.last_residual_ratio,
            refine_steps=stm.last_refine_steps, verified=stm.verified)

        # -- breakdown machinery: injected zero pivot must route through
        #    the pivot-repaired fallback and stay finite -------------------
        s_fb = SpTRSV.build(L, strategy="levelset",
                            guard=GuardConfig(on_breakdown="fallback",
                                              refine_steps=1))
        s_fb.refresh(inject_values(L, "zero_pivot", seed=7), validate=False)
        x_fb = np.asarray(s_fb.solve(b))
        stf = s_fb.guard.stats
        emit("guard.fallback.fired", stf.fallback_solves)
        emit("guard.fallback.pivot_alarms", stf.pivot_alarms)
        emit("guard.fallback.finite", bool(np.isfinite(x_fb).all()))
        results["fallback"] = dict(
            fired=stf.fallback_solves, pivot_alarms=stf.pivot_alarms,
            finite=bool(np.isfinite(x_fb).all()))

        if smoke:
            # PR-9 acceptance: bf16 storage + refinement recovers fp64-class
            # componentwise accuracy within the step budget, the guarded
            # fp64 path costs <= 1.15x the unguarded one, and the injected
            # zero-pivot breakdown actually exercises the fallback.
            assert stm.verified == stm.solves, stm.report()
            assert stm.last_residual_ratio <= tol, (
                f"mixed residual {stm.last_residual_ratio:.2e} > "
                f"tol {tol:.2e}")
            assert stm.last_refine_steps <= MAX_REFINE_STEPS, stm.report()
            assert overhead <= MAX_OVERHEAD, (
                f"guarded overhead {overhead:.3f}x > {MAX_OVERHEAD}x")
            assert st.verified == st.solves, st.report()
            assert st.last_refine_steps == 0, st.report()
            assert stf.fallback_solves == 1, stf.report()
            assert stf.pivot_alarms >= 1, stf.report()
            assert np.isfinite(x_fb).all()
            print("  smoke assertions passed "
                  f"(overhead {overhead:.3f}x <= {MAX_OVERHEAD}x, mixed "
                  f"residual {stm.last_residual_ratio:.1e} <= {tol:.1e} in "
                  f"{stm.last_refine_steps} step(s), fallback fired)")

        if json_path:
            write_bench_json(json_path, "guard", results,
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
