"""Schedule-coarsening benchmark: sync points, build time, per-solve time.

The paper removes barriers by rewriting equations; coarsening removes them
by *merging* adjacent levels under a cost model (arXiv:2503.05408's lever,
applied to our segment schedule).  On a lung2-class matrix the level-set
schedule has ~478 segments — 478 barrier-separated XLA program regions —
while the coarsened schedule packs thin runs into super-level slabs whose
intra-slab chains run back-to-back inside one segment.

Reported per configuration:

* ``segments``       barrier count of the executed schedule (sync points)
* ``build_s``        schedule build + executor trace + compile time
* ``solve_s``        median per-solve wall time
* ``max_err``        vs the row-serial oracle solve

``--smoke`` runs a scaled-down matrix and *asserts* the PR-3 acceptance
criteria: >= 4x fewer executed segments, oracle-match to fp tolerance, and
per-solve time within noise of the uncoarsened baseline — a CI guard
against schedule-size regressions the unit tests cannot see.

Usage::

    python -m benchmarks.coarsen             # full lung2-scale run
    python -m benchmarks.coarsen --smoke     # CI smoke w/ assertions
    python -m benchmarks.coarsen --smoke --json BENCH_coarsen.json
"""
from __future__ import annotations

import argparse
import time

import jax.numpy as jnp
import numpy as np

from repro.core import SpTRSV
from repro.core.coarsen import CoarsenConfig, coarsen_stats
from repro.sparse import lung2_like

try:  # runnable both as `python -m benchmarks.coarsen` and as a file
    from .common import emit, flush_csv, timeit, write_bench_json
except ImportError:  # pragma: no cover
    from common import emit, flush_csv, timeit, write_bench_json


def run(*, smoke: bool = False, json_path: str = ""):
    print("== coarsen: synchronization-aware level merging ==")
    if smoke:
        L = lung2_like(scale=0.05, fat_levels=8, thin_run=12, dtype=np.float32)
        iters, warmup = 10, 2  # sub-ms solves: medians need samples on CI
    else:
        L = lung2_like(scale=1.0, dtype=np.float32)
        iters, warmup = 5, 2
    emit("coarsen.rows", L.n)
    emit("coarsen.nnz", L.nnz)

    rng = np.random.default_rng(0)
    b = jnp.asarray(rng.standard_normal(L.n).astype(np.float32))
    oracle = np.asarray(SpTRSV.build(L, strategy="serial").solve(b))

    results = {}
    for coarsen, tag in ((None, "base"), (True, "coarsen")):
        t0 = time.perf_counter()
        s = SpTRSV.build(L, strategy="levelset", coarsen=coarsen)
        s.solve(b).block_until_ready()  # include trace+compile in build_s
        build_s = time.perf_counter() - t0
        solve_s = timeit(s.solve, b, iters=iters, warmup=warmup)
        err = float(np.abs(np.asarray(s.solve(b)) - oracle).max())
        segs = s.schedule.num_segments
        emit(f"coarsen.{tag}.segments", segs)
        emit(f"coarsen.{tag}.build_s", round(build_s, 4), "s")
        emit(f"coarsen.{tag}.solve_s", f"{solve_s:.3e}", "s")
        emit(f"coarsen.{tag}.max_err", f"{err:.2e}")
        results[tag] = dict(segments=segs, build_s=build_s,
                            solve_s=solve_s, err=err, schedule=s.schedule)

    st = coarsen_stats(results["base"]["schedule"],
                       results["coarsen"]["schedule"])
    print("  " + st.summary())
    ratio = results["base"]["segments"] / max(results["coarsen"]["segments"], 1)
    speedup = results["base"]["solve_s"] / results["coarsen"]["solve_s"]
    emit("coarsen.segment_reduction", round(ratio, 2), "x")
    emit("coarsen.solve_speedup", round(speedup, 3), "x")
    emit("coarsen.build_speedup",
         round(results["base"]["build_s"] / results["coarsen"]["build_s"], 3),
         "x")

    # auto planner on the same matrix — must build and match the oracle
    s_auto = SpTRSV.build(L, strategy="auto")
    err_auto = float(np.abs(np.asarray(s_auto.solve(b)) - oracle).max())
    emit("coarsen.auto.strategy", s_auto.strategy,
         coarsen=s_auto.plan.coarsen)
    emit("coarsen.auto.max_err", f"{err_auto:.2e}")

    if smoke:
        # PR-3 acceptance: >= 4x fewer sync points, fp-tolerance solution,
        # per-solve time no worse than the uncoarsened baseline.  The
        # deterministic asserts guard the real regressions; the timing one
        # gets generous slack because a sub-millisecond median on a shared
        # CI runner is noisy — it exists to catch gross blowups (e.g. a fat
        # wavefront slipping into a chain is a ~10x padded-work change).
        assert ratio >= 4.0, f"segment reduction {ratio:.1f}x < 4x"
        assert results["coarsen"]["err"] < 1e-5, results["coarsen"]["err"]
        assert err_auto < 1e-5, err_auto
        assert results["coarsen"]["solve_s"] <= 2.5 * results["base"]["solve_s"], (
            f"coarsened solve {results['coarsen']['solve_s']:.3e}s vs "
            f"baseline {results['base']['solve_s']:.3e}s")
        print("  smoke assertions passed "
              f"({ratio:.1f}x fewer segments, err {results['coarsen']['err']:.1e})")

    if json_path:
        results["segment_reduction"] = ratio
        results["solve_speedup"] = speedup
        results["auto"] = dict(strategy=s_auto.strategy,
                               coarsen=s_auto.plan.coarsen, err=err_auto)
        write_bench_json(json_path, "coarsen", results, n=L.n, nnz=L.nnz)
    return results


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small matrix + acceptance assertions (CI)")
    ap.add_argument("--json", default="", help="write shared-schema JSON here")
    ap.add_argument("--csv", default="")
    args = ap.parse_args()
    run(smoke=args.smoke, json_path=args.json)
    if args.csv:
        flush_csv(args.csv)
