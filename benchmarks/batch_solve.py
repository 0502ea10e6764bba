"""Multi-RHS SpTRSV throughput sweep: per-solve wall time vs batch width.

The paper amortizes analysis cost over many solves of one L; batching
amortizes *execution* overhead the same way — per-level launch cost and the
underfilled lane dimension of thin levels are paid once per level per batch,
not once per level per RHS.  On a lung2-class matrix (hundreds of levels,
most of them 2 rows wide) this is the difference between a latency-bound and
a throughput-bound solve.

Sweeps ``m ∈ {1, 8, 64, 256}`` over the pure-JAX strategies (and the Pallas
kernels in interpret mode when ``--pallas`` is given — interpret is far too
slow for wall-clock claims, so it is excluded from the default sweep) and
reports seconds per *solve* (batch time / m), which should fall — or at
worst stay flat — as m grows.

Usage::

    python -m benchmarks.batch_solve             # full sweep
    python -m benchmarks.batch_solve --dry-run   # tiny smoke (CI)
"""
from __future__ import annotations

import argparse

import jax.numpy as jnp
import numpy as np

from repro.core import RewriteConfig, SpTRSV
from repro.sparse import lung2_like

try:  # runnable both as `python -m benchmarks.batch_solve` and as a file
    from .common import emit, flush_csv, timeit, write_bench_json
except ImportError:  # pragma: no cover
    from common import emit, flush_csv, timeit, write_bench_json


def run(*, dry_run: bool = False, pallas: bool = False,
        json_path: str = ""):
    print("== batch_solve: per-solve wall time vs batch width ==")
    if dry_run:
        L = lung2_like(scale=0.02, fat_levels=4, thin_run=6, dtype=np.float32)
        widths = (1, 8)
        iters, warmup = 2, 1
    else:
        # lung2_like(478 levels)-class input: scale=1.0 gives ~110k rows,
        # ~480 levels, 94% of them 2 rows wide.
        L = lung2_like(scale=1.0, dtype=np.float32)
        widths = (1, 8, 64, 256)
        iters, warmup = 5, 2
    emit("batch.rows", L.n)
    emit("batch.nnz", L.nnz)

    strategies = ["levelset", "levelset_unroll"]
    if pallas:
        strategies += ["pallas_level", "pallas_fused"]

    rng = np.random.default_rng(0)
    results = {}
    for strategy in strategies:
        for rewrite, tag in ((None, "base"),
                             (RewriteConfig(thin_threshold=2), "rewrite")):
            s = SpTRSV.build(L, strategy=strategy, rewrite=rewrite)
            base_per_solve = None
            for m in widths:
                B = jnp.asarray(
                    rng.normal(size=(L.n, m)).astype(np.float32))
                arg = B[:, 0] if m == 1 else B
                t = timeit(s.solve, arg, iters=iters, warmup=warmup)
                per_solve = t / m
                if base_per_solve is None:
                    base_per_solve = per_solve
                speedup = base_per_solve / per_solve
                emit(
                    f"batch.{strategy}.{tag}.m{m}.per_solve_ms",
                    f"{per_solve * 1e3:.3f}", "ms",
                    batch=m, speedup_vs_m1=f"{speedup:.2f}x",
                )
                results[(strategy, tag, m)] = per_solve
    # Headline: did per-solve time improve (or at least not regress) with m?
    for strategy in strategies:
        for tag in ("base", "rewrite"):
            series = [results[(strategy, tag, m)] for m in widths]
            trend = "improving" if series[-1] <= series[0] else "REGRESSING"
            emit(f"batch.{strategy}.{tag}.trend", trend,
                 m1_ms=f"{series[0]*1e3:.3f}",
                 mmax_ms=f"{series[-1]*1e3:.3f}")
    if json_path:
        flat = {f"{strategy}.{tag}.m{m}": {"per_solve_s": t}
                for (strategy, tag, m), t in results.items()}
        write_bench_json(json_path, "batch", flat, n=L.n, nnz=L.nnz)
    return results


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dry-run", action="store_true",
                    help="tiny matrix, 2 widths, 2 iters (CI smoke)")
    ap.add_argument("--pallas", action="store_true",
                    help="include Pallas kernels (interpret mode; slow)")
    ap.add_argument("--json", default="", help="write shared-schema JSON here")
    ap.add_argument("--csv", default=None, help="write results CSV here")
    args = ap.parse_args(argv)
    run(dry_run=args.dry_run, pallas=args.pallas, json_path=args.json)
    if args.csv:
        flush_csv(args.csv)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
