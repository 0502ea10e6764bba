"""Permuted-space packed execution + value-only refresh benchmark.

Measures the two claims of the permuted-execution PR on a lung2-class
matrix:

* ``refresh`` — re-solving the same sparsity pattern with new values
  (every numeric re-factorization of an iterative PCG/IC workload) reuses
  the cached symbolic schedule and the compiled executable:
  ``SpTRSV.refresh`` is one O(nnz) value re-pack, asserted **>= 10x** faster
  than a cold ``SpTRSV.build`` (which pays analysis + packing + trace +
  compile).
* ``permuted vs scatter`` — per-solve wall time of the permuted-space
  packed executor against the legacy per-segment scatter executor for each
  strategy; permuted must be no slower, and is typically faster on the
  levelset paths (contiguous b̂/x̂ slices instead of row-id gathers and
  scatters).

Reported per configuration (also emitted as JSON with ``--json`` for the
CI perf-trajectory artifact):

* ``build_s``      cold build incl. executor trace + compile + first solve
* ``refresh_s``    value-only refresh (cached schedule, no re-trace)
* ``solve_s``      median per-solve wall time (permuted / scatter)
* packed-buffer bytes and padding waste from ``SpTRSV.stats()``

Usage::

    python -m benchmarks.refresh              # full lung2-scale run
    python -m benchmarks.refresh --smoke      # CI smoke w/ assertions
    python -m benchmarks.refresh --smoke --json BENCH_refresh.json
"""
from __future__ import annotations

import argparse
import time

import jax.numpy as jnp
import numpy as np

from repro.core import SpTRSV
from repro.core.csr import CSRMatrix
from repro.sparse import lung2_like

try:  # runnable both as `python -m benchmarks.refresh` and as a file
    from .common import emit, flush_csv, timeit, write_bench_json
except ImportError:  # pragma: no cover
    from common import emit, flush_csv, timeit, write_bench_json


def _new_values(L: CSRMatrix, seed: int) -> np.ndarray:
    """Regenerated values on the same pattern, kept diagonally dominant."""
    rng = np.random.default_rng(seed)
    data = (L.data + 0.05 * rng.standard_normal(L.nnz)).astype(L.dtype)
    data[L.indptr[1:] - 1] += 2.0  # lower-triangular: diagonal last per row
    return data


def run(*, smoke: bool = False, json_path: str = ""):
    print("== refresh: permuted-space packed execution + value-only refresh ==")
    if smoke:
        L = lung2_like(scale=0.05, fat_levels=8, thin_run=12, dtype=np.float32)
        iters, warmup = 20, 3
        strategies = ("levelset", "levelset_unroll", "serial")
    else:
        # full lung2 scale; serial (minutes of scan) and pallas interpret
        # mode are left to --smoke — this run measures the two claims where
        # they matter, on the generated level-set executors
        L = lung2_like(scale=1.0, dtype=np.float32)
        iters, warmup = 5, 2
        strategies = ("levelset", "levelset_unroll")
    emit("refresh.rows", L.n)
    emit("refresh.nnz", L.nnz)

    rng = np.random.default_rng(0)
    b = jnp.asarray(rng.standard_normal(L.n).astype(np.float32))
    oracle = np.asarray(SpTRSV.build(L, strategy="serial").solve(b))
    new_data = _new_values(L, seed=1)
    results: dict = {"n": L.n, "nnz": L.nnz, "strategies": {}}

    for strategy in strategies:
        coarsen = None if strategy == "serial" else True
        row: dict = {}
        for layout in ("permuted", "scatter"):
            t0 = time.perf_counter()
            s = SpTRSV.build(L, strategy=strategy, coarsen=coarsen,
                             layout=layout)
            s.solve(b).block_until_ready()  # trace + compile included
            build_s = time.perf_counter() - t0
            solve_s = timeit(s.solve, b, iters=iters, warmup=warmup)
            err = float(np.abs(np.asarray(s.solve(b)) - oracle).max())
            emit(f"refresh.{strategy}.{layout}.build_s", round(build_s, 4), "s")
            emit(f"refresh.{strategy}.{layout}.solve_s", f"{solve_s:.3e}", "s")
            emit(f"refresh.{strategy}.{layout}.max_err", f"{err:.2e}")
            row[layout] = dict(build_s=build_s, solve_s=solve_s, err=err)
            if layout == "permuted":
                st = s.stats()
                emit(f"refresh.{strategy}.packed_value_bytes",
                     st["packed_value_bytes"], "B")
                emit(f"refresh.{strategy}.padded_value_bytes",
                     st["padded_value_bytes"], "B")
                row["stats"] = {k: st[k] for k in (
                    "packed_value_bytes", "packed_index_bytes",
                    "padded_value_bytes", "permutation_applied", "segments")}
                # value-only refresh: cached schedule, no re-trace/compile
                t0 = time.perf_counter()
                s.refresh(new_data)
                s.solve(b).block_until_ready()  # must hit the jit cache
                refresh_s = time.perf_counter() - t0
                emit(f"refresh.{strategy}.refresh_s",
                     round(refresh_s, 4), "s")
                row["refresh_s"] = refresh_s
                # refreshed solver must match a cold build on the new values
                fresh = SpTRSV.build(
                    CSRMatrix(L.indptr, L.indices, new_data, L.shape),
                    strategy=strategy, coarsen=coarsen)
                rerr = float(np.abs(np.asarray(s.solve(b))
                                    - np.asarray(fresh.solve(b))).max())
                emit(f"refresh.{strategy}.refresh_err", f"{rerr:.2e}")
                row["refresh_err"] = rerr
        speed = row["scatter"]["solve_s"] / row["permuted"]["solve_s"]
        ratio = row["permuted"]["build_s"] / row["refresh_s"]
        emit(f"refresh.{strategy}.permuted_speedup", round(speed, 3), "x")
        emit(f"refresh.{strategy}.refresh_speedup", round(ratio, 1), "x",
             note="cold build / refresh")
        results["strategies"][strategy] = row

    if smoke:
        # Acceptance: refresh >= 10x faster than a cold build; permuted
        # per-solve time no slower than the scatter path (generous slack:
        # sub-millisecond medians on shared CI runners are noisy — the
        # assert exists to catch structural regressions, e.g. a per-segment
        # re-permute sneaking back in, not 10% jitter).
        for strategy, row in results["strategies"].items():
            ratio = row["permuted"]["build_s"] / row["refresh_s"]
            assert ratio >= 10.0, (
                f"{strategy}: refresh only {ratio:.1f}x faster than cold "
                f"build ({row['refresh_s']:.3f}s vs "
                f"{row['permuted']['build_s']:.3f}s)")
            assert row["refresh_err"] < 1e-5, (strategy, row["refresh_err"])
            assert row["permuted"]["err"] < 1e-5, (strategy, row["permuted"])
            # serial has no permuted space (same scan, values as runtime
            # buffers) — its guard only catches gross blowups; sub-100us
            # medians on shared runners jitter +-50%
            slack = 2.0 if strategy == "serial" else 1.15
            assert row["permuted"]["solve_s"] <= slack * row["scatter"]["solve_s"], (
                f"{strategy}: permuted solve "
                f"{row['permuted']['solve_s']:.3e}s slower than scatter "
                f"{row['scatter']['solve_s']:.3e}s")
        print("  smoke assertions passed (refresh >= 10x cold build, "
              "permuted <= scatter per-solve)")

    if json_path:
        write_bench_json(json_path, "refresh", results, n=L.n, nnz=L.nnz)
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
