"""Calibration micro-run: measure the planner's pricing coefficients on the
live device and write them as a ``calibration.json`` table.

:func:`repro.core.coarsen.plan_strategy` prices strategies in
FLOP-equivalents using per-backend coefficients
(:mod:`repro.core.calibrate`).  The shipped defaults are conservative; this
micro-run replaces the row for the *current* backend family with measured
numbers:

* **gather throughput** — reference flops/second of the padded ELL
  gather-FMA the level executors are made of (a jitted ``spmv_ref``-shaped
  contraction).  This anchors the FLOP-equivalent unit.
* **launch cost** — wall time of one dispatch of a trivially small jitted
  kernel, converted to FLOP-equivalents at the measured gather throughput.
  This is the per-segment barrier price.
* **serial step cost** — per-row wall time of the ``lax.scan`` serial
  solver at two sizes, split into the base + scale-with-n model the planner
  uses (latency-bound rows; the carried x vector falls out of cache as n
  grows).
* **gemm cost** — relative price of one dense batched-GEMM flop of the
  blocked executor's diagonal-block apply, measured against the gather
  reference (contiguous flops are cheaper than gathered ones everywhere,
  dramatically so on MXU hardware).
* **trsm cost** — fixed per-diagonal-block overhead of the batched block
  apply, from a two-point linear fit over the batch dimension.

Unmeasured keys (lane width, fused dispatch shape and row bound) keep the
shipped defaults for the family — they are device *facts*, not timings.

Usage::

    python -m benchmarks.calibrate                     # print the row
    python -m benchmarks.calibrate --json calibration.json
    python -m benchmarks.calibrate --smoke --bench-json BENCH_calibrate.json
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import SpTRSV
from repro.core.calibrate import (
    DEFAULT_CALIBRATIONS,
    get_calibration,
    save_calibrations,
)
from repro.kernels.backend import resolve_backend
from repro.sparse import chain_matrix

try:  # runnable both as `python -m benchmarks.calibrate` and as a file
    from .common import emit, timeit, write_bench_json
except ImportError:  # pragma: no cover
    from common import emit, timeit, write_bench_json


def _gather_flops_per_s(n: int = 1 << 16, K: int = 8, iters: int = 20):
    """Reference throughput of the padded ELL gather-FMA contraction."""
    rng = np.random.default_rng(0)
    cols = jnp.asarray(rng.integers(0, n, size=(K, n)).astype(np.int32))
    vals = jnp.asarray(rng.standard_normal((K, n)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal(n).astype(np.float32))

    @jax.jit
    def gather_fma(v, cols, vals):
        return jnp.sum(vals * v[cols], axis=0)

    t = timeit(gather_fma, v, cols, vals, iters=iters, warmup=5)
    return 2.0 * K * n / t


def _launch_seconds(iters: int = 50):
    """Per-dispatch overhead: one trivially small jitted kernel."""
    x = jnp.zeros((8,), jnp.float32)

    @jax.jit
    def tiny(x):
        return x + 1.0

    return timeit(tiny, x, iters=iters, warmup=5)


def _serial_row_seconds(n: int, iters: int = 5):
    """Per-row wall time of the lax.scan serial solver at size n."""
    L = chain_matrix(n, dtype=np.float32)
    s = SpTRSV.build(L, strategy="serial")
    b = jnp.asarray(np.random.default_rng(1).standard_normal(n)
                    .astype(np.float32))
    return timeit(s.solve, b, iters=iters, warmup=2) / n


def _block_apply_seconds(B: int, T: int = 32, iters: int = 20):
    """Wall time of the blocked executor's batched diagonal-block apply
    ``(B, T, T) x (B, T) -> (B, T)`` at batch size B."""
    from repro.kernels.trsm_block.ops import make_block_apply

    rng = np.random.default_rng(2)
    dinv = jnp.asarray(rng.standard_normal((B, T, T)).astype(np.float32))
    rhs = jnp.asarray(rng.standard_normal((B, T)).astype(np.float32))
    apply = jax.jit(make_block_apply(None))
    return timeit(apply, dinv, rhs, iters=iters, warmup=5)


def run(*, json_path: str = "", smoke: bool = False, bench_json: str = ""):
    print("== calibrate: planner pricing coefficients (micro-run) ==")
    bk = resolve_backend(None)
    key = bk.calibration_key
    base = get_calibration(key)
    it_scale = 3 if smoke else 1

    flops_per_s = _gather_flops_per_s(iters=max(20 // it_scale, 5))
    launch_s = _launch_seconds(iters=max(50 // it_scale, 10))
    launch_cost = launch_s * flops_per_s
    n_small, n_big = (1 << 10, 1 << 13) if smoke else (1 << 11, 1 << 15)
    row_small = _serial_row_seconds(n_small)
    row_big = _serial_row_seconds(n_big)
    # fit per-row cost = base + scale * n (FLOP-equivalents)
    scale = max((row_big - row_small) / (n_big - n_small), 0.0) * flops_per_s
    serial_base = max(row_small * flops_per_s - scale * n_small, 1.0)

    # blocked-executor coefficients: dense flop price from the marginal cost
    # per diagonal block (a two-point fit over the batch dimension strips the
    # dispatch overhead), per-block overhead from the intercept.
    T = 32
    b_small, b_big = (64, 256) if smoke else (128, 512)
    t_small = _block_apply_seconds(b_small, T=T, iters=max(20 // it_scale, 5))
    t_big = _block_apply_seconds(b_big, T=T, iters=max(20 // it_scale, 5))
    per_block_s = max((t_big - t_small) / (b_big - b_small), 0.0)
    gemm_cost = max(per_block_s * flops_per_s / (2.0 * T * T), 1e-4)
    intercept_s = max(t_small - per_block_s * b_small, 0.0)
    trsm_cost = max(intercept_s * flops_per_s / b_small, 1.0)

    measured = dataclasses.replace(
        base,
        launch_cost=round(launch_cost, 1),
        gather_cost=1.0,  # the gather micro-run defines the reference unit
        serial_step_cost=round(serial_base, 2),
        serial_step_cost_scale=round(scale, 4),
        gemm_cost=round(gemm_cost, 4),
        trsm_cost=round(trsm_cost, 2),
        source="measured",
    )
    emit("calibrate.backend", bk.name, family=key)
    emit("calibrate.gather_gflops", round(flops_per_s / 1e9, 3), "GFLOP/s")
    emit("calibrate.launch_us", round(launch_s * 1e6, 2), "us")
    emit("calibrate.launch_cost", measured.launch_cost, "flop-eq")
    emit("calibrate.serial_step_cost", measured.serial_step_cost, "flop-eq")
    emit("calibrate.serial_step_cost_scale", measured.serial_step_cost_scale)
    emit("calibrate.gemm_cost", measured.gemm_cost, "flop-eq/flop")
    emit("calibrate.trsm_cost", measured.trsm_cost, "flop-eq/block")

    table = dict(DEFAULT_CALIBRATIONS)
    table[key] = measured
    if json_path:
        save_calibrations(json_path, table)
        print(f"  wrote {json_path}")
    if bench_json:
        write_bench_json(
            bench_json, "calibrate",
            {key: {f.name: getattr(measured, f.name)
                   for f in dataclasses.fields(measured)},
             "gather_gflops": flops_per_s / 1e9,
             "launch_us": launch_s * 1e6},
            backend=bk.name)
    return table


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fewer iterations / smaller scan sizes (CI)")
    ap.add_argument("--json", default="",
                    help="write the refreshed calibration table here")
    ap.add_argument("--bench-json", default="",
                    help="write a shared-schema BENCH_*.json trajectory "
                         "artifact of the measured row")
    args = ap.parse_args()
    run(json_path=args.json, smoke=args.smoke, bench_json=args.bench_json)
