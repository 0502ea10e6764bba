"""IC(0)/SpTRSV preconditioner: shared-analysis vs reverse-permute baseline.

The preconditioner apply is two triangular sweeps — forward ``L y = r`` and
backward ``Lᵀ z = y``.  The legacy construction materialized the backward
sweep as a *lower* solve on the reverse-permuted transpose: an extra
``from_coo`` transpose, another ``from_coo`` permutation, and a second full
``SpTRSV.build`` (level analysis, rewrite, packing) that knows nothing about
the first.  The shared-analysis construction (``SpTRSV.build_pair``) derives
the backward level sets from the forward DAG arrays and packs backward slabs
from an O(nnz) CSC view — one symbolic analysis for both sweeps.

Reported: build time (legacy vs shared), per-apply time, and PCG iteration
counts with each preconditioner (must be identical — the two constructions
compute the same operator).

Usage::

    python -m benchmarks.preconditioner             # full run
    python -m benchmarks.preconditioner --dry-run   # tiny smoke (CI)
"""
from __future__ import annotations

import argparse
import time

import jax.numpy as jnp
import numpy as np

from repro.core import RewriteConfig, SpTRSV
from repro.core.csr import from_coo
from repro.core.pcg import make_ic_preconditioner, pcg
from repro.sparse import ic0_factor, lung2_like, poisson2d

try:  # runnable both as `python -m benchmarks.preconditioner` and as a file
    from .common import emit, flush_csv, timeit
except ImportError:  # pragma: no cover
    from common import emit, flush_csv, timeit


def legacy_make_ic_preconditioner(L, *, strategy="levelset",
                                  rewrite=RewriteConfig(thin_threshold=2)):
    """The pre-transpose-support construction, kept verbatim as the
    baseline: transpose via from_coo, reverse-permute to lower-triangular,
    and a second independent SpTRSV.build for the backward sweep."""
    n = L.n
    rows = np.repeat(np.arange(n), L.row_nnz())
    Lt = from_coo(L.indices, rows, L.data, (n, n))
    rows_t = np.repeat(np.arange(n), Lt.row_nnz())
    Lt_rev = from_coo(n - 1 - rows_t, n - 1 - Lt.indices, Lt.data, (n, n))

    fwd = SpTRSV.build(L, strategy=strategy, rewrite=rewrite)
    bwd = SpTRSV.build(Lt_rev, strategy=strategy, rewrite=rewrite)

    def apply(r):
        y = fwd.solve(r)
        return bwd.solve(y[::-1])[::-1]

    return apply


def _time_build(fn, iters: int) -> float:
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def run(*, dry_run: bool = False):
    print("== preconditioner: shared-analysis vs reverse-permute baseline ==")
    # Build/apply comparison on a lung2-class factor — the paper's workload:
    # hundreds of levels, most of them thin, where per-row DAG traversal
    # dominates the analysis.  PCG iteration check on a poisson IC(0) system.
    if dry_run:
        L = lung2_like(scale=0.02, fat_levels=4, thin_run=6, dtype=np.float32)
        A = poisson2d(12, 12, dtype=np.float32)
        build_iters, tol, maxiter = 2, 1e-5, 200
    else:
        L = lung2_like(scale=0.25, dtype=np.float32)
        A = poisson2d(96, 96, dtype=np.float32)
        build_iters, tol, maxiter = 5, 1e-6, 1500
    emit("precond.rows", L.n)
    emit("precond.nnz", L.nnz)
    rewrite = RewriteConfig(thin_threshold=2)

    t_legacy = _time_build(
        lambda: legacy_make_ic_preconditioner(L, rewrite=rewrite), build_iters)
    t_shared = _time_build(
        lambda: make_ic_preconditioner(L, rewrite=rewrite), build_iters)
    emit("precond.build.legacy_ms", f"{t_legacy * 1e3:.2f}", "ms")
    emit("precond.build.shared_ms", f"{t_shared * 1e3:.2f}", "ms",
         speedup=f"{t_legacy / t_shared:.2f}x")

    M_legacy = legacy_make_ic_preconditioner(L, rewrite=rewrite)
    M_shared = make_ic_preconditioner(L, rewrite=rewrite)

    rng = np.random.default_rng(0)
    r = jnp.asarray(rng.normal(size=L.n).astype(np.float32))
    z_legacy = np.asarray(M_legacy(r))
    z_shared = np.asarray(M_shared(r))
    err = float(np.max(np.abs(z_legacy - z_shared))
                / max(np.max(np.abs(z_legacy)), 1e-30))
    emit("precond.apply.max_rel_diff", f"{err:.2e}")
    assert err < 1e-4, "shared-analysis apply diverged from the baseline"

    t_apply_legacy = timeit(M_legacy, r, iters=5, warmup=2)
    t_apply_shared = timeit(M_shared, r, iters=5, warmup=2)
    emit("precond.apply.legacy_ms", f"{t_apply_legacy * 1e3:.3f}", "ms")
    emit("precond.apply.shared_ms", f"{t_apply_shared * 1e3:.3f}", "ms",
         speedup=f"{t_apply_legacy / t_apply_shared:.2f}x")

    Lic = ic0_factor(A)
    b = jnp.asarray(rng.normal(size=A.n).astype(np.float32))
    res_legacy = pcg(A, b, legacy_make_ic_preconditioner(Lic, rewrite=rewrite),
                     tol=tol, maxiter=maxiter)
    res_shared = pcg(A, b, make_ic_preconditioner(Lic, rewrite=rewrite),
                     tol=tol, maxiter=maxiter)
    emit("precond.pcg.iters.legacy", res_legacy.iters)
    emit("precond.pcg.iters.shared", res_shared.iters)
    # The two constructions are the same operator up to f32 rounding (the
    # eliminations run over different representations), so a residual sitting
    # exactly at the tolerance boundary may converge one iteration apart —
    # allow that ulp-level wiggle, fail on anything larger.
    iter_slack = max(1, res_legacy.iters // 20)
    assert abs(res_shared.iters - res_legacy.iters) <= iter_slack, (
        "shared-analysis preconditioner changed PCG iteration count: "
        f"{res_shared.iters} vs {res_legacy.iters}")

    if t_shared >= t_legacy:
        print("  !! build-time regression: shared-analysis slower than baseline")
    print(f"  build {t_legacy*1e3:.1f} -> {t_shared*1e3:.1f} ms "
          f"({t_legacy/t_shared:.2f}x), PCG iters unchanged "
          f"({res_shared.iters})")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--csv", default="")
    args = ap.parse_args()
    run(dry_run=args.dry_run)
    if args.csv:
        flush_csv(args.csv)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
