"""Criticality-guided rewriting + transform planner benchmark.

Measures the three claims of the selective-rewriting PR on a lung2-class
matrix:

* **vectorized rewrite engine** — the batched NumPy/CSR elimination rounds
  against the seed-era per-row dict loop.  Reported at two boundaries:
  ``engine`` times the elimination+materialization phase that the
  vectorization actually replaced (``_rewrite_loop`` vs
  ``_rewrite_vectorized`` — the policy selection, L' level analysis and
  criticality stats around it are shared by both engines verbatim), and
  ``end_to_end`` times the full ``rewrite_matrix`` call per engine.
  ``--smoke`` asserts the engine phase is **>= 10x** faster.
* **critical_path policy** — weighted critical path before/after for
  ``policy="thin"`` vs ``policy="critical_path"``; ``--smoke`` asserts the
  criticality-guided rewrite cuts the weighted critical path **>= 25%**
  within the default fill budget.
* **transform planner** — ``strategy="auto"`` decisions (rewrite vs coarsen
  vs both, with full candidate cost tables) across matrix classes, plus a
  value-only replay timing (the array-form plan's O(nnz) refresh path).

Usage::

    python -m benchmarks.rewrite_planner              # full lung2 scale
    python -m benchmarks.rewrite_planner --smoke      # CI smoke w/ asserts
    python -m benchmarks.rewrite_planner --smoke --json BENCH_rewrite_planner.json
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax.numpy as jnp

from repro.core import RewriteConfig, SpTRSV, replay_rewrite_values, rewrite_matrix
from repro.core.csr import CSRMatrix
from repro.core.levels import build_level_sets
from repro.core.rewrite import _participants, _rewrite_loop, _rewrite_vectorized
from repro.sparse import banded_lower, chain_matrix, lung2_like, random_lower

try:  # runnable both as `python -m benchmarks.rewrite_planner` and as a file
    from .common import emit, flush_csv, write_bench_json
except ImportError:  # pragma: no cover
    from common import emit, flush_csv, write_bench_json


def _best_of(f, reps, *args, **kwargs):
    best, out = float("inf"), None
    for _ in range(reps):
        t0 = time.perf_counter()
        out = f(*args, **kwargs)
        best = min(best, time.perf_counter() - t0)
    return best, out


def run(*, smoke: bool = False, json_path: str = ""):
    print("== rewrite_planner: criticality-guided rewriting + transform planner ==")
    # full lung2 scale in both modes: the engine-speedup margin grows with
    # size (the dict loop's Python constants dominate more), so smoke runs
    # the same matrix and just trims repetitions
    L = lung2_like(scale=1.0, dtype=np.float64)
    levels = build_level_sets(L)
    emit("rewrite_planner.rows", L.n)
    emit("rewrite_planner.nnz", L.nnz)
    results: dict = {"n": L.n, "nnz": L.nnz}

    # --- engine comparison: dict loop vs batched vectorized rounds --------
    cfg = RewriteConfig(thin_threshold=2)
    diag = L.diagonal()
    part = _participants(L, levels, cfg, upper=False)
    reps = 3 if smoke else 5
    t_vec_eng, _ = _best_of(_rewrite_vectorized, reps, L, levels, cfg,
                            upper=False, part=part, diag=diag)
    t_loop_eng, _ = _best_of(_rewrite_loop, 1, L, levels, cfg,
                             upper=False, part=part, diag=diag)
    t_vec_e2e, res_v = _best_of(
        rewrite_matrix, reps, L, levels, RewriteConfig(engine="vectorized"))
    t_loop_e2e, res_l = _best_of(
        rewrite_matrix, 1, L, levels, RewriteConfig(engine="loop"))
    assert res_v.stats.nnz_after == res_l.stats.nnz_after  # same decisions
    eng_ratio = t_loop_eng / t_vec_eng
    e2e_ratio = t_loop_e2e / t_vec_e2e
    emit("rewrite_planner.engine.loop_s", round(t_loop_eng, 4), "s")
    emit("rewrite_planner.engine.vectorized_s", round(t_vec_eng, 4), "s")
    emit("rewrite_planner.engine.speedup", round(eng_ratio, 1), "x")
    emit("rewrite_planner.end_to_end.loop_s", round(t_loop_e2e, 4), "s")
    emit("rewrite_planner.end_to_end.vectorized_s", round(t_vec_e2e, 4), "s")
    emit("rewrite_planner.end_to_end.speedup", round(e2e_ratio, 1), "x")
    results["engine"] = dict(loop_s=t_loop_eng, vectorized_s=t_vec_eng,
                             speedup=eng_ratio)
    results["end_to_end"] = dict(loop_s=t_loop_e2e, vectorized_s=t_vec_e2e,
                                 speedup=e2e_ratio)

    # --- policy comparison: thin vs critical_path --------------------------
    results["policies"] = {}
    for policy in ("thin", "critical_path"):
        t_build, res = _best_of(
            rewrite_matrix, reps, L, levels, RewriteConfig(policy=policy))
        s = res.stats
        cp_red = s.critical_path_reduction
        emit(f"rewrite_planner.{policy}.build_s", round(t_build, 4), "s")
        emit(f"rewrite_planner.{policy}.critical_path",
             f"{s.critical_path_before} -> {s.critical_path_after}",
             note=f"-{100*cp_red:.1f}%")
        emit(f"rewrite_planner.{policy}.rows_rewritten", s.rows_rewritten)
        emit(f"rewrite_planner.{policy}.fill_ratio",
             round(s.nnz_after / s.nnz_before, 3))
        results["policies"][policy] = dict(
            build_s=t_build,
            critical_path_before=s.critical_path_before,
            critical_path_after=s.critical_path_after,
            critical_path_reduction=cp_red,
            rows_rewritten=s.rows_rewritten,
            nnz_before=s.nnz_before, nnz_after=s.nnz_after,
            levels_before=s.levels_before, levels_after=s.levels_after,
            eliminations_skipped=s.eliminations_skipped)

    # --- value-only replay (array-form plan) -------------------------------
    rng = np.random.default_rng(1)
    d2 = L.data + 0.05 * rng.standard_normal(L.nnz)
    d2[L.indptr[1:] - 1] += 2.0
    L2 = CSRMatrix(L.indptr, L.indices, d2, L.shape)
    t_replay, _ = _best_of(replay_rewrite_values, reps, L2, res_v.plan,
                           res_v.L, res_v.E)
    emit("rewrite_planner.replay_s", round(t_replay, 4), "s",
         note=f"{t_vec_e2e/t_replay:.1f}x faster than a fresh rewrite")
    results["replay"] = dict(replay_s=t_replay,
                             vs_fresh_rewrite=t_vec_e2e / t_replay)

    # --- transform planner decisions across matrix classes -----------------
    mats = {
        "lung2": lung2_like(scale=0.1 if smoke else 0.25, dtype=np.float32),
        "chain": chain_matrix(2000, dtype=np.float32),
        "random": random_lower(2000, avg_offdiag=3.0, seed=0, dtype=np.float32),
        "banded": banded_lower(1500, bandwidth=8, seed=1, dtype=np.float32),
    }
    results["planner"] = {}
    rng = np.random.default_rng(0)
    for name, M in mats.items():
        t0 = time.perf_counter()
        s = SpTRSV.build(M, strategy="auto")
        build_s = time.perf_counter() - t0
        b = rng.standard_normal(M.n).astype(np.float32)
        err = float(np.abs(
            np.asarray(s.solve(jnp.asarray(b)))
            - np.asarray(SpTRSV.build(M, strategy="serial")
                         .solve(jnp.asarray(b)))).max())
        emit(f"rewrite_planner.auto.{name}",
             f"{s.strategy}"
             + (f"+rewrite:{s.plan.rewrite}" if s.plan.rewrite else "")
             + ("+coarsen" if s.plan.coarsen else ""),
             note=f"build {build_s:.2f}s, err {err:.1e}")
        results["planner"][name] = dict(
            strategy=s.strategy, rewrite=s.plan.rewrite,
            coarsen=s.plan.coarsen, build_s=build_s, err=err,
            costs={k: float(v) for k, v in s.plan.costs.items()})

    if smoke:
        # Acceptance (ISSUE 5): criticality-guided rewrite cuts the weighted
        # critical path >= 25% within the default fill budget, and the
        # batched engine replaces the dict loop at >= 10x.
        cp = results["policies"]["critical_path"]
        assert cp["critical_path_reduction"] >= 0.25, cp
        assert cp["nnz_after"] <= RewriteConfig().max_fill_ratio * cp["nnz_before"], cp
        assert eng_ratio >= 10.0, (
            f"vectorized engine only {eng_ratio:.1f}x faster than the dict "
            f"loop ({t_vec_eng:.3f}s vs {t_loop_eng:.3f}s)")
        # end-to-end (shared analysis included on both sides) must also win
        # clearly — guards a regression hiding in the shared phases
        assert e2e_ratio >= 2.0, (t_loop_e2e, t_vec_e2e)
        # the planner must transform the lung2-class matrix and leave the
        # chain to a sequential executor (the serial scan, or the sync-free
        # sweep once its candidate is priced) without rewriting it
        assert results["planner"]["lung2"]["rewrite"] is not None
        assert results["planner"]["chain"]["strategy"] in ("serial", "sweep")
        assert results["planner"]["chain"]["rewrite"] is None
        for name, row in results["planner"].items():
            assert row["err"] < 1e-4, (name, row["err"])
        print("  smoke assertions passed (critical path -"
              f"{100*cp['critical_path_reduction']:.0f}%, engine "
              f"{eng_ratio:.1f}x, planner transforms recorded)")

    if json_path:
        write_bench_json(json_path, "rewrite_planner", results,
                         n=L.n, nnz=L.nnz)
    return results


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="smaller matrix + acceptance assertions (CI)")
    ap.add_argument("--json", default="", help="write results JSON here")
    ap.add_argument("--csv", default="")
    args = ap.parse_args()
    run(smoke=args.smoke, json_path=args.json)
    if args.csv:
        flush_csv(args.csv)
