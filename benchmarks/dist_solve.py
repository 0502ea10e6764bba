"""Distributed SpTRSV: collective count/bytes vs rewriting (the paper's
barrier-removal story at pod scale — each level boundary is one collective).

Runs on 8 virtual CPU devices; reports per-solve collective counts & bytes
for the two exchange strategies (psum = naive full-vector barrier port,
all_gather = value-only exchange) with and without equation rewriting, plus
wall time.  The multi-chip roofline projection of the same schedule lives in
EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import RewriteConfig, SpTRSV
from repro.core.dist import shard_schedule
from repro.core.codegen import build_schedule
from repro.launch.mesh import make_mesh
from repro.sparse import lung2_like

from .common import emit, timeit


def run(full_scale: bool = True):
    print("== dist_solve: level collectives with/without rewriting ==")
    mesh = make_mesh((8,), ("data",))
    L = lung2_like(scale=0.25 if full_scale else 0.05, dtype=np.float32)
    b = jnp.asarray(np.random.default_rng(0).normal(size=L.n).astype(np.float32))

    for label, rw in (("base", None),
                      ("rewrite", RewriteConfig(thin_threshold=2))):
        for strat in ("psum", "all_gather"):
            s = SpTRSV.build(L, strategy="distributed", mesh=mesh,
                             dist_strategy=strat, rewrite=rw)
            target = s.rewrite_result.L if s.rewrite_result else L
            sched = build_schedule(target)
            d = shard_schedule(sched, 8)
            t = timeit(s.solve, b, iters=3, warmup=1)
            emit(f"dist.{label}.{strat}.levels", d.num_levels,
                 note="= collectives/solve")
            emit(f"dist.{label}.{strat}.bytes", d.collective_bytes(4, strat),
                 "B/solve")
            emit(f"dist.{label}.{strat}.ms", f"{t*1e3:.2f}", "ms")
    return True


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
