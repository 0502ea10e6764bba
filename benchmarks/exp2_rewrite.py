"""Paper §V Experiment 2: end-to-end solve with equation rewriting applied.

Paper (lung2, serial run of the rewritten generated code): 2.06 ms vs
1.98 ms unrewritten — rewriting pays +10% FLOPs, the win arrives with
parallel hardware (fewer, fatter levels).  On TPU/XLA the "parallel
hardware" is the vector unit: we report solve time with/without rewriting
AND the structural metrics that determine the parallel win (levels =
sequential segments; padded-FLOP waste = idle lanes).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import RewriteConfig, SpTRSV
from repro.sparse import lung2_like

from .common import emit, timeit


def run(full_scale: bool = True):
    print("== exp2_rewrite: rewritten solver end-to-end ==")
    L = lung2_like(scale=1.0 if full_scale else 0.1, dtype=np.float32)
    b = jnp.asarray(np.random.default_rng(0).normal(size=L.n).astype(np.float32))

    base = SpTRSV.build(L, strategy="levelset")
    rw = SpTRSV.build(L, strategy="levelset",
                      rewrite=RewriteConfig(thin_threshold=2))
    # §Perf solver iteration 1: rewritten rows carry fill-in; one max-width
    # slab per level pays their K for every native row.  nnz-bucketed slabs
    # (the paper's "multiple functions per thick level") cap the padding.
    rw_bucket = SpTRSV.build(L, strategy="levelset",
                             rewrite=RewriteConfig(thin_threshold=2),
                             bucket_pad_ratio=2.0)

    t_base = timeit(base.solve, b, iters=5, warmup=2)
    t_rw = timeit(rw.solve, b, iters=5, warmup=2)
    t_rwb = timeit(rw_bucket.solve, b, iters=5, warmup=2)
    st = rw.stats

    emit("exp2.levelset_ms", f"{t_base*1e3:.2f}", "ms")
    emit("exp2.rewritten_ms", f"{t_rw*1e3:.2f}", "ms")
    emit("exp2.rewritten_bucketed_ms", f"{t_rwb*1e3:.2f}", "ms",
         note="beyond-paper: nnz-bucketed slabs")
    emit("exp2.padded_flops_plain", rw.schedule.padded_flops())
    emit("exp2.padded_flops_bucketed", rw_bucket.schedule.padded_flops())
    emit("exp2.slabs_plain", rw.schedule.num_levels)
    emit("exp2.slabs_bucketed", rw_bucket.schedule.num_levels)
    emit("exp2.speedup", f"{t_base/t_rw:.2f}", "x")
    emit("exp2.levels", f"{st.levels_before}->{st.levels_after}")
    emit("exp2.barriers_removed", f"{100*st.level_reduction:.1f}", "%")
    emit("exp2.flop_increase", f"{100*st.flop_increase:.1f}", "%")
    emit("exp2.paper_serial_rewritten_ms", 2.06, "ms", role="paper lung2")

    x0 = np.asarray(base.solve(b))
    x1 = np.asarray(rw.solve(b))
    x2 = np.asarray(rw_bucket.solve(b))
    np.testing.assert_allclose(x0, x1, rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(x0, x2, rtol=2e-3, atol=2e-4)
    print("  [check] rewritten (+bucketed) solutions match unrewritten")
    return {"base": t_base, "rewritten": t_rw, "bucketed": t_rwb, "stats": st}


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
