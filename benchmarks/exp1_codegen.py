"""Paper §V Experiment 1: specialized-codegen solver vs handwritten baseline
(serial execution, no rewriting).

Paper (lung2, dual-socket Westmere, clang): generated 1.98 ms vs handwritten
level-set 1.14 ms (the prototype generator loses ~1.7x from over-long code /
no merging).  Here both solvers are XLA-compiled; the "generated" one is the
matrix-specialized level-set executor (structure baked in as constants), the
"handwritten" baseline is the generic row-serial scan (Algorithm 1).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import RewriteConfig, SpTRSV
from repro.sparse import lung2_like

from .common import emit, timeit


def run(full_scale: bool = True):
    print("== exp1_codegen: specialized executor vs serial baseline ==")
    L = lung2_like(scale=1.0 if full_scale else 0.1, dtype=np.float32)
    b = jnp.asarray(np.random.default_rng(0).normal(size=L.n).astype(np.float32))

    serial = SpTRSV.build(L, strategy="serial")          # Algorithm 1
    levelset = SpTRSV.build(L, strategy="levelset")      # generated, no rewrite
    unrolled = SpTRSV.build(L, strategy="levelset_unroll", unroll_threshold=4)

    t_serial = timeit(serial.solve, b, iters=5, warmup=2)
    t_level = timeit(levelset.solve, b, iters=5, warmup=2)
    t_unroll = timeit(unrolled.solve, b, iters=5, warmup=2)

    emit("exp1.rows", L.n)
    emit("exp1.serial_ms", f"{t_serial*1e3:.2f}", "ms", role="handwritten Algorithm-1")
    emit("exp1.levelset_ms", f"{t_level*1e3:.2f}", "ms", role="generated per-level")
    emit("exp1.levelset_unroll_ms", f"{t_unroll*1e3:.2f}", "ms",
         role="generated + tiny-level constant unroll")
    emit("exp1.paper_generated_ms", 1.98, "ms", role="paper lung2")
    emit("exp1.paper_handwritten_ms", 1.14, "ms", role="paper lung2")
    return {"serial": t_serial, "levelset": t_level, "unroll": t_unroll}


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
