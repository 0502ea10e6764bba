"""Pallas kernel micro-benchmarks.

Wall-clock on this CPU container measures the *interpret-mode* kernels —
meaningless as TPU time — so alongside a CPU sanity timing we report the
structural metrics the TPU roofline cares about: padded FLOPs (lane
occupancy), VMEM working set per block, HBM bytes per solve.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import RewriteConfig, SpTRSV
from repro.core.codegen import build_schedule
from repro.sparse import lung2_like

from .common import emit, timeit


def run(full_scale: bool = False):
    print("== kernels_bench: Pallas kernel structure + sanity timing ==")
    L = lung2_like(scale=0.05, dtype=np.float32)
    b = jnp.asarray(np.random.default_rng(0).normal(size=L.n).astype(np.float32))
    sched = build_schedule(L)
    n = L.n

    emit("kern.matrix_rows", n)
    emit("kern.levels", sched.num_levels)
    pf = sched.padded_flops()
    emit("kern.padded_flops", pf)
    emit("kern.useful_flops", L.solve_flops())
    emit("kern.lane_occupancy", f"{100*L.solve_flops()/max(pf,1):.1f}", "%",
         note="ELL padding waste = idle lanes")
    # VMEM working set of the fused kernel: x (n_pad f32) + largest slab block
    x_bytes = 4 * (n + 1)
    slab_bytes = max(4 * (2 * s.K + 2) * min(s.R, 512) for s in sched.slabs)
    emit("kern.fused_vmem_x_bytes", x_bytes, "B", budget="~16MiB VMEM")
    emit("kern.level_block_bytes", slab_bytes, "B")
    emit("kern.hbm_bytes_per_solve", 4 * (2 * L.nnz + 2 * n), "B",
         note="vals+cols+b+x streams")

    for strat in ("levelset", "pallas_level", "pallas_fused"):
        s = SpTRSV.build(L, strategy=strat, interpret=True)
        t = timeit(s.solve, b, iters=3, warmup=1)
        emit(f"kern.{strat}.cpu_ms", f"{t*1e3:.2f}", "ms",
             note="interpret-mode sanity" if "pallas" in strat else "XLA CPU")
    return True


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
