"""Paper Fig. 6: #levels and #FLOPs before/after equation rewriting.

Paper (lung2, 109,460 rows / 492,564 nnz / 478 levels, 94% thin):
    levels 478 -> 66 (-86% synchronization barriers), FLOPs +10%.
We reproduce on the structural twin `lung2_like` (SuiteSparse is offline)
plus the chain / IC(0)-Poisson workloads, and validate the same regime:
large barrier reduction at single-digit-% FLOP increase.
"""
from __future__ import annotations

import numpy as np

from repro.core import RewriteConfig, rewrite_matrix
from repro.core.levels import build_level_sets
from repro.sparse import chain_matrix, ic0_factor, lung2_like, poisson2d

from .common import emit


def run(full_scale: bool = True):
    print("== fig6_levels: equation rewriting level/FLOP transformation ==")
    mats = {
        "lung2_like": lung2_like(scale=1.0 if full_scale else 0.1),
        "chain_4096": chain_matrix(4096),
        "ic0_poisson_64x64": ic0_factor(poisson2d(64, 64)),
    }
    results = {}
    for name, L in mats.items():
        lv = build_level_sets(L)
        res = rewrite_matrix(L, lv, RewriteConfig(thin_threshold=2))
        st = res.stats
        emit(f"{name}.rows", L.n)
        emit(f"{name}.nnz", L.nnz)
        emit(f"{name}.levels_before", st.levels_before)
        emit(f"{name}.levels_after", st.levels_after)
        emit(f"{name}.barrier_reduction", f"{100*st.level_reduction:.1f}", "%")
        emit(f"{name}.flops_before", st.flops_before)
        emit(f"{name}.flops_after", st.flops_after)
        emit(f"{name}.flop_increase", f"{100*st.flop_increase:.1f}", "%")
        emit(f"{name}.thin_fraction", f"{100*lv.thin_fraction(2):.1f}", "%")
        results[name] = st

    st = results["lung2_like"]
    # paper-claims validation (structural twin): 478->66 = -86%; +10% FLOPs.
    # FLOP overhead is scale-dependent (fill-in amortizes over fat levels),
    # so the +10% regime check applies at full scale only.
    assert st.levels_before > 400, st.levels_before
    assert st.level_reduction > 0.80, st.summary()
    if full_scale:
        assert st.flop_increase < 0.20, st.summary()
    print(f"  [paper check] lung2-like: {st.summary()}")
    print(f"  [paper claim] lung2     : levels 478 -> 66 (-86.2%), FLOPs +10%")
    return results


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
