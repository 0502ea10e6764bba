"""Differential fuzz harness: pathological triangular patterns through every
strategy × rewrite-policy × layout × transpose × batch combination against a
NumPy (dense ``np.linalg.solve``) oracle at few-ulp tolerance.

Two tiers:

* the default (tier-1) run sweeps a deterministic rotating slice of the
  combination grid per pattern — every grid dimension is exercised on every
  CI run, in bounded time;
* ``pytest -m fuzz`` (the nightly job) runs the exhaustive grid — including
  the distributed strategy — over ``FUZZ_SEEDS`` seeds per pattern
  (default 3; the nightly sets a larger budget).

Any failing configuration dumps the matrix + combination to an ``.npz``
repro file (``FUZZ_REPRO_DIR``, default ``tests/_fuzz_repro``) and names the
file in the assertion message, so a nightly failure is replayable without
re-deriving the random state.

Tolerances: solutions are compared in float64.  For well-conditioned
patterns the bound is a few ulp (scaled by the oracle's magnitude); the
``RESIDUAL_PATTERNS`` (``near_singular``'s ~9-decade diagonal spread,
``extreme_scale``'s fp32-edge magnitudes, ``denormal_pivot``'s fp32-subnormal
pivots) make forward error against an oracle the wrong criterion — they
assert the componentwise residual bound
``|L x - b| <= tol * (|L| |x| + |b|)`` instead (the backward stability test
substitution actually satisfies).
"""
import itertools
import json
import os
import pathlib

import numpy as np
import jax.numpy as jnp
import pytest

from jax import enable_x64
from repro.core import GuardConfig, RewriteConfig, SpTRSV
from repro.sparse import PATHOLOGICAL_PATTERNS, pathological

STRATEGIES = ["serial", "levelset", "levelset_unroll",
              "pallas_level", "pallas_fused", "sweep", "blocked"]
POLICIES = {
    "none": None,
    "thin": RewriteConfig(thin_threshold=2),
    "critical_path": RewriteConfig(policy="critical_path"),
}
LAYOUTS = ["permuted", "scatter"]
PATTERNS = sorted(PATHOLOGICAL_PATTERNS)
# patterns whose conditioning makes forward error against the oracle
# meaningless — checked with the componentwise residual criterion instead
RESIDUAL_PATTERNS = {"near_singular", "extreme_scale", "denormal_pivot"}

# (strategy, policy, layout, transpose, batch) — the full local grid
GRID = list(itertools.product(STRATEGIES, sorted(POLICIES), LAYOUTS,
                              [False, True], [0, 3]))
# tier-1 rotating slice: stride through the grid with a per-pattern phase so
# every dimension value appears every run, but each pattern only builds ~7
# solver variants (full grid x all patterns is the nightly's job)
_STRIDE = 17

# GPU (pallas-triton) lowering grid: the pallas strategies re-run under the
# interpret:gpu backend — same layouts/transpose/batch dimensions, so the
# triton-style kernels get the identical oracle treatment without hardware
GPU_STRATEGIES = ["pallas_level", "pallas_fused"]
GPU_GRID = list(itertools.product(GPU_STRATEGIES, sorted(POLICIES), LAYOUTS,
                                  [False, True], [0, 3]))
_GPU_STRIDE = 7


def _combos_for(pattern: str, exhaustive: bool):
    if exhaustive:
        return GRID
    phase = PATTERNS.index(pattern)
    return GRID[phase::_STRIDE]


def _gpu_combos_for(pattern: str, exhaustive: bool):
    if exhaustive:
        return GPU_GRID
    phase = PATTERNS.index(pattern)
    return GPU_GRID[phase::_GPU_STRIDE]


def _oracle(L, b, transpose):
    A = L.to_dense()
    return np.linalg.solve(A.T if transpose else A, b)


def _dump_repro(L, pattern, seed, combo, err_msg):
    out_dir = pathlib.Path(os.environ.get(
        "FUZZ_REPRO_DIR", pathlib.Path(__file__).parent / "_fuzz_repro"))
    out_dir.mkdir(parents=True, exist_ok=True)
    strategy, policy, layout, transpose, batch = combo
    name = f"{pattern}_s{seed}_{strategy}_{policy}_{layout}" \
           f"_t{int(transpose)}_b{batch}.npz"
    path = out_dir / name
    np.savez(path, indptr=L.indptr, indices=L.indices, data=L.data,
             shape=np.asarray(L.shape),
             combo=json.dumps({"pattern": pattern, "seed": seed,
                               "strategy": strategy, "policy": policy,
                               "layout": layout, "transpose": transpose,
                               "batch": batch, "error": err_msg}))
    return path


def _check(L, pattern, x, b, x_ref, transpose, combo, seed):
    x = np.asarray(x)
    assert x.shape == x_ref.shape
    try:
        assert np.isfinite(x).all(), "non-finite entries in solution"
        if pattern in RESIDUAL_PATTERNS:
            # componentwise backward-error bound: |A x - b| <= tol (|A||x| + |b|)
            A = L.to_dense()
            if transpose:
                A = A.T
            resid = np.abs(A @ x - b)
            bound = np.abs(A) @ np.abs(x) + np.abs(b)
            tol = 256 * L.n * np.finfo(np.float64).eps
            worst = (resid / np.maximum(bound, 1e-300)).max()
            assert worst <= tol, f"residual {worst:.2e} > {tol:.2e}"
        else:
            scale = max(np.abs(x_ref).max(), 1.0)
            np.testing.assert_allclose(x, x_ref, rtol=5e-12,
                                       atol=5e-12 * scale)
    except AssertionError as err:
        path = _dump_repro(L, pattern, seed, combo, str(err))
        raise AssertionError(
            f"differential mismatch for {combo} on {pattern}(seed={seed}) "
            f"— repro dumped to {path}\n{err}") from None


def _run_combo(L, pattern, seed, combo, mesh=None, backend=None, guard=None):
    strategy, policy, layout, transpose, batch = combo
    kw = dict(strategy=strategy, layout=layout, transpose=transpose,
              rewrite=POLICIES[policy])
    if strategy == "distributed":
        kw["mesh"] = mesh
    if backend is not None:
        kw["backend"] = backend
    if guard is not None:
        kw["guard"] = guard
    s = SpTRSV.build(L, **kw)
    rng = np.random.default_rng(10_000 + seed)
    if batch:
        b = rng.standard_normal((L.n, batch))
    else:
        b = rng.standard_normal(L.n)
    x = s.solve(jnp.asarray(b))
    _check(L, pattern, x, b, _oracle(L, b, transpose), transpose, combo, seed)


@pytest.mark.parametrize("pattern", PATTERNS)
def test_differential_slice(pattern):
    """Tier-1: rotating slice of the grid on one seed per pattern."""
    L = pathological(pattern, n=72, seed=1)
    with enable_x64():
        for combo in _combos_for(pattern, exhaustive=False):
            _run_combo(L, pattern, 1, combo)


@pytest.mark.parametrize("pattern", PATTERNS)
def test_differential_gpu_backend_slice(pattern):
    """Tier-1: the pallas-triton (GPU) lowerings, executed under the
    interpret backend (``backend="interpret:gpu"``), on a rotating slice of
    the strategy × policy × layout × transpose × batch grid — the same
    oracle and tolerances as the TPU-lowering slice."""
    L = pathological(pattern, n=72, seed=1)
    with enable_x64():
        for combo in _gpu_combos_for(pattern, exhaustive=False):
            _run_combo(L, pattern, 1, combo, backend="interpret:gpu")


# --------------------------------------------------------------------------
# blocked executor: the full blocked × transpose × batch × layout sub-grid
# runs in tier-1 (the rotating slice above only samples it) — supernodal
# schedules have enough moving parts (panel gathers, padded dense blocks,
# block-level DAG) that every pattern gets the complete 8-combo slice,
# including ``jagged_rows`` where amalgamation finds nothing and the
# executor must degrade to all-1×1 blocks.
# --------------------------------------------------------------------------
BLOCKED_GRID = list(itertools.product(["blocked"], ["none"], LAYOUTS,
                                      [False, True], [0, 3]))


@pytest.mark.parametrize("pattern", PATTERNS)
def test_differential_blocked_slice(pattern):
    """Tier-1: blocked strategy over the full layout × transpose × batch
    sub-grid, one seed per pattern."""
    L = pathological(pattern, n=72, seed=1)
    with enable_x64():
        for combo in BLOCKED_GRID:
            _run_combo(L, pattern, 1, combo)


# --------------------------------------------------------------------------
# sweep executor: pathological convergence — the fallback must actually fire
# --------------------------------------------------------------------------
@pytest.mark.parametrize("pattern", ["near_singular", "dense_last_row"])
def test_sweep_fallback_fires_on_pathological(pattern):
    """Patterns the Jacobi sweep iteration cannot certify (a ~9-decade
    diagonal spread / a dense final row accumulating the whole vector):
    k=1 speculation must fail verification, the exact fallback must fire,
    and the corrected answer must still satisfy the same oracle criteria as
    every other strategy."""
    from repro.core import SpTRSV as _S
    from repro.core.sweep import SweepConfig

    L = pathological(pattern, n=72, seed=1)
    with enable_x64():
        rng = np.random.default_rng(10_001)
        b = rng.standard_normal(L.n)
        s = _S.build(L, strategy="sweep", sweep=SweepConfig(k=1))
        x = s.solve(jnp.asarray(b))
        assert s.sweep_stats.fallback_solves == 1, \
            "speculation unexpectedly passed verification at k=1"
        assert s.sweep_stats.fallback_columns == 1
        combo = ("sweep", "none", "permuted", False, 0)
        _check(L, pattern, x, b, _oracle(L, b, False), False, combo, 1)


# --------------------------------------------------------------------------
# guarded execution: fp32-edge patterns and mixed-precision refinement get
# the same differential treatment as the plain strategies
# --------------------------------------------------------------------------
EXTREME_PATTERNS = ["extreme_scale", "denormal_pivot"]
GUARD_STRATEGIES = ["serial", "levelset", "levelset_unroll", "sweep",
                    "blocked"]
GUARD_GRID = list(itertools.product(GUARD_STRATEGIES, ["none"], LAYOUTS,
                                    [False, True], [0, 3]))
_GUARD_STRIDE = 3


@pytest.mark.parametrize("pattern", EXTREME_PATTERNS)
def test_differential_guarded_extremes(pattern):
    """Tier-1: the fp32-edge patterns (values that overflow/underflow any
    float32 pipeline, pivots at the fp32 subnormal floor) through *guarded*
    solvers with ``on_breakdown="fallback"`` — verification must either pass
    outright or route through the corrective path, and the returned solution
    must satisfy the same componentwise residual criterion as every other
    strategy.  Rotating slice of strategy × layout × transpose × batch."""
    L = pathological(pattern, n=72, seed=1)
    phase = EXTREME_PATTERNS.index(pattern)
    with enable_x64():
        for combo in GUARD_GRID[phase::_GUARD_STRIDE]:
            _run_combo(L, pattern, 1, combo,
                       guard=GuardConfig(on_breakdown="fallback"))


MIXED_PATTERNS = ["arrow", "bidiag_chain", "power_law", "singleton_ladder"]
MIXED_GRID = list(itertools.product(["levelset", "sweep", "blocked"],
                                    ["none"], ["permuted"],
                                    [False, True], [0, 3]))
_MIXED_STRIDE = 2


def _run_mixed_combo(L, pattern, seed, combo):
    """precision="mixed" stores the packed values in bf16 (fp32 diagonal) and
    must still match the float64 oracle after guarded iterative refinement —
    forward error here, not just residual, because these patterns are
    well-conditioned and refinement claims fp64-class accuracy."""
    strategy, policy, layout, transpose, batch = combo
    s = SpTRSV.build(L, strategy=strategy, layout=layout, transpose=transpose,
                     rewrite=POLICIES[policy],
                     guard=GuardConfig(precision="mixed", refine_steps=6,
                                       on_breakdown="refine"))
    rng = np.random.default_rng(10_000 + seed)
    b = rng.standard_normal((L.n, batch) if batch else L.n)
    x = np.asarray(s.solve(jnp.asarray(b)))
    x_ref = _oracle(L, b, transpose)
    scale = max(np.abs(x_ref).max(), 1.0)
    try:
        np.testing.assert_allclose(x, x_ref, rtol=1e-9, atol=1e-9 * scale)
    except AssertionError as err:
        path = _dump_repro(L, pattern, seed, combo, str(err))
        raise AssertionError(
            f"mixed-precision mismatch for {combo} on {pattern}(seed={seed})"
            f" — repro dumped to {path}\n{err}") from None


@pytest.mark.parametrize("pattern", MIXED_PATTERNS)
def test_differential_mixed_precision(pattern):
    """Tier-1: guarded ``precision="mixed"`` vs the float64 oracle on the
    well-conditioned patterns, rotating slice of strategy × transpose ×
    batch (permuted layout only — mixed requires runtime value buffers)."""
    L = pathological(pattern, n=72, seed=1)
    phase = MIXED_PATTERNS.index(pattern)
    with enable_x64():
        for combo in MIXED_GRID[phase::_MIXED_STRIDE]:
            _run_mixed_combo(L, pattern, 1, combo)


@pytest.mark.fuzz
@pytest.mark.parametrize("pattern", EXTREME_PATTERNS)
def test_differential_guarded_exhaustive(pattern):
    """Nightly: full guarded grid on the fp32-edge patterns plus the full
    mixed-precision grid on the well-conditioned ones, FUZZ_SEEDS seeds."""
    seeds = int(os.environ.get("FUZZ_SEEDS", "3"))
    with enable_x64():
        for seed in range(seeds):
            L = pathological(pattern, n=96, seed=seed)
            for combo in GUARD_GRID:
                _run_combo(L, pattern, seed, combo,
                           guard=GuardConfig(on_breakdown="fallback"))
            Lw = pathological(MIXED_PATTERNS[seed % len(MIXED_PATTERNS)],
                              n=96, seed=seed)
            for combo in MIXED_GRID:
                _run_mixed_combo(Lw, MIXED_PATTERNS[seed % len(MIXED_PATTERNS)],
                                 seed, combo)


@pytest.mark.fuzz
@pytest.mark.parametrize("pattern", PATTERNS)
def test_differential_exhaustive(pattern):
    """Nightly: the full strategy × policy × layout × transpose × batch grid
    (distributed included) over FUZZ_SEEDS seeds."""
    import jax
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    seeds = int(os.environ.get("FUZZ_SEEDS", "3"))
    with enable_x64():
        for seed in range(seeds):
            L = pathological(pattern, n=96, seed=seed)
            for combo in GRID:
                _run_combo(L, pattern, seed, combo)
            for combo in itertools.product(
                    ["distributed"], sorted(POLICIES), LAYOUTS,
                    [False, True], [0, 3]):
                _run_combo(L, pattern, seed, combo, mesh=mesh)
            for combo in GPU_GRID:
                _run_combo(L, pattern, seed, combo, backend="interpret:gpu")
