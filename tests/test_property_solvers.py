"""Property-based correctness harness for every SpTRSV strategy.

Randomized (seeded) sweep over the matrix generator suite asserting each
strategy × {rewrite on/off} × {f32, f64} matches a NumPy forward-substitution
oracle, plus the rewrite invariant ``L' x = E b ⟺ L x = b`` checked directly
on the transformed system (no executor in the loop).

Uses the hypothesis-or-fallback harness in ``_hypothesis_compat`` so the
sweep runs (deterministically) even where hypothesis isn't installed.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from jax import enable_x64
from repro.core import RewriteConfig, SpTRSV, rewrite_matrix
from repro.sparse import banded_lower, chain_matrix, lung2_like, random_lower


def np_fsolve(L, b):
    """Forward-substitution oracle (host numpy, float64).

    Handles b of shape (n,) or (n, m)."""
    x = np.zeros(b.shape, dtype=np.float64)
    for i in range(L.n):
        c, v = L.row(i)
        deps = v[:-1][:, None] * x[c[:-1]] if b.ndim == 2 else v[:-1] * x[c[:-1]]
        x[i] = (b[i] - deps.sum(axis=0)) / v[-1]
    return x


def _make_matrix(kind: str, n: int, seed: int, dtype=np.float64):
    if kind == "random":
        return random_lower(n, avg_offdiag=3.0, seed=seed, dtype=dtype)
    if kind == "banded":
        return banded_lower(n, bandwidth=5, fill=0.5, seed=seed, dtype=dtype)
    if kind == "chain":
        return chain_matrix(n, dtype=dtype)
    if kind == "lung2":
        # lung2_like sizes itself from its level-structure params; map n
        # loosely onto the thin-run length so the sweep varies structure.
        return lung2_like(scale=0.02, fat_levels=3,
                          thin_run=3 + n % 6, seed=seed, dtype=dtype)
    raise ValueError(kind)


@st.composite
def matrix_spec(draw):
    kind = draw(st.sampled_from(["random", "banded", "chain", "lung2"]))
    n = draw(st.integers(20, 120))
    seed = draw(st.integers(0, 2**31 - 1))
    return kind, n, seed


LOCAL_STRATEGIES = ["serial", "levelset", "levelset_unroll",
                    "pallas_level", "pallas_fused"]


@given(matrix_spec())
@settings(max_examples=6, deadline=None)
def test_all_strategies_match_oracle_f32(spec):
    kind, n, seed = spec
    L = _make_matrix(kind, n, seed, dtype=np.float32)
    rng = np.random.default_rng(seed ^ 0x5EED)
    b = rng.normal(size=L.n).astype(np.float32)
    x_ref = np_fsolve(L.astype(np.float64), b.astype(np.float64))
    for strategy in LOCAL_STRATEGIES:
        for rewrite in (None, RewriteConfig(thin_threshold=3)):
            s = SpTRSV.build(L, strategy=strategy, rewrite=rewrite)
            x = np.asarray(s.solve(jnp.asarray(b)))
            np.testing.assert_allclose(
                x, x_ref, rtol=2e-3, atol=2e-4,
                err_msg=f"{kind} n={n} seed={seed} {strategy} "
                        f"rewrite={rewrite is not None}")


@given(matrix_spec())
@settings(max_examples=4, deadline=None)
def test_all_strategies_match_oracle_f64(spec):
    kind, n, seed = spec
    with enable_x64():
        L = _make_matrix(kind, n, seed, dtype=np.float64)
        rng = np.random.default_rng(seed ^ 0xF64)
        b = rng.normal(size=L.n)
        x_ref = np_fsolve(L, b)
        for strategy in LOCAL_STRATEGIES:
            for rewrite in (None, RewriteConfig(thin_threshold=3)):
                s = SpTRSV.build(L, strategy=strategy, rewrite=rewrite)
                x = np.asarray(s.solve(jnp.asarray(b, dtype=jnp.float64)))
                assert x.dtype == np.float64
                np.testing.assert_allclose(
                    x, x_ref, rtol=1e-10, atol=1e-11,
                    err_msg=f"{kind} n={n} seed={seed} {strategy} "
                            f"rewrite={rewrite is not None}")


@given(matrix_spec(), st.integers(1, 6))
@settings(max_examples=8, deadline=None)
def test_rewrite_invariant_direct(spec, thin_threshold):
    """L' x = E b has the same solution as L x = b — checked with the numpy
    oracle on both systems, independent of any executor."""
    kind, n, seed = spec
    L = _make_matrix(kind, n, seed, dtype=np.float64)
    res = rewrite_matrix(L, config=RewriteConfig(thin_threshold=thin_threshold))
    rng = np.random.default_rng(seed ^ 0xE)
    b = rng.normal(size=L.n)
    x_orig = np_fsolve(L, b)
    b_prime = res.E.to_dense() @ b
    x_rewritten = np_fsolve(res.L, b_prime)
    np.testing.assert_allclose(x_rewritten, x_orig, rtol=1e-9, atol=1e-10)
    # and the rewrite must not have grown past its fill budget
    assert res.L.nnz <= 2.0 * L.nnz + L.n


@given(matrix_spec(), st.integers(2, 7))
@settings(max_examples=4, deadline=None)
def test_oracle_batched_consistency(spec, m):
    """The multi-RHS oracle itself: columns of np_fsolve(L, B) are the
    single-RHS solves (guards the harness the batched tests lean on)."""
    kind, n, seed = spec
    L = _make_matrix(kind, n, seed, dtype=np.float64)
    rng = np.random.default_rng(seed)
    B = rng.normal(size=(L.n, m))
    X = np_fsolve(L, B)
    for j in range(m):
        np.testing.assert_allclose(X[:, j], np_fsolve(L, B[:, j]),
                                   rtol=1e-12, atol=1e-12)
