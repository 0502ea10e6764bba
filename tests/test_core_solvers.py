"""All executor strategies must agree with the dense solve, with and
without rewriting, across dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import RewriteConfig, SpTRSV
from repro.sparse import banded_lower, chain_matrix, lung2_like, random_lower


def np_fsolve(L, b):
    x = np.zeros(L.n)
    for i in range(L.n):
        c, v = L.row(i)
        x[i] = (b[i] - (v[:-1] * x[c[:-1]]).sum()) / v[-1]
    return x


MATRICES = {
    "random": lambda: random_lower(257, avg_offdiag=3.0, seed=11, dtype=np.float32),
    "banded": lambda: banded_lower(300, bandwidth=6, fill=0.6, seed=2, dtype=np.float32),
    "chain": lambda: chain_matrix(100, dtype=np.float32),
    "lung2_small": lambda: lung2_like(scale=0.02, fat_levels=5, thin_run=8, dtype=np.float32),
}
STRATS = ["serial", "levelset", "levelset_unroll", "pallas_level", "pallas_fused"]


@pytest.mark.parametrize("mat", MATRICES)
@pytest.mark.parametrize("strategy", STRATS)
@pytest.mark.parametrize("rewrite", [None, RewriteConfig(thin_threshold=3)])
def test_solver_matches_reference(mat, strategy, rewrite):
    L = MATRICES[mat]()
    rng = np.random.default_rng(5)
    b = rng.normal(size=L.n).astype(np.float32)
    x_ref = np_fsolve(L.astype(np.float64), b.astype(np.float64))
    s = SpTRSV.build(L, strategy=strategy, rewrite=rewrite)
    x = np.asarray(s.solve(jnp.asarray(b)))
    assert x.shape == (L.n,)
    assert np.isfinite(x).all()
    np.testing.assert_allclose(x, x_ref, rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("dist_strategy", ["all_gather", "psum"])
def test_distributed_solver(dist_strategy):
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((8,), ("data",))
    L = random_lower(400, avg_offdiag=3.0, seed=4, dtype=np.float32)
    b = np.random.default_rng(1).normal(size=400).astype(np.float32)
    x_ref = np_fsolve(L.astype(np.float64), b.astype(np.float64))
    s = SpTRSV.build(
        L,
        strategy="distributed",
        mesh=mesh,
        dist_strategy=dist_strategy,
        rewrite=RewriteConfig(thin_threshold=4),
    )
    x = np.asarray(s.solve(jnp.asarray(b)))
    np.testing.assert_allclose(x, x_ref, rtol=2e-3, atol=2e-4)


def test_rewrite_reduces_distributed_collectives():
    """The paper's story at scale: fewer levels => fewer collectives."""
    from repro.core import build_level_sets, build_schedule, rewrite_matrix
    from repro.core.dist import shard_schedule

    L = lung2_like(scale=0.05, fat_levels=6, thin_run=10, dtype=np.float32)
    base = build_schedule(L)
    res = rewrite_matrix(L, config=RewriteConfig(thin_threshold=2))
    opt = build_schedule(res.L, res.levels)
    d_base = shard_schedule(base, 8)
    d_opt = shard_schedule(opt, 8)
    assert d_opt.num_levels < d_base.num_levels * 0.5
    assert d_opt.collective_bytes() < d_base.collective_bytes() * 0.8


def test_float64_path():
    from jax import enable_x64

    with enable_x64():
        L = random_lower(150, avg_offdiag=3.0, seed=9, dtype=np.float64)
        b = np.random.default_rng(3).normal(size=150)
        x_ref = np_fsolve(L, b)
        s = SpTRSV.build(L, strategy="levelset")
        x = np.asarray(s.solve(jnp.asarray(b, dtype=jnp.float64)))
        np.testing.assert_allclose(x, x_ref, rtol=1e-12, atol=1e-13)
