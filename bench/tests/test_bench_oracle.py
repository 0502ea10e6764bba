"""The float64 oracle copied into the benchmark passes a sound f32 solve and
fails a perturbed answer and a bfloat16-rounded one."""
import ml_dtypes
import numpy as np
import pytest

from bench import harness, oracle
from bench.generators import ic0_poisson2d, lung2_like

LUNG2 = dict(scale=0.01, fat_levels=6, fat_rows=3770, thin_run=16,
             structure_seed=0, diag_low=4.0, offdiag_std=0.25, dtype="float32")


def _solve(L, b, transpose):
    from scipy.sparse.linalg import spsolve_triangular

    A = L.scipy()
    return spsolve_triangular(A.T.tocsr() if transpose else A, b,
                              lower=not transpose)


@pytest.mark.parametrize("transpose", [False, True])
def test_solve_errors_separate_sound_perturbed_and_bf16(transpose):
    cell = harness.load_cell("lung2.bwd.m8" if transpose else "lung2.fwd.m1")
    L = lung2_like.make(LUNG2, 3)["L"]
    b = np.random.default_rng(0).standard_normal((L.n, 8)).astype(np.float32)
    x = _solve(L, b.astype(np.float64), transpose).astype(np.float32)
    ok, _ = oracle.judge(oracle.solve_errors(L, b, x, transpose=transpose),
                         cell.limits)
    assert ok
    bad = x.copy()
    bad[L.n // 2, 3] *= 1.01
    ok, checks = oracle.judge(oracle.solve_errors(L, b, bad, transpose=transpose),
                              cell.limits)
    assert not ok and checks["residual"]["value"] > checks["residual"]["limit"]
    low = x.astype(ml_dtypes.bfloat16).astype(np.float32)
    ok, _ = oracle.judge(oracle.solve_errors(L, b, low, transpose=transpose),
                         cell.limits)
    assert not ok
    nan = x.copy()
    nan[0, 0] = np.nan
    assert not oracle.judge(oracle.solve_errors(L, b, nan, transpose=transpose),
                            cell.limits)[0]


def test_pcg_errors_separate_sound_and_bf16():
    limits = harness.load_cell("ic0_pcg.p512").limits
    A = ic0_poisson2d.make({"nx": 12, "ny": 10, "shift": 0.05,
                            "dtype": "float32"}, 0)["A"]
    b = (A.scipy() @ np.random.default_rng(1).standard_normal(A.n)).astype(np.float32)
    from scipy.sparse.linalg import spsolve

    x = spsolve(A.scipy().tocsc(), b.astype(np.float64)).astype(np.float32)
    assert oracle.judge(oracle.pcg_errors(A, b, x), limits)[0]
    low = x.astype(ml_dtypes.bfloat16).astype(np.float32)
    assert not oracle.judge(oracle.pcg_errors(A, b, low), limits)[0]
    assert not oracle.judge(oracle.pcg_errors(A, b, np.zeros_like(x)), limits)[0]


def test_judge_fails_a_number_not_read():
    ok, checks = oracle.judge({}, {"residual": 1.0})
    assert not ok and checks["residual"]["value"] == float("inf")
