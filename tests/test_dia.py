"""The SpMV operator's format (``codegen.build_spmv``): diagonals (DIA) for a
stencil, ELL for a scattered pattern, and ``codegen.spmv`` in either form
against ELL and SciPy."""
import collections

import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp

from repro.core import obs
from repro.core.codegen import (Diagonals, build_ell, build_spmv, ell_spmv,
                                spmv)
from repro.core.csr import CSRMatrix, eye_csr, from_dense
from repro.core.mg import _triangle
from repro.sparse import lung2_like, poisson2d, random_lower, stencil27


def _csr(S: sp.spmatrix, dtype=np.float32) -> CSRMatrix:
    S = sp.csr_matrix(S)
    S.sort_indices()
    return CSRMatrix(S.indptr.astype(np.int64), S.indices.astype(np.int64),
                     S.data.astype(dtype), S.shape)


def _scipy(M: CSRMatrix) -> sp.csr_matrix:
    return sp.csr_matrix((M.data.astype(np.float64), M.indices, M.indptr),
                         shape=M.shape)


def _random_spd(n: int = 200) -> CSRMatrix:
    L = _scipy(random_lower(n, seed=5))
    return _csr(L + L.T)


def _band_rectangular() -> CSRMatrix:
    """A 7 x 10 matrix with three diagonals, one starting off the matrix."""
    rng = np.random.default_rng(6)
    dense = np.zeros((7, 10))
    for off in (-2, 1, 4):
        for i in range(7):
            if 0 <= i + off < 10:
                dense[i, i + off] = rng.normal()
    return from_dense(dense.astype(np.float32))


STENCILS = {
    "poisson2d": lambda: poisson2d(9, 11, dtype=np.float32),
    "poisson2d_upper": lambda: _triangle(poisson2d(9, 11, dtype=np.float32),
                                         upper=True, strict=True),
    "stencil27": lambda: stencil27(6, 5, 4, dtype=np.float32),
    "stencil27_upper": lambda: _triangle(stencil27(6, 5, 4, dtype=np.float32),
                                         upper=True, strict=True),
}
SCATTERED = {
    "lung2_like": lambda: lung2_like(scale=0.01, dtype=np.float32),
    "random_spd": _random_spd,
}
DIAGONAL = {
    **STENCILS,
    "band_rectangular": _band_rectangular,
    "one_by_one": lambda: from_dense(np.array([[2.5]], dtype=np.float32)),
    "identity": lambda: eye_csr(17, dtype=np.float32),
    "no_entries": lambda: CSRMatrix(np.zeros(4, np.int64),
                                    np.zeros(0, np.int64),
                                    np.zeros(0, np.float32), (3, 3)),
}


def _loop_dia(M: CSRMatrix):
    """Reference: the offsets each row holds, then one entry at a time."""
    offsets = sorted({int(c) - i for i in range(M.n) for c in M.row(i)[0]})
    vals = np.zeros((len(offsets), M.n), dtype=M.dtype)
    for i in range(M.n):
        for c, v in zip(*M.row(i)):
            vals[offsets.index(int(c) - i), i] = v
    return tuple(offsets), vals


@pytest.mark.parametrize("make", DIAGONAL.values(), ids=DIAGONAL.keys())
def test_build_spmv_equals_the_row_loop_bit_for_bit(make):
    M = make()
    pattern, vals = build_spmv(M)
    offsets, want = _loop_dia(M)
    assert isinstance(pattern, Diagonals) and pattern.offsets == offsets
    assert vals.dtype == want.dtype and vals.shape == want.shape
    assert vals.tobytes() == want.tobytes()


@pytest.mark.parametrize("make", STENCILS.values(), ids=STENCILS.keys())
def test_a_row_without_a_diagonal_holds_zero_there(make):
    M = make()
    pattern, vals = build_spmv(M)
    stored = np.zeros(vals.shape, dtype=bool)
    rows = np.repeat(np.arange(M.n), M.row_nnz())
    d = np.searchsorted(pattern.offsets, M.indices - rows)
    stored[d, rows] = True
    assert (~stored).any()                 # the grid's edges lack some
    assert not vals[~stored].any()
    assert np.count_nonzero(stored) == M.nnz


@pytest.mark.parametrize("m", [None, 3], ids=["n", "n_by_3"])
@pytest.mark.parametrize("make", STENCILS.values(), ids=STENCILS.keys())
def test_dia_spmv_equals_ell_and_scipy(make, m):
    M = make()
    pattern, vals = build_spmv(M)
    assert isinstance(pattern, Diagonals)
    assert len(pattern.offsets) <= 2 * build_ell(M).K
    rng = np.random.default_rng(7)
    v = rng.standard_normal(M.n if m is None else (M.n, m)).astype(np.float32)
    got = np.asarray(jax.jit(spmv)(pattern, jnp.asarray(vals),
                                   jnp.asarray(v)))
    ell = np.asarray(ell_spmv(build_ell(M), jnp.asarray(v)))
    want = _scipy(M) @ v.astype(np.float64)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, ell, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("m", [None, 3], ids=["n", "n_by_3"])
def test_dia_spmv_of_a_rectangular_band(m):
    M = _band_rectangular()
    pattern, vals = build_spmv(M)
    assert pattern.offsets == (-2, 1, 4)
    v = np.random.default_rng(8).standard_normal(
        10 if m is None else (10, m)).astype(np.float32)
    got = np.asarray(spmv(pattern, jnp.asarray(vals), jnp.asarray(v)))
    np.testing.assert_allclose(got, _scipy(M) @ v.astype(np.float64),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("make", SCATTERED.values(), ids=SCATTERED.keys())
def test_a_scattered_pattern_keeps_ell(make):
    M = make()
    pattern, vals = build_spmv(M)
    ell = build_ell(M)
    assert isinstance(pattern, np.ndarray)
    assert pattern.tobytes() == ell.cols.tobytes()
    assert vals.tobytes() == ell.vals.tobytes()


def test_the_rule_weighs_the_bytes_of_each_format():
    """A tridiagonal matrix with one more entry in each of its first rows,
    each on a diagonal of its own: ELL width 4, and three diagonals plus
    one per extra entry.  DIA is picked while ``ndiag · b ≤ 4 · (b + 4)``:
    up to 8 diagonals in float32, 6 in float64, 12 in float16."""
    n = 16

    def form(extra, dtype):
        dense = np.eye(n, k=-1) + 2 * np.eye(n) + np.eye(n, k=1)
        for j in range(extra):
            dense[j, 2 * j + 3] = 1.0
        return type(build_spmv(from_dense(dense.astype(dtype)))[0])

    assert form(5, np.float32) is Diagonals
    assert form(6, np.float32) is np.ndarray
    assert form(6, np.float16) is Diagonals
    assert form(3, np.float64) is Diagonals
    assert form(4, np.float64) is np.ndarray


def test_each_build_counts_its_entries_in_the_form_chosen(monkeypatch):
    monkeypatch.setattr(obs, "_counts", collections.Counter())
    stencil, scattered = poisson2d(9, 11, dtype=np.float32), _random_spd()
    build_spmv(stencil)
    build_spmv(stencil)
    build_spmv(scattered)
    counts = obs.snapshot()
    assert counts[obs.SPMV_DIA_ENTRIES] == 2 * 5 * stencil.n
    assert counts[obs.SPMV_ELL_ENTRIES] == build_ell(scattered).vals.size
