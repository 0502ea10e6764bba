"""Whole-matrix and off-diagonal ELL packing (``codegen.build_ell``,
``codegen.build_offdiag_ell``) against per-row loops."""
import numpy as np
import pytest

from repro.core.codegen import build_ell, build_offdiag_ell
from repro.core.csr import CSRMatrix, eye_csr, from_coo, from_dense
from repro.sparse import poisson2d


def _loop_ell(M: CSRMatrix):
    """Reference: one row at a time, entries in CSR order from slot 0."""
    K = max(int(M.row_nnz().max()), 1)
    cols = np.zeros((K, M.n), dtype=np.int32)
    vals = np.zeros((K, M.n), dtype=M.dtype)
    val_src = np.full((K, M.n), -1, dtype=np.int64)
    for i in range(M.n):
        lo, hi = int(M.indptr[i]), int(M.indptr[i + 1])
        k = hi - lo
        cols[:k, i] = M.indices[lo:hi]
        vals[:k, i] = M.data[lo:hi]
        val_src[:k, i] = np.arange(lo, hi, dtype=np.int64)
    return cols, vals, val_src


def _random_with_empty_rows():
    rng = np.random.default_rng(3)
    n = 60
    rows = rng.choice(np.arange(n)[rng.random(n) < 0.6], size=300)
    cols = rng.integers(0, n, size=300)
    M = from_coo(rows, cols, rng.normal(size=300).astype(np.float32), (n, n))
    assert (M.row_nnz() == 0).any()
    return M


CASES = [
    _random_with_empty_rows,
    lambda: from_dense(np.array([[2.5]])),
    lambda: CSRMatrix(np.zeros(4, np.int64), np.zeros(0, np.int64),
                      np.zeros(0, np.float32), (3, 3)),
    lambda: eye_csr(17, dtype=np.float32),
    lambda: poisson2d(24, 24, dtype=np.float32),
]
IDS = ["random_empty_rows", "one_by_one", "no_entries", "diagonal",
       "poisson2d_24"]


@pytest.mark.parametrize("make", CASES, ids=IDS)
def test_build_ell_equals_the_row_loop_bit_for_bit(make):
    M = make()
    ell = build_ell(M)
    cols, vals, val_src = _loop_ell(M)
    assert ell.K == cols.shape[0] == max(int(M.row_nnz().max()), 1)
    for got, want in ((ell.cols, cols), (ell.vals, vals),
                      (ell.val_src, val_src)):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.tobytes() == want.tobytes()


def _loop_offdiag_ell(M: CSRMatrix, upper: bool):
    """Reference: one row at a time, the diagonal (last entry of a lower
    row, first of an upper one) left out."""
    row_nnz = M.row_nnz() - 1
    K = max(int(row_nnz.max()) if row_nnz.size else 0, 1)
    cols = np.zeros((K, M.n), dtype=np.int32)
    vals = np.zeros((K, M.n), dtype=M.dtype)
    val_src = np.full((K, M.n), -1, dtype=np.int64)
    for i in range(M.n):
        lo, hi = int(M.indptr[i]), int(M.indptr[i + 1])
        sl = slice(lo + 1, hi) if upper else slice(lo, hi - 1)
        k = sl.stop - sl.start
        cols[:k, i] = M.indices[sl]
        vals[:k, i] = M.data[sl]
        val_src[:k, i] = np.arange(sl.start, sl.stop, dtype=np.int64)
    return cols, vals, val_src


def _lower_with_diagonal(M: CSRMatrix) -> CSRMatrix:
    """``tril(M)`` with every diagonal entry stored (4 where ``M`` has none)."""
    rows = np.repeat(np.arange(M.n), M.row_nnz())
    keep = M.indices < rows
    idx = np.arange(M.n)
    return from_coo(np.concatenate([rows[keep], idx]),
                    np.concatenate([M.indices[keep], idx]),
                    np.concatenate([M.data[keep],
                                    np.full(M.n, 4.0, M.dtype)]), M.shape)


@pytest.mark.parametrize("upper", [False, True], ids=["lower", "upper"])
@pytest.mark.parametrize("make", CASES, ids=IDS)
def test_build_offdiag_ell_equals_the_row_loop_bit_for_bit(make, upper):
    L = _lower_with_diagonal(make())
    M = L.transpose() if upper else L
    ell, diag, diag_src = build_offdiag_ell(M, upper=upper)
    cols, vals, val_src = _loop_offdiag_ell(M, upper)
    assert ell.K == cols.shape[0]
    for got, want in ((ell.cols, cols), (ell.vals, vals),
                      (ell.val_src, val_src)):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.tobytes() == want.tobytes()
    first = M.indptr[:-1] if upper else M.indptr[1:] - 1
    assert np.array_equal(diag_src, first)
    assert diag.tobytes() == M.data[first].tobytes()
