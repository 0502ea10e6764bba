"""Whole-matrix ELL packing (``codegen.build_ell``) against a per-row loop."""
import numpy as np
import pytest

from repro.core.codegen import build_ell
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


@pytest.mark.parametrize("make", [
    _random_with_empty_rows,
    lambda: from_dense(np.array([[2.5]])),
    lambda: CSRMatrix(np.zeros(4, np.int64), np.zeros(0, np.int64),
                      np.zeros(0, np.float32), (3, 3)),
    lambda: eye_csr(17, dtype=np.float32),
    lambda: poisson2d(24, 24, dtype=np.float32),
], ids=["random_empty_rows", "one_by_one", "no_entries", "diagonal",
        "poisson2d_24"])
def test_build_ell_equals_the_row_loop_bit_for_bit(make):
    M = make()
    ell = build_ell(M)
    cols, vals, val_src = _loop_ell(M)
    assert ell.K == cols.shape[0] == max(int(M.row_nnz().max()), 1)
    for got, want in ((ell.cols, cols), (ell.vals, vals),
                      (ell.val_src, val_src)):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.tobytes() == want.tobytes()
