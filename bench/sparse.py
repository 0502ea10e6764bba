"""The benchmark's own sparse container, on the host in NumPy.

Generators under ``bench/generators`` return these; the drivers hand the
arrays to the program in its own container, and the oracle reads them in
float64 through SciPy.  Nothing here imports the program.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import scipy.sparse as sp


@dataclasses.dataclass(frozen=True)
class Csr:
    """Square CSR matrix: int64 ``indptr`` (n+1,), int64 ``indices`` (nnz,)
    sorted within each row, ``data`` (nnz,)."""

    indptr: np.ndarray
    indices: np.ndarray
    data: np.ndarray

    @property
    def n(self) -> int:
        return int(self.indptr.shape[0] - 1)

    @property
    def nnz(self) -> int:
        return int(self.indices.shape[0])

    def rows(self) -> np.ndarray:
        """Row id of every stored entry."""
        return np.repeat(np.arange(self.n, dtype=np.int64), np.diff(self.indptr))

    def scipy(self, dtype=np.float64) -> sp.csr_matrix:
        return sp.csr_matrix((self.data.astype(dtype), self.indices, self.indptr),
                             shape=(self.n, self.n))


def from_coo(rows, cols, vals, n: int, dtype) -> Csr:
    """CSR from coordinates: rows then columns ascending, duplicates summed."""
    rows = np.asarray(rows, dtype=np.int64)
    cols = np.asarray(cols, dtype=np.int64)
    vals = np.asarray(vals, dtype=np.float64)
    key, inverse = np.unique(rows * n + cols, return_inverse=True)
    data = np.zeros(key.size, dtype=np.float64)
    np.add.at(data, inverse, vals)
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.add.at(indptr, key // n + 1, 1)
    return Csr(np.cumsum(indptr), key % n, data.astype(dtype))
