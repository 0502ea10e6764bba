"""HPCG 3.1's problem and multigrid hierarchy on an ``nx`` x ``ny`` x ``nz``
grid (``GenerateProblem``, ``GenerateCoarseProblem``).

The same matrices as the program's ``repro.sparse.stencil27`` and
``hpcg_hierarchy`` (a copy, so that the yardstick does not move with the
program): 26 on the diagonal and -1 for each of the up to 26 neighbours
inside the grid, row ``i = x + nx (y + ny z)``; each coarser level halves
every dimension and re-applies the stencil, and coarse point ``(i, j, k)``
injects from fine point ``(2i, 2j, 2k)``.  The seed plays no part here: the
driver draws right-hand sides from it.
"""
from __future__ import annotations

import numpy as np

from bench.sparse import Csr


def stencil27(nx: int, ny: int, nz: int, dtype) -> Csr:
    """HPCG's 27-point operator; columns ascend within each row."""
    z, y, x = (a.ravel() for a in np.indices((nz, ny, nx), dtype=np.int64))
    n = x.size
    row = np.arange(n, dtype=np.int64)
    nbr = np.empty((27, n), dtype=np.int64)
    ok = np.empty((27, n), dtype=bool)
    offsets = [(dz, dy, dx) for dz in (-1, 0, 1) for dy in (-1, 0, 1)
               for dx in (-1, 0, 1)]
    for k, (dz, dy, dx) in enumerate(offsets):   # ascending column order
        nbr[k] = row + dx + nx * dy + nx * ny * dz
        ok[k] = ((0 <= x + dx) & (x + dx < nx) & (0 <= y + dy) & (y + dy < ny)
                 & (0 <= z + dz) & (z + dz < nz))
    nbr, ok = nbr.T, ok.T
    indices = nbr[ok]
    indptr = np.concatenate([[0], np.cumsum(ok.sum(axis=1))])
    data = np.where(indices == np.repeat(row, ok.sum(axis=1)), 26.0, -1.0)
    return Csr(indptr, indices, data.astype(dtype))


def lower(A: Csr) -> Csr:
    """``tril(A)``, diagonal included."""
    keep = A.indices <= A.rows()
    counts = np.bincount(A.rows()[keep], minlength=A.n)
    return Csr(np.concatenate([[0], np.cumsum(counts)]), A.indices[keep],
               A.data[keep])


def hierarchy(nx: int, ny: int, nz: int, levels: int, dtype) -> tuple:
    """``(operators, f2c)``, the finest level first; ``f2c[l][i]`` is the row
    of level ``l`` that row ``i`` of level ``l + 1`` injects from."""
    ops, maps = [], []
    for lv in range(levels):
        ops.append(stencil27(nx, ny, nz, dtype))
        if lv + 1 < levels:
            if nx % 2 or ny % 2 or nz % 2:
                raise ValueError(f"grid {(nx, ny, nz)} cannot be halved")
            k, j, i = (a.ravel() for a in np.indices(
                (nz // 2, ny // 2, nx // 2), dtype=np.int64))
            maps.append(2 * i + nx * (2 * j) + nx * ny * (2 * k))
            nx, ny, nz = nx // 2, ny // 2, nz // 2
    return ops, maps


def make(config: dict, seed: int) -> dict:
    """``{"A", "L", "levels", "f2c"}``: the finest operator, its lower
    triangle, every level's operator (the finest first) and the injection
    maps between them."""
    ops, maps = hierarchy(config["nx"], config["ny"], config["nz"],
                          config["mg_levels"], config["dtype"])
    return {"A": ops[0], "L": lower(ops[0]), "levels": ops, "f2c": maps}
