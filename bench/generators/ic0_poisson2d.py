"""IC(0) of the 5-point Laplacian on an ``nx`` x ``ny`` grid.

The same matrices as the program's ``repro.sparse.poisson2d`` and
``ic0_factor`` (a copy, so that the yardstick does not move with the
program), computed by grid wavefronts instead of row by row.  On the 5-point
pattern IC(0) has no fill term: ``L[i,i-1] = a[i,i-1] / L[i-1,i-1]``,
``L[i,i-nx] = a[i,i-nx] / L[i-nx,i-nx]`` and
``L[i,i] = sqrt(max((1+shift) a[i,i] - L[i,i-1]^2 - L[i,i-nx]^2, 1e-8))``,
all in float64 and rounded once to the configuration's dtype.  The seed
plays no part here: the drivers draw right-hand sides from it.
"""
from __future__ import annotations

import numpy as np

from bench.sparse import Csr, from_coo


def poisson2d(nx: int, ny: int, dtype) -> Csr:
    """4 on the diagonal, -1 to each grid neighbour; row i = y * nx + x."""
    y, x = np.divmod(np.arange(nx * ny, dtype=np.int64), nx)
    i = y * nx + x
    rows, cols, vals = [i], [i], [np.full(i.size, 4.0)]
    for dx, dy in ((-1, 0), (1, 0), (0, -1), (0, 1)):
        ok = (x + dx >= 0) & (x + dx < nx) & (y + dy >= 0) & (y + dy < ny)
        rows.append(i[ok])
        cols.append((y[ok] + dy) * nx + x[ok] + dx)
        vals.append(np.full(int(ok.sum()), -1.0))
    return from_coo(np.concatenate(rows), np.concatenate(cols),
                    np.concatenate(vals), nx * ny, dtype)


def ic0(nx: int, ny: int, shift: float, dtype) -> Csr:
    """Lower IC(0) factor of ``poisson2d(nx, ny)`` with the diagonal scaled
    by ``1 + shift``, with the pattern of its lower triangle."""
    d = np.zeros((ny, nx))      # L[i, i]
    west = np.zeros((ny, nx))   # L[i, i-1]
    south = np.zeros((ny, nx))  # L[i, i-nx]
    y_all, x_all = np.indices((ny, nx))
    for w in range(nx + ny - 1):          # wavefront x + y = w
        on = (x_all + y_all) == w
        ys, xs = y_all[on], x_all[on]
        s = np.full(ys.size, 4.0 * (1.0 + shift))
        has_w, has_s = xs > 0, ys > 0
        west[ys[has_w], xs[has_w]] = -1.0 / d[ys[has_w], xs[has_w] - 1]
        south[ys[has_s], xs[has_s]] = -1.0 / d[ys[has_s] - 1, xs[has_s]]
        s -= west[ys, xs] ** 2 + south[ys, xs] ** 2
        d[ys, xs] = np.sqrt(np.maximum(s, 1e-8))
    i = np.arange(nx * ny, dtype=np.int64).reshape(ny, nx)
    rows = np.concatenate([i[1:, :].ravel(), i[:, 1:].ravel(), i.ravel()])
    cols = np.concatenate([(i[1:, :] - nx).ravel(), (i[:, 1:] - 1).ravel(),
                           i.ravel()])
    vals = np.concatenate([south[1:, :].ravel(), west[:, 1:].ravel(),
                           d.ravel()])
    return from_coo(rows, cols, vals, nx * ny, dtype)


def make(config: dict, seed: int) -> dict:
    """``{"A": Csr, "L": Csr}``: the SPD system and its IC(0) factor."""
    nx, ny = config["nx"], config["ny"]
    return {"A": poisson2d(nx, ny, config["dtype"]),
            "L": ic0(nx, ny, config["shift"], config["dtype"])}
