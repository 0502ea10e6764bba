"""Structural twin of SuiteSparse Norris/lung2 (arXiv:2103.11445 §V).

The pattern follows the program's ``repro.sparse.lung2_like`` step for step
(a copy, so that the yardstick does not move with the program): ``fat_levels``
fat wavefronts of ``fat_rows`` rows, each followed by a run of ``thin_run``
levels of two chained rows.  The pattern is drawn from the configuration's
fixed ``structure_seed``, so every run solves the same pattern and finds the
same compiled programs; the values are drawn from the run's seed.
"""
from __future__ import annotations

import numpy as np

from bench.sparse import Csr, from_coo


def pattern(scale: float, fat_levels: int, fat_rows: int, thin_run: int,
            seed: int) -> Csr:
    """The lung2 twin at ``seed``: the same random stream as the program's
    generator, so ``seed`` 0 gives its pattern (values are replaced)."""
    rng = np.random.default_rng(seed)
    fat_rows = max(4, int(fat_rows * scale))
    rows, cols, vals = [], [], []
    next_id = 0
    prev_fat = None
    prev_thin: list = []

    def add(i, j, v):
        rows.append(i)
        cols.append(j)
        vals.append(v)

    for _ in range(fat_levels):
        ids = np.arange(next_id, next_id + fat_rows)
        next_id += fat_rows
        for i in ids:
            add(i, i, 4.0 + rng.random())
            if prev_thin:
                add(i, int(prev_thin[-2 + int(rng.integers(0, 2))]),
                    rng.normal() * 0.25)
            if prev_fat is not None:
                k = int(rng.integers(1, 4))
                for j in rng.choice(prev_fat, size=min(k, prev_fat.size),
                                    replace=False):
                    add(i, int(j), rng.normal() * 0.25)
        prev_fat = ids
        prev_thin = []
        pair_prev: list = []
        for _t in range(thin_run):
            pair = [next_id, next_id + 1]
            next_id += 2
            for idx, i in enumerate(pair):
                add(i, i, 4.0 + rng.random())
                if pair_prev:
                    add(i, pair_prev[idx], rng.normal() * 0.25)
                else:
                    add(i, int(rng.choice(prev_fat)), rng.normal() * 0.25)
                if rng.random() < 0.5:
                    j = int(rng.choice(prev_fat))
                    if j != i:
                        add(i, j, rng.normal() * 0.1)
            pair_prev = pair
            prev_thin.extend(pair)
    return from_coo(rows, cols, vals, next_id, np.float64)


def make(config: dict, seed: int) -> dict:
    """``{"L": Csr}`` in the configuration's dtype: the fixed pattern, a
    diagonal drawn from ``diag_low + U(0, 1)`` and off-diagonals from
    ``N(0, offdiag_std)``, both from ``seed``."""
    p = pattern(config["scale"], config["fat_levels"], config["fat_rows"],
                config["thin_run"], config["structure_seed"])
    rng = np.random.default_rng(seed)
    data = rng.standard_normal(p.nnz) * config["offdiag_std"]
    diag = p.indptr[1:] - 1
    data[diag] = config["diag_low"] + rng.random(p.n)
    return {"L": Csr(p.indptr, p.indices, data.astype(config["dtype"]))}
