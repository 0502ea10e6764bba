"""HPCG's multigrid preconditioner (``repro.core.mg``) and its problem
(``repro.sparse.stencil27``, ``hpcg_hierarchy``) against row-by-row NumPy
readings of the HPCG 3.1 reference code: ``GenerateProblem``,
``GenerateCoarseProblem``, ``ComputeSYMGS_ref``, ``ComputeMG_ref`` and its
CG, in float64.  The references use neither of the program's identities
(the shared ``U x⁰`` and the restriction at the ``f2c`` rows only)."""
import numpy as np
import pytest
import scipy.sparse as sp

import jax.numpy as jnp
from jax import enable_x64

from repro.core import make_mg_preconditioner, obs
from repro.core.pcg import pcg
from repro.sparse import hpcg_hierarchy, stencil27

GRIDS = [(16, 16, 16), (24, 24, 24)]


def _generate_problem(nx, ny, nz):
    """GenerateProblem, one row at a time."""
    rows, cols, vals = [], [], []
    for iz in range(nz):
        for iy in range(ny):
            for ix in range(nx):
                row = ix + nx * (iy + ny * iz)
                for sz in (-1, 0, 1):
                    for sy in (-1, 0, 1):
                        for sx in (-1, 0, 1):
                            x, y, z = ix + sx, iy + sy, iz + sz
                            if 0 <= x < nx and 0 <= y < ny and 0 <= z < nz:
                                col = x + nx * (y + ny * z)
                                rows.append(row)
                                cols.append(col)
                                vals.append(26.0 if col == row else -1.0)
    n = nx * ny * nz
    return sp.csr_matrix((vals, (rows, cols)), shape=(n, n))


def _scipy(A):
    return sp.csr_matrix((A.data, A.indices, A.indptr), shape=A.shape)


def _symgs_ref(A, r, x):
    """ComputeSYMGS_ref: a forward sweep in natural row order, then a
    backward one, each row updated in place from the current x."""
    x = x.copy()
    diag = A.diagonal()
    order = list(range(A.shape[0]))
    for sweep in (order, order[::-1]):
        for i in sweep:
            lo, hi = A.indptr[i], A.indptr[i + 1]
            s = r[i] - A.data[lo:hi] @ x[A.indices[lo:hi]] + diag[i] * x[i]
            x[i] = s / diag[i]
    return x


def _mg_ref(As, f2c, r, depth=0):
    """ComputeMG_ref: x = 0, SYMGS, restrict the whole r - A x by
    injection, recurse, prolong, SYMGS; the coarsest level one SYMGS."""
    A = As[depth]
    x = _symgs_ref(A, r, np.zeros_like(r))
    if depth == len(f2c):
        return x
    rc = (r - A @ x)[f2c[depth]]
    x[f2c[depth]] += _mg_ref(As, f2c, rc, depth + 1)
    return _symgs_ref(A, r, x)


def _pcg_ref(A, b, M, tol, maxiter):
    """The CG recurrence ``pcg`` runs, with ``M`` as the preconditioner."""
    x, r = np.zeros_like(b), b.copy()
    z = M(r)
    p, rz, b_norm = z, r @ z, np.linalg.norm(b)
    for it in range(maxiter):
        ap = A @ p
        alpha = rz / (p @ ap)
        x, r = x + alpha * p, r - alpha * ap
        if np.linalg.norm(r) <= tol * b_norm:
            return x, it + 1
        z = M(r)
        rz_new = r @ z
        p, rz = z + (rz_new / rz) * p, rz_new
    return x, maxiter


@pytest.mark.parametrize("dims", [(5, 4, 3), (16, 16, 16)])
def test_stencil27_is_generate_problem(dims):
    A = stencil27(*dims)
    ref = _generate_problem(*dims)
    ref.sort_indices()
    assert A.shape == ref.shape
    assert np.array_equal(A.indptr, ref.indptr)
    assert np.array_equal(A.indices, ref.indices)
    assert np.array_equal(A.data, ref.data)


@pytest.mark.parametrize("dims", GRIDS)
def test_hierarchy_is_generate_coarse_problem(dims):
    As, f2c = hpcg_hierarchy(*dims)
    assert len(As) == 4 and len(f2c) == 3
    nx, ny, nz = dims
    for lv in range(3):
        cx, cy, cz = nx // 2, ny // 2, nz // 2
        want = [2 * ix + nx * (2 * iy + ny * 2 * iz)
                for iz in range(cz) for iy in range(cy) for ix in range(cx)]
        assert np.array_equal(f2c[lv], want)
        ref = _generate_problem(cx, cy, cz)
        ref.sort_indices()
        assert np.array_equal(As[lv + 1].indices, ref.indices)
        nx, ny, nz = cx, cy, cz


@pytest.mark.parametrize("dims", GRIDS)
def test_symgs_is_compute_symgs_ref(dims):
    with enable_x64():
        As, f2c = hpcg_hierarchy(*dims)
        level = make_mg_preconditioner(As, f2c).levels[0]
        A = _scipy(As[0])
        rng = np.random.default_rng(1)
        r, x0 = rng.standard_normal((2, A.shape[0]))
        for start, got in ((np.zeros_like(r), level.symgs(jnp.asarray(r))),
                           (x0, level.symgs(jnp.asarray(r), jnp.asarray(x0)))):
            want = _symgs_ref(A, r, start)
            np.testing.assert_allclose(np.asarray(got), want, rtol=1e-11,
                                       atol=1e-12 * np.abs(want).max())


@pytest.mark.parametrize("dims", GRIDS)
def test_vcycle_is_compute_mg_ref(dims):
    with enable_x64():
        As, f2c = hpcg_hierarchy(*dims)
        mg = make_mg_preconditioner(As, f2c)
        r = np.random.default_rng(2).standard_normal(As[0].n)
        want = _mg_ref([_scipy(A) for A in As], f2c, r)
        np.testing.assert_allclose(np.asarray(mg(jnp.asarray(r))), want,
                                   rtol=1e-11,
                                   atol=1e-12 * np.abs(want).max())


def test_mg_pcg_is_the_reference_cg():
    """Equal iteration counts, and answers within 1e-9 of each other
    relative to their size: both run the same f64 recurrence, apart in the
    order of their sums only."""
    with enable_x64():
        As, f2c = hpcg_hierarchy(16, 16, 16)
        mg = make_mg_preconditioner(As, f2c)
        A = _scipy(As[0])
        b = A @ np.random.default_rng(3).standard_normal(A.shape[0])
        before = obs.snapshot()
        res = pcg(As[0], jnp.asarray(b), mg, tol=1e-10, maxiter=100)
        after = obs.snapshot()
        x_ref, iters = _pcg_ref(
            A, b, lambda v: _mg_ref([_scipy(M) for M in As], f2c, v),
            tol=1e-10, maxiter=100)
        assert res.converged and res.iters == iters
        x = np.asarray(res.x)
        assert np.linalg.norm(x - x_ref) <= 1e-9 * np.linalg.norm(x_ref)
        assert np.linalg.norm(b - A @ x) <= 1e-10 * np.linalg.norm(b) * 1.01
        # pcg applies M once before its loop and once in each iteration but
        # the last, which stops on the residual before applying it
        cycles = after.get(obs.MG_VCYCLES, 0) - before.get(obs.MG_VCYCLES, 0)
        assert cycles == res.iters


def test_every_vcycle_enqueues_the_same_programs():
    As, f2c = hpcg_hierarchy(16, 16, 16, dtype=np.float32)
    mg = make_mg_preconditioner(As, f2c)
    r = jnp.asarray(np.random.default_rng(4).standard_normal(As[0].n),
                    jnp.float32)
    counts = []
    for _ in range(3):
        before = obs.snapshot()
        r = mg(r)
        after = obs.snapshot()
        assert (after.get(obs.MG_VCYCLES, 0)
                - before.get(obs.MG_VCYCLES, 0)) == 1
        counts.append(after.get(obs.MG_DISPATCHES, 0)
                      - before.get(obs.MG_DISPATCHES, 0))
    # per level above the coarsest: two SYMGS (3 + 4 programs), the
    # restriction and the prolongation; the coarsest: one SYMGS from 0
    assert counts == [9 * 3 + 3] * 3


@pytest.mark.parametrize("dims, levels", [
    ((12, 16, 16), 4), ((16, 16, 20), 4), ((4, 4, 4), 4), ((8, 8, 8), 0)])
def test_bad_dimensions_are_refused(dims, levels):
    with pytest.raises(ValueError):
        hpcg_hierarchy(*dims, levels=levels)


def test_bad_hierarchies_are_refused():
    with pytest.raises(ValueError):
        stencil27(0, 4, 4)
    As, f2c = hpcg_hierarchy(8, 8, 8, levels=2)
    with pytest.raises(ValueError):
        make_mg_preconditioner(As, [])
    with pytest.raises(ValueError):
        make_mg_preconditioner(As, [f2c[0] + As[0].n])
    with pytest.raises(ValueError):
        make_mg_preconditioner(As, [f2c[0][:-1]])
