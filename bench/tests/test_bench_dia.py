"""The ``dia_share.spmv`` reader: 0 from a program that counts no SpMV
format, the counted share otherwise, 100 from the program's own stencil
operators."""
import collections
import sys

import numpy as np
import pytest

from bench import harness

READ = harness.load_module("metrics", "dia_share.spmv").read


@pytest.fixture
def counts(monkeypatch):
    from repro.core import obs

    fresh = collections.Counter()
    monkeypatch.setattr(obs, "_counts", fresh)
    return fresh


def test_a_program_without_the_counters_reads_zero(counts, monkeypatch):
    counts["pcg.iterations"] = 93
    assert READ({}) == 0.0
    monkeypatch.setitem(sys.modules, "repro.core.obs", None)
    assert READ({}) == 0.0


@pytest.mark.parametrize("dia, ell, share", [
    (30, 10, 75.0), (7, 0, 100.0), (0, 5, 0.0)])
def test_the_share_of_entries_stored_by_diagonals(counts, dia, ell, share):
    counts.update({"spmv.dia_entries": dia, "spmv.ell_entries": ell})
    assert READ({}) == pytest.approx(share)


def test_the_programs_stencil_operators_read_all_dia(counts):
    import jax.numpy as jnp

    from repro.core import make_mg_preconditioner
    from repro.core.pcg import pcg
    from repro.sparse import hpcg_hierarchy

    As, f2c = hpcg_hierarchy(8, 8, 8, levels=2, dtype=np.float32)
    b = jnp.ones(As[0].n, jnp.float32)
    pcg(As[0], b, make_mg_preconditioner(As, f2c), tol=1e-6, maxiter=2)
    assert counts["spmv.dia_entries"] > 0
    assert READ({}) == 100.0
