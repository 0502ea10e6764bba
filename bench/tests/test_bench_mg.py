"""The HPCG cell's files at a size a test run holds, on the CPU: the
generator copy gives the program's matrices, the plain reference solves the
system SciPy solves, the harness's run of the cell comes out correct with
the program and with the float32 reference and not with the bfloat16
control, an altered answer or a wrong V-cycle, and its six per-layer
readers read synthetic contexts."""
import collections
import dataclasses
import time

import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp
from jax import enable_x64
from scipy.sparse.linalg import spsolve

from bench import harness, oracle_mg, spans
from bench import trace_reduce as tr
from bench.drivers.mgcg import Program, level_sizes
from bench.generators import hpcg_27pt
from bench.reference import _levels
from bench.reference_mg import ReferenceMGCG, as_program
from bench.tests.test_bench_spans import write_xplane

TINY = dict(nx=16, ny=16, nz=16)
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def tiny():
    cell = harness.load_cell("hpcg.symgs")
    return dataclasses.replace(cell, config={**cell.config, **TINY})


def run(program=None) -> dict:
    result, report = harness.run_cell(
        tiny(), seed=2**32 + 29, seconds=0.2, trace=False,
        t0=time.perf_counter(), peaks=None, device=DEVICE, program=program)
    assert report["answers_checked"] > 0
    return result


@pytest.mark.parametrize("dims", [(16, 16, 16), (8, 16, 24)])
def test_generator_is_the_programs(dims):
    from repro.sparse import hpcg_hierarchy

    ops, maps = hpcg_27pt.hierarchy(*dims, 4, np.float32)
    As, f2c = hpcg_hierarchy(*dims, levels=4, dtype=np.float32)
    for ours, theirs in zip(ops, As, strict=True):
        assert np.array_equal(ours.indptr, theirs.indptr)
        assert np.array_equal(ours.indices, theirs.indices)
        assert np.array_equal(ours.data, theirs.data)
    for ours, theirs in zip(maps, f2c, strict=True):
        assert np.array_equal(ours, theirs)
    L = hpcg_27pt.lower(ops[0])
    want = sp.tril(ops[0].scipy(np.float32)).tocsr()
    assert np.array_equal(L.indptr, want.indptr)
    assert np.array_equal(L.indices, want.indices)


@pytest.mark.parametrize("dims", [(16, 16, 16), (8, 16, 24)])
def test_dag_levels_follow_the_stated_formula(dims):
    """Row (x, y, z) of tril(A) sits at level x + 2y + 4z (the config's
    ``dag_levels``)."""
    nx, ny, nz = dims
    L = hpcg_27pt.lower(hpcg_27pt.stencil27(nx, ny, nz, np.float32))
    z, y, x = (a.ravel() for a in np.indices((nz, ny, nx)))
    assert np.array_equal(_levels(L, False), x + 2 * y + 4 * z)


def test_reference_solves_what_scipy_solves():
    with enable_x64():
        ops, maps = hpcg_27pt.hierarchy(16, 16, 16, 4, np.float64)
        A = ops[0].scipy()
        b = A @ np.random.default_rng(5).standard_normal(A.shape[0])
        ref = ReferenceMGCG(ops, maps, tol=1e-10, maxiter=100,
                            dtype=jnp.float64)
        x, iters, ok = ref.solve(b)
        want = spsolve(A.tocsc(), b)
        assert ok and 0 < iters < 100
        assert (np.linalg.norm(np.asarray(x) - want)
                <= 1e-8 * np.linalg.norm(want))
        assert ref.solve(b, maxiter=2)[1] == 2


@pytest.mark.parametrize("who, dtype, correct", [
    ("program", None, True), ("witness_f32", jnp.float32, True),
    ("control_bf16", jnp.bfloat16, False)])
def test_only_the_control_is_not_correct(who, dtype, correct):
    result = run(None if dtype is None else as_program(dtype))
    assert result["correct"] is correct
    if who == "program":
        assert result["failed"] == 0
        assert set(result["metrics"]) == {m["name"]
                                          for m in tiny().end_to_end}


def test_pcg_s_is_the_window_over_its_iterations():
    """HPCG times fixed work: ``pcg_s`` is seconds a CG iteration, not a
    solve, whose iterations follow the seeded right-hand side."""
    result, report = harness.run_cell(
        tiny(), seed=2**33 + 3, seconds=0.2, trace=False,
        t0=time.perf_counter(), peaks=None, device=DEVICE)
    window = report["window"]
    assert result["correct"] and sum(window["iterations"]) > 0
    assert result["metrics"]["pcg_s"]["value"] == pytest.approx(
        window["seconds"] / sum(window["iterations"]))


def test_answer_altered_where_produced(monkeypatch):
    from repro.core import pcg as pcg_module

    pcg = pcg_module.pcg

    def broken(*args, **kwargs):
        res = pcg(*args, **kwargs)
        return dataclasses.replace(res, x=res.x.at[0].multiply(1.01))

    monkeypatch.setattr(pcg_module, "pcg", broken)
    assert not run()["correct"]


def _no_post_smoother(symgs):
    def fault(self, r, x0=None):
        return symgs(self, r) if x0 is None else x0
    return fault


def _no_upper_term(upper_rhs):
    def fault(cols, vals, x0, r):
        c, _ = upper_rhs(cols, vals, x0, r)
        return c * 0, r
    return fault


@pytest.mark.parametrize("fault", ["post_smoother_dropped",
                                   "upper_term_dropped"])
def test_a_wrong_vcycle_is_not_correct(fault, monkeypatch):
    """CG reaches ``tol`` under a wrong V-cycle too, so the true residual
    passes it; the V-cycle check does not."""
    from repro.core import mg

    if fault == "post_smoother_dropped":
        monkeypatch.setattr(mg.MGLevel, "symgs",
                            _no_post_smoother(mg.MGLevel.symgs))
    else:
        monkeypatch.setattr(mg, "_upper_rhs", _no_upper_term(mg._upper_rhs))
    result = run()
    checks = result["checks"]
    assert not result["correct"]
    assert checks["true_residual"]["value"] <= checks["true_residual"]["limit"]
    assert checks["vcycle_error"]["value"] > checks["vcycle_error"]["limit"]


def test_a_skipped_coarsest_level_is_caught_at_48_cubed(monkeypatch):
    """The coarsest level's correction is small beside the finest level's
    sweeps, so it shows only on a probe that carries smooth components and
    a grid whose coarsest level is not a handful of rows: at 48^3 (coarsest
    6^3) skipping it reads several times the limit, the sound V-cycle a
    small part of it."""
    from repro.core import mg

    cell = harness.load_cell("hpcg.symgs")
    limit = cell.limits["vcycle_error"]
    ops, maps = hpcg_27pt.hierarchy(48, 48, 48, 4, np.float32)
    sut = Program(ops, maps, tol=1e-6, maxiter=100)
    probe = harness.rng(2**32 + 7, 2).standard_normal(ops[0].n).astype(
        np.float32)

    def error():
        z = np.asarray(sut.precondition(jnp.asarray(probe)))
        return oracle_mg.vcycle_errors(ops, maps, probe, z)["vcycle_error"]

    assert error() <= limit / 4
    cycle = mg.MGPreconditioner._cycle

    def skip_coarsest(self, depth, r):
        if depth == len(self.levels) - 1:
            return jnp.zeros_like(r)
        return cycle(self, depth, r)

    monkeypatch.setattr(mg.MGPreconditioner, "_cycle", skip_coarsest)
    assert error() > 2 * limit


class _Solver:
    def __init__(self, segments):
        self.segments = segments

    def stats(self):
        return {"segments": self.segments}


def test_sync_segments_counts_each_sweep():
    read = harness.load_module("metrics", "sync_segments.mg").read
    objs = [(_Solver(644), 2), (_Solver(644), 2), (_Solver(4), 1),
            (_Solver(4), 1)]
    assert read({"objects": objs}) == 4 * 644 + 2 * 4
    assert read({"objects": []}) is None
    assert read({"objects": [_Solver(644)]}) is None


def test_dispatches_per_vcycle_reads_the_programs_counters(monkeypatch):
    from repro.core import obs

    read = harness.load_module("metrics", "dispatches_per_vcycle.mg").read
    monkeypatch.setattr(obs, "_counts", collections.Counter())
    assert read({}) is None
    obs.count(obs.MG_VCYCLES, 12)
    obs.count(obs.MG_DISPATCHES, 12 * 30)
    assert read({}) == 30


def test_roofline_counts_the_algorithms_bytes():
    mod = harness.load_module("metrics", "mg_roofline")
    ops, maps = hpcg_27pt.hierarchy(8, 8, 8, 2, np.float32)
    levels = level_sizes(ops, maps)
    n, nc = ops[0].n, ops[1].n
    nnz_l = [(A.nnz + A.n) // 2 for A in ops]
    sweep = mod.sweep_bytes
    want = (4 * sweep(n, nnz_l[0], 4) + sweep(n, nnz_l[0] - n, 4)
            + levels[0]["nnz_f2c_rows"] * 8 + (nc + 1) * 4 + n * 4
            + 2 * nc * 4 + 3 * nc * 4 + 2 * sweep(nc, nnz_l[1], 4)
            + sweep(n, ops[0].nnz, 4) + 14 * n * 4)
    assert [lv["nnz_L"] for lv in levels] == nnz_l
    assert mod.iteration_bytes(levels) == want
    ctx = {"trace": {"busy_s": 0.5}, "mg_levels": levels,
           "pcg_iters": [10], "value_bytes": 4,
           "peaks": {"hbm_bytes_per_s": 819e9}}
    assert mod.read(ctx) == pytest.approx(100 * want / 819e9 / 0.05)
    assert mod.read({**ctx, "pcg_iters": []}) is None


SEG = "jit(solve)/" + spans.SEGMENT
PERM = "jit(solve)/" + spans.PERMUTE
# busy 600 ns: sweeps 250, the V-cycle's own ops 150, CG's SpMV 200; idle
# [250, 300], [450, 700], [900, 1000], of which the V-cycle span covers 200
MG_HOST = [(tr.WINDOW, 0, 1000), ("bench.pcg_solve", 0, 1000),
           ("mg.vcycle", 100, 600)]
MG_DEV = [(PERM + "/gather", 0, 50), (SEG, 50, 250),
          ("jit(_upper_rhs)/mg.smooth/jit(_ell_matvec)/gather", 300, 400),
          ("jit(_restrict)/mg.transfer/sub", 400, 450),
          ("jit(_ell_matvec)/gather", 700, 900)]
READERS = {"sweep_share.mg": 100 * 250 / 600,
           "mg_ops_share.mg": 100 * 150 / 600,
           "idle_share.mg.vcycle": 20.0}


@pytest.mark.parametrize("metric", sorted(READERS))
def test_trace_reader_reads_the_programs_scopes(metric, tmp_path,
                                                monkeypatch):
    read = harness.load_module("metrics", metric).read
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path)
    ctx = {"trace": {"window_s": 1e-6}}
    assert read(ctx) is None   # no trace file
    write_xplane(tmp_path / "cell" / "plugins" / "profile" / "1"
                 / "h.xplane.pb", MG_HOST, MG_DEV)
    assert read(ctx) == pytest.approx(READERS[metric])
    assert read({"trace": {"window_s": 2e-6}}) is None


def test_vcycle_idle_is_not_read_from_an_unaligned_trace(tmp_path,
                                                        monkeypatch):
    """A device program without a matching host enqueue leaves the trace
    unaligned: its idle cannot be put down to host spans, so nothing is
    read (the harness then refuses the run)."""
    read = harness.load_module("metrics", "idle_share.mg.vcycle").read
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path)
    write_xplane(tmp_path / "cell" / "plugins" / "profile" / "1"
                 / "h.xplane.pb", MG_HOST, MG_DEV, programs=[(0, 10)])
    trace = spans.find({"trace": {"window_s": 1e-6}})
    assert trace.skew_ns is None
    assert read({"trace": {"window_s": 1e-6}}) is None
