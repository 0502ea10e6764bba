"""At a size a test run holds, on the CPU: the harness's run of each cell
comes out correct with the program, and not correct with the control (the
plain reference in bfloat16 in the program's place) or with the timed path
broken underneath by each fault the cell can have.  The chip's readings at
the cells' own sizes are in PERF.md; ``bench/control.py`` takes them."""
import dataclasses
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.reference import as_program

TINY = {"lung2.fwd.m1": dict(scale=0.01, fat_levels=6),
        "lung2.bwd.m8": dict(scale=0.01, fat_levels=6),
        "ic0_pcg.p512": dict(nx=12, ny=12)}
CELLS = list(TINY)
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def tiny(name: str):
    cell = harness.load_cell(name)
    return dataclasses.replace(cell, config={**cell.config, **TINY[name]})


def run(name: str, program=None) -> dict:
    result, report = harness.run_cell(
        tiny(name), seed=2**32 + 17, seconds=0.2, trace=False,
        t0=time.perf_counter(), peaks=None, device=DEVICE, program=program)
    assert report["answers_checked"] > 0
    assert list(result)[-1] == "checks"
    return result


@pytest.mark.parametrize("name", CELLS)
def test_program_is_correct(name):
    result = run(name)
    assert result["correct"] and result["failed"] == 0
    assert set(result["metrics"]) == {m["name"] for m in tiny(name).end_to_end}


@pytest.mark.parametrize("name", CELLS)
def test_control_in_bf16_is_not_correct(name):
    driver = tiny(name).traffic["driver"]
    assert not run(name, as_program(driver, jnp.bfloat16))["correct"]


@pytest.mark.parametrize("name", CELLS)
def test_reference_in_f32_is_correct(name):
    driver = tiny(name).traffic["driver"]
    assert run(name, as_program(driver, jnp.float32))["correct"]


def _break_solve(monkeypatch, fault):
    from repro.core import SpTRSV

    solve = SpTRSV.solve
    monkeypatch.setattr(SpTRSV, "solve", lambda self, b: fault(solve(self, b)))


def _break_pcg(monkeypatch, fault):
    from repro.core import pcg as pcg_module

    pcg = pcg_module.pcg

    def broken(*args, **kwargs):
        res = pcg(*args, **kwargs)
        return dataclasses.replace(res, x=fault(res.x))

    monkeypatch.setattr(pcg_module, "pcg", broken)


@pytest.mark.parametrize("name", CELLS)
def test_answer_altered_where_produced(name, monkeypatch):
    def fault(x):
        return x.at[0].multiply(1.01)

    (_break_pcg if name.startswith("ic0") else _break_solve)(monkeypatch, fault)
    assert not run(name)["correct"]


def test_half_the_batch_left_out(monkeypatch):
    _break_solve(monkeypatch, lambda x: x.at[:, x.shape[1] // 2:].set(0.0))
    assert not run("lung2.bwd.m8")["correct"]


def test_pcg_returning_its_state_unchanged(monkeypatch):
    _break_pcg(monkeypatch, jnp.zeros_like)
    assert not run("ic0_pcg.p512")["correct"]


def test_pcg_right_hand_sides_are_fresh_and_follow_the_seed():
    from bench.drivers import pcg
    from bench.generators import ic0_poisson2d

    A = ic0_poisson2d.poisson2d(12, 12, np.float32)
    a, b = (pcg.right_hand_sides(A, 2**33 + 5, 4) for _ in range(2))
    c = pcg.right_hand_sides(A, 2**33 + 6, 4)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert len({x.tobytes() for x in a + c}) == 8


def test_pcg_program_hands_its_solver_pair_to_the_planner_metric():
    from bench.drivers import pcg
    from bench.generators import ic0_poisson2d

    A = ic0_poisson2d.poisson2d(12, 12, np.float32)
    L = ic0_poisson2d.ic0(12, 12, 0.0, np.float32)
    prog = pcg.Program(A, L, tol=1e-6, maxiter=100)
    objs = prog.layer_objects()
    want = sum(o.stats()["segments"] for o in objs)
    read = harness.load_module("metrics", "sync_segments.pcg").read
    assert len(objs) == 2 and want > 0
    assert read({"objects": objs}) == want


def test_traced_run_stops_when_a_listed_metric_reads_nothing():
    """On the CPU the trace holds no TPU operation, so the device metrics
    that BENCHMARK.json lists for the cell find nothing: the run stops
    rather than print a line without them."""
    with pytest.raises(KeyError, match="idle_share.solve"):
        harness.run_cell(
            tiny("lung2.fwd.m1"), seed=2**32 + 19, seconds=0.2, trace=True,
            t0=time.perf_counter(), peaks=None, device=DEVICE)


@pytest.mark.parametrize("ahead", [0, 3])
def test_solve_window_waits_for_every_call_it_sent(ahead):
    """Closed loop (``ahead`` 0) times each call; pipelined, the window
    counts every call it sent, each answered by the time it closes."""
    from bench.drivers import solve

    class Doubler:
        def solve(self, b):
            return b * 2.0

    pool = [jnp.full((4,), float(k)) for k in range(3)]
    sample = harness.Reservoir(10, harness.rng(2**33 + 1, 2))
    lat = solve._pump(Doubler(), pool, ahead, calls=7, sample=sample)
    assert len(lat) == (7 if ahead == 0 else 0) and sample.seen == 7
    for i, x in sample.items:
        np.testing.assert_array_equal(np.asarray(x), 2.0 * (i % 3))
    t = time.perf_counter()
    sample = harness.Reservoir(1, harness.rng(2**33 + 1, 2))
    solve._pump(Doubler(), pool, ahead, until=t + 0.05, sample=sample)
    assert sample.seen > 0 and time.perf_counter() >= t + 0.05
