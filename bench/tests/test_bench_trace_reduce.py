"""The reduction from trace events to busy time, idle share and idle gaps,
on synthetic events (nanoseconds)."""
import pytest

from bench import trace_reduce as tr

DEV = "/device:TPU:0"


def test_union_merges_overlaps_and_touching():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 10)]) == [
        (0, 4), (5, 7), (9, 10)]


def test_gaps_fill_the_window():
    assert tr.gaps([(2, 4), (6, 7)], 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert tr.gaps([(0, 10)], 0, 10) == []


def test_self_times_subtract_nested_ops():
    ops = [("while", 0, 100), ("fusion", 10, 30), ("fusion", 40, 60),
           ("copy", 100, 110)]
    t = tr.self_times(ops)
    assert t["while"] == pytest.approx(60e-9)
    assert t["fusion"] == pytest.approx(40e-9)
    assert t["copy"] == pytest.approx(10e-9)


def test_reduce_idle_share_and_gap_attribution():
    ev = tr.Events(
        device={DEV: [("a", 100, 300), ("b", 250, 400), ("a", 600, 900),
                      ("late", 1100, 1300)]},
        host=[(tr.WINDOW, 0, 1000), ("bench.solve_call", 50, 450),
              ("readback", 400, 600), ("bench.solve_call", 550, 940)])
    out = tr.reduce(ev)
    # busy: [100,400] + [600,900] = 600 ns of a 1000 ns window; the op
    # running past the window is clipped away
    assert out["busy_s"] == pytest.approx(600e-9)
    assert out["window_s"] == pytest.approx(1000e-9)
    assert out["idle_share"] == pytest.approx(0.4)
    gaps = dict(out["idle_gaps"])
    # [0,100] mid 50 -> bench.solve_call; [400,600] mid 500 -> readback;
    # [900,1000] mid 950 -> no inner span (window excluded)
    assert gaps["readback"] == pytest.approx(200e-9)
    assert gaps["bench.solve_call"] == pytest.approx(100e-9)
    assert gaps["(no host span)"] == pytest.approx(100e-9)
    assert sum(gaps.values()) == pytest.approx(400e-9)
    assert dict(out["device_ops"])["a"] == pytest.approx(500e-9)


def test_reduce_averages_devices_and_needs_a_window():
    ev = tr.Events(device={DEV: [("a", 0, 50)], "/device:TPU:1": [("a", 0, 100)]},
                   host=[(tr.WINDOW, 0, 100)])
    out = tr.reduce(ev)
    assert out["devices"] == 2
    assert out["busy_s"] == pytest.approx(75e-9)
    assert tr.reduce(tr.Events(device=ev.device, host=[])) is None
    assert tr.reduce(tr.Events(device={}, host=[(tr.WINDOW, 0, 100)])) is None
