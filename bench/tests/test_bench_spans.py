"""The program-span and scope reductions of ``bench/spans.py``, on synthetic
traces written in the profiler's own format (nanoseconds), on a real CPU
trace, and through the metric readers that use them."""
import glob
import os
import time

import pytest

from bench import harness, spans
from bench import trace_reduce as tr

DEV = "/device:TPU:0"
SEG = "jit(solve)/" + spans.SEGMENT
PERM = "jit(solve)/" + spans.PERMUTE


def write_xplane(path, host, device, programs=()):
    """An ``.xplane.pb`` holding ``host`` spans [(name, start, end)] on one
    host thread, ``device`` ops [(tf_op, start, end)] on ``XLA Ops`` and
    ``programs`` [(start, end)] on ``XLA Modules``."""
    space = spans.xspace_class()()

    def plane(name, events, stat=None):
        p = space.planes.add(name=name)
        if stat:
            e = p.stat_metadata.add(key=1)
            e.value.id, e.value.name = 1, "tf_op"
        line = p.lines.add(name=tr.OPS_LINE if stat else "python",
                           timestamp_ns=0)
        for i, (name, s, t) in enumerate(events, start=1):
            m = p.event_metadata.add(key=i)
            m.value.id = i
            if stat:
                m.value.name = f"%op.{i}"
                m.value.stats.add(metadata_id=1, str_value=name)
            else:
                m.value.name = name
            line.events.add(metadata_id=i, offset_ps=s * 1000,
                            duration_ps=(t - s) * 1000)

    plane("/host:CPU", host)
    plane(DEV, device, stat=True)
    if programs:
        dev = space.planes[-1]
        line = dev.lines.add(name=spans.MODULES_LINE, timestamp_ns=0)
        m = dev.event_metadata.add(key=len(device) + 1)
        m.value.id, m.value.name = m.key, "jit_solve"
        for s, t in programs:
            line.events.add(metadata_id=m.key, offset_ps=s * 1000,
                            duration_ps=(t - s) * 1000)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_bytes(space.SerializeToString())
    return str(path)


# a PCG solve: set-up with two readbacks, two iterations with one each, the
# last readback under a PjRt span; the device idles in each readback, in
# set-up before its first op, and between an iteration's dispatches
PCG_HOST = [(tr.WINDOW, 0, 2000), ("bench.pcg_solve", 0, 2000),
            (spans.PCG_SETUP, 0, 600), (spans.PCG_READBACK, 300, 400),
            (spans.PCG_READBACK, 450, 550),
            (spans.PCG_ITER, 600, 1300), (spans.PCG_READBACK, 1000, 1100),
            (spans.PCG_ITER, 1300, 1950), (spans.PCG_READBACK, 1800, 1900),
            ("ReadSyncFlag", 1810, 1890)]
PCG_DEV = [("jit(matvec)/gather", 100, 300), ("jit(matvec)/x", 400, 450),
           (SEG, 550, 1000), (SEG, 1100, 1200), (SEG, 1250, 1800),
           (SEG, 1900, 1960)]


def trace_of(tmp_path, host, device, programs=()):
    return spans.load(write_xplane(tmp_path / "t.xplane.pb", host, device,
                                   programs))


def test_load_reads_spans_scopes_and_window(tmp_path):
    t = trace_of(tmp_path, PCG_HOST, PCG_DEV)
    assert t.window == (0, 2000) and t.window_s == pytest.approx(2e-6)
    assert sorted(t.host) == sorted(PCG_HOST)
    assert t.device[DEV] == PCG_DEV
    assert trace_of(tmp_path / "none", PCG_HOST[1:], PCG_DEV) is None


def test_readback_nested_in_iter_under_pjrt_span_is_a_readback(tmp_path):
    idle = spans.idle_under(trace_of(tmp_path, PCG_HOST, PCG_DEV),
                            spans.PCG_ORDER)
    # gaps: [0,100] set-up; [300,400] readback; [450,550] readback;
    # [1000,1100] readback; [1200,1250] loop; [1800,1900] readback (under
    # ReadSyncFlag, inside pcg.iter); [1960,2000] outside every PCG span
    assert idle[spans.PCG_READBACK] == pytest.approx(400e-9)
    assert idle[spans.PCG_SETUP] == pytest.approx(100e-9)
    assert idle[spans.PCG_ITER] == pytest.approx(50e-9)
    assert idle[None] == pytest.approx(40e-9)


def test_idle_is_split_at_span_edges(tmp_path):
    """A gap that a span covers in part counts only that part."""
    host = [(tr.WINDOW, 0, 1000), ("bench.solve_call", 0, 1000),
            (spans.SOLVE, 250, 300), (spans.SOLVE, 380, 460)]
    t = trace_of(tmp_path, host, [("jit(solve)/x", 0, 200),
                                  ("jit(solve)/x", 400, 1000)])
    idle = spans.idle_under(t, (spans.SOLVE,))
    assert idle[spans.SOLVE] == pytest.approx(70e-9)
    assert idle[None] == pytest.approx(130e-9)


def test_setup_readback_and_loop_split_the_idle_share(tmp_path):
    """Each gap goes to one name, so the three never overlap and sum to at
    most the idle share the reduction gives."""
    t = trace_of(tmp_path, PCG_HOST, PCG_DEV)
    idle = spans.idle_under(t, spans.PCG_ORDER)
    reduced = tr.reduce(tr.Events(device={DEV: PCG_DEV}, host=PCG_HOST))
    total = reduced["idle_share"] * reduced["window_s"]
    assert sum(idle.values()) == pytest.approx(total)
    assert sum(idle[n] for n in spans.PCG_ORDER) <= total


def test_device_times_move_later_until_no_program_precedes_its_enqueue(
        tmp_path):
    host = [(tr.WINDOW, 0, 1000), (spans.ENQUEUE, 100, 150),
            (spans.SOLVE, 90, 160), (spans.ENQUEUE, 500, 560),
            (spans.SOLVE, 490, 570)]
    dev = [(SEG, 120, 400), (SEG, 530, 900)]
    # the first program starts 30 ns before its enqueue ends, the second 30
    t = trace_of(tmp_path, host, dev, [(120, 400), (530, 900)])
    assert t.skew_ns == pytest.approx(30)
    assert t.device[DEV] == [(SEG, 150, 430), (SEG, 560, 930)]
    # idle [0,150], [430,560], [930,1000]: sptrsv.solve holds 60 + 70 ns
    assert spans.idle_under(t, (spans.SOLVE,))[spans.SOLVE] == \
        pytest.approx(130e-9)
    # a program that started after its enqueue moves nothing
    late = trace_of(tmp_path / "late", host, dev, [(200, 400), (600, 900)])
    assert late.skew_ns == 0 and late.device[DEV] == dev
    # unpaired programs: not aligned, so no idle is put down to host spans
    odd = trace_of(tmp_path / "odd", host, dev, [(120, 400)])
    assert odd.skew_ns is None and odd.device[DEV] == dev


def test_scope_time_is_the_union_of_a_while_op_and_its_body(tmp_path):
    dev = [(PERM + "/gather", 0, 100), (SEG + "/while", 100, 500),
           (SEG + "/while/body/gather", 200, 300),
           (SEG + "/while/body/div", 250, 350),
           ("jit(solve)/dynamic-update-slice", 500, 600),
           (PERM + "/gather", 950, 1100)]
    t = trace_of(tmp_path, [(tr.WINDOW, 0, 1000)], dev)
    assert spans.scope_seconds(t, spans.SEGMENT) == pytest.approx(
        (400e-9, 650e-9))
    # the last permutation is clipped to the window
    assert spans.scope_seconds(t, spans.PERMUTE)[0] == pytest.approx(150e-9)
    assert spans.in_scope(SEG + "/while", spans.SEGMENT)
    assert not spans.in_scope("jit(solve)/sptrsv.segmentx", spans.SEGMENT)


class _Solver:
    def stats(self):
        return {"segments": 4}


SOLVE_HOST = [(tr.WINDOW, 0, 1000), ("bench.solve_call", 0, 500),
              (spans.SOLVE, 0, 100), ("bench.solve_call", 500, 1000),
              (spans.SOLVE, 500, 600)]
SOLVE_DEV = [(PERM + "/gather", 100, 150), (SEG, 150, 400),
             (PERM + "/gather", 400, 450), (PERM + "/gather", 600, 650),
             (SEG, 650, 900), (PERM + "/gather", 900, 950)]

READERS = {  # metric -> (host, device, value)
    "idle_share.pcg.setup": (PCG_HOST, PCG_DEV, 5.0),
    "idle_share.pcg.readback": (PCG_HOST, PCG_DEV, 20.0),
    "idle_share.pcg.loop": (PCG_HOST, PCG_DEV, 2.5),
    # gaps [0,100], [450,600], [950,1000]: sptrsv.solve covers [0,100]
    # and [500,600]
    "idle_share.solve.host": (SOLVE_HOST, SOLVE_DEV, 20.0),
    # 200 ns of permutations in 700 ns busy
    "permute_share.solve": (SOLVE_HOST, SOLVE_DEV, 100 * 200 / 700),
    # 500 ns in segments over 2 calls of 4 segments
    "segment_us.solve": (SOLVE_HOST, SOLVE_DEV, 500e-3 / 2 / 4),
}


@pytest.mark.parametrize("metric", sorted(READERS))
def test_trace_reader_reads_its_window_and_nothing_else(metric, tmp_path,
                                                        monkeypatch):
    host, dev, want = READERS[metric]
    read = harness.load_module("metrics", metric).read
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path)
    window_s = (host[0][2] - host[0][1]) / 1e9
    ctx = {"trace": {"window_s": window_s}, "objects": [_Solver()],
           "calls": 2}
    assert read({**ctx, "trace": None}) is None
    assert read(ctx) is None   # no trace file
    write_xplane(tmp_path / "cell" / "plugins" / "profile" / "1"
                 / "h.xplane.pb", host, dev)
    assert read(ctx) == pytest.approx(want)
    # a trace of another window, or one without the program's spans and
    # scopes, reads nothing
    assert read({**ctx, "trace": {"window_s": 2 * window_s}}) is None
    newer = write_xplane(tmp_path / "cell" / "plugins" / "profile" / "2"
                         / "h.xplane.pb", [host[0]],
                         [("jit(solve)/gather", s, e) for _, s, e in dev])
    os.utime(newer, (time.time() + 60, time.time() + 60))
    assert spans.find(ctx).host == [host[0]]
    assert read(ctx) is None


def test_readbacks_per_iter_reads_the_programs_counters(monkeypatch):
    import collections

    from repro.core import obs

    read = harness.load_module("metrics", "readbacks_per_iter.pcg").read
    monkeypatch.setattr(obs, "_counts", collections.Counter())
    assert read({}) is None
    obs.count(obs.ITERATIONS, 95)
    obs.count(obs.READBACKS, 2 * 95 + 2)
    assert read({}) == pytest.approx(192 / 95)


def test_load_finds_the_programs_spans_in_a_cpu_trace(tmp_path):
    """The parser against a file the profiler wrote: the same host spans as
    ``trace_reduce.load``, the PCG and solve spans among them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.pcg import make_ic_preconditioner, pcg
    from repro.sparse import ic0_factor, poisson2d

    A = poisson2d(6, 6, dtype=np.float32)
    M = make_ic_preconditioner(ic0_factor(A), strategy="levelset",
                               rewrite=None)
    b = jnp.ones(A.n, jnp.float32)
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(tr.WINDOW):
            pcg(A, b, M, tol=1e-6)
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    t = spans.load(path)
    names = {h[0] for h in t.host}
    assert {spans.SOLVE, spans.PCG_SETUP, spans.PCG_ITER,
            spans.PCG_READBACK} <= names
    assert sorted(t.host) == pytest.approx(sorted(tr.load(path).host))
