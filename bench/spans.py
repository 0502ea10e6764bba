"""What the program's own spans and scopes say about the traced window.

The program marks host work with profiler spans and names its device ops by
scope (``repro.core.obs``): both land in the traced run's ``.xplane.pb``.  A
reader gets only its context, so :func:`find` opens the newest trace under
``bench/.trace/`` and keeps it only if its ``bench.window`` is the window
the harness reduced.

The file puts the device's events on the host's clock, but not exactly: on
a v5e a program shows as starting 0.3-0.5 ms before the host finished
enqueueing it.  :func:`load` moves the device's events later by the least
amount for which no program starts before its enqueue ended (the k-th
program of the ``XLA Modules`` lines paired with the k-th
``DoEnqueueProgram`` span); the launch latency beyond that cannot be seen.
Where the two counts differ the trace is not aligned, and idle time is not
put down to host spans.  Two questions are asked of it:

- idle under a span: the part of the device's idle gaps in the window (as
  ``bench/trace_reduce.py`` finds them) that lies inside spans of that
  name, at any depth; where spans of several names cover an instant, the
  first name in the order given takes it.  Not the gap's middle, as
  ``trace_reduce.attribute`` takes it: a closed loop's gap runs from one
  call's last op past the next call's dispatch, which is a small part of it;
- device time in a scope: the union of the ``XLA Ops`` intervals whose
  scope path (the op's ``tf_op`` stat, ``jit(solve)/sptrsv.segment/...``)
  holds the scope, clipped to the window.

Both average over the devices that ran anything, as the reduction does.  A
program without the spans or scopes reads None.  Times are in seconds.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import os
from pathlib import Path

from bench import trace_reduce

TRACE_DIR = Path(__file__).resolve().parent / ".trace"

# the program's names (repro.core.obs), kept as strings here so that the
# benchmark can read a program that has none of them
SOLVE = "sptrsv.solve"
PCG_SETUP = "pcg.setup"
PCG_ITER = "pcg.iter"
PCG_READBACK = "pcg.readback"
PCG_ORDER = (PCG_READBACK, PCG_SETUP, PCG_ITER)
PERMUTE = "sptrsv.permute"
SEGMENT = "sptrsv.segment"
# the TPU runtime's names: a program's run on the device, its enqueue
MODULES_LINE = "XLA Modules"
ENQUEUE = "DoEnqueueProgram"


@dataclasses.dataclass(eq=False)
class Trace:
    """``host``: [(span, start_ns, end_ns)] of every host thread;
    ``device``: plane -> [(scope path, start_ns, end_ns)] of its ``XLA Ops``;
    ``window``: (start_ns, end_ns) of the longest ``bench.window``;
    ``skew_ns``: what was added to the device's times, None where the trace
    could not be aligned."""

    host: list
    device: dict
    window: tuple
    skew_ns: float | None = 0.0

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


@functools.lru_cache(maxsize=1)
def xspace_class():
    """The profiler's ``XSpace`` message, declared with the fields read here
    (field numbers of tsl/profiler/protobuf/xplane.proto; a map is a
    repeated key-value message on the wire)."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(name="bench_xplane.proto",
                                           package="bench_xplane",
                                           syntax="proto3")

    def message(name, *fields):
        m = f.message_type.add(name=name)
        for fname, number, ftype, repeated, type_name in fields:
            m.field.add(name=fname, number=number, type=ftype,
                        label=F.LABEL_REPEATED if repeated
                        else F.LABEL_OPTIONAL,
                        type_name=type_name and f".bench_xplane.{type_name}")

    i64, u64, s, msg = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_STRING, F.TYPE_MESSAGE
    message("XStat", ("metadata_id", 1, i64, False, None),
            ("str_value", 5, s, False, None), ("ref_value", 7, u64, False, None))
    message("XEvent", ("metadata_id", 1, i64, False, None),
            ("offset_ps", 2, i64, False, None),
            ("duration_ps", 3, i64, False, None),
            ("stats", 4, msg, True, "XStat"))
    message("XLine", ("name", 2, s, False, None),
            ("timestamp_ns", 3, i64, False, None),
            ("events", 4, msg, True, "XEvent"))
    message("XEventMetadata", ("id", 1, i64, False, None),
            ("name", 2, s, False, None), ("stats", 5, msg, True, "XStat"))
    message("XStatMetadata", ("id", 1, i64, False, None),
            ("name", 2, s, False, None))
    message("EventMetadataEntry", ("key", 1, i64, False, None),
            ("value", 2, msg, False, "XEventMetadata"))
    message("StatMetadataEntry", ("key", 1, i64, False, None),
            ("value", 2, msg, False, "XStatMetadata"))
    message("XPlane", ("name", 2, s, False, None),
            ("lines", 3, msg, True, "XLine"),
            ("event_metadata", 4, msg, True, "EventMetadataEntry"),
            ("stat_metadata", 5, msg, True, "StatMetadataEntry"))
    message("XSpace", ("planes", 1, msg, True, "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def _events(line):
    for e in line.events:
        start = line.timestamp_ns + e.offset_ps / 1000
        yield e, start, start + e.duration_ps / 1000


def _tf_op(stats, stat_names: dict) -> str | None:
    for st in stats:
        if stat_names.get(st.metadata_id) == "tf_op":
            return st.str_value or stat_names.get(st.ref_value, "")
    return None


@functools.lru_cache(maxsize=1)
def _load(path: str, _mtime_ns: int) -> Trace | None:
    space = xspace_class()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    host, device, programs = [], {}, []
    for plane in space.planes:
        meta = {m.key: m.value for m in plane.event_metadata}
        ordinal = plane.name[len(trace_reduce.DEVICE_PREFIX):]
        if (plane.name.startswith(trace_reduce.DEVICE_PREFIX)
                and ordinal.isdigit()):
            stat_names = {m.key: m.value.name for m in plane.stat_metadata}
            scopes = {k: _tf_op(m.stats, stat_names) for k, m in meta.items()}
            ops = device.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    programs.extend(s for _, s, _ in _events(line))
                if line.name != trace_reduce.OPS_LINE:
                    continue
                for e, s, t in _events(line):
                    scope = (scopes.get(e.metadata_id)
                             or _tf_op(e.stats, stat_names) or "")
                    ops.append((scope, s, t))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend((meta[e.metadata_id].name, s, t)
                            for e, s, t in _events(line)
                            if e.duration_ps > 0 and e.metadata_id in meta)
    windows = [h for h in host if h[0] == trace_reduce.WINDOW]
    if not windows:
        return None
    _, w0, w1 = max(windows, key=lambda h: h[2] - h[1])
    skew = _skew(programs, [e for n, _, e in host if n == ENQUEUE])
    if skew:
        device = {k: [(n, s + skew, e + skew) for n, s, e in ops]
                  for k, ops in device.items()}
    return Trace(host=host, device=device, window=(w0, w1), skew_ns=skew)


def _skew(programs: list, enqueued: list) -> float | None:
    """The least shift (ns, never negative) of device times under which no
    program starts before its enqueue ended; None where the counts differ."""
    if len(programs) != len(enqueued):
        return None
    return max([0.0] + [q - p for p, q in zip(sorted(programs),
                                               sorted(enqueued))])


def load(path: str) -> Trace | None:
    """The spans and scoped device ops of an ``.xplane.pb``; None without a
    ``bench.window`` span."""
    return _load(path, os.stat(path).st_mtime_ns)


def find(ctx: dict) -> Trace | None:
    """The newest trace under ``TRACE_DIR``, if its window is the one the
    harness reduced into ``ctx["trace"]``."""
    reduced = ctx.get("trace")
    files = glob.glob(str(TRACE_DIR / "*" / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    if not reduced or not files:
        return None
    trace = load(max(files, key=os.path.getmtime))
    if trace is None or abs(trace.window_s - reduced["window_s"]) > 1e-8:
        return None
    return trace


def _busy(ops: list, window: tuple) -> list:
    w0, w1 = window
    return trace_reduce.union((max(s, w0), min(e, w1)) for _, s, e in ops
                              if e > w0 and s < w1)


def _clip(merged: tuple, s: float, e: float) -> list:
    """The parts of ``[s, e]`` that ``merged`` (intervals, their starts)
    covers."""
    spans, starts = merged
    i = max(bisect.bisect_right(starts, s) - 1, 0)
    out = []
    while i < len(spans) and spans[i][0] < e:
        a, b = spans[i]
        if b > s:
            out.append((max(a, s), min(b, e)))
        i += 1
    return out


def _length(intervals: list) -> float:
    return sum(e - s for s, e in trace_reduce.union(intervals))


@functools.lru_cache(maxsize=4)
def idle_under(trace: Trace, order: tuple) -> dict:
    """Idle seconds inside the spans of each name of ``order``; idle time
    inside spans of several names goes to the first of them, the rest to
    ``None``."""
    merged = {}
    for name in order:
        spans = trace_reduce.union((s, e) for n, s, e in trace.host
                                   if n == name)
        merged[name] = (spans, [a for a, _ in spans])
    out = dict.fromkeys((*order, None), 0.0)
    planes = [b for b in (_busy(ops, trace.window)
                          for ops in trace.device.values()) if b]
    for busy in planes:
        for s, e in trace_reduce.gaps(busy, *trace.window):
            taken, before = [], 0.0
            for name in order:
                taken += _clip(merged[name], s, e)
                now = _length(taken) if taken else 0.0
                out[name] += (now - before) / 1e9 / len(planes)
                before = now
            out[None] += (e - s - before) / 1e9 / len(planes)
    return out


def _has_span(trace: Trace, name: str) -> bool:
    w0, w1 = trace.window
    return any(n == name and e > w0 and s < w1 for n, s, e in trace.host)


def idle_share(ctx: dict, name: str, order: tuple) -> float | None:
    """% of the window idle inside ``name`` spans, where no span of a name
    before it in ``order`` covers the instant."""
    trace = find(ctx)
    if trace is None or trace.skew_ns is None or not _has_span(trace, name):
        return None
    return 100.0 * idle_under(trace, order)[name] / trace.window_s


def in_scope(path: str, scope: str) -> bool:
    return scope in path.split("/")


def scope_seconds(trace: Trace, scope: str) -> tuple:
    """``(in scope, busy)``: device seconds in the window under ``scope``
    and in all, mean over the devices that ran anything."""
    got = [(_busy([o for o in ops if in_scope(o[0], scope)], trace.window),
            _busy(ops, trace.window)) for ops in trace.device.values()]
    got = [(sum(e - s for s, e in a), sum(e - s for s, e in b))
           for a, b in got if b]
    if not got:
        return 0.0, 0.0
    return (sum(a for a, _ in got) / len(got) / 1e9,
            sum(b for _, b in got) / len(got) / 1e9)


def scoped(ctx: dict, scope: str) -> tuple | None:
    """:func:`scope_seconds` of the run's trace; None where no op carries
    ``scope``."""
    trace = find(ctx)
    if trace is None:
        return None
    inside, busy = scope_seconds(trace, scope)
    return (inside, busy) if inside > 0 and busy > 0 else None
