"""From a profiler trace of the measured window to device busy time, idle
share and where the idle time went.

The window is the host span ``bench.window`` that the harness wraps around
the traced calls.  On each device, busy time is the union of the intervals
in which an XLA operation ran (the ``XLA Ops`` line of the device's plane),
clipped to the window; idle share is ``1 - busy / window``, averaged over the
devices that ran anything.  Each idle gap is put down to the innermost host
span that covers its middle: what the host was doing while the device
waited.  Times are in seconds.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re

WINDOW = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Events:
    """``device``: plane name -> [(op name, start_ns, end_ns)];
    ``host``: [(span name, start_ns, end_ns)] from every host thread."""

    device: dict
    host: list


def load(path: str) -> Events:
    """Read an ``.xplane.pb`` written by ``jax.profiler.trace``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device, host = {}, []
    for plane in data.planes:
        lines = {line.name: line for line in plane.lines}
        ordinal = plane.name[len(DEVICE_PREFIX):]
        if plane.name.startswith(DEVICE_PREFIX) and ordinal.isdigit():
            if OPS_LINE in lines:
                device[plane.name] = [
                    (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                    for e in lines[OPS_LINE].events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns),
                             int(e.start_ns + e.duration_ns))
                            for e in line.events if e.duration_ns > 0)
    return Events(device=device, host=host)


def op_label(hlo: str) -> str:
    """A device op's event name is its HLO text; keep the instruction name,
    result shape, opcode, operand shapes and fusion kind, without layouts."""
    name, _, rest = hlo.partition(" = ")
    if not rest:
        return hlo[:120]
    rest = re.sub(r"\{[^{}]*\}", "", rest)
    m = re.match(r"^(.*?) ([\w-]+)\((.*)$", rest)
    if not m:
        return f"{name.lstrip('%')}: {rest[:100]}"
    result, opcode, tail = m.groups()
    args = re.findall(r"\w+\[[\d,]*\]", tail.split(")", 1)[0])
    kind = re.search(r"kind=(\w+)", tail)
    label = f"{name.lstrip('%')}: {result} {opcode}({', '.join(args)})"
    return (label + (f" {kind.group(1)}" if kind else ""))[:160]


def union(intervals) -> list:
    """Merged, sorted ``[(start, end)]`` covering the same points."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: list, start: int, end: int) -> list:
    """The parts of ``[start, end]`` that ``busy`` (merged) leaves free."""
    out, t = [], start
    for s, e in busy:
        if s > t:
            out.append((t, min(s, end)))
        t = max(t, e)
        if t >= end:
            break
    if t < end:
        out.append((t, end))
    return [(s, e) for s, e in out if e > s]


def self_times(ops: list) -> dict:
    """Seconds of each op name, less the time of ops nested inside it (a
    loop op holds its body's ops on the same line)."""
    out: dict = collections.defaultdict(int)
    stack: list = []   # enclosing (name, end), innermost last
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and (stack[-1][1] <= s or stack[-1][1] < e):
            stack.pop()   # ended, or only overlapping: not a parent
        if stack:
            out[stack[-1][0]] -= e - s
        stack.append((name, e))
        out[name] += e - s
    return {k: v / 1e9 for k, v in out.items()}


def attribute(gap_list: list, host: list) -> dict:
    """Idle seconds by the innermost host span covering each gap's middle
    (``(no host span)`` where none does)."""
    spans = sorted(host, key=lambda h: h[1])
    starts = [h[1] for h in spans]
    out: dict = collections.defaultdict(float)
    active: list = []
    taken = 0
    for s, e in sorted(gap_list, key=lambda g: g[0] + g[1]):
        mid = (s + e) / 2
        hi = bisect.bisect_right(starts, mid)
        active.extend(spans[taken:hi])
        taken = max(taken, hi)
        active = [h for h in active if h[2] >= mid]
        name = (min(active, key=lambda h: h[2] - h[1])[0] if active
                else "(no host span)")
        out[name] += (e - s) / 1e9
    return dict(out)


def reduce(events: Events, *, top: int = 10) -> dict | None:
    """Busy and idle time of the traced window; None when the trace holds
    no window span or no device operation inside it."""
    windows = [h for h in events.host if h[0] == WINDOW]
    if not windows:
        return None
    _, w0, w1 = max(windows, key=lambda h: h[2] - h[1])
    busy_s, idle = [], collections.defaultdict(float)
    op_time: dict = collections.defaultdict(float)
    inner = [h for h in events.host if h[0] != WINDOW]
    for _, plane in sorted(events.device.items()):
        clipped = [(n, max(s, w0), min(e, w1)) for n, s, e in plane
                   if e > w0 and s < w1]
        if not clipped:
            continue
        merged = union((s, e) for _, s, e in clipped)
        busy_s.append(sum(e - s for s, e in merged) / 1e9)
        for k, v in attribute(gaps(merged, w0, w1), inner).items():
            idle[k] += v
        for k, v in self_times(clipped).items():
            op_time[k] += v
    if not busy_s:
        return None
    window_s = (w1 - w0) / 1e9
    busy = sum(busy_s) / len(busy_s)
    ops_sorted = sorted(op_time.items(), key=lambda kv: -kv[1])
    idle_sorted = sorted(idle.items(), key=lambda kv: -kv[1])
    return {"busy_s": busy, "window_s": window_s,
            "idle_share": 1.0 - busy / window_s,
            "devices": len(busy_s),
            "device_ops": [[op_label(k), v / len(busy_s)]
                           for k, v in ops_sorted[:top]],
            "idle_gaps": [[k, v / len(busy_s)] for k, v in idle_sorted[:top]]}
