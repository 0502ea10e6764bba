"""Triangular solves from one caller, closed loop or pipelined.

Traffic keys: ``transpose`` (solve ``Lᵀ x = b``), ``m`` (right-hand sides
per call), ``pool`` (seeded right-hand sides kept on the device and cycled
through), ``ahead`` (calls dispatched ahead of the one waited for: 0 sends
the next solve when the last one's answer is ready, and times each call),
``warmup_calls``, ``trace_calls`` (calls in the traced window) and
``check_sample`` (answers drawn from the seed for the oracle).

A pipelined window closes so: when its time is up it sends nothing more,
waits for every call it sent, and reads the clock after that wait; the rate
counts all of those calls over all of that time.

The program's entry is ``SpTRSV.build(L, strategy="auto", transpose=...)``
and ``.solve``, built for the one direction the cell runs.
"""
from __future__ import annotations

import collections
import contextlib
import time

import numpy as np

from bench import harness, oracle
from bench.sparse import Csr


class Program:
    """The system under test: the repository's planned solver."""

    def __init__(self, L: Csr, *, transpose: bool):
        from repro.core import CSRMatrix, SpTRSV

        mat = CSRMatrix(L.indptr, L.indices, L.data, (L.n, L.n))
        self.solver = SpTRSV.build(mat, strategy="auto", transpose=transpose)

    def solve(self, b):
        return self.solver.solve(b)

    def layer_objects(self) -> list:
        return [self.solver]


def run(cell, matrices: dict, seed: int, seconds: float, trace_dir, t0: float,
        program=None) -> dict:
    """Build, warm up, run the window, check a seeded sample of its answers.
    ``program(L, transpose=...)`` replaces :class:`Program` (the control and
    the tests put other solvers in its place)."""
    import jax

    tr = cell.traffic
    L = matrices["L"]
    transpose, m = bool(tr["transpose"]), int(tr["m"])
    t = time.perf_counter()
    sut = (program or Program)(L, transpose=transpose)
    build_s = time.perf_counter() - t

    shape = (tr["pool"], L.n) if m == 1 else (tr["pool"], L.n, m)
    pool_host = harness.rng(seed, 1).standard_normal(shape).astype(L.data.dtype)
    pool = [jax.device_put(b) for b in pool_host]
    jax.block_until_ready(pool)
    ahead = int(tr["ahead"])
    t = time.perf_counter()
    _pump(sut, pool, ahead, calls=tr["warmup_calls"])
    warmup_s = time.perf_counter() - t

    sample = harness.Reservoir(tr["check_sample"], harness.rng(seed, 2))
    counter = harness.CompileCounter()
    setup_s = time.perf_counter() - t0
    with counter.active(), harness.traced_window(trace_dir):
        start = time.perf_counter()
        traced = trace_dir is not None
        lat = _pump(sut, pool, ahead, sample=sample, traced=traced,
                    calls=tr["trace_calls"] if traced else None,
                    until=start + seconds)
        end = time.perf_counter()
    window_s = end - start
    peak = harness.memory_peak_bytes()

    readings = [oracle.solve_errors(L, pool_host[j % len(pool)],
                                    np.asarray(x), transpose=transpose)
                for j, x in sample.items]
    calls = sample.seen
    e2e = {"setup_s": setup_s, "rhs_per_s": calls * m / window_s}
    if lat:
        e2e["p95_ms"] = float(np.percentile(lat, 95)) * 1e3
    return {
        "end_to_end": e2e,
        "attempted": calls, "failed": 0,
        "readings": oracle.worst(readings), "checked": len(readings),
        "compiles": counter.report(), "memory_peak_bytes": peak,
        "window": {"calls": calls, "seconds": window_s},
        "layer": {"objects": sut.layer_objects()
                  if hasattr(sut, "layer_objects") else [],
                  "build_s": build_s, "warmup_s": warmup_s, "calls": calls,
                  "n": L.n, "nnz": L.nnz, "m": m,
                  "value_bytes": L.data.dtype.itemsize},
    }


def _pump(sut, pool, ahead: int, *, calls=None, until=None, sample=None,
          traced=False) -> list:
    """Send ``calls`` solves, or solves until the clock reads ``until``,
    with ``ahead`` calls in flight beyond the one waited for; then wait for
    all of them.  Offers each ``(index, answer)`` to ``sample``.  Returns
    each call's latency in seconds in a closed loop, else nothing."""
    import jax

    lat, flight = [], collections.deque()
    i = 0
    while (i < calls if calls is not None
           else time.perf_counter() < until):
        s = time.perf_counter()
        with (jax.profiler.TraceAnnotation("bench.solve_call") if traced
              else contextlib.nullcontext()):
            x = sut.solve(pool[i % len(pool)])
            flight.append(x)
            if len(flight) > ahead:
                jax.block_until_ready(flight.popleft())
        if sample is not None:
            sample.offer((i, x))
        if not ahead:
            lat.append(time.perf_counter() - s)
        i += 1
    jax.block_until_ready(list(flight))
    return lat
