"""What every cell shares: finding its files by name, the measured window's
helpers, tracing, and turning a driver's output into the result line.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Its files are
found by name: the configuration's file from ``configs[].file``, whose
``generator`` names ``bench/generators/<generator>.py``; the traffic mix
``bench/traffic/<traffic>.json``, whose ``driver`` names
``bench/drivers/<driver>.py``; the correctness limits
``bench/workloads/<cell>.json``; and each per-layer metric's reader
``bench/metrics/<metric>.py``.  Adding a cell, a mix or a metric adds files
and entries; nothing here changes.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import importlib.util
import json
import os
import shutil
from pathlib import Path

import numpy as np

from bench import oracle

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = BENCH / ".trace"
CACHE_DIR = BENCH / ".jax_cache"


@dataclasses.dataclass
class Cell:
    """One workload with everything its files say."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list   # metric entries of BENCHMARK.json this cell reports
    per_layer: list


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench.{kind}.{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench: dict | None = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its files read."""
    bench = bench or benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload named {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    limits = json.loads((BENCH / "workloads" / f"{name}.json")
                        .read_text())["limits"]
    return Cell(name=name, chips=w["chips"], config=config, traffic=traffic,
                limits=limits,
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, name)],
                per_layer=[m for m in bench["per_layer"] if _reports(m, name)])


def rng(seed: int, stream: int) -> np.random.Generator:
    """Independent NumPy stream ``stream`` of the run's seed (any size)."""
    return np.random.default_rng([int(seed), stream])


class Reservoir:
    """A uniform sample of ``k`` items from a stream of unknown length,
    drawn from ``gen`` (algorithm R)."""

    def __init__(self, k: int, gen: np.random.Generator):
        self.k, self.gen, self.items, self.seen = k, gen, [], 0

    def offer(self, item) -> None:
        if len(self.items) < self.k:
            self.items.append(item)
        else:
            j = int(self.gen.integers(self.seen + 1))
            if j < self.k:
                self.items[j] = item
        self.seen += 1


class CompileCounter:
    """Counts, while active, the programs JAX traced, lowered and handed to
    the backend, and how many of those the persistent cache served.
    ``compiled`` = backend requests the persistent cache did not serve."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    BACKEND = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.counts = {"traced": 0, "lowered": 0, "backend": 0,
                       "cache_hits": 0}
        self._on = False

    def _duration(self, event, _secs, **_kw):
        if self._on:
            key = {self.TRACE: "traced", self.LOWER: "lowered",
                   self.BACKEND: "backend"}.get(event)
            if key:
                self.counts[key] += 1

    def _event(self, event, **_kw):
        if self._on and event == self.HIT:
            self.counts["cache_hits"] += 1

    @contextlib.contextmanager
    def active(self):
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)
        self._on = True
        try:
            yield self
        finally:
            self._on = False
            mon.unregister_event_duration_listener(self._duration)
            mon.unregister_event_listener(self._event)

    def report(self) -> dict:
        return {**self.counts,
                "compiled": self.counts["backend"] - self.counts["cache_hits"]}


@contextlib.contextmanager
def traced_window(trace_dir: Path | None):
    """Profile the block (the ``bench.window`` span) into ``trace_dir``, or
    run it plainly when ``trace_dir`` is None.  The Python tracer and HLO
    protos stay off: they would slow the host and swell the file."""
    import jax

    if trace_dir is None:
        yield
        return
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    with jax.profiler.trace(str(trace_dir), profiler_options=opts):
        with jax.profiler.TraceAnnotation("bench.window"):
            yield


def trace_file(trace_dir: Path) -> str:
    files = sorted(glob.glob(str(trace_dir / "plugins" / "profile" / "*"
                                 / "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"the profiler wrote no trace under {trace_dir}")
    return files[-1]


def memory_peak_bytes() -> int | None:
    """Peak bytes in use on the fullest local device, where reported."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at ``JAX_COMPILATION_CACHE_DIR``
    when set, else at the fixed ``bench/.jax_cache``; cache every program,
    however quick to compile."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool, t0: float,
             peaks: dict | None, device: dict, program=None) -> tuple:
    """Run ``cell`` once; ``(result, report)``: the result line's object,
    and a report of what a reader of the run needs besides (compilations in
    the window, answers checked).  ``program`` replaces the driver's system
    under test."""
    matrices = load_module("generators", cell.config["generator"]).make(
        cell.config, seed)
    trace_dir = TRACE_DIR / cell.name if trace else None
    out = load_module("drivers", cell.traffic["driver"]).run(
        cell, matrices, seed, seconds, trace_dir, t0, program=program)
    ok, checks = oracle.judge(out["readings"], cell.limits)
    ok = ok and out["checked"] > 0
    device = {**device, "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": bool(ok), "attempted": out["attempted"],
              "failed": out["failed"]}
    reduced = None
    if trace:
        from bench import trace_reduce

        reduced = trace_reduce.reduce(trace_reduce.load(trace_file(trace_dir)))
        ctx = {**out["layer"], "trace": reduced, "peaks": peaks}
        values = {m["name"]: (load_module("metrics", m["name"]).read(ctx), m)
                  for m in cell.per_layer}
        what = "per-layer metric readers found nothing to read for"
    else:
        values = {m["name"]: (out["end_to_end"].get(m["name"]), m)
                  for m in cell.end_to_end}
        what = f"driver {cell.traffic['driver']!r} does not measure"
    missing = [k for k, (v, _) in values.items() if v is None]
    if missing:
        raise KeyError(f"{what} {missing}, which BENCHMARK.json lists for "
                       f"{cell.name!r}")
    result["metrics"] = {k: {"value": float(v), "unit": m["unit"]}
                         for k, (v, m) in values.items()}
    if reduced is not None:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
    result["device"] = device
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    report = {"compiles_in_window": out["compiles"], "window": out["window"],
              "answers_checked": out["checked"]}
    return result, report

