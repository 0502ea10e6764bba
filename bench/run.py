#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload lung2.fwd.m1 --seed 7 --seconds 40 --trace 0

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its files are
found by name (see ``bench/harness.py``).  With ``--trace 0`` the line holds
the cell's end-to-end metrics from a window of ``--seconds``; with
``--trace 1`` its per-layer metrics from a short profiled window.  Either
way the answers of the window are checked against the float64 oracle, and
the numbers compared are printed beside their limits, last on standard
error and last in the line.  Set-up (``setup_s``) runs from the start of
this script to the start of the window.  The run refuses, printing no
result, unless JAX finds a TPU with as many chips as the cell asks for.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# libtpu would otherwise log to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    cell = harness.load_cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    kind = devices[0].device_kind
    peaks = json.loads((harness.BENCH / "peaks.json").read_text())
    if kind not in peaks:
        print(f"bench: no peaks for device kind {kind!r} in bench/peaks.json",
              file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices)}
    result, report = harness.run_cell(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t0=T0, peaks=peaks[kind], device=device)
    print(json.dumps(report), flush=True)
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
