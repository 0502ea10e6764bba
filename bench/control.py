#!/usr/bin/env python3
"""Readings that the correctness limits of a cell are set from, in one
process on the chip: the program on many seeds, and the control (the plain
reference of ``bench/reference.py`` put in the program's place and computed
in bfloat16, the precision below the float32 the configurations state) on a
few.  A float32 run of the reference is printed beside them as a witness.

    python3 bench/control.py --workload lung2.fwd.m1 --seeds 101-112 \\
        --control-seeds 201-203 --seconds 2

Each run goes through the cell's own driver, matrices and traffic, with a
short window; one JSON line per run, then the lower reading (largest over
the program's seeds) and the upper reading (smallest over the control's) of
each number.  The benchmark's own runs never run this.
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


def seeds(text: str) -> list:
    """``"3,5,9-12"`` -> ``[3, 5, 9, 10, 11, 12]``."""
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--control-seeds", type=seeds, required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from bench import harness
    from bench.reference import as_program

    cell = harness.load_cell(args.workload)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    harness.enable_compile_cache()
    driver = cell.traffic["driver"]
    runs = ([("program", s, None) for s in args.seeds]
            + [("control_bf16", s, as_program(driver, jnp.bfloat16))
               for s in args.control_seeds]
            + [("witness_f32", args.control_seeds[0],
                as_program(driver, jnp.float32))])
    readings: dict = {}
    for who, seed, program in runs:
        t = time.perf_counter()
        result, report = harness.run_cell(
            cell, seed=seed, seconds=args.seconds, trace=False, t0=t,
            peaks=None, device=device, program=program)
        values = {k: c["value"] for k, c in result["checks"].items()}
        readings.setdefault(who, []).append(values)
        print(json.dumps({"who": who, "seed": seed, "readings": values,
                          "correct": result["correct"],
                          "attempted": result["attempted"],
                          "failed": result["failed"],
                          "answers_checked": report["answers_checked"],
                          "wall_s": time.perf_counter() - t}), flush=True)
    names = list(cell.limits)
    summary = {
        "workload": cell.name, "limits": cell.limits,
        "lower": {k: max(r[k] for r in readings["program"]) for k in names},
        "upper": {k: min(r[k] for r in readings["control_bf16"])
                  for k in names},
        "program_seeds": len(args.seeds),
        "control_seeds": len(args.control_seeds),
        "device": device}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
