"""Sweep driver: every (arch × shape × mesh) dry-run cell as an isolated
subprocess (compile crashes/memory never take down the sweep), bounded
parallelism, JSON results cached — re-running skips finished cells.

    PYTHONPATH=src python benchmarks/run_dryruns.py [--jobs 3] [--mesh both]
        [--only arch1,arch2] [--force]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from concurrent.futures import ThreadPoolExecutor, as_completed

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs import ARCH_IDS, SHAPES, get_config, runs_cell  # noqa: E402

OUT = os.path.join(os.path.dirname(__file__), "results", "dryrun")


def set_out(path):
    global OUT
    OUT = path

# cheapest-first so failures surface early; heavy MoE/deep nets last
ORDER = ["xlstm-350m", "gemma3-1b", "recurrentgemma-2b", "paligemma-3b",
         "whisper-medium", "granite-3-8b", "gemma3-12b", "llama4-scout-17b-a16e",
         "qwen1.5-32b", "arctic-480b"]
SHAPE_ORDER = ["train_4k", "decode_32k", "prefill_32k", "long_500k"]


def cell_path(arch, shape, mesh):
    return os.path.join(OUT, f"{arch}__{shape}__{mesh}.json")


def run_one(arch, shape, mesh, timeout=7200):
    path = cell_path(arch, shape, mesh)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"  # children never take the chip
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
           "--shape", shape, "--mesh", mesh, "--out", OUT]
    t0 = time.time()
    try:
        r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                           timeout=timeout,
                           cwd=os.path.join(os.path.dirname(__file__), ".."))
        ok = r.returncode == 0
        if not ok and not os.path.exists(path):
            with open(path, "w") as f:
                json.dump({"arch": arch, "shape": shape, "mesh": mesh,
                           "status": "crash", "stderr": r.stderr[-3000:]}, f)
    except subprocess.TimeoutExpired:
        ok = False
        with open(path, "w") as f:
            json.dump({"arch": arch, "shape": shape, "mesh": mesh,
                       "status": "timeout", "timeout_s": timeout}, f)
    return arch, shape, mesh, ok, time.time() - t0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--jobs", type=int, default=3)
    ap.add_argument("--mesh", default="both", choices=["pod", "multipod", "both"])
    ap.add_argument("--only", default="")
    ap.add_argument("--shapes", default="")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--outdir", default="")
    args = ap.parse_args()

    if args.outdir:
        set_out(os.path.join(os.path.dirname(__file__), "results", args.outdir))
    os.makedirs(OUT, exist_ok=True)
    archs = [a for a in ORDER if a in ARCH_IDS]
    if args.only:
        archs = [a for a in archs if a in args.only.split(",")]
    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]
    shapes = args.shapes.split(",") if args.shapes else SHAPE_ORDER

    cells = []
    for mesh in meshes:
        for shape in shapes:
            for arch in archs:
                cfg = get_config(arch)
                if not runs_cell(cfg, shape):
                    # record the skip without spawning a process
                    p = cell_path(arch, shape, mesh)
                    if not os.path.exists(p):
                        from repro.configs import skip_reason
                        with open(p, "w") as f:
                            json.dump({"arch": arch, "shape": shape,
                                       "mesh": mesh, "status": "skipped",
                                       "reason": skip_reason(cfg, shape)}, f)
                    continue
                if not args.force and os.path.exists(cell_path(arch, shape, mesh)):
                    rec = json.load(open(cell_path(arch, shape, mesh)))
                    if rec.get("status") == "ok":
                        continue
                cells.append((arch, shape, mesh))

    print(f"[sweep] {len(cells)} cells to run, jobs={args.jobs}")
    n_ok = n_fail = 0
    with ThreadPoolExecutor(max_workers=args.jobs) as ex:
        futs = {ex.submit(run_one, *c): c for c in cells}
        for fut in as_completed(futs):
            arch, shape, mesh, ok, dt = fut.result()
            n_ok += ok
            n_fail += not ok
            print(f"[sweep] {'OK  ' if ok else 'FAIL'} {arch} x {shape} x {mesh}"
                  f" ({dt:.0f}s)  [{n_ok} ok / {n_fail} fail]", flush=True)
    print(f"[sweep] done: {n_ok} ok, {n_fail} fail")


if __name__ == "__main__":
    main()
