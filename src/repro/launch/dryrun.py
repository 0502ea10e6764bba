"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production meshes and extract roofline terms.

    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-12b \
        --shape train_4k --mesh pod      # 16x16 single pod (256 chips)
    ... --mesh multipod                  # 2x16x16 (512 chips)

Writes JSON results to --out (default benchmarks/results/dryrun).
Exit code 0 iff compile succeeded.
"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS_EXTRA", "")
)
# A dry run compiles for 512 virtual CPU devices; on a TPU host it must not
# take the chip, which belongs to one process at a time.
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST precede any jax import (jax locks device count at first init).

import argparse
import json
import sys
import time
import traceback


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             *, verbose: bool = True) -> dict:
    import jax
    from repro.configs import SHAPES, get_config, runs_cell, skip_reason
    from repro.launch.mesh import make_production_mesh
    from repro.launch.roofline import analyze_compiled, model_flops
    from repro.launch.specs import make_cell

    # NOTE: cost_analysis counts a `while` body once regardless of trip
    # count; roofline terms therefore come from the trip-count-aware HLO
    # parser (launch.hlo_parse) — scans stay rolled and compiles stay fast.
    cfg = get_config(arch)
    sh = SHAPES[shape_name]
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_kind}
    if not runs_cell(cfg, shape_name):
        rec["status"] = "skipped"
        rec["reason"] = skip_reason(cfg, shape_name)
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    ndev = mesh.size
    t0 = time.time()
    try:
        fn, args = make_cell(arch, shape_name, mesh)
        with mesh:
            lowered = jax.jit(fn).lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            ana = analyze_compiled(compiled, ndev)
        mf = model_flops(cfg, sh)
        # cost_analysis flops are per-device on the partitioned module
        hlo_global = ana["hlo_flops"] * ndev
        rec.update(
            status="ok",
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            devices=ndev,
            analysis=ana,
            model_flops_global=mf,
            useful_ratio=(mf / hlo_global) if hlo_global else None,
        )
        if verbose:
            ma = ana.get("memory") or {}
            print(f"[dryrun] {arch} x {shape_name} x {mesh_kind}: OK "
                  f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
            print(f"  memory_analysis: {ma}")
            print(f"  cost_analysis: flops/dev={ana['hlo_flops']:.3e} "
                  f"bytes/dev={ana['hlo_bytes']:.3e}")
            print(f"  terms: {ana['terms']}  dominant={ana['dominant']}")
            print(f"  collectives: { {k: v for k, v in ana['collective'].items() if k != 'counts'} }")
    except Exception as e:  # noqa: BLE001
        rec.update(status="fail", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
        if verbose:
            print(f"[dryrun] {arch} x {shape_name} x {mesh_kind}: FAIL {e}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fname = f"{arch}__{shape_name}__{mesh_kind}.json"
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(rec, f, indent=1, default=str)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    ap.add_argument("--out", default="benchmarks/results/dryrun")
    args = ap.parse_args(argv)
    rec = run_cell(args.arch, args.shape, args.mesh, args.out)
    sys.exit(0 if rec["status"] in ("ok", "skipped") else 1)


if __name__ == "__main__":
    main()
