"""``chip_smoke.py`` rehearsed on the CPU at tiny sizes, plus what keeps the
TPU main path honest: explicit gather kernels refused at build on a
compiled ``tpu`` backend, ``auto`` planning none of them, and the compile
cache placed from outside.

The phases run here through the interpret backend; the chip run itself is
``python3 chip_smoke.py`` on a TPU host.
"""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax

from repro.core import SpTRSV
from repro.sparse import banded_lower, ic0_factor, lung2_like, poisson2d

ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
cs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cs)


def _within_tol(record):
    assert record["max_residual"] <= cs.RESIDUAL_TOL
    assert record["residual_tol"] == cs.RESIDUAL_TOL


# --------------------------------------------------------------------------
# a. the device check
# --------------------------------------------------------------------------
@pytest.mark.parametrize("alone", [False, True],
                         ids=["in_checkout", "script_alone"])
def test_device_check_refuses_cpu(tmp_path, alone):
    script = ROOT / "chip_smoke.py"
    cwd = ROOT
    if alone:
        shutil.copy(script, tmp_path / "chip_smoke.py")
        script, cwd = tmp_path / "chip_smoke.py", tmp_path
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "no TPU" in r.stderr


# --------------------------------------------------------------------------
# b-e. the phases at tiny sizes
# --------------------------------------------------------------------------
def test_phase_paper_tiny():
    rec = cs.phase_paper(scale=0.02, m=4)
    _within_tol(rec)
    assert rec["phase"] == "paper" and rec["n"] > 0
    assert not rec["plan_fwd"].startswith("pallas")
    assert rec["refresh_s"] >= 0 and "refreshed_bwd_m4_s" in rec


def test_phase_pcg_tiny():
    rec = cs.phase_pcg(cs.ic0_problem(24))
    assert rec["iters"] > 0
    assert rec["true_relative_residual"] <= 2 * rec["tol"]


def test_phase_band_tiny():
    rec = cs.phase_band(n=512, bandwidth=8, m=4, require_kernel=False)
    _within_tol(rec)
    assert rec["plan"] == "blocked"
    assert rec["tpu_custom_call"] is False     # interpret backend, no Mosaic


def test_phase_serving_tiny():
    # sizes at which the planner promotes a non-serial pair both ways
    rec = cs.phase_serving(cs.ic0_problem(48)[1],
                           lung2=lung2_like(scale=0.05, dtype=np.float32),
                           lung2_requests=2, ic0_requests=2)
    _within_tol(rec)
    assert rec["failed"] == 0
    # two bursts; each tenant sends 2 forward + 2 transpose requests
    assert rec["completed"] == rec["submitted"] == 2 * 3 * (2 + 2)
    assert rec["entries"] == 2 and rec["promotions"] == 2
    assert all(p != "serial" for pair in rec["plans"].values() for p in pair)


def test_phase_distributed_on_virtual_devices():
    rec = cs.phase_distributed(chips=4, scale=0.02, m=4)
    _within_tol(rec)
    assert rec["chips"] == 4
    assert rec["max_relative_difference_vs_one_device"] <= cs.RESIDUAL_TOL


def test_check_solve_rejects_a_wrong_answer():
    L = lung2_like(scale=0.02, dtype=np.float32)
    b = np.random.default_rng(0).standard_normal(L.n).astype(np.float32)
    x = np.array(SpTRSV.build(L, strategy="serial").solve(b))
    assert cs.check_solve(L, b, x)["residual"] <= cs.RESIDUAL_TOL
    x[L.n // 2] *= 1.001
    with pytest.raises(cs.SmokeFailure, match="componentwise residual"):
        cs.check_solve(L, b, x)


# --------------------------------------------------------------------------
# the planner and SpTRSV.build on a compiled tpu backend (nothing compiles)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("strategy", ["pallas_fused", "pallas_level"])
def test_explicit_gather_kernel_on_tpu_raises_at_build(strategy):
    L = lung2_like(scale=0.02, dtype=np.float32)
    with pytest.raises(NotImplementedError,
                       match="Mosaic compiler refuses.*Only 2D gather"):
        SpTRSV.build(L, strategy=strategy, backend="tpu")
    # the interpret backends keep running the same kernels on the host
    s = SpTRSV.build(L, strategy=strategy, backend="interpret")
    assert np.isfinite(np.asarray(s.solve(np.ones(L.n, np.float32)))).all()


@pytest.mark.parametrize("name", ["lung2", "ic0", "band"])
def test_auto_on_tpu_plans_no_pallas_gather_kernel(name):
    L = {"lung2": lambda: lung2_like(scale=0.25, dtype=np.float32),
         "ic0": lambda: ic0_factor(poisson2d(64, 64, dtype=np.float32)),
         "band": lambda: banded_lower(2048, bandwidth=24, fill=1.0,
                                      dtype=np.float32)}[name]()
    for s in SpTRSV.build_pair(L, strategy="auto", backend="tpu"):
        assert s.backend == "tpu"
        assert not s.strategy.startswith("pallas")
        assert not any(k.startswith("pallas") for k in s.plan.costs)


# --------------------------------------------------------------------------
# compile cache placement
# --------------------------------------------------------------------------
def test_compile_cache_dir_follows_env_else_checkout(monkeypatch, tmp_path):
    from repro.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(CHECKOUT_CACHE_DIR)
        assert CHECKOUT_CACHE_DIR == ROOT / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

