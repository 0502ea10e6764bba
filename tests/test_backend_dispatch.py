"""Backend abstraction: resolution, lowering dispatch, and planner pricing.

Everything here runs on the CPU host — hardware backends are asserted by
monkeypatching ``jax.default_backend`` (resolution is pure) and by checking
*which lowering module* each kernel package's ``select_lowering`` returns,
never by executing a compiled kernel.  This is the CI story for the backend
matrix: dispatch targets and planner candidate sets are pinned for tpu/gpu
without the hardware.
"""
import warnings

import numpy as np
import pytest

import jax

from repro.core import SpTRSV
from repro.core.analysis import analyze
from repro.core.calibrate import (
    BackendCalibration,
    DEFAULT_CALIBRATIONS,
    get_calibration,
    load_calibrations,
    save_calibrations,
)
from repro.core.coarsen import plan_strategy
from repro.core.codegen import build_schedule
from repro.core.levels import build_level_sets
from repro.kernels.backend import (
    BACKENDS,
    KernelBackend,
    default_backend_name,
    resolve_backend,
)
from repro.sparse import lung2_like


def _mk():
    L = lung2_like(scale=0.02, fat_levels=4, thin_run=6, dtype=np.float32)
    levels = build_level_sets(L)
    an = analyze(L, levels, upper=False)
    sched = build_schedule(L, levels, upper=False)
    return L, an, sched


# --------------------------------------------------------------------------
# resolution
# --------------------------------------------------------------------------
def test_default_backend_mapping(monkeypatch):
    for platform, expected in [("tpu", "tpu"), ("gpu", "gpu"),
                               ("cuda", "gpu"), ("rocm", "gpu"),
                               ("cpu", "interpret")]:
        monkeypatch.setattr(jax, "default_backend", lambda p=platform: p)
        assert default_backend_name() == expected
        bk = resolve_backend(None)
        assert bk is BACKENDS[expected]
    # an unknown platform is an error, never a silent interpreter
    monkeypatch.setattr(jax, "default_backend", lambda: "metal")
    with pytest.raises(ValueError, match="no kernel backend for JAX "
                       "platform 'metal'"):
        default_backend_name()


def test_resolve_backend_specs():
    assert resolve_backend("tpu") == KernelBackend("tpu", "tpu", False)
    assert resolve_backend("gpu").platform == "gpu"
    assert resolve_backend("cuda") is resolve_backend("gpu")
    assert resolve_backend("interpret").interpret
    assert resolve_backend("interpret").platform == "tpu"
    assert resolve_backend("interpret:gpu").platform == "gpu"
    assert resolve_backend("cpu") is resolve_backend("interpret")
    with pytest.raises(ValueError, match="unknown kernel backend"):
        resolve_backend("vulkan")


def test_resolve_backend_interpret_alias():
    # interpret=True wraps the resolved platform in the interpreter
    assert resolve_backend("tpu", interpret=True).name == "interpret"
    assert resolve_backend("gpu", interpret=True).name == "interpret:gpu"
    # interpret=False forces the compiled twin of the same family
    assert resolve_backend("interpret", interpret=False).name == "tpu"
    assert resolve_backend("interpret:gpu", interpret=False).name == "gpu"
    # passing a resolved backend through is the identity
    bk = resolve_backend("interpret:gpu")
    assert resolve_backend(bk) is bk
    # calibration keys: interpreters are priced as the host
    assert resolve_backend("interpret").calibration_key == "cpu"
    assert resolve_backend("tpu").calibration_key == "tpu"
    assert resolve_backend("gpu").calibration_key == "gpu"


# --------------------------------------------------------------------------
# kernel-package dispatch targets
# --------------------------------------------------------------------------
@pytest.mark.parametrize("pkg", ["sptrsv_level", "sptrsv_fused",
                                 "spmv_ell", "trsm_block"])
def test_select_lowering_dispatch(pkg, monkeypatch):
    import importlib

    ops = importlib.import_module(f"repro.kernels.{pkg}.ops")
    low_tpu = importlib.import_module(f"repro.kernels.{pkg}.lowering_tpu")
    low_gpu = importlib.import_module(f"repro.kernels.{pkg}.lowering_gpu")
    assert ops.select_lowering("tpu") is low_tpu
    assert ops.select_lowering("interpret") is low_tpu
    assert ops.select_lowering("gpu") is low_gpu
    assert ops.select_lowering("interpret:gpu") is low_gpu
    # default resolution follows the (monkeypatched) jax platform
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    assert ops.select_lowering(None) is low_gpu
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.select_lowering(None) is low_tpu


def test_kernel_shims_reexport_tpu_lowering():
    from repro.kernels.sptrsv_level import kernel as k, lowering_tpu as lt

    assert k.level_solve_blocks is lt.level_solve_blocks


# --------------------------------------------------------------------------
# planner pricing per backend
# --------------------------------------------------------------------------
def test_plan_strategy_prices_candidates_per_backend():
    _, an, sched = _mk()
    d_tpu = plan_strategy(an, sched, backend="tpu")
    d_gpu = plan_strategy(an, sched, backend="gpu")
    d_cpu = plan_strategy(an, sched, backend="cpu")
    # the GPU family resolves to its compiled lowerings: fused is priced
    assert "pallas_fused" in d_gpu.costs
    # cpu has no compiled pallas path, and Mosaic refuses the fused
    # kernel's gather on tpu — fused is gated, not outscored
    assert "pallas_fused" not in d_cpu.costs
    assert "pallas_fused" not in d_tpu.costs
    # both backends price the full levelset candidate set too
    for d in (d_tpu, d_gpu):
        assert {"serial", "levelset", "levelset_unroll"} <= set(d.costs)
    # per-backend coefficients: the same schedule prices differently
    assert d_tpu.costs["levelset"] != d_gpu.costs["levelset"]
    assert "backend=tpu" in d_tpu.reason
    assert "backend=gpu" in d_gpu.reason


def test_plan_strategy_accepts_resolved_backend(monkeypatch):
    _, an, sched = _mk()
    d = plan_strategy(an, sched, backend=resolve_backend("gpu"))
    assert "pallas_fused" in d.costs
    # interpret backends are priced as the host: no fused candidate
    d_i = plan_strategy(an, sched, backend=resolve_backend("interpret:gpu"))
    assert "pallas_fused" not in d_i.costs
    # None resolves through jax.default_backend()
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    assert "pallas_fused" in plan_strategy(an, sched).costs
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    d_tpu = plan_strategy(an, sched)
    assert "backend=tpu" in d_tpu.reason
    assert "pallas_fused" not in d_tpu.costs
    with pytest.raises(ValueError, match="unknown planner backend"):
        plan_strategy(an, sched, backend="vulkan")


def test_plan_strategy_fused_gate_is_calibration_driven():
    _, an, sched = _mk()
    # shrink the fused row budget below n: candidate disappears without any
    # platform check involved
    tiny = BackendCalibration(backend="tpu", fused_max_rows=an.n - 1,
                              fused_num_launches="one", lane_width=128)
    d = plan_strategy(an, sched, backend="tpu", calibration=tiny)
    assert "pallas_fused" not in d.costs
    # per-level launch pricing scales with the schedule depth
    one = BackendCalibration(backend="gpu", fused_max_rows=10**9,
                             fused_num_launches="one")
    per = BackendCalibration(backend="gpu", fused_max_rows=10**9,
                             fused_num_launches="per_level")
    c_one = plan_strategy(an, sched, backend="gpu", calibration=one).costs
    c_per = plan_strategy(an, sched, backend="gpu", calibration=per).costs
    assert c_per["pallas_fused"] > c_one["pallas_fused"]


def test_coarsen_module_has_no_hardcoded_platform_checks():
    import inspect

    import repro.core.coarsen as coarsen

    src = inspect.getsource(coarsen)
    assert 'backend == "tpu"' not in src
    assert '_FUSED_VMEM_ROWS' not in src


# --------------------------------------------------------------------------
# calibration table
# --------------------------------------------------------------------------
def test_calibration_defaults_and_roundtrip(tmp_path):
    assert get_calibration("cpu").fused_max_rows == 0
    assert get_calibration("tpu").fused_num_launches == "one"
    assert get_calibration("gpu").fused_num_launches == "per_level"
    with pytest.raises(ValueError, match="no calibration"):
        get_calibration("vulkan")
    path = tmp_path / "calibration.json"
    measured = {"cpu": BackendCalibration(backend="cpu", launch_cost=123.0,
                                          source="measured")}
    save_calibrations(path, measured)
    loaded = load_calibrations(path)
    assert loaded["cpu"] == measured["cpu"]
    # overlay: rows the file carries win, others fall through to defaults
    assert get_calibration("cpu", loaded).launch_cost == 123.0
    assert get_calibration("tpu", loaded) == DEFAULT_CALIBRATIONS["tpu"]


# --------------------------------------------------------------------------
# solver-level knob + deprecation
# --------------------------------------------------------------------------
def test_build_records_backend_and_interpret_deprecation():
    L, _, _ = _mk()
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        s = SpTRSV.build(L, strategy="pallas_level", backend="interpret:gpu")
    assert s.backend == "interpret:gpu"
    assert s.stats()["backend"] == "interpret:gpu"
    # default on this CPU host resolves to the interpret backend
    assert SpTRSV.build(L, strategy="serial").backend == "interpret"
    with pytest.warns(DeprecationWarning, match="interpret= knob is "
                      "deprecated"):
        s2 = SpTRSV.build(L, strategy="serial", interpret=True)
    assert s2.backend == "interpret"


def test_build_pair_threads_backend():
    L, _, _ = _mk()
    fwd, bwd = SpTRSV.build_pair(L, strategy="pallas_level",
                                 backend="interpret:gpu")
    assert fwd.backend == bwd.backend == "interpret:gpu"
    b = np.random.default_rng(3).standard_normal(L.n).astype(np.float32)
    import jax.numpy as jnp

    z = np.asarray(bwd.solve(fwd.solve(jnp.asarray(b))))
    assert np.isfinite(z).all()
