"""Per-backend cost calibration for the transform planner.

:func:`repro.core.coarsen.plan_strategy` prices every strategy × transform
combination with a launch-cost/padded-FLOP model.  The coefficients of that
model are *device* properties — how expensive a kernel launch (barrier) is
relative to a gathered FMA, how wide the vector lanes are, whether a fused
single-dispatch solve exists at all — so they live here in one
:class:`BackendCalibration` row per backend family instead of as constants
scattered through the planner.

Rows are keyed by the **calibration key** of a resolved
:class:`repro.kernels.backend.KernelBackend` (``cpu`` / ``tpu`` / ``gpu``;
interpret-mode backends execute on the host and are priced as ``cpu``).

``DEFAULT_CALIBRATIONS`` ships conservative defaults:

``cpu``   the historical planner constants (the interpreter / XLA:CPU path
          the test-suite runs) — ``fused_max_rows=0`` because pallas has no
          compiled CPU lowering, so the fused kernel is never a candidate
``tpu``   128-wide lanes; ``fused_max_rows=0`` because the v5e Mosaic
          compiler refuses the fused kernel's gather (its dispatch shape
          stays ``fused_num_launches="one"``: one sequential grid)
``gpu``   kernel launches are the synchronization primitive (pricier than a
          TPU grid step), the fused layout executes as one launch **per
          wavefront span** (``fused_num_launches="per_level"``), 32-wide
          warps, no VMEM residency bound (x lives in GMEM)

A machine-measured table can replace the defaults: ``benchmarks/calibrate.py``
times launch overhead and gather throughput on the live device and writes
``calibration.json``; :func:`load_calibrations` / :func:`refresh` merge it
over the defaults (rows keep ``source="measured"`` so ``plan.reason`` lines
stay auditable).
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, Optional, Union

__all__ = [
    "BackendCalibration",
    "DEFAULT_CALIBRATIONS",
    "get_calibration",
    "load_calibrations",
    "save_calibrations",
    "refresh",
]


@dataclasses.dataclass(frozen=True)
class BackendCalibration:
    """Planner pricing coefficients for one backend family.

    All costs are in FLOP-equivalents (the planner's common currency).

    ``launch_cost``            one barrier-separated kernel launch /
                               collective / generated code region
    ``substep_cost``           one intra-chain ``fori_loop`` sub-step of a
                               coarsened segment (no barrier, no new region)
    ``gather_cost``            relative price of one padded gather/FMA flop
                               (1.0 = the model's reference throughput)
    ``serial_step_cost``       per-row base cost of the ``lax.scan`` serial
                               solver (latency-bound)
    ``serial_step_cost_scale`` its growth with n (the scan carries the whole
                               x vector; big systems fall out of cache)
    ``lane_width``             vector/warp lane width rows are padded to
    ``fused_max_rows``         largest n the fused single-dispatch solve can
                               hold (0 = fused never a candidate on this
                               backend — e.g. cpu, where pallas has no
                               compiled lowering)
    ``fused_num_launches``     ``"one"`` — the whole fused solve is a single
                               dispatch (TPU sequential grid); ``"per_level"``
                               — one launch per wavefront span (GPU
                               level-scheduled walk)
    ``gemm_cost``              relative price of one *dense* batched-GEMM /
                               TRSM flop of the blocked executor's diagonal-
                               block apply — contiguous, no index stream, so
                               cheaper than a gathered flop everywhere and
                               dramatically so on MXU/tensor-core hardware
    ``trsm_cost``              fixed per-diagonal-block overhead of the
                               blocked apply (reshape + batched dispatch
                               bookkeeping), in FLOP-equivalents
    ``mixed_gather_discount``  multiplier on ``gather_cost`` when the guard's
                               ``precision="mixed"`` mode stores values in
                               bf16 — half the value-stream bytes, so
                               gather-bound terms cheapen by however much of
                               the stream is values rather than indices on
                               this backend (host caches benefit less than
                               bandwidth-bound accelerators)
    ``source``                 ``"default"`` (shipped) or ``"measured"``
                               (``benchmarks/calibrate.py`` micro-run)
    """

    backend: str
    launch_cost: float = 4096.0
    substep_cost: float = 2048.0
    gather_cost: float = 1.0
    serial_step_cost: float = 16.0
    serial_step_cost_scale: float = 0.06
    lane_width: int = 8
    fused_max_rows: int = 0
    fused_num_launches: str = "per_level"
    gemm_cost: float = 0.25
    trsm_cost: float = 64.0
    mixed_gather_discount: float = 0.75
    source: str = "default"

    def __post_init__(self):
        assert self.fused_num_launches in ("one", "per_level"), \
            self.fused_num_launches


DEFAULT_CALIBRATIONS: Dict[str, BackendCalibration] = {
    # Historical planner constants — the host path every CI run exercises.
    "cpu": BackendCalibration(backend="cpu"),
    # The fused kernel is no candidate: Mosaic refuses its gather (jnp.take
    # over the whole VMEM-resident x; only a gather whose indices have the
    # operand's own 2-D shape lowers), so the v5e compiler rejects it at the
    # first solve.  A gather kernel that compiles sets this again.
    "tpu": BackendCalibration(
        backend="tpu",
        lane_width=128,
        fused_max_rows=0,
        fused_num_launches="one",
        gemm_cost=0.05,   # MXU: dense block flops are nearly free
        trsm_cost=32.0,
        mixed_gather_discount=0.55,  # HBM-bound gathers: bytes ≈ time
    ),
    # Kernel launches ARE the barriers (pricier than a TPU grid step); the
    # fused layout runs one launch per wavefront span; x in GMEM, so the
    # row bound is memory- not VMEM-limited.
    "gpu": BackendCalibration(
        backend="gpu",
        launch_cost=6144.0,
        gather_cost=0.5,
        serial_step_cost=32.0,
        lane_width=32,
        fused_max_rows=50_000_000,
        fused_num_launches="per_level",
        gemm_cost=0.1,    # tensor cores; still pays GMEM block loads
        trsm_cost=48.0,
        mixed_gather_discount=0.55,  # GMEM-bound gathers: bytes ≈ time
    ),
}


def get_calibration(
    key: str,
    table: Optional[Dict[str, BackendCalibration]] = None,
) -> BackendCalibration:
    """Calibration row for a backend family (``cpu`` / ``tpu`` / ``gpu``).
    ``table`` overrides the shipped defaults row-by-row (rows it does not
    carry fall through to the defaults)."""
    if table is not None and key in table:
        return table[key]
    try:
        return DEFAULT_CALIBRATIONS[key]
    except KeyError:
        raise ValueError(
            f"no calibration for backend family {key!r}; expected one of "
            f"{sorted(DEFAULT_CALIBRATIONS)}") from None


def save_calibrations(path: Union[str, Path],
                      table: Dict[str, BackendCalibration]) -> None:
    """Write a calibration table as JSON (one object per backend family)."""
    payload = {k: dataclasses.asdict(v) for k, v in sorted(table.items())}
    Path(path).write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")


def load_calibrations(path: Union[str, Path]) -> Dict[str, BackendCalibration]:
    """Read a calibration table written by :func:`save_calibrations` (or by
    ``benchmarks/calibrate.py``).  Unknown keys in a row are ignored so old
    tables survive field additions; a file that is not a JSON object of
    per-backend rows raises ``ValueError`` naming the path."""
    try:
        raw = json.loads(Path(path).read_text())
    except json.JSONDecodeError as err:
        raise ValueError(f"malformed calibration file {path}: {err}") from None
    if not isinstance(raw, dict):
        raise ValueError(
            f"malformed calibration file {path}: expected a JSON object of "
            f"backend rows, got {type(raw).__name__}")
    fields = {f.name for f in dataclasses.fields(BackendCalibration)}
    table = {}
    for key, row in raw.items():
        if not isinstance(row, dict):
            raise ValueError(
                f"malformed calibration file {path}: row {key!r} is not an "
                f"object")
        kw = {k: v for k, v in row.items() if k in fields}
        kw.setdefault("backend", key)
        table[key] = BackendCalibration(**kw)
    return table


def refresh(path: Union[str, Path]) -> Dict[str, BackendCalibration]:
    """Defaults overlaid with a measured table (missing file → defaults)."""
    table = dict(DEFAULT_CALIBRATIONS)
    p = Path(path)
    if p.exists():
        table.update(load_calibrations(p))
    return table
