"""Kernel backend abstraction: pluggable TPU / GPU / interpret lowerings.

The paper's framework generates specialized SpTRSV code for one target; the
kernel layer here is specialized per *device family* instead, behind one
small interface.  A :class:`KernelBackend` names

* which **lowering family** a kernel package should use (``platform``:
  ``"tpu"`` = Mosaic lowerings with VMEM-resident operands, ``"gpu"`` =
  pallas-triton lowerings with GMEM gather loads), and
* whether ``pallas_call`` runs in **interpret mode** (``interpret=True`` —
  the correctness harness that executes any lowering on the host CPU).

Every kernel package (``sptrsv_level``, ``sptrsv_fused``, ``spmv_ell``,
``trsm_block``) keeps its lowering-specific code in ``lowering_tpu.py`` /
``lowering_gpu.py`` modules exposing the *same* entry points, and its
``ops.py`` dispatches through :func:`resolve_backend` — so the composition
layers (`SpTRSV.build`, the packed/permuted layout, the planner, serving)
thread a single ``backend=`` knob instead of an ``interpret: bool``.

Backend specs (strings accepted anywhere a ``backend=`` knob appears):

``None``            resolve from ``jax.default_backend()``: ``tpu`` → the
                    compiled TPU lowerings, ``gpu``/``cuda``/``rocm`` → the
                    compiled GPU lowerings, ``cpu`` → the interpret backend
                    (pallas has no CPU codegen; interpret is the only way a
                    pallas strategy can execute there); any other platform
                    raises
``"tpu"``           compiled Mosaic lowerings.  Only ``trsm_block``
                    compiles for v5e; the gather kernels (``sptrsv_level``,
                    ``sptrsv_fused``, ``spmv_ell``) are refused at build
                    by :func:`refuse_mosaic_gather`
``"gpu"``           compiled pallas-triton lowerings (aliases: ``cuda``,
                    ``rocm``)
``"interpret"``     TPU lowerings under the pallas interpreter (the
                    historical ``interpret=True`` harness; alias: ``cpu``,
                    ``interpret:tpu``)
``"interpret:gpu"`` GPU lowerings under the pallas interpreter — how CI
                    exercises the triton-style kernels without a GPU

The legacy ``interpret: bool`` knob maps onto this: ``interpret=True``
wraps the resolved platform's lowerings in the interpreter,
``interpret=False`` forces the compiled path.  :func:`resolve_backend`
implements both so call sites only deal in backends.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Optional, Union

__all__ = [
    "KernelBackend",
    "BACKENDS",
    "resolve_backend",
    "default_backend_name",
    "refuse_mosaic_gather",
    "warn_interpret_deprecated",
]

# Lowering families a kernel package must provide.
PLATFORMS = ("tpu", "gpu")


@dataclasses.dataclass(frozen=True)
class KernelBackend:
    """One resolved kernel target.

    ``name``      canonical spec (``tpu`` / ``gpu`` / ``interpret`` /
                  ``interpret:gpu``) — recorded on solvers and in stats
    ``platform``  lowering family to dispatch to (``tpu`` or ``gpu``)
    ``interpret`` run ``pallas_call`` under the interpreter (host CPU)
    """

    name: str
    platform: str
    interpret: bool

    def __post_init__(self):
        assert self.platform in PLATFORMS, self.platform

    @property
    def calibration_key(self) -> str:
        """Which :mod:`repro.core.calibrate` row prices this backend: the
        interpreter executes on the host, so it is priced as ``cpu``."""
        return "cpu" if self.interpret else self.platform

    def interpreted(self) -> "KernelBackend":
        """The interpret-mode twin of this backend (same lowering family)."""
        if self.interpret:
            return self
        name = "interpret" if self.platform == "tpu" else "interpret:gpu"
        return KernelBackend(name=name, platform=self.platform, interpret=True)

    def compiled(self) -> "KernelBackend":
        """The compiled twin of this backend (same lowering family)."""
        if not self.interpret:
            return self
        return KernelBackend(name=self.platform, platform=self.platform,
                             interpret=False)


# Canonical backends, keyed by every accepted spelling.
_TPU = KernelBackend(name="tpu", platform="tpu", interpret=False)
_GPU = KernelBackend(name="gpu", platform="gpu", interpret=False)
_INTERP = KernelBackend(name="interpret", platform="tpu", interpret=True)
_INTERP_GPU = KernelBackend(name="interpret:gpu", platform="gpu",
                            interpret=True)

BACKENDS = {
    "tpu": _TPU,
    "gpu": _GPU,
    "cuda": _GPU,
    "rocm": _GPU,
    "interpret": _INTERP,
    "interpret:tpu": _INTERP,
    "cpu": _INTERP,
    "interpret:gpu": _INTERP_GPU,
}


def default_backend_name() -> str:
    """Canonical backend spec for the current JAX platform.  Kept as its own
    function so tests can monkeypatch ``jax.default_backend`` and assert the
    mapping without real hardware."""
    import jax

    platform = jax.default_backend()
    if platform == "tpu":
        return "tpu"
    if platform in ("gpu", "cuda", "rocm"):
        return "gpu"
    if platform == "cpu":
        # pallas has no CPU codegen: kernels can only run interpreted
        return "interpret"
    raise ValueError(
        f"no kernel backend for JAX platform {platform!r}; pass backend= "
        f"explicitly (one of {sorted(set(BACKENDS))})")


def refuse_mosaic_gather(bk: KernelBackend, kernel: str) -> None:
    """Refuse, at build time, a compiled ``tpu`` backend for a kernel that
    gathers rows of a whole VMEM-resident vector (``jnp.take`` with
    indices of another shape than the operand).  Mosaic lowers a gather
    only when the indices have the operand's own 2-D shape, so the chip's
    compiler refuses these kernels at their first solve; raising here
    names that refusal before any work is queued.  Interpret backends and
    the GPU family are unaffected."""
    if bk.platform == "tpu" and not bk.interpret:
        raise NotImplementedError(
            f"{kernel}: the Mosaic compiler refuses this kernel's gather on "
            "a TPU (NotImplementedError: Only 2D gather is supported; the "
            "batched fused kernel: ValueError: Shape mismatch in input, "
            "indices and output); use strategy='levelset' or 'auto', or "
            "backend='interpret' for a correctness run")


def resolve_backend(
    spec: Union[None, str, KernelBackend] = None,
    *,
    interpret: Optional[bool] = None,
) -> KernelBackend:
    """Resolve a ``backend=`` knob (and the deprecated ``interpret=`` alias)
    to a :class:`KernelBackend`.

    ``spec=None`` resolves from ``jax.default_backend()`` (see
    :func:`default_backend_name`).  ``interpret`` — when not ``None`` —
    overrides the resolved backend's mode: ``True`` wraps the lowerings in
    the interpreter, ``False`` forces the compiled path (on a CPU host that
    compiled path will fail at lowering time, exactly as the legacy
    ``interpret=False`` did)."""
    if isinstance(spec, KernelBackend):
        bk = spec
    else:
        if spec is None:
            spec = default_backend_name()
        try:
            bk = BACKENDS[spec.lower()]
        except KeyError:
            raise ValueError(
                f"unknown kernel backend {spec!r}; expected one of "
                f"{sorted(set(BACKENDS))}") from None
    if interpret is True:
        bk = bk.interpreted()
    elif interpret is False:
        bk = bk.compiled()
    return bk


def warn_interpret_deprecated(where: str) -> None:
    """One-release deprecation notice for the old ``interpret: bool`` knob."""
    warnings.warn(
        f"{where}: the interpret= knob is deprecated; pass backend="
        "('tpu' | 'gpu' | 'interpret' | 'interpret:gpu', or None to resolve "
        "from jax.default_backend()) instead.  interpret=True maps to the "
        "interpret backend; interpret=False forces the compiled lowering.",
        DeprecationWarning,
        stacklevel=3,
    )
