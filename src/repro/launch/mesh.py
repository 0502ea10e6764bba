"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run process
sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and only then builds the mesh.

Single pod:  (data=16, model=16)            = 256 chips  (TPU v5e pod)
Multi-pod:   (pod=2, data=16, model=16)     = 512 chips  (2 pods)

The ``pod`` axis is an outer data-parallel axis (gradient all-reduce crosses
the inter-pod links exactly once per step); ``data`` carries batch + FSDP
sharding inside a pod; ``model`` carries tensor/expert parallelism on the
fastest links.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh

__all__ = ["make_production_mesh", "make_mesh", "local_mesh"]


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """jax.make_mesh with explicit Auto axis types (silences the v0.9
    behaviour-change warning; we use in/out_shardings + shard_map, not
    explicit-mode sharding-in-types)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def local_mesh(model: Optional[int] = None) -> Mesh:
    """Best-effort mesh from whatever devices exist (elastic: the same
    checkpoint restores onto any shape).  Used by train.py/serve.py."""
    n = jax.device_count()
    model = model or 1
    assert n % model == 0, (n, model)
    return make_mesh((n // model, model), ("data", "model"))
