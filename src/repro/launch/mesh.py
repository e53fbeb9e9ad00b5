"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import, and smoke tests must keep seeing 1 device.

Mesh shapes (TPU v5e pods):
  single-pod:  (data=16, model=16)              = 256 chips
  multi-pod:   (pod=2, data=16, model=16)       = 512 chips
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _axis_kwargs(n: int) -> dict:
    return {"axis_types": (AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False, data: int = 16,
                         model: int = 16):
    """256 chips per pod; (data, model) split configurable for the
    mesh-shape experiments in EXPERIMENTS.md §Perf (data*model must be 256)."""
    assert data * model == 256, (data, model)
    shape = (2, data, model) if multi_pod else (data, model)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_kwargs(len(axes)))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (cpu) devices exist — for tests."""
    return jax.make_mesh((data, model), ("data", "model"), **_axis_kwargs(2))


def batch_axes(mesh) -> tuple:
    """Axes the batch dimension shards over."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)
