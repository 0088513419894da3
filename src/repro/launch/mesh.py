"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import, and smoke tests must keep seeing 1 device.

Axes:
  * ``pod``   — inter-pod (DCN) axis; composes with ``data`` for
                data-parallel/FSDP work so exactly one fused gradient
                all-reduce crosses the pod boundary per step.
  * ``data``  — intra-pod data parallel / FSDP axis (ICI).
  * ``model`` — tensor/expert parallel axis (ICI).
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axis types, over ``devices`` (default:
    all of them)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """A small mesh over whatever devices exist (tests, examples)."""
    n = len(jax.devices())
    model_axis = min(model_axis, n)
    return make_mesh((n // model_axis, model_axis), ("data", "model"))


def data_axes(mesh) -> tuple[str, ...]:
    """Mesh axes forming the data-parallel dimension."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis_size(mesh) -> int:
    return mesh.shape.get("model", 1)
