"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — required because
the dry-run must set XLA_FLAGS before any jax initialisation.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto(axes) -> tuple:
    """GSPMD-propagated (Auto) axis types: the sharding rules in
    parallel/sharding.py constrain only inputs and a few activations."""
    return (AxisType.Auto,) * len(axes)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import math

    import numpy as np
    from jax.sharding import Mesh
    need = math.prod(shape)
    devs = jax.devices()
    if len(devs) == need:
        return jax.make_mesh(shape, axes, axis_types=_auto(axes))
    if len(devs) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, have {len(devs)} — run "
            f"under XLA_FLAGS=--xla_force_host_platform_device_count=512")
    return Mesh(np.array(devs[:need]).reshape(shape), axes)


def fsdp_axes(mesh) -> tuple:
    """Axes carrying the batch / FSDP dimension."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh) -> str:
    return "model"


def make_mesh_from_plan(tp: int, dp: int, *, pod: int = 1, devices=None):
    """Build a mesh realising a ChipLight ``ParallelPlan``'s TP x DP grid
    (EP/CP ride the data axis, see parallel/plan.py) over ``devices``
    (default: all of them)."""
    if pod > 1:
        axes = ("pod", "data", "model")
        return jax.make_mesh((pod, dp, tp), axes, axis_types=_auto(axes),
                             devices=devices)
    axes = ("data", "model")
    return jax.make_mesh((dp, tp), axes, axis_types=_auto(axes),
                         devices=devices)
