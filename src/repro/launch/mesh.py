"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state. Single-pod: 16x16 = 256 chips (data x model).
Multi-pod: 2 x 16 x 16 = 512 chips with a leading pure-DP "pod" axis.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 2, model: int = 2):
    """Small mesh over however many host devices exist (tests/examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // data))
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def batch_axes(mesh) -> tuple:
    """Logical batch axis = all pure-DP mesh axes."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
