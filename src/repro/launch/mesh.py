"""Production mesh construction (function, not constant — importing this
module never touches jax device state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_host_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests)."""
    n = len(jax.devices())
    if data * model > n:
        data, model = n, 1
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))
