"""Production mesh construction (deliberately a function — importing this
module never touches jax device state)."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips).

    Axes: ``model`` is the fast-ICI tensor axis, ``data`` the FSDP/batch
    axis, ``pod`` the slow (DCN-class) pure-DP axis — only gradient
    all-reduce crosses it.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1, data: int | None = None):
    """Small mesh over whatever devices exist (tests / CPU runs)."""
    n = len(jax.devices())
    data = data or (n // model)
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
