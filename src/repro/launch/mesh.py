"""Mesh construction (functions — importing this module never touches jax
device state, so a caller can still force the host platform's device
count before JAX starts)."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    Axes: ("pod", "data", "model") multi-pod / ("data", "model") single-pod.
    DP spans pod×data; TP/EP/SP span model.  More pods widen only the pure-
    DP outer axis — the design scales by adding pods, not by resharding.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — tests/examples."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
