"""Production mesh construction (harness MULTI-POD DRY-RUN spec).

A FUNCTION, not a module-level constant: importing this module never touches
jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _make_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(model: int = 1) -> Mesh:
    """Small mesh over however many (host) devices exist — tests/examples."""
    n = len(jax.devices())
    model = max(1, min(model, n))
    data = n // model
    return _make_mesh((data, model), ("data", "model"))
