"""Production mesh factory (TPU v5e target).

A FUNCTION, not a module constant, so importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax init; smoke
tests and benches see the single real CPU device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _make_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(model: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    assert n % model == 0, (n, model)
    return _make_mesh((n // model, model), ("data", "model"))
