"""Production mesh definitions.

Functions, not module-level constants — importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before the first jax init).

Every mesh here has `Auto` axes: the program places arrays with
NamedShardings and leaves the per-op layouts to GSPMD. Code that reads the
ambient mesh (`models/layers.py::current_mesh`) sees a mesh entered with
`jax.set_mesh(mesh)`.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """`jax.make_mesh` with Auto (GSPMD) axes instead of its Explicit
    default."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_smoke_mesh(model: int = 1):
    """Single-host mesh over however many devices exist (tests/examples)."""
    n = len(jax.devices())
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))


def dp_axes(mesh) -> tuple:
    """The data-parallel axes of a mesh (pod folds into DP)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def axis_size(mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1
