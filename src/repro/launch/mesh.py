"""Production meshes.

Functions, not module-level constants, so importing this module never
touches jax device state.  The single-pod production mesh is 16×16 = 256
chips (one TPU v5e pod); the multi-pod mesh stacks a leading "pod" axis:
2 × 16 × 16 = 512 chips.  The planner folds ("pod", "data") into one
logical data-parallel axis; "model" carries TP/EP/vocab sharding.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """`jax.make_mesh` with every axis Auto (GSPMD-partitioned)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_abstract_mesh(shape, axes):
    """A device-free `jax.sharding.AbstractMesh` of the given shape."""
    return jax.sharding.AbstractMesh(tuple(shape), tuple(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 4, model: int = 2):
    """Small mesh over host devices (tests / examples)."""
    return make_mesh((data, model), ("data", "model"))
