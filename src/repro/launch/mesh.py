"""Production mesh definitions (see MULTI-POD DRY-RUN spec).

``make_production_mesh`` is a function — importing this module never touches
jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _mk(shape, axes) -> Mesh:
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh(shape, axes) -> Mesh:
    """Arbitrary mesh for tests/examples (e.g. (1,1) on a laptop)."""
    return _mk(shape, axes)


def make_device_mesh(shape, axes, devices) -> Mesh:
    """Mesh over an explicit device subset (unlike ``jax.make_mesh``, which
    always grabs the whole process device list). The node topology uses this
    to carve one host's devices into independent socket-group meshes."""
    import numpy as np

    arr = np.asarray(devices, dtype=object).reshape(tuple(shape))
    return Mesh(arr, tuple(axes))


def single_device_mesh() -> Mesh:
    return make_mesh((1, 1), ("data", "model"))
