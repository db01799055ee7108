"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
initialization and only then builds the mesh.
"""
from __future__ import annotations

import math

import jax
from jax import make_mesh
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(data: int = 2, model: int = 2, pod: int = 0) -> Mesh:
    """Small mesh for CPU tests (requires host-device-count >= product)."""
    if pod:
        return make_mesh(
            (pod, data, model), ("pod", "data", "model"),
            axis_types=(AxisType.Auto,) * 3,
        )
    return make_mesh(
        (data, model), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )


def local_mesh() -> Mesh:
    """Every device of this process as a (data, model) grid.

    ``model`` is the largest divisor of the device count not above its
    square root, ``data`` the rest: 2x2 on a four-chip host, 1x1 on one.
    """
    n = len(jax.devices())
    model = max(m for m in range(1, math.isqrt(n) + 1) if n % m == 0)
    return make_mesh(
        (n // model, model), ("data", "model"),
        axis_types=(AxisType.Auto,) * 2,
    )


def single_device_mesh() -> Mesh:
    """1x1 mesh: lets the same pjit code paths run on one CPU device."""
    return make_mesh(
        (1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )
