"""Production mesh construction (deliverable e, step 1).

A function — not a module-level constant — so importing this module
never touches jax device state. The dry-run launcher sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any
jax import; smoke tests and benches see the real single device.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """``jax.make_mesh`` with Auto axes. Without ``axis_types`` current
    JAX makes the axes Explicit, and the sharding rules' implicit
    resharding (e.g. the embedding gather) then raises."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh() -> Mesh:
    """1x1 mesh on the real local device (smoke tests, examples)."""
    return make_mesh((1, 1), ("data", "model"))
