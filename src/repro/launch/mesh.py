"""Production mesh construction.

Defined as FUNCTIONS so importing this module never touches jax device state
(the dry-run sets XLA_FLAGS before any jax import). Every axis is Auto: the
model's shard_map islands and sharding rules are written for GSPMD
propagation, not explicit per-axis types.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh():
    """1-device mesh with the production axis names (smoke tests)."""
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def required_devices(multi_pod: bool = False) -> int:
    return 512 if multi_pod else 256
