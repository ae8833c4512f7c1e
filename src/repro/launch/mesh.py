"""Mesh construction.

Functions, not module constants: importing this module never touches jax
device state (device count is locked at first use).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names, devices=None):
    """`jax.make_mesh` with Auto axis types: the programs here place their
    shardings through in/out specs and shard_map, never through explicit
    sharding-in-types (jax's default)."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = ("data", "model") — 256 chips.
    Multi-pod:  (2, 16, 16) = ("pod", "data", "model") — 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """All locally-visible devices as (1, N) ("data", "model") — used by
    smoke tests and examples."""
    return make_mesh((1, len(jax.devices())), ("data", "model"))
