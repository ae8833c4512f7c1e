"""Seeded random weights, made by the benchmark on the device in one call.

The tree has the program's parameter layout (taken from the shapes of its
init, nothing else of it), and every leaf is drawn here from the seed:
norm scales are ones, the token embedding is N(0, 0.02), every other matrix
a truncated normal of standard deviation 1/sqrt(fan_in), where fan_in is
the leaf's first axis after the stacked-layer axis. The same arrays feed the
program and the plain reference.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, including ones past 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _path_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def _leaf(key, name: str, shape, dtype):
    if name.endswith("scale"):
        return jnp.ones(shape, dtype)
    if name.endswith("tok"):
        return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)
    dims = shape[1:] if name.startswith("periods") else shape
    std = dims[0] ** -0.5
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def make_params(shapes, seed: int):
    """Draw every leaf of ``shapes`` (a pytree of ShapeDtypeStruct) from
    ``seed``, in one jitted call; returns device arrays of the leaf dtypes."""
    leaves = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_path_name(p) for p, _ in leaves[0]]
    treedef = leaves[1]

    def draw(key):
        out = []
        for name, (_, s) in zip(names, leaves[0]):
            k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            out.append(_leaf(k, name, s.shape, s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    params = jax.jit(draw)(seed_key(seed))
    jax.block_until_ready(params)
    return params
