"""Pallas TPU kernel: stride-1 INT8 max-pool, multi-level reuse (paper §4.2.1).

Implements the hardware comparison tree literally: level 1 computes
``mp(3,·)`` from the input, each further level widens the window by 2 via
``mp(r,n) = max(mp(r-2,n-1), mp(r-2,n+1))`` — log-depth, all lanes busy,
INT8 comparators only (quantization is hoisted before pooling exactly so
this unit never sees FP16, per the paper).

Halo handling: plain BlockSpecs address non-overlapping tiles, so the input
is bound **three times** — centre block j plus neighbour blocks j−1 / j+1
(clamped at the edges) — and the kernel stitches the `window//2` guard
columns from the neighbours before pooling, the VMEM analogue of the
shift-register overlap between adjacent hardware tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import interpret_default

DEFAULT_BLOCK_N = 4096


def _pool_row(x: jax.Array, window: int) -> jax.Array:
    """Multi-level reuse pooling of a 1-D int32 row (edge fill 0)."""
    def shift(v, off):
        pad = jnp.zeros((abs(off),), v.dtype)
        return jnp.concatenate([pad, v[:-off]] if off > 0 else [v[-off:], pad])
    out = jnp.maximum(jnp.maximum(shift(x, 1), x), shift(x, -1))
    for _ in range((window - 3) // 2):
        out = jnp.maximum(shift(out, 1), shift(out, -1))
    return out


def _kernel(c_ref, l_ref, r_ref, out_ref, *, window: int, bn: int, nblocks: int):
    j = pl.program_id(1)
    halo = window // 2
    centre = c_ref[0].astype(jnp.int32)                     # (bn,)
    left = l_ref[0, bn - halo:].astype(jnp.int32)           # (halo,)
    right = r_ref[0, :halo].astype(jnp.int32)
    # Kill the wrapped-around halo at the global edges (clamped index maps
    # re-deliver the centre block there).
    left = jnp.where(j == 0, 0, left)
    right = jnp.where(j == nblocks - 1, 0, right)
    row = jnp.concatenate([left, centre, right])
    out_ref[0] = _pool_row(row, window)[halo:halo + bn].astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("window", "block_n", "interpret"))
def maxpool_pallas(bins: jax.Array, window: int, *, block_n: int = DEFAULT_BLOCK_N,
                   interpret: bool | None = None) -> jax.Array:
    """bins (BH, N) uint8 → pooled (BH, N) uint8, stride-1 window `window`."""
    if interpret is None:
        interpret = interpret_default()
    if window == 1:
        return bins
    assert window % 2 == 1 and window >= 3
    bh, n = bins.shape
    bn = min(block_n, n)
    assert n % bn == 0 and window // 2 < bn
    nblocks = n // bn

    def centre(b, j):
        return (b, j)

    def left(b, j):
        return (b, jnp.maximum(j - 1, 0))

    def right(b, j):
        return (b, jnp.minimum(j + 1, nblocks - 1))

    return pl.pallas_call(
        functools.partial(_kernel, window=window, bn=bn, nblocks=nblocks),
        grid=(bh, nblocks),
        in_specs=[
            pl.BlockSpec((1, bn), centre),
            pl.BlockSpec((1, bn), left),
            pl.BlockSpec((1, bn), right),
        ],
        out_specs=pl.BlockSpec((1, bn), centre),
        out_shape=jax.ShapeDtypeStruct((bh, n), jnp.uint8),
        name="maxpool_pallas",
        interpret=interpret,
    )(bins, bins, bins)
