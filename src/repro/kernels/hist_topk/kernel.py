"""Pallas TPU kernel: histogram Top-K threshold locating (paper §3.2 / §4.2.2).

The ASIC uses an SRAM read-accumulate-write pipeline with tag isolation and
RAW-bypass registers. The TPU-native formulation is hazard-free: each block
of INT8 bins becomes a (BN, 256) one-hot integer matrix whose column sum is
the block's histogram — an MXU/VPU-friendly reduction — accumulated across
the key-block grid dimension into a VMEM scratch accumulator. At the final
block the kernel runs the 256-wide reverse prefix scan and emits both the
histogram and the located threshold.

Grid = (B·KV, N/BN); the scratch histogram plays the role of the paper's
pseudo-dual-port SRAM, and grid-sequential accumulation replaces its
read-after-write bypass network.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import interpret_default

NUM_BINS = 256
DEFAULT_BLOCK_N = 2048


def _kernel(bins_ref, k_ref, hist_out_ref, thr_out_ref, acc_ref, *, nblocks: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    blk = bins_ref[0].astype(jnp.int32)                       # (BN,)
    # One-hot histogram of the block: compare against the bin iota.
    bin_ids = jax.lax.broadcasted_iota(jnp.int32, (blk.shape[0], NUM_BINS), 1)
    onehot = (blk[:, None] == bin_ids).astype(jnp.int32)      # (BN, 256)
    acc_ref[...] += jnp.sum(onehot, axis=0)

    @pl.when(j == nblocks - 1)
    def _finalize():
        hist = acc_ref[...]                                   # (256,)
        hist_out_ref[0] = hist
        # Reverse prefix sum: counts of bins >= b.
        rev_cum = jnp.cumsum(hist[::-1])[::-1]
        reached = rev_cum >= k_ref[0]
        ids = jax.lax.broadcasted_iota(jnp.int32, (NUM_BINS,), 0)
        t = jnp.max(jnp.where(reached, ids, 0))
        thr_out_ref[0] = jnp.maximum(t, 1)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def hist_threshold_pallas(bins: jax.Array, k: jax.Array,
                          *, block_n: int = DEFAULT_BLOCK_N,
                          interpret: bool | None = None):
    """bins (BH, N) uint8, k (BH,) int32 → (hist (BH,256) int32, thr (BH,) int32)."""
    if interpret is None:
        interpret = interpret_default()
    bh, n = bins.shape
    bn = min(block_n, n)
    assert n % bn == 0, f"N={n} not divisible by block {bn}"
    nblocks = n // bn
    hist, thr = pl.pallas_call(
        functools.partial(_kernel, nblocks=nblocks),
        grid=(bh, nblocks),
        in_specs=[
            pl.BlockSpec((1, bn), lambda b, j: (b, j)),
            pl.BlockSpec((1,), lambda b, j: (b,)),
        ],
        out_specs=[
            pl.BlockSpec((1, NUM_BINS), lambda b, j: (b, 0)),
            pl.BlockSpec((1,), lambda b, j: (b,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, NUM_BINS), jnp.int32),
            jax.ShapeDtypeStruct((bh,), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((NUM_BINS,), jnp.int32)],
        name="hist_threshold_pallas",
        interpret=interpret,
    )(bins, k.astype(jnp.int32))
    return hist, thr
