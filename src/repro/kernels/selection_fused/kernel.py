"""Pallas TPU kernel: fused selection phases 2-3 (paper Alg. 1 lines 3-14).

One pass over the relevance scores does INT8 binning (with a precomputed
global [lo, hi] affine), the stride-1 max-pool (halo via neighbour-block
views, as in the maxpool kernel), and the 256-bin histogram accumulation;
the final grid step runs the reverse prefix scan and emits the threshold.

This is the fusion the roofline §Perf analysis points at: in the XLA path
each of bins/pooled/one-hot is an HBM round-trip; here scores stream
HBM→VMEM once and only the pooled bins + (256,) histogram + threshold
leave the chip. The ASIC pipelines the same three stages back-to-back
(Score RAM → Quant/Pool → Threshold Locating) — this kernel is that
pipeline with VMEM playing the role of the inter-stage RAMs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import LANE, interpret_default, round_up

NUM_BINS = 256
DEFAULT_BLOCK_N = 512
_EPS = 1e-6

# TPU tiling: the last two dims of every block are full or (8k, 128k)-
# aligned, so scores, bins and masks travel as (·, 1, N) lane rows, per-row
# scalars as (·, 1, 1), and histograms as (256, 1) columns. The stride-1
# maxpool is a max over lane rotations (`pltpu.roll`), with the halo lanes
# supplied by the neighbour block (flat kernel) or by explicit halo columns
# placed where the rotation wraps (paged kernel) — no unaligned lane
# concatenation. max is exact, so any evaluation order gives the same bins.


def _window_max(ext, halo: int, left=None, right=None):
    """max over offsets |d| ≤ halo of the row ``ext`` (1, N).

    Without neighbours the rotation wraps within ``ext`` itself (the caller
    parks halo values in the wrapped lanes); with ``left``/``right``
    neighbour rows the wrapped lanes are taken from them instead."""
    n = ext.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, ext.shape, 1)
    out = ext
    for d in range(1, halo + 1):
        fwd = pltpu.roll(ext, n - d, 1)                     # ext[t + d]
        bwd = pltpu.roll(ext, d, 1)                         # ext[t - d]
        if right is not None:
            fwd = jnp.where(lane < n - d, fwd, pltpu.roll(right, n - d, 1))
            bwd = jnp.where(lane >= d, bwd, pltpu.roll(left, d, 1))
        out = jnp.maximum(out, jnp.maximum(fwd, bwd))
    return out


def _accumulate_hist(acc_ref, pooled, keep=None):
    """acc (256, 1) += histogram of the (1, N) row ``pooled`` (lanes where
    ``keep`` holds)."""
    bins = jax.lax.broadcasted_iota(jnp.int32, (NUM_BINS, 1), 0)
    hit = pooled == bins                                    # (256, N)
    if keep is not None:
        hit = hit & keep
    acc_ref[...] += jnp.sum(hit.astype(jnp.int32), axis=1, keepdims=True)


def _kernel(k_ref, len_ref, s_ref, sl_ref, sr_ref, lo_ref, hi_ref,
            bins_out_ref, hist_out_ref, thr_out_ref, hist_acc, ge_acc,
            *, window: int, bn: int, nblocks: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        hist_acc[...] = jnp.zeros_like(hist_acc)
        ge_acc[...] = jnp.zeros_like(ge_acc)

    lo = lo_ref[0]                                          # (1, 1)
    scale = jnp.maximum((hi_ref[0] - lo) / 254.0, _EPS)
    valid_len = len_ref[b]

    def to_bins(vals, offset):                              # (1, bn) rows
        pos = offset + jax.lax.broadcasted_iota(jnp.int32, vals.shape, 1)
        b = jnp.clip(jnp.round((vals - lo) / scale) + 1.0, 1.0, 255.0)
        return jnp.where(pos < valid_len, b, 0.0).astype(jnp.int32)

    centre = to_bins(s_ref[0], j * bn)
    if window > 1:
        left = jnp.where(j == 0, 0, to_bins(sl_ref[0], (j - 1) * bn))
        right = jnp.where(j == nblocks - 1, 0, to_bins(sr_ref[0], (j + 1) * bn))
        pooled = _window_max(centre, window // 2, left, right)
        # pooling never resurrects masked slots
        pooled = jnp.where(centre > 0, pooled, 0)
    else:
        pooled = centre
    bins_out_ref[0] = pooled

    _accumulate_hist(hist_acc, pooled)
    ids = jax.lax.broadcasted_iota(jnp.int32, (NUM_BINS, 1), 0)
    ge_acc[...] += jnp.sum((pooled >= ids).astype(jnp.int32), axis=1,
                           keepdims=True)

    @pl.when(j == nblocks - 1)
    def _finalize():
        hist_out_ref[0] = hist_acc[...]
        # ge[b] = #(pooled ≥ b) is the reverse prefix sum of the histogram:
        # the threshold is the highest bin whose tail holds ≥ k tokens.
        reached = ge_acc[...] >= k_ref[b]
        thr = jnp.max(jnp.where(reached, ids, 0), axis=0, keepdims=True)
        thr_out_ref[0] = jnp.maximum(thr, 1)


@functools.partial(jax.jit, static_argnames=("window", "block_n", "interpret"))
def fused_bin_pool_threshold_pallas(scores: jax.Array, lo: jax.Array,
                                    hi: jax.Array, k: jax.Array,
                                    lengths: jax.Array, *, window: int = 7,
                                    block_n: int = DEFAULT_BLOCK_N,
                                    interpret: bool | None = None):
    """scores (BH, N) f32; lo/hi/k/lengths (BH,) → (pooled bins (BH,N) u8,
    hist (BH,256) i32, threshold (BH,) i32)."""
    if interpret is None:
        interpret = interpret_default()
    bh, n = scores.shape
    bn = block_n if n % block_n == 0 else n
    assert window == 1 or (window % 2 == 1 and window // 2 < bn)
    nblocks = n // bn

    row = lambda c: pl.BlockSpec((1, 1, bn), lambda b, j, *_: (b, 0, c(j)))
    scalar = pl.BlockSpec((1, 1, 1), lambda b, j, *_: (b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bh, nblocks),
        in_specs=[
            row(lambda j: j),
            row(lambda j: jnp.maximum(j - 1, 0)),
            row(lambda j: jnp.minimum(j + 1, nblocks - 1)),
            scalar, scalar,
        ],
        out_specs=[
            row(lambda j: j),
            pl.BlockSpec((1, NUM_BINS, 1), lambda b, j, *_: (b, 0, 0)),
            scalar,
        ],
        scratch_shapes=[pltpu.VMEM((NUM_BINS, 1), jnp.int32),
                        pltpu.VMEM((NUM_BINS, 1), jnp.int32)],
    )
    s3 = scores.astype(jnp.float32).reshape(bh, 1, n)
    pooled, hist, thr = pl.pallas_call(
        functools.partial(_kernel, window=window, bn=bn, nblocks=nblocks),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bh, 1, n), jnp.int32),
            jax.ShapeDtypeStruct((bh, NUM_BINS, 1), jnp.int32),
            jax.ShapeDtypeStruct((bh, 1, 1), jnp.int32),
        ],
        name="fused_bin_pool_threshold_pallas",
        interpret=interpret,
    )(k.astype(jnp.int32), lengths.astype(jnp.int32), s3, s3, s3,
      lo.astype(jnp.float32).reshape(bh, 1, 1),
      hi.astype(jnp.float32).reshape(bh, 1, 1))
    return (pooled.reshape(bh, n).astype(jnp.uint8), hist[..., 0],
            thr.reshape(bh))


# ---------------------------------------------------------------------------
# Paged / sharded variant: phases 2-3 over block-decomposed scores with
# EXPLICIT halo columns and EXTERNAL bounds. The sharded tick computes its
# binning affine from pmin/pmax-merged bounds and its maxpool halos from a
# psum of pre-pool block edges — both cross-chip collectives — so unlike the
# flat kernel above, this one takes (lo, hi) and the halo columns as inputs
# and emits the raw (256,) histogram WITHOUT a threshold: the threshold is
# located after the histogram psum. One grid step consumes one logical
# block's scores in place (they never leave VMEM between binning, pooling
# and histogram accumulation). Each block travels as one lane row of width
# W ≥ BS + 2·halo: its BS scores in lanes [0, BS), the right halo in
# [BS, BS+halo) and the left halo in the last `halo` lanes, where the
# rotations of `_window_max` wrap.
# ---------------------------------------------------------------------------


def _paged_select_kernel(s_ref, lo_ref, hi_ref, halo_ref, valid_ref,
                         force_ref, pooled_ref, hist_ref, acc_ref,
                         *, window: int, bs: int, mb: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Binning affine — `quantization.binning_affine` inlined (same f32
    # expression tree ⇒ bit-identical bins to `bins_from_bounds`).
    lo = lo_ref[0, 0]                                           # (1, 1)
    offset = jnp.where(jnp.isfinite(lo), lo, 0.0)
    scale = jnp.maximum((hi_ref[0, 0] - offset) / 254.0, _EPS)
    s = s_ref[0, 0, 0]                                          # (1, W)
    valid = valid_ref[0, 0] != 0                                # (1, W)
    b = jnp.clip(jnp.round((s - offset) / scale) + 1.0, 1.0, 255.0)
    bins = jnp.where(valid, b, 0.0).astype(jnp.int32)
    if window > 1:
        pooled = _window_max(bins + halo_ref[0, 0, 0], window // 2)
        # pooling never resurrects masked slots
        pooled = jnp.where(bins > 0, pooled, 0)
    else:
        pooled = bins
    pooled = jnp.where((force_ref[0, 0] != 0) & valid, 255, pooled)
    pooled_ref[0, 0, 0] = pooled

    lane = jax.lax.broadcasted_iota(jnp.int32, pooled.shape, 1)
    _accumulate_hist(acc_ref, pooled, lane < bs)

    @pl.when(j == mb - 1)
    def _finalize():
        hist_ref[0, 0] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_fused_select_pallas(scores: jax.Array, lo: jax.Array, hi: jax.Array,
                              from_left: jax.Array, from_right: jax.Array,
                              blk_valid: jax.Array, force: jax.Array,
                              *, window: int = 7,
                              interpret: bool | None = None):
    """Fused INT8 binning + blocked maxpool + histogram over paged scores.

    scores (S, KV, MB, BS) f32, sentinel-masked (`SCORE_NEG_INF` at invalid
    positions); lo/hi (S, KV) f32 GLOBAL bounds (already pmin/pmax-merged);
    from_left/from_right (S, KV, MB, halo) uint8 pre-pool halo bin columns
    of each block's neighbours (already psum'd across shards; all-zero rows
    at sequence boundaries; pass zeros with halo=1 when window == 1);
    blk_valid/force (S, MB, BS) int8 validity / sink-recent forcing columns.
    Returns (pooled (S, KV, MB, BS) u8, hist (S, KV, 256) i32). The
    histogram is raw — threshold location happens AFTER the cross-shard
    histogram psum.
    """
    if interpret is None:
        interpret = interpret_default()
    s, kv, mb, bs = scores.shape
    halo = from_left.shape[-1]
    assert window == 1 or window // 2 == halo, (window, halo)
    w = round_up(bs + 2 * halo, LANE)

    def lanes(x, dt):                      # (..., BS) → (..., 1, W) lane rows
        x = jnp.pad(x.astype(dt), [(0, 0)] * (x.ndim - 1) + [(0, w - bs)])
        return x.reshape(x.shape[:-1] + (1, w))

    halos = jnp.zeros((s, kv, mb, w), jnp.int32)
    halos = halos.at[..., bs:bs + halo].set(from_right.astype(jnp.int32))
    halos = halos.at[..., w - halo:].set(from_left.astype(jnp.int32))
    blk_row = pl.BlockSpec((1, 1, 1, 1, w), lambda i, k, j: (i, k, j, 0, 0))
    col_row = pl.BlockSpec((1, 1, 1, w), lambda i, k, j: (i, j, 0, 0))
    bound = pl.BlockSpec((1, 1, 1, 1), lambda i, k, j: (i, k, 0, 0))
    pooled, hist = pl.pallas_call(
        functools.partial(_paged_select_kernel, window=window, bs=bs, mb=mb),
        grid=(s, kv, mb),
        in_specs=[blk_row, bound, bound, blk_row, col_row, col_row],
        out_specs=[
            blk_row,
            pl.BlockSpec((1, 1, NUM_BINS, 1), lambda i, k, j: (i, k, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s, kv, mb, 1, w), jnp.int32),
            jax.ShapeDtypeStruct((s, kv, NUM_BINS, 1), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((NUM_BINS, 1), jnp.int32)],
        name="paged_fused_select_pallas",
        interpret=interpret,
    )(lanes(scores, jnp.float32), lo.astype(jnp.float32).reshape(s, kv, 1, 1),
      hi.astype(jnp.float32).reshape(s, kv, 1, 1), halos.reshape(s, kv, mb, 1, w),
      lanes(blk_valid, jnp.int32), lanes(force, jnp.int32))
    return pooled[..., 0, :bs].astype(jnp.uint8), hist[..., 0]
