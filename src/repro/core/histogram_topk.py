"""Approximate histogram-based Top-K filtering (paper §3.2, Algorithm 1 phases 2-3).

Three O(n) stages, no sorting:

1. **Histogram generation** — count occurrences of each INT8 bin (256 bins)
   with a per-row scatter-add (`histogram256`). Histograms are purely
   additive, so histograms of shards simply **add**: the sharded island
   psums one 256-element histogram per row into a global threshold, and the
   flat `histogram_topk` uses it directly.
2. **Threshold locating** — reverse prefix sum from bin 255 down; the first
   bin whose cumulative count reaches K is the approximate threshold.
3. **Parallel filtering** — keep all elements ≥ threshold; compact their
   indices into a fixed-capacity buffer with a cumsum-scatter (the
   data-parallel equivalent of the paper's bitonic mask-compaction).

The paged (blocked) form `histogram_topk_blocked` builds no histogram and
scatters nothing: it finds the same threshold bit by bit by counting the
elements at or above each candidate, and compacts through prefix sums of
the per-block keep counts, then of one block's keep flags per output slot.
Its result is bit-identical to the three stages above.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

NUM_BINS = 256


class Selection(NamedTuple):
    """Fixed-capacity sparse pattern.

    indices:  (..., k_cap) int32 — selected token positions, padded with 0.
    mask:     (..., k_cap) bool  — True for real selections.
    count:    (...,) int32       — number of selected tokens (≤ k_cap).
    threshold:(...,) int32       — located INT8 threshold bin.
    """

    indices: jax.Array
    mask: jax.Array
    count: jax.Array
    threshold: jax.Array


def histogram256(bins: jax.Array, axis: int = -1) -> jax.Array:
    """Per-row 256-bin histogram of uint8 data.

    bins: (..., n) uint8 → (..., 256) int32.

    Two lowerings (§Perf it-2): the baseline materializes the (…, n, 256)
    one-hot (the literal translation of the MXU formulation — the Pallas
    kernel tiles the same contraction *in VMEM*, where it's free); the
    optimized XLA path uses a one-pass scatter-add, O(n) bytes.
    """
    from repro.flags import PERF
    if not PERF.hist_scatter_add:
        onehot = jax.nn.one_hot(bins.astype(jnp.int32), NUM_BINS,
                                dtype=jnp.int32, axis=-1)
        return jnp.sum(jnp.moveaxis(onehot, axis if axis >= 0 else axis - 1, -2),
                       axis=-2)
    if axis != -1:
        bins = jnp.moveaxis(bins, axis, -1)
    lead = bins.shape[:-1]
    n = bins.shape[-1]
    flat = bins.reshape(-1, n).astype(jnp.int32)

    def row_hist(row):
        return jnp.zeros((NUM_BINS,), jnp.int32).at[row].add(1, mode="drop")

    return jax.vmap(row_hist)(flat).reshape(*lead, NUM_BINS)


def locate_threshold(hist: jax.Array, k: jax.Array | int) -> jax.Array:
    """Reverse-prefix-sum threshold (paper Algorithm 1 lines 9-14).

    hist: (..., 256) int32; returns (...,) int32 bin index T such that
    ``count(bins ≥ T) ≥ k`` with T as large as possible (clamped to ≥ 1 so
    that masked-out bin 0 never passes).
    """
    rev_cum = jnp.cumsum(hist[..., ::-1], axis=-1)[..., ::-1]  # counts ≥ bin b
    reached = rev_cum >= jnp.asarray(k)[..., None]
    # Highest bin index where cumulative count ≥ k; if never reached, take 1.
    bin_ids = jnp.arange(NUM_BINS, dtype=jnp.int32)
    t = jnp.max(jnp.where(reached, bin_ids, jnp.int32(0)), axis=-1)
    return jnp.maximum(t, 1)


def compact_indices(keep: jax.Array, k_cap: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Dense-store of sparse indices: compact ``keep`` mask positions.

    keep: (..., n) bool → (indices (..., k_cap) int32, mask (..., k_cap) bool,
    count (...,) int32). A prefix sum assigns each kept element its output
    slot; elements past capacity are dropped (paper's Index-RAM capacity).
    """
    n = keep.shape[-1]
    pos = jnp.cumsum(keep.astype(jnp.int32), axis=-1) - 1          # slot per kept elem
    valid = keep & (pos < k_cap)
    src = jnp.arange(n, dtype=jnp.int32)
    src = jnp.broadcast_to(src, keep.shape)
    # Scatter src -> out[pos] where valid. Use one-hot-free scatter via `at`.
    out_shape = keep.shape[:-1] + (k_cap,)
    flat_keep = valid.reshape(-1, n)
    flat_pos = pos.reshape(-1, n)
    flat_src = src.reshape(-1, n)

    def row_scatter(kp, ps, sc):
        tgt = jnp.where(kp, ps, k_cap)  # dropped rows scatter to OOB slot
        return jnp.zeros((k_cap,), jnp.int32).at[tgt].set(sc, mode="drop")

    out = jax.vmap(row_scatter)(flat_keep, flat_pos, flat_src).reshape(out_shape)
    count = jnp.minimum(jnp.sum(keep.astype(jnp.int32), axis=-1), k_cap)
    slot = jnp.arange(k_cap, dtype=jnp.int32)
    mask = slot < count[..., None]
    return out, mask, count


def histogram_topk(bins: jax.Array, k: jax.Array | int, k_cap: int) -> Selection:
    """Full O(n) approximate Top-K over INT8 score bins.

    bins: (..., n) uint8 (bin 0 = masked/invalid); ``k`` target count;
    ``k_cap`` fixed capacity of the index buffer (≥ k; slack absorbs the
    paper's ~0.19% threshold-tie overshoot plus pooling spread).
    """
    hist = histogram256(bins)
    t = locate_threshold(hist, k)
    keep = bins >= t[..., None].astype(bins.dtype)
    indices, mask, count = compact_indices(keep, k_cap)
    return Selection(indices, mask, count, t)


def _counted_threshold(bins: jax.Array, k: jax.Array | int) -> jax.Array:
    """`locate_threshold(histogram256(bins), k)` over bins (..., nb, bs),
    with no histogram: the count of elements ≥ t falls as t rises, so the
    largest bin T with count(bins ≥ T) ≥ k is found one bit at a time from
    the most significant down, each step one compare-and-count per row."""
    k = jnp.asarray(k)
    t = jnp.zeros(bins.shape[:-2], jnp.int32)
    for bit in range(7, -1, -1):                      # the 8 bits of a bin
        cand = t | (1 << bit)
        at_or_above = jnp.sum(bins >= cand[..., None, None].astype(bins.dtype),
                              axis=(-2, -1), dtype=jnp.int32)
        t = jnp.where(at_or_above >= k, cand, t)
    return jnp.maximum(t, 1)


def histogram_topk_blocked(bins: jax.Array, k: jax.Array | int,
                           k_cap: int) -> Selection:
    """Block-decomposed `histogram_topk`: bins (..., nb, bs) in page order.

    Identical to `histogram_topk` on the flattened bins — the same
    threshold, and the compacted indices in the *logical* (flattened)
    coordinate — with no scatter and no sort. The threshold is counted
    (`_counted_threshold`). Output slot j lies in block b, the number of
    blocks whose inclusive keep-count prefix is ≤ j; its rank in that block
    is j minus the keeps before it, and its offset there is the number of
    positions of the block's own keep prefix that are ≤ that rank. Slots
    past the kept count read index 0, masked out.
    """
    nb, bs = bins.shape[-2], bins.shape[-1]
    t = _counted_threshold(bins, k)
    keep = bins >= t[..., None, None].astype(bins.dtype)          # (..., nb, bs)
    per_block = jnp.sum(keep, axis=-1, dtype=jnp.int32)            # (..., nb)
    through = jnp.cumsum(per_block, axis=-1)                       # inclusive
    count = jnp.minimum(through[..., -1], k_cap)
    slot = jnp.arange(k_cap, dtype=jnp.int32)
    passed = through[..., None, :] <= slot[:, None]                # (..., k_cap, nb)
    block = jnp.minimum(jnp.sum(passed, axis=-1, dtype=jnp.int32), nb - 1)
    before = jnp.sum(jnp.where(passed, per_block[..., None, :], 0), axis=-1)
    rank = slot - before
    row = jnp.take_along_axis(keep, block[..., None], axis=-2)     # (..., k_cap, bs)
    row_through = jnp.cumsum(row.astype(jnp.int32), axis=-1)
    offset = jnp.sum(row_through <= rank[..., None], axis=-1, dtype=jnp.int32)
    mask = slot < count[..., None]
    indices = jnp.where(mask, block * bs + offset, 0)
    return Selection(indices, mask, count, t)


def exact_topk_indices(scores: jax.Array, k: int) -> jax.Array:
    """O(n log k) exact Top-K baseline (``Std_TopK``) for tests/benchmarks."""
    _, idx = jax.lax.top_k(scores, k)
    return idx.astype(jnp.int32)
