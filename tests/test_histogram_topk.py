"""Property tests for the O(n) histogram Top-K (paper §3.2)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

import importlib

ht = importlib.import_module("repro.core.histogram_topk")


@given(st.integers(1, 5), st.integers(16, 512), st.integers(1, 64))
@settings(max_examples=30, deadline=None)
def test_threshold_guarantee(rows, n, k):
    """count(bins ≥ T) ≥ min(k, count(bins ≥ 1)): the approximate threshold
    never under-selects (overshoot-only, as the paper argues)."""
    rng = np.random.default_rng(rows * 7919 + n * 13 + k)
    bins = rng.integers(0, 256, size=(rows, n)).astype(np.uint8)
    hist = ht.histogram256(jnp.asarray(bins))
    t = np.asarray(ht.locate_threshold(hist, k))
    for r in range(rows):
        got = int((bins[r] >= t[r]).sum())
        avail = int((bins[r] >= 1).sum())
        assert got >= min(k, avail)
    assert np.all(t >= 1)


@given(st.integers(1, 4), st.integers(8, 256))
@settings(max_examples=30, deadline=None)
def test_histogram_counts(rows, n):
    rng = np.random.default_rng(rows * 31 + n)
    bins = rng.integers(0, 256, size=(rows, n)).astype(np.uint8)
    hist = np.asarray(ht.histogram256(jnp.asarray(bins)))
    assert hist.sum(-1).tolist() == [n] * rows
    for r in range(rows):
        np.testing.assert_array_equal(hist[r], np.bincount(bins[r], minlength=256))


@given(st.integers(8, 200), st.integers(1, 64), st.floats(0.0, 1.0))
@settings(max_examples=40, deadline=None)
def test_compact_indices_semantics(n, k_cap, density):
    rng = np.random.default_rng(int(n * 1000 + k_cap + density * 97))
    keep = rng.random((2, n)) < density
    idx, mask, count = ht.compact_indices(jnp.asarray(keep), k_cap)
    idx, mask, count = map(np.asarray, (idx, mask, count))
    for r in range(2):
        expect = np.nonzero(keep[r])[0][:k_cap]
        got = idx[r][mask[r]]
        np.testing.assert_array_equal(got, expect)       # in-order compaction
        assert count[r] == min(int(keep[r].sum()), k_cap)
        assert not mask[r][count[r]:].any()


def test_exact_recovery_when_no_ties():
    """With distinct bins and generous capacity, histogram top-k ⊇ exact."""
    rng = np.random.default_rng(3)
    scores = rng.permutation(256)[:200].astype(np.uint8).reshape(1, 200)
    scores = np.maximum(scores, 1)
    k = 40
    sel = ht.histogram_topk(jnp.asarray(scores), k, k_cap=64)
    chosen = set(np.asarray(sel.indices)[0][np.asarray(sel.mask)[0]].tolist())
    exact = set(np.argsort(scores[0])[::-1][:k].tolist())
    # approximate = exact ∪ (ties at the threshold); with distinct values the
    # only slack is duplicates of the threshold bin value
    assert exact <= chosen or len(chosen - exact) <= 2


def test_overshoot_is_bounded_statistically():
    """Paper: ~0.19% overshoot for uniform data at 5% retention."""
    rng = np.random.default_rng(0)
    n, k = 65536, 3277
    bins = np.clip((rng.random((4, n)) * 254 + 1), 1, 255).astype(np.uint8)
    sel = ht.histogram_topk(jnp.asarray(bins), k, k_cap=n)
    count = np.asarray(sel.count)
    overshoot = (count - k) / n
    assert np.all(overshoot >= 0) and np.all(overshoot < 0.01)


def test_masked_bins_never_selected():
    rng = np.random.default_rng(1)
    bins = rng.integers(1, 256, size=(1, 128)).astype(np.uint8)
    bins[0, 64:] = 0   # masked region
    sel = ht.histogram_topk(jnp.asarray(bins), 32, k_cap=64)
    chosen = np.asarray(sel.indices)[0][np.asarray(sel.mask)[0]]
    assert np.all(chosen < 64)


@functools.partial(jax.jit, static_argnums=2)
def _composed_blocked(bins, k, k_cap):
    """What `histogram_topk_blocked` computes, by the histogram stages on the
    flattened bins: per-block histograms summed, the reverse-prefix
    threshold, the cumsum-scatter compaction."""
    flat = bins.reshape(bins.shape[:-2] + (-1,))
    t = ht.locate_threshold(jnp.sum(ht.histogram256(bins), axis=-2), k)
    keep = flat >= t[..., None].astype(flat.dtype)
    return ht.Selection(*ht.compact_indices(keep, k_cap), t)


def _assert_same_selection(got, want):
    for name, a, b in zip(ht.Selection._fields, got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)


@pytest.mark.parametrize("top", [2, 5, 256])          # bins drawn from 0..top-1
@pytest.mark.parametrize("nb", [1, 3, 64])
@pytest.mark.parametrize("bs", [8, 16, 32, 128])
def test_blocked_topk_matches_histogram_stages(bs, nb, top):
    """The counted threshold and the block-prefix compaction give the
    histogram stages' selection bit for bit: indices, mask, count and
    threshold, for targets from nothing to past everything and capacities
    from one slot to past n. Row 0 is all equal, row 1 all zero."""
    n = nb * bs
    rng = np.random.default_rng(bs * 1000 + nb * 10 + top)
    bins = rng.integers(0, top, size=(2, 3, nb, bs)).astype(np.uint8)
    bins[0, 0] = rng.integers(1, top) if top > 1 else 1
    bins[0, 1] = 0
    bins = jnp.asarray(bins)
    blocked = jax.jit(ht.histogram_topk_blocked, static_argnums=2)
    for k in (0, 1, n // 20 + 1, n, n + 5):
        kept = int(jnp.max(_composed_blocked(bins, k, n).count))
        for k_cap in sorted({1, max(1, kept // 2), n + 5}):
            _assert_same_selection(blocked(bins, k, k_cap),
                                   _composed_blocked(bins, k, k_cap))


def test_blocked_topk_traced_per_row_k():
    """k may be a traced array, one target per row, under jit."""
    rng = np.random.default_rng(7)
    nb, bs = 16, 32
    bins = jnp.asarray(rng.integers(0, 256, size=(2, 3, nb, bs)), jnp.uint8)
    k = jnp.asarray([[0, 1, 25], [256, nb * bs, nb * bs + 5]], jnp.int32)
    got = jax.jit(ht.histogram_topk_blocked, static_argnums=2)(bins, k, 128)
    _assert_same_selection(got, _composed_blocked(bins, k, 128))
