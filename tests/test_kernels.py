"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret=True)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quantization as qz
from repro.kernels import (
    flash_attention, flash_attention_ref, hist_threshold, hist_threshold_ref,
    maxpool_int8, maxpool_int8_ref, score_estimate, score_estimate_ref,
    sparse_flash_decode, sparse_flash_decode_ref)


@pytest.mark.parametrize("bh,g,r,n", [
    (1, 1, 16, 256), (2, 4, 64, 512), (3, 2, 32, 1024), (2, 8, 128, 2048)])
def test_score_est_sweep(rng, bh, g, r, n):
    kf = jnp.asarray(rng.normal(size=(bh, n, r)), jnp.float32)
    k2 = qz.quantize_key_features(kf)
    words = qz.pack2bit(k2.codes)
    qf = jnp.asarray(rng.normal(size=(bh, g, r)), jnp.float32)
    q3 = qz.quantize_query_features(qf)
    ref = score_estimate_ref(q3.codes, q3.scale, words, k2.scale, k2.zero)
    out = score_estimate(q3.codes, q3.scale, words, k2.scale, k2.zero,
                         interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("bh,n,k", [(1, 256, 16), (4, 4096, 200), (2, 8192, 1024)])
def test_hist_topk_sweep(rng, bh, n, k):
    bins = jnp.asarray(rng.integers(0, 256, size=(bh, n)), jnp.uint8)
    h_ref, t_ref = hist_threshold_ref(bins, jnp.full((bh,), k, jnp.int32))
    h, t = hist_threshold(bins, k, interpret=True)
    np.testing.assert_array_equal(np.asarray(h), np.asarray(h_ref))
    np.testing.assert_array_equal(np.asarray(t), np.asarray(t_ref))


@pytest.mark.parametrize("bh,n,window,block", [
    (1, 512, 3, 4096), (2, 4096, 7, 1024), (3, 8192, 11, 2048), (2, 256, 7, 128)])
def test_maxpool_sweep(rng, bh, n, window, block):
    bins = jnp.asarray(rng.integers(0, 256, size=(bh, n)), jnp.uint8)
    from repro.kernels.maxpool.kernel import maxpool_pallas
    ref = maxpool_int8_ref(bins, window)
    out = maxpool_pallas(bins, window, block_n=block, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("bh,g,c,hd,density", [
    (1, 1, 256, 64, 1.0), (2, 4, 512, 128, 0.7), (3, 2, 1024, 128, 0.3),
    (2, 8, 256, 256, 0.9)])
def test_flash_decode_sweep(rng, bh, g, c, hd, density):
    kc = jnp.asarray(rng.integers(-127, 128, size=(bh, c, hd)), jnp.int8)
    vc = jnp.asarray(rng.integers(-127, 128, size=(bh, c, hd)), jnp.int8)
    ks = jnp.asarray(rng.random((bh, c)) * 0.02 + 1e-3, jnp.float32)
    vs = jnp.asarray(rng.random((bh, c)) * 0.02 + 1e-3, jnp.float32)
    mask = jnp.asarray(rng.random((bh, c)) < density)
    mask = mask.at[:, 0].set(True)  # at least one valid
    q = jnp.asarray(rng.normal(size=(bh, g, hd)), jnp.float32)
    ref = sparse_flash_decode_ref(q, kc, ks, vc, vs, mask)
    out = sparse_flash_decode(q, kc, ks, vc, vs, mask, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bh,t,s,hd,causal,window", [
    (2, 256, 256, 64, True, 0), (1, 512, 512, 128, True, 128),
    (2, 128, 512, 64, False, 0), (1, 1024, 1024, 128, True, 0)])
def test_flash_prefill_sweep(rng, bh, t, s, hd, causal, window, dtype):
    q = jnp.asarray(rng.normal(size=(bh, t, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(bh, s, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(bh, s, hd)), dtype)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window)
    out = flash_attention(q, k, v, causal=causal, window=window, interpret=True)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


def test_flash_prefill_matches_xla_path(rng):
    """The chunked-scan XLA flash (runtime path) == kernel == naive ref."""
    from repro.models.attention import flash_attention_xla
    bh, t, hd = 2, 256, 64
    q = jnp.asarray(rng.normal(size=(1, t, bh, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, t, bh, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, t, bh, hd)), jnp.float32)
    xla = flash_attention_xla(q, k, v, causal=True, chunk=64)
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(bh, t, hd)
    ref = flash_attention_ref(fold(q), fold(k), fold(v), causal=True)
    np.testing.assert_allclose(np.asarray(fold(xla)), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_prefill_xla_pads_ragged_keys(rng, causal):
    """A key length the scan chunk does not divide (a 200-token prompt over
    64-key chunks) is padded with masked keys: same result as the naive
    reference, causal or not."""
    from repro.models.attention import flash_attention_xla
    bh, t, hd = 2, 200, 64
    q = jnp.asarray(rng.normal(size=(1, t, bh, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, t, bh, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, t, bh, hd)), jnp.float32)
    xla = flash_attention_xla(q, k, v, causal=causal, chunk=64)
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(bh, t, hd)
    ref = flash_attention_ref(fold(q), fold(k), fold(v), causal=causal)
    np.testing.assert_allclose(np.asarray(fold(xla)), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("bh,n,window,block", [
    (2, 1024, 7, 512), (1, 4096, 1, 4096), (3, 2048, 11, 1024), (2, 512, 3, 128)])
def test_selection_fused_sweep(rng, bh, n, window, block):
    from repro.kernels.selection_fused.kernel import fused_bin_pool_threshold_pallas
    from repro.kernels.selection_fused.ref import fused_bin_pool_threshold_ref
    scores = jnp.asarray(rng.normal(size=(bh, n)) * 4, jnp.float32)
    lengths = jnp.asarray(rng.integers(n // 2, n + 1, size=(bh,)), jnp.int32)
    pos = jnp.arange(n)[None, :]
    masked = jnp.where(pos < lengths[:, None], scores, jnp.inf)
    lo = jnp.min(jnp.where(jnp.isfinite(masked), masked, jnp.inf), axis=-1)
    hi = jnp.max(jnp.where(pos < lengths[:, None], scores, -jnp.inf), axis=-1)
    k = jnp.full((bh,), max(8, n // 16), jnp.int32)
    ref = fused_bin_pool_threshold_ref(scores, lo, hi, k, lengths, window=window)
    out = fused_bin_pool_threshold_pallas(scores, lo, hi, k, lengths,
                                          window=window, block_n=block,
                                          interpret=True)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(ref[0]))
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(ref[1]))
    np.testing.assert_array_equal(np.asarray(out[2]), np.asarray(ref[2]))
