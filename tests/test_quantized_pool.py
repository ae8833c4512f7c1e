"""Tiered KV memory: quantized block-pool storage + host spill of cold blocks.

Covers the acceptance criteria of the tiered-KV-memory change:

  * per-block symmetric quant/dequant helpers obey the half-step
    reconstruction bound and code-range contract; int4 nibble pack/unpack
    round-trips bit-exactly (hypothesis when available, plus a
    deterministic fallback);
  * `gather_selected_paged` over fp16/int8/int4 pools returns EXACTLY the
    pool's stored codes and scales for every selected position — i.e. the
    gather is bit-identical to a quantize-then-dequantize reference read
    straight off the storage buffers through the page table;
  * the pool primitives are mode-generic: scrambled vs contiguous
    same-mode pools attend identically, `cow_block` copies the packed
    buffers verbatim, shared-prefix reads match a single-owner flat
    reference, and per-shard (block_range) gathers compose to the flat
    gather;
  * host-spill lifecycle: demote → histogram resurrect → promote is
    bit-exact (greedy outputs identical to an all-hot engine), leaks no
    blocks, and survives both policy-driven spill and a prompt whose
    block footprint exceeds the whole device pool (wave admission).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (
    SalcaParams, cow_block, empty_paged_cache, gather_selected_paged,
    prefill_cache, prefill_into_pages, salca_decode_attention_paged,
    share_blocks)
from repro.core import quantization as qz
from repro.core.cache import _BLOCK_DATA_FIELDS
from repro.models import get_model
from repro.runtime.serve import Request, ServingEngine

try:
    from hypothesis import given, settings, strategies as hst
    HAVE_HYPOTHESIS = True
except ImportError:            # container without hypothesis: fallback only
    HAVE_HYPOTHESIS = False

CFG = get_config("qwen3-0.6b").reduced()
MAX_SEQ = 64
BS = 16
MB = MAX_SEQ // BS
MODES = ("int8", "fp16", "int4")

PARAMS = SalcaParams(feature_sparsity=0.5, k=16, k_cap=32, pool_window=7)


@pytest.fixture(scope="module")
def model_params():
    return get_model(CFG).init(jax.random.PRNGKey(0))


def _prompt(rng, n):
    return rng.integers(0, CFG.vocab_size, n).astype(np.int32)


# ---------------------------------------------------------------------------
# Quant/dequant helper invariants
# ---------------------------------------------------------------------------

def _check_roundtrip(x: np.ndarray, bits: int) -> None:
    """sym_quantize_axes invariants for a (BS, KV, HD) block: code range,
    per-(kv-head) shared scale shape, and the half-step error bound."""
    codes, scale = qz.sym_quantize_axes(jnp.asarray(x), bits, axes=(-3, -1))
    maxabs = (1 << (bits - 1)) - 1
    assert codes.dtype == jnp.int8
    assert int(jnp.max(jnp.abs(codes))) <= maxabs
    assert scale.shape == (1, x.shape[1], 1)
    y = np.asarray(qz.sym_dequantize_axes(codes, scale))
    bound = np.broadcast_to(np.asarray(scale) * 0.5 + 1e-7, x.shape)
    assert (np.abs(y - x) <= bound).all()
    if bits == 4:              # nibble packing round-trips bit-exactly
        packed = qz.pack_int4(codes)
        assert packed.shape[-1] == codes.shape[-1] // 2
        np.testing.assert_array_equal(np.asarray(qz.unpack_int4(packed)),
                                      np.asarray(codes))


@pytest.mark.parametrize("bits", [4, 8])
def test_sym_quantize_axes_roundtrip_deterministic(bits):
    master = np.random.default_rng(11)
    for scl in (1e-3, 1.0, 37.5):
        x = (master.normal(size=(BS, 2, 32)) * scl).astype(np.float32)
        _check_roundtrip(x, bits)
    _check_roundtrip(np.zeros((BS, 2, 32), np.float32), bits)   # all-zero block


def test_pack_int4_full_code_range():
    codes = jnp.asarray(np.tile(np.arange(-7, 8, dtype=np.int8), 16)[: 16 * 14]
                        .reshape(16, 14))
    np.testing.assert_array_equal(
        np.asarray(qz.unpack_int4(qz.pack_int4(codes))), np.asarray(codes))


if HAVE_HYPOTHESIS:
    @settings(max_examples=100, derandomize=True, deadline=None)
    @given(seed=hst.integers(0, 2**31 - 1), bits=hst.sampled_from([4, 8]),
           scale_exp=hst.integers(-6, 6))
    def test_sym_quantize_axes_roundtrip_hypothesis(seed, bits, scale_exp):
        rng = np.random.default_rng(seed)
        x = (rng.normal(size=(BS, 2, 32)) * 10.0 ** scale_exp)
        _check_roundtrip(x.astype(np.float32), bits)


# ---------------------------------------------------------------------------
# Gather == storage reference, bit-exactly, all three modes
# ---------------------------------------------------------------------------

def _mode_pool(rng, dt, t=40, slots=3, slot=1, num_blocks=20,
               pages3=(13, 2, 7)):
    """Contiguous int8 prefill transcoded into a `dt`-mode pool over
    scrambled physical blocks. Returns (dense_src, pool, pages)."""
    k = jnp.asarray(rng.normal(size=(1, t, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, t, 2, 32)), jnp.float32)
    dense = prefill_cache(k, v, max_seq=MAX_SEQ, params=PARAMS)
    pool = empty_paged_cache(num_blocks, BS, slots, MB, kv_heads=2,
                             head_dim=32, r=16, kv_pool_dtype=dt)
    need = -(-t // BS)
    pages = np.full(MB, -1, np.int32)
    pages[:need] = list(pages3)[:need]
    pool = prefill_into_pages(pool, dense, slot, jnp.asarray(pages))
    return dense, pool, pages


def _storage_row(pool, dt, pg, off, h):
    """(k_codes, k_scale, v_codes, v_scale) for one token, read straight off
    the pool buffers — per-token scales for int8, the block's scale row 0
    for fp16/int4, nibble-unpacked codes for int4."""
    soff = off if dt == "int8" else 0
    kc = np.asarray(pool.k_codes)[pg, h, off]
    vc = np.asarray(pool.v_codes)[pg, h, off]
    if dt == "int4":
        kc = np.asarray(qz.unpack_int4(jnp.asarray(kc)))
        vc = np.asarray(qz.unpack_int4(jnp.asarray(vc)))
    return (kc, np.asarray(pool.k_scale)[pg, h, soff],
            vc, np.asarray(pool.v_scale)[pg, h, soff])


@pytest.mark.parametrize("dt", MODES)
def test_gather_matches_storage_reference(rng, dt):
    _, pool, _ = _mode_pool(rng, dt)
    q3 = jnp.asarray(rng.normal(size=(3, 4, 32)), jnp.float32)
    _, sel = salca_decode_attention_paged(q3, pool, PARAMS,
                                          return_selection=True)
    kc, ks, vc, vs = (np.asarray(a) for a in
                      gather_selected_paged(pool, sel))
    assert kc.shape[-1] == 32      # int4 unpacks back to full head_dim
    idx, msk = np.asarray(sel.indices), np.asarray(sel.mask)
    table = np.asarray(pool.page_table)
    checked = 0
    for s, h, c in np.argwhere(msk):
        pg, off = table[s, idx[s, h, c] // BS], idx[s, h, c] % BS
        assert pg >= 0
        rkc, rks, rvc, rvs = _storage_row(pool, dt, pg, off, h)
        np.testing.assert_array_equal(kc[s, h, c], rkc)
        np.testing.assert_array_equal(vc[s, h, c], rvc)
        assert ks[s, h, c] == rks and vs[s, h, c] == rvs
        checked += 1
    assert checked > 0             # the selection actually picked tokens


@pytest.mark.parametrize("dt", ("fp16", "int4"))
def test_scrambled_pages_invisible_per_mode(rng, dt):
    """Same request through contiguous and scrambled physical blocks of two
    same-mode pools: identical selection, identical attention output."""
    k = jnp.asarray(rng.normal(size=(1, 40, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 40, 2, 32)), jnp.float32)
    dense = prefill_cache(k, v, max_seq=MAX_SEQ, params=PARAMS)
    pools = []
    for pages3 in ((0, 1, 2), (13, 2, 7)):
        pool = empty_paged_cache(20, BS, 3, MB, kv_heads=2, head_dim=32,
                                 r=16, kv_pool_dtype=dt)
        pages = np.full(MB, -1, np.int32)
        pages[:3] = pages3
        pools.append(prefill_into_pages(pool, dense, 1, jnp.asarray(pages)))
    q3 = jnp.asarray(rng.normal(size=(3, 4, 32)), jnp.float32)
    o_a, sel_a = salca_decode_attention_paged(q3, pools[0], PARAMS,
                                              return_selection=True)
    o_b, sel_b = salca_decode_attention_paged(q3, pools[1], PARAMS,
                                              return_selection=True)
    np.testing.assert_array_equal(np.asarray(sel_a.indices[1]),
                                  np.asarray(sel_b.indices[1]))
    np.testing.assert_allclose(np.asarray(o_a[1]), np.asarray(o_b[1]),
                               rtol=1e-5, atol=1e-6)


def test_f16_bit_decode_exact_for_every_pattern():
    """The flash-decode kernel's in-VMEM fp16 decode (codes arrive as int16
    bit patterns) equals the f16 → f32 conversion for all 65536 patterns:
    zeros, subnormals, normals, infinities; NaNs stay NaN."""
    from repro.kernels.flash_decode.kernel import _f16_bits_to_f32
    bits = np.arange(-32768, 32768, dtype=np.int16)
    want = bits.view(np.float16).astype(np.float32)
    got = np.asarray(_f16_bits_to_f32(jnp.asarray(bits)))
    nan = np.isnan(want)
    np.testing.assert_array_equal(np.isnan(got), nan)
    np.testing.assert_array_equal(got[~nan].view(np.uint32),
                                  want[~nan].view(np.uint32))


@pytest.mark.parametrize("dt", MODES)
def test_paged_flash_kernel_matches_ref_per_mode(rng, dt):
    """The paged flash-decode kernel (Pallas, interpret) against its blocked
    XLA oracle on the same selection, in every pool mode."""
    from repro.kernels.flash_decode.ops import sparse_flash_decode_paged
    _, pool, _ = _mode_pool(rng, dt)
    q3 = jnp.asarray(rng.normal(size=(3, 4, 32)), jnp.float32)
    _, sel = salca_decode_attention_paged(q3, pool, PARAMS,
                                          return_selection=True)
    assert int(sel.count.sum()) > 0
    o_p = sparse_flash_decode_paged(q3, pool, sel, impl="pallas",
                                    interpret=True)
    o_r = sparse_flash_decode_paged(q3, pool, sel, impl="ref")
    np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_r),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# CoW / prefix sharing / shard-local gather are mode-generic
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dt", MODES)
def test_cow_copies_mode_buffers_verbatim(rng, dt):
    """`cow_block` on a shared block of a fp16/int4 pool copies every packed
    data field bit-exactly (no transcode on the private copy)."""
    _, pool, pages = _mode_pool(rng, dt)
    pool = share_blocks(pool, 1, 2, 0)          # slot 0 aliases blocks 13, 2
    assert int(pool.refcount[pages[1]]) == 2
    cowed = cow_block(pool, 0, 1, 5)            # privatize logical block 1
    for f in _BLOCK_DATA_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(cowed, f)[5]),
                                      np.asarray(getattr(pool, f)[pages[1]]))
    assert int(cowed.page_table[0, 1]) == 5
    assert int(cowed.refcount[5]) == 1 and int(cowed.refcount[pages[1]]) == 1


@pytest.mark.parametrize("dt", ("fp16", "int4"))
def test_shared_prefix_reads_match_flat_per_mode(rng, dt):
    """A sharer aliasing two prefix blocks of a fp16/int4 pool reads them
    exactly like a single-owner pool prefilled from the same source."""
    t = 40
    k = jnp.asarray(rng.normal(size=(1, t, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, t, 2, 32)), jnp.float32)
    dense = prefill_cache(k, v, max_seq=MAX_SEQ, params=PARAMS)
    pool = empty_paged_cache(20, BS, 3, MB, kv_heads=2, head_dim=32,
                             r=16, kv_pool_dtype=dt)
    pages = np.full(MB, -1, np.int32)
    pages[:3] = [13, 2, 7]
    pool = prefill_into_pages(pool, dense, 1, jnp.asarray(pages))
    pool = share_blocks(pool, 1, 2, 0)          # slot 0: first 32 tokens
    # Single-owner reference: the shared 32 tokens, encoded with the donor's
    # heavy-channel set (what the shared feature blocks hold) and transcoded
    # into a second same-mode pool.
    ref = prefill_cache(k[:, :32], v[:, :32], max_seq=MAX_SEQ, params=PARAMS,
                        heavy_idx=dense.heavy_idx)
    solo = empty_paged_cache(20, BS, 3, MB, kv_heads=2, head_dim=32,
                             r=16, kv_pool_dtype=dt)
    pages0 = np.full(MB, -1, np.int32)
    pages0[:2] = [4, 9]
    solo = prefill_into_pages(solo, ref, 0, jnp.asarray(pages0))
    q = jnp.asarray(rng.normal(size=(3, 4, 32)), jnp.float32)
    o_sh, sel_sh = salca_decode_attention_paged(q, pool, PARAMS,
                                                return_selection=True)
    o_so, sel_so = salca_decode_attention_paged(q, solo, PARAMS,
                                                return_selection=True)
    np.testing.assert_array_equal(np.asarray(sel_sh.indices[0]),
                                  np.asarray(sel_so.indices[0]))
    np.testing.assert_allclose(np.asarray(o_sh[0]), np.asarray(o_so[0]),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("dt", MODES)
def test_shard_local_gather_composes(rng, dt):
    """Per-shard gathers (sliced local data + block_range) reproduce the
    flat gather row-for-row on the blocks each shard owns, with every
    selected position owned by exactly one shard — for every pool mode."""
    _, pool, _ = _mode_pool(rng, dt)
    q3 = jnp.asarray(rng.normal(size=(3, 4, 32)), jnp.float32)
    _, sel = salca_decode_attention_paged(q3, pool, PARAMS,
                                          return_selection=True)
    flat = tuple(np.asarray(a) for a in gather_selected_paged(pool, sel))
    idx, msk = np.asarray(sel.indices), np.asarray(sel.mask)
    table = np.asarray(pool.page_table)
    pg_global = np.take_along_axis(
        np.broadcast_to(table[:, None, :], (3, 2, MB)),
        idx // BS, axis=-1)                              # (S, KV, C)
    owners = np.zeros_like(idx)
    for lo, hi in ((0, 10), (10, 20)):
        local = pool._replace(**{f: getattr(pool, f)[lo:hi]
                                 for f in _BLOCK_DATA_FIELDS})
        part = tuple(np.asarray(a) for a in
                     gather_selected_paged(local, sel, block_range=(lo, hi)))
        owned = msk & (pg_global >= lo) & (pg_global < hi)
        owners += owned.astype(idx.dtype)
        for s, h, c in np.argwhere(owned):
            for fl, pt in zip(flat, part):
                np.testing.assert_array_equal(pt[s, h, c], fl[s, h, c])
    np.testing.assert_array_equal(owners[msk], 1)        # exactly one owner


# ---------------------------------------------------------------------------
# Host-spill lifecycle (engine level)
# ---------------------------------------------------------------------------

def test_spill_engine_validation(model_params):
    with pytest.raises(ValueError):              # host tier needs a block pool
        ServingEngine(CFG, model_params, max_seq=MAX_SEQ, slots=1,
                      host_spill=True)
    with pytest.raises(ValueError):              # precision knob names the pool
        ServingEngine(CFG, model_params, max_seq=MAX_SEQ, slots=1,
                      kv_pool_dtype="fp16")
    # host_spill × prefix_sharing is SUPPORTED since the persistent-cache
    # PR: radix-published blocks are skipped by demotion while resident and
    # may demote to the cache's host tier once unowned.
    eng = ServingEngine(CFG, model_params, max_seq=MAX_SEQ, slots=1,
                        paged=True, block_size=BS, prefix_sharing=True,
                        host_spill=True)
    assert eng.prefix_sharing and eng.host_spill
    with pytest.raises(ValueError):              # cursor block must stay hot
        ServingEngine(CFG, model_params, max_seq=MAX_SEQ, slots=1, paged=True,
                      block_size=BS, host_spill=True, spill_keep_recent=0)


@pytest.mark.slow
def test_demote_resurrect_promote_roundtrip(model_params, rng):
    """Mid-decode demotion of a selected block: the histogram-scored
    promotion pass resurrects it before the next tick, greedy outputs stay
    bit-identical to an all-hot engine, and nothing leaks."""
    prompt = _prompt(rng, 40)
    hot = ServingEngine(CFG, model_params, max_seq=MAX_SEQ, slots=1,
                        paged=True, block_size=BS, num_blocks=6)
    r_hot = Request(rid=0, prompt=prompt.copy(), max_new_tokens=8)
    hot.submit(r_hot)
    hot.run()

    eng = ServingEngine(CFG, model_params, max_seq=MAX_SEQ, slots=1,
                        paged=True, block_size=BS, num_blocks=6,
                        host_spill=True, demote_after=10**6,
                        spill_keep_recent=2)
    req = Request(rid=0, prompt=prompt.copy(), max_new_tokens=8)
    eng.submit(req)
    eng._admit()
    eng._tick(), eng._tick()
    eng.demote_block(0, 0)                       # oldest block → host tier
    assert eng._slot_blocks[0][0] == -1 and len(eng._spilled) == 1
    assert eng.stats.cold_blocks == 1
    eng.run()

    assert req.stop_reason == "length" and req.output == r_hot.output
    assert eng.stats.demotions == 1 and eng.stats.promotions == 1
    assert eng.stats.pcie_bytes == 2 * eng._block_bytes
    assert eng.stats.peak_cold_blocks == 1
    assert not eng._spilled and not eng._spill_score
    assert eng._alloc.total_free == 6            # no leaked blocks
    assert int(np.asarray(eng._refcount).sum()) == 0


@pytest.mark.slow
def test_spill_policy_demotes_and_completes(model_params, rng):
    """Policy-driven spill: a block whose selection histogram stops moving
    demotes after `demote_after` ticks, requests still complete with a
    `length` stop, blocks move both ways, and the pool drains. At test
    scale `salca_params_for` floors k at 128 ≥ max_seq, so the selection
    touches every block every tick — the histogram reader is stubbed to
    report block 0 unselected, the signal a long-context filter produces."""
    eng = ServingEngine(CFG, model_params, max_seq=MAX_SEQ, slots=2,
                        paged=True, block_size=BS, num_blocks=8,
                        host_spill=True, demote_after=1, spill_keep_recent=1)
    real_hist = eng._sel_hist_fn
    def cold_block0(state):
        h = np.asarray(real_hist(state)).copy()
        h[:, 0] = 0
        return h
    eng._sel_hist_fn = cold_block0
    reqs = [Request(rid=i, prompt=_prompt(rng, 40), max_new_tokens=6)
            for i in range(2)]
    for r in reqs:
        eng.submit(r)
    stats = eng.run()
    assert stats.completed == 2 and stats.overflows == 0
    assert all(r.stop_reason == "length" and len(r.output) == 6 for r in reqs)
    assert stats.demotions >= 1 and stats.peak_cold_blocks >= 1
    assert stats.pcie_bytes == \
        (stats.demotions + stats.promotions) * eng._block_bytes
    assert not eng._spilled and eng._alloc.total_free == 8
    s = stats.summary()
    assert s["demotions"] == stats.demotions


@pytest.mark.slow
def test_wave_admission_prompt_exceeds_pool(model_params, rng):
    """A prompt whose block footprint exceeds the ENTIRE device pool admits
    via spill waves and decodes to completion — the device tier holds only
    a sliding window of hot blocks."""
    eng = ServingEngine(CFG, model_params, max_seq=128, slots=1, paged=True,
                        block_size=BS, num_blocks=4, host_spill=True,
                        demote_after=10**6, spill_keep_recent=2)
    req = Request(rid=0, prompt=_prompt(rng, 100), max_new_tokens=4)
    eng.submit(req)                              # 7 blocks > 4-block pool
    stats = eng.run()
    assert req.stop_reason == "length" and len(req.output) == 4
    assert stats.overflows == 0
    assert stats.demotions >= 3                  # at least the overshoot
    assert not eng._spilled and eng._alloc.total_free == 4
    assert int(np.asarray(eng._refcount).sum()) == 0
