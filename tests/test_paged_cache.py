"""Paged block-pool KV cache: block-granular storage + page-table resolution.

Covers the acceptance criteria of the paged-cache refactor:
  * blocked selection primitives (halo maxpool, additive per-block histogram)
    are bit-identical to their flat forms;
  * `prefill_into_pages` / `append_token_paged` / `map_block` / `free_pages`
    round-trip a request through scrambled physical blocks;
  * paged decode attention matches the contiguous `SalcaCache` path (fp32
    tolerance) at the core, kernel-wrapper, and model level — including
    slots reusing physical blocks freed by completed requests;
  * the paged serving engine admits mixed-length requests that a dense pool
    of the same HBM budget cannot hold concurrently, and surfaces block
    exhaustion as an `overflow` stop instead of clipping silently.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (
    SalcaParams, append_token, append_token_paged, empty_paged_cache,
    free_pages, histogram_topk, histogram_topk_blocked, map_block,
    maxpool1d_blocked, maxpool1d_reuse, paged_cache_bytes, prefill_cache,
    prefill_into_pages, salca_decode_attention, salca_decode_attention_paged,
    select_sparse_pattern, select_sparse_pattern_blocked, share_blocks)
from repro.models import get_model
from repro.runtime.serve import Request, ServingEngine

CFG = get_config("qwen3-0.6b").reduced()
MAX_SEQ = 64
BS = 16
MB = MAX_SEQ // BS

PARAMS = SalcaParams(feature_sparsity=0.5, k=16, k_cap=32, pool_window=7)


@pytest.fixture(scope="module")
def api():
    return get_model(CFG)


@pytest.fixture(scope="module")
def params(api):
    return api.init(jax.random.PRNGKey(0))


def _prompt(rng, n):
    return rng.integers(0, CFG.vocab_size, n).astype(np.int32)


# ---------------------------------------------------------------------------
# Blocked selection primitives == flat forms
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [3, 5, 7])
def test_maxpool_blocked_matches_flat(rng, window):
    x = jnp.asarray(rng.integers(0, 256, (2, 3, 4, 16)), jnp.uint8)
    blocked = maxpool1d_blocked(x, window)
    flat = maxpool1d_reuse(x.reshape(2, 3, 64), window).reshape(x.shape)
    np.testing.assert_array_equal(np.asarray(blocked), np.asarray(flat))


def test_histogram_topk_blocked_matches_flat(rng):
    bins = jnp.asarray(rng.integers(0, 256, (2, 2, 4, 16)), jnp.uint8)
    flat_sel = histogram_topk(bins.reshape(2, 2, 64), 10, 16)
    blk_sel = histogram_topk_blocked(bins, 10, 16)
    for a, b in zip(flat_sel, blk_sel):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("k", [0, 64, 200])     # k=0, k == n, k > n
def test_histogram_topk_blocked_edge_k(rng, k):
    """Degenerate targets (nothing / everything requested) stay bit-identical
    between the additive per-block merge and the flat histogram."""
    bins = jnp.asarray(rng.integers(0, 256, (2, 2, 4, 16)), jnp.uint8)
    flat_sel = histogram_topk(bins.reshape(2, 2, 64), k, 64)
    blk_sel = histogram_topk_blocked(bins, k, 64)
    for a, b in zip(flat_sel, blk_sel):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_histogram_topk_blocked_all_equal_scores(rng):
    """All-equal scores: the threshold ties on every element; blocked and
    flat must tie-break identically (they share the compaction)."""
    bins = jnp.full((2, 2, 4, 16), 113, jnp.uint8)
    flat_sel = histogram_topk(bins.reshape(2, 2, 64), 10, 16)
    blk_sel = histogram_topk_blocked(bins, 10, 16)
    for a, b in zip(flat_sel, blk_sel):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("k,k_cap", [(0, 16), (64, 64), (200, 64)])
def test_select_sparse_pattern_blocked_edge_k(rng, k, k_cap):
    scores = jnp.asarray(rng.normal(size=(2, 2, 64)), jnp.float32)
    valid = jnp.asarray(rng.integers(0, 2, (2, 1, 64)), bool)
    p = SalcaParams(feature_sparsity=0.5, k=k, k_cap=k_cap, pool_window=7)
    flat_sel = select_sparse_pattern(scores, p, valid)
    blk_sel = select_sparse_pattern_blocked(scores, p, valid, block_size=16)
    for a, b in zip(flat_sel, blk_sel):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_select_sparse_pattern_blocked_all_equal(rng):
    scores = jnp.full((2, 2, 64), 0.25, jnp.float32)
    valid = jnp.ones((2, 1, 64), bool)
    p = SalcaParams(feature_sparsity=0.5, k=10, k_cap=16, pool_window=7)
    flat_sel = select_sparse_pattern(scores, p, valid)
    blk_sel = select_sparse_pattern_blocked(scores, p, valid, block_size=16)
    for a, b in zip(flat_sel, blk_sel):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Pool primitives (cache level)
# ---------------------------------------------------------------------------

def _scrambled_pool(rng, t=40, slots=3, slot=1, num_blocks=20):
    """Contiguous prefill + the same request scattered over scrambled
    physical blocks of a paged pool. Returns (dense, pool, pages)."""
    k = jnp.asarray(rng.normal(size=(1, t, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, t, 2, 32)), jnp.float32)
    dense = prefill_cache(k, v, max_seq=MAX_SEQ, params=PARAMS)
    pool = empty_paged_cache(num_blocks, BS, slots, MB, kv_heads=2,
                             head_dim=32, r=16)
    need = -(-t // BS)
    pages = np.full(MB, -1, np.int32)
    pages[:need] = [13, 2, 7, 11][:need]
    pool = prefill_into_pages(pool, dense, slot, jnp.asarray(pages))
    return dense, pool, pages


def test_prefill_into_pages_and_free(rng):
    t = 40
    dense, pool, pages = _scrambled_pool(rng, t=t)
    assert int(pool.length[1]) == t
    assert int(pool.length[0]) == 0 and int(pool.length[2]) == 0
    np.testing.assert_array_equal(np.asarray(pool.page_table[1]), pages)
    assert int(pool.page_table[0, 0]) == -1
    # block contents: logical block j lives at physical row pages[j]
    for j in range(-(-t // BS)):
        np.testing.assert_array_equal(
            np.asarray(pool.k_codes[pages[j]]).swapaxes(0, 1)[: min(BS, t - j * BS)],
            np.asarray(dense.k_codes[0, j * BS: min((j + 1) * BS, t)]))
    b = paged_cache_bytes(pool)
    assert b["total"] == b["kv_region"] + b["feature_region"] + b["page_table"]
    freed = free_pages(pool, 1)
    assert int(freed.length[1]) == 0
    assert int(freed.page_table[1, 0]) == -1
    assert int(freed.valid_mask().sum()) == 0


def test_prefill_into_pages_validates(rng):
    pool = empty_paged_cache(8, BS, 2, MB, kv_heads=2, head_dim=32, r=16)
    k = jnp.asarray(rng.normal(size=(1, 8, 2, 32)), jnp.float32)
    big = prefill_cache(k, k, max_seq=2 * MAX_SEQ, params=PARAMS)
    with pytest.raises(ValueError):
        prefill_into_pages(pool, big, 0, jnp.zeros((MB,), jnp.int32))


def test_append_token_paged_boundary_and_drop(rng):
    """Appends resolve through the page table across block boundaries;
    unmapped slots / exhausted capacity drop the write without advancing
    the cursor (no silent clip)."""
    dense, pool, _ = _scrambled_pool(rng, t=40)
    kd, pp = dense, pool
    fresh = [17, 18, 19]
    for _ in range(10):                      # crosses the 40→48 boundary
        kt = jnp.asarray(rng.normal(size=(1, 2, 32)), jnp.float32)
        vt = jnp.asarray(rng.normal(size=(1, 2, 32)), jnp.float32)
        kd = append_token(kd, kt, vt)
        k3 = jnp.zeros((3, 2, 32), jnp.float32).at[1].set(kt[0])
        v3 = jnp.zeros((3, 2, 32), jnp.float32).at[1].set(vt[0])
        cur = int(pp.length[1])
        if cur % BS == 0 and int(pp.page_table[1, cur // BS]) < 0:
            pp = map_block(pp, 1, cur // BS, fresh.pop(0))
        pp = append_token_paged(pp, k3, v3)
    assert int(pp.length[1]) == 50
    assert int(pp.length[0]) == 0            # unmapped slot: write dropped
    q = jnp.asarray(rng.normal(size=(1, 4, 32)), jnp.float32)
    q3 = jnp.zeros((3, 4, 32), jnp.float32).at[1].set(q[0])
    o_dense = salca_decode_attention(q, kd, PARAMS)
    o_paged = salca_decode_attention_paged(q3, pp, PARAMS)
    np.testing.assert_allclose(np.asarray(o_paged[1]), np.asarray(o_dense[0]),
                               rtol=1e-5, atol=1e-6)


def test_paged_attention_parity_scrambled_pages(rng):
    dense, pool, _ = _scrambled_pool(rng, t=40)
    q = jnp.asarray(rng.normal(size=(1, 4, 32)), jnp.float32)
    q3 = jnp.zeros((3, 4, 32), jnp.float32).at[1].set(q[0])
    o_dense, sel_d = salca_decode_attention(q, dense, PARAMS,
                                            return_selection=True)
    o_paged, sel_p = salca_decode_attention_paged(q3, pool, PARAMS,
                                                  return_selection=True)
    # identical selection (logical indices) and attention output
    np.testing.assert_array_equal(np.asarray(sel_p.indices[1]),
                                  np.asarray(sel_d.indices[0]))
    np.testing.assert_allclose(np.asarray(o_paged[1]), np.asarray(o_dense[0]),
                               rtol=1e-5, atol=1e-6)


def test_shared_prefix_block_selection_matches_flat(rng):
    """A prefix block referenced by multiple slots: blocked selection and
    paged attention for BOTH the sharer and the donor are bit-identical /
    fp32-close to their flat single-owner forms — sharing is invisible to
    the read path."""
    t = 40
    k = jnp.asarray(rng.normal(size=(1, t, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, t, 2, 32)), jnp.float32)
    dense = prefill_cache(k, v, max_seq=MAX_SEQ, params=PARAMS)
    pool = empty_paged_cache(20, BS, 3, MB, kv_heads=2, head_dim=32, r=16)
    pages = np.full(MB, -1, np.int32)
    pages[:3] = [13, 2, 7]
    pool = prefill_into_pages(pool, dense, 1, jnp.asarray(pages))
    pool = share_blocks(pool, 1, 2, 0)      # slot 0 aliases blocks 13 and 2
    assert int(pool.refcount[13]) == 2 and int(pool.refcount[2]) == 2
    # Flat reference for the sharer: the first 32 tokens, encoded with the
    # donor's heavy-channel set (what the shared feature blocks hold).
    ref = prefill_cache(k[:, :32], v[:, :32], max_seq=MAX_SEQ, params=PARAMS,
                        heavy_idx=dense.heavy_idx)
    q = jnp.asarray(rng.normal(size=(1, 4, 32)), jnp.float32)
    q3 = jnp.zeros((3, 4, 32), jnp.float32).at[0].set(q[0]).at[1].set(q[0])
    o_flat, sel_f = salca_decode_attention(q, ref, PARAMS,
                                           return_selection=True)
    o_paged, sel_p = salca_decode_attention_paged(q3, pool, PARAMS,
                                                  return_selection=True)
    np.testing.assert_array_equal(np.asarray(sel_p.indices[0]),
                                  np.asarray(sel_f.indices[0]))
    np.testing.assert_allclose(np.asarray(o_paged[0]), np.asarray(o_flat[0]),
                               rtol=1e-5, atol=1e-6)
    o_d, sel_d = salca_decode_attention(q, dense, PARAMS,
                                        return_selection=True)
    np.testing.assert_array_equal(np.asarray(sel_p.indices[1]),
                                  np.asarray(sel_d.indices[0]))
    np.testing.assert_allclose(np.asarray(o_paged[1]), np.asarray(o_d[0]),
                               rtol=1e-5, atol=1e-6)


def test_flash_decode_paged_wrapper(rng):
    from repro.kernels.flash_decode.ops import sparse_flash_decode_paged
    dense, pool, _ = _scrambled_pool(rng, t=40)
    q3 = jnp.asarray(rng.normal(size=(3, 4, 32)), jnp.float32)
    _, sel = salca_decode_attention_paged(q3, pool, PARAMS,
                                          return_selection=True)
    out = sparse_flash_decode_paged(q3, pool, sel, impl="ref")
    ref = salca_decode_attention_paged(q3, pool, PARAMS)
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(ref[1]),
                               rtol=1e-5, atol=1e-6)


def test_paged_ops_jit_safe(rng):
    """Traced slot / pages / block args compile once and match eager."""
    dense, _, _ = _scrambled_pool(rng, t=40)
    pool = empty_paged_cache(20, BS, 3, MB, kv_heads=2, head_dim=32, r=16)
    pages = jnp.asarray(np.array([5, 9, 1] + [-1] * (MB - 3), np.int32))
    p1 = jax.jit(prefill_into_pages)(pool, dense, jnp.int32(2), pages)
    assert int(p1.length[2]) == 40
    p2 = jax.jit(map_block)(p1, jnp.int32(2), jnp.int32(3), jnp.int32(15))
    assert int(p2.page_table[2, 3]) == 15
    p3 = jax.jit(free_pages)(p2, jnp.int32(2))
    assert int(p3.length[2]) == 0 and int(p3.page_table[2, 0]) == -1


# ---------------------------------------------------------------------------
# Model-level parity (paged pool vs dense slot pool)
# ---------------------------------------------------------------------------

def test_paged_decode_matches_dense_pool(api, params, rng):
    """Per-slot logits from the paged pool match the contiguous SalcaCache
    slot pool within fp32 tolerance, with scrambled non-contiguous pages."""
    pa, pb = _prompt(rng, 12), _prompt(rng, 20)
    _, sa = api.prefill(params, {"tokens": jnp.asarray(pa[None])}, MAX_SEQ)
    _, sb = api.prefill(params, {"tokens": jnp.asarray(pb[None])}, MAX_SEQ)
    pool_d = api.init_state(3, MAX_SEQ)
    pool_d = api.write_into_slot(pool_d, sa, 1)
    pool_d = api.write_into_slot(pool_d, sb, 2)
    pool_p = api.init_paged_state(3, MAX_SEQ, BS, num_blocks=10)
    pg_a = np.full(MB, -1, np.int32); pg_a[:1] = [7]
    pg_b = np.full(MB, -1, np.int32); pg_b[:2] = [3, 1]
    pool_p = api.write_into_pages(pool_p, sa, 1, jnp.asarray(pg_a))
    pool_p = api.write_into_pages(pool_p, sb, 2, jnp.asarray(pg_b))
    active = jnp.asarray([False, True, True])
    for t in (7, 11, 2):
        tok = jnp.asarray([0, t, 9], jnp.int32)
        ld, pool_d = api.decode_step(params, pool_d, tok, None, active=active)
        lp, pool_p = api.decode_step(params, pool_p, tok, None, active=active)
        np.testing.assert_allclose(np.asarray(lp[1]), np.asarray(ld[1]),
                                   rtol=2e-3, atol=2e-4)
        np.testing.assert_allclose(np.asarray(lp[2]), np.asarray(ld[2]),
                                   rtol=2e-3, atol=2e-4)
    assert int(pool_p.pos[1]) == 15 and int(pool_p.pos[2]) == 23
    assert int(pool_p.pos[0]) == 0           # inactive slot held


# ---------------------------------------------------------------------------
# Paged serving engine
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_engine_paged_parity_and_block_reuse(params, rng):
    """Same requests through dense and paged engines produce identical
    greedy outputs — including a second wave that reuses physical blocks
    freed by the first (the stale-data-behind-valid-mask contract)."""
    prompts = [_prompt(rng, n) for n in (12, 30, 12, 20)]
    e_d = ServingEngine(CFG, params, max_seq=MAX_SEQ, slots=2)
    e_p = ServingEngine(CFG, params, max_seq=MAX_SEQ, slots=2, paged=True,
                        block_size=BS, num_blocks=8)
    rd = [Request(rid=i, prompt=p.copy(), max_new_tokens=5)
          for i, p in enumerate(prompts)]
    rp = [Request(rid=i, prompt=p.copy(), max_new_tokens=5)
          for i, p in enumerate(prompts)]
    for r in rd:
        e_d.submit(r)
    for r in rp:
        e_p.submit(r)
    sd, sp = e_d.run(), e_p.run()
    assert sd.completed == sp.completed == 4
    for a, b in zip(rd, rp):
        assert a.output == b.output
    # second wave: every block has been freed and is reused
    assert sorted(e_p._free_blocks) == list(range(8))
    p2 = _prompt(rng, 25)
    r2d = Request(rid=9, prompt=p2.copy(), max_new_tokens=4)
    r2p = Request(rid=9, prompt=p2.copy(), max_new_tokens=4)
    e_d.submit(r2d)
    e_p.submit(r2p)
    e_d.run(), e_p.run()
    assert r2d.output == r2p.output
    assert sp.block_pool_size == 8 and sp.peak_blocks_in_use <= 8
    assert sp.summary()["block_utilization"] <= 1.0


@pytest.mark.slow
def test_engine_paged_overflow_stop_reason(params, rng):
    """Block exhaustion finishes the request with an `overflow` stop reason
    and counts the dropped write — no silent clip."""
    engine = ServingEngine(CFG, params, max_seq=MAX_SEQ, slots=2, paged=True,
                           block_size=BS, num_blocks=3)
    # Each fits the pool alone (lifetime ≤ 3 resp. 2 blocks) — only their
    # *contention* starves the free list.
    ra = Request(rid=0, prompt=_prompt(rng, 30), max_new_tokens=18)
    rb = Request(rid=1, prompt=_prompt(rng, 14), max_new_tokens=18)
    engine.submit(ra)
    engine.submit(rb)
    stats = engine.run()
    assert stats.completed == 2
    assert stats.overflows >= 1 and stats.dropped_writes == stats.overflows
    assert "overflow" in (ra.stop_reason, rb.stop_reason)
    overflowed = ra if ra.stop_reason == "overflow" else rb
    assert overflowed.stats()["stop_reason"] == "overflow"
    assert len(overflowed.output) < 18
    # freed blocks all returned
    assert sorted(engine._free_blocks) == list(range(3))


@pytest.mark.slow
def test_engine_paged_admits_more_mixed_requests(params, rng):
    """At a fixed token budget, the paged pool admits strictly more mixed-
    length requests concurrently than dense per-slot stripes (acceptance
    criterion for the block-pool refactor)."""
    budget = 2 * MAX_SEQ                     # dense: 2 slots × max_seq
    e_d = ServingEngine(CFG, params, max_seq=MAX_SEQ, slots=2)
    e_p = ServingEngine(CFG, params, max_seq=MAX_SEQ, slots=6, paged=True,
                        block_size=BS, num_blocks=budget // BS)
    for i in range(5):                       # five 1-block shorts
        e_d.submit(Request(rid=i, prompt=_prompt(rng, 12), max_new_tokens=3))
        e_p.submit(Request(rid=i, prompt=_prompt(rng, 12), max_new_tokens=3))
    sd, sp = e_d.run(), e_p.run()
    assert sd.completed == sp.completed == 5
    assert sd.peak_active_slots == 2         # capped by dense stripes
    assert sp.peak_active_slots == 5         # packed into the block pool
    assert sp.peak_blocks_in_use <= budget // BS


def test_engine_paged_validation(params):
    with pytest.raises(ValueError):          # block_size must divide max_seq
        ServingEngine(CFG, params, max_seq=MAX_SEQ, slots=2, paged=True,
                      block_size=24)
    engine = ServingEngine(CFG, params, max_seq=MAX_SEQ, slots=2, paged=True,
                           block_size=BS, num_blocks=2)
    with pytest.raises(ValueError):          # prompt alone exceeds the pool
        engine.submit(Request(rid=0, prompt=np.zeros(40, np.int32),
                              max_new_tokens=2))
    with pytest.raises(ValueError):          # lifetime (prompt+new-1) does too
        engine.submit(Request(rid=1, prompt=np.zeros(20, np.int32),
                              max_new_tokens=14))
