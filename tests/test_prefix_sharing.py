"""Prefix-sharing copy-on-write paged KV cache: refcounts, CoW, parity.

Covers the acceptance criteria of the prefix-sharing refactor:

  * cache-level semantics: `share_blocks` aliases physical blocks with
    refcounts, `append_token_paged` treats a shared-block write as a CoW
    fault (dropped, cursor held), `cow_block` copies all seven fields and
    remaps only the writer, `free_pages` is decref-based and double-free
    safe;
  * property suite (hypothesis when available, plus a deterministic
    fallback): random admit/share/decode/finish interleavings preserve the
    refcount invariants — every block's refcount equals the number of
    page-table entries referencing it, free list ∩ mapped = ∅, and no block
    leaks once every request finished;
  * engine parity: N requests sharing a prefix produce bit-identical greedy
    outputs to the same N requests run unshared (paged and dense pools,
    sparse and dense-oracle attention), including CoW triggering mid-decode
    on the first divergent token;
  * the engine-side double-free regression (overflow finish racing a reset
    must not corrupt the free list).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (
    SalcaParams, append_token_paged, cow_block, empty_paged_cache, free_pages,
    map_block, prefill_cache, prefill_into_pages, share_blocks)
from repro.models import get_model
from repro.runtime.serve import Request, ServingEngine

try:
    from hypothesis import given, settings, strategies as hst
    HAVE_HYPOTHESIS = True
except ImportError:            # container without hypothesis: fallback only
    HAVE_HYPOTHESIS = False

CFG = get_config("qwen3-0.6b").reduced()
# Static weight-derived heavy channels: the request-independent set that
# lets divergent-tail requests share feature blocks (with the paper's
# per-input sets, the engine's heavy gate disables sharing instead).
CFG_STATIC = dataclasses.replace(CFG, salca_static_channels=True)
CFG_ORACLE = dataclasses.replace(CFG_STATIC, salca=False)

MAX_SEQ = 128               # engine logical capacity (room for 63+2 tokens)
BS = 16

PARAMS = SalcaParams(feature_sparsity=0.5, k=16, k_cap=32, pool_window=7)


@pytest.fixture(scope="module")
def model_params():
    # Shapes don't depend on the salca flags, so one init serves all cfgs.
    return get_model(CFG).init(jax.random.PRNGKey(0))


def _prompt(rng, n):
    return rng.integers(0, CFG.vocab_size, n).astype(np.int32)


def _src_cache(rng, t, max_seq=24, kv=2, hd=32):
    k = jnp.asarray(rng.normal(size=(1, t, kv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, t, kv, hd)), jnp.float32)
    return prefill_cache(k, v, max_seq=max_seq, params=PARAMS)


# ---------------------------------------------------------------------------
# Cache-level semantics
# ---------------------------------------------------------------------------

def test_share_blocks_aliases_and_refcounts(rng):
    pool = empty_paged_cache(12, 4, 3, 6, kv_heads=2, head_dim=32, r=16)
    src = _src_cache(rng, t=10)                 # 3 blocks (2 full + partial)
    pages = jnp.asarray(np.array([5, 2, 9, -1, -1, -1], np.int32))
    pool = prefill_into_pages(pool, src, 1, pages)
    np.testing.assert_array_equal(
        np.asarray(pool.refcount),
        np.bincount([5, 2, 9], minlength=12))
    shared = share_blocks(pool, 1, 2, 0)        # alias first 2 blocks into slot 0
    assert np.asarray(shared.page_table[0]).tolist()[:2] == [5, 2]
    assert int(shared.page_table[0, 2]) == -1
    assert int(shared.refcount[5]) == 2 and int(shared.refcount[2]) == 2
    assert int(shared.refcount[9]) == 1
    assert int(shared.length[0]) == 8           # min(src len 10, 2 blocks × 4)
    np.testing.assert_array_equal(np.asarray(shared.heavy_idx[0]),
                                  np.asarray(shared.heavy_idx[1]))


def test_append_is_a_cow_fault_until_serviced(rng):
    """A write landing in a block with refcount > 1 is dropped with the
    cursor held; after `cow_block` privatizes it, the write lands and the
    source block's bytes are untouched."""
    pool = empty_paged_cache(12, 4, 3, 6, kv_heads=2, head_dim=32, r=16)
    src = _src_cache(rng, t=6)                  # 1 full block + partial
    pool = prefill_into_pages(
        pool, src, 1, jnp.asarray(np.array([5, 2, -1, -1, -1, -1], np.int32)))
    pool = share_blocks(pool, 1, 2, 0)          # both cursors inside block 2
    k = jnp.asarray(rng.normal(size=(3, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(3, 2, 32)), jnp.float32)
    before = np.asarray(pool.k_codes[2])
    faulted = append_token_paged(pool, k, v)    # both target block 2 (rc 2)
    assert int(faulted.length[0]) == 6 and int(faulted.length[1]) == 6
    np.testing.assert_array_equal(np.asarray(faulted.k_codes[2]), before)
    # Service slot 0's fault: copy block, remap only slot 0, move one ref.
    cowed = cow_block(pool, 0, 1, 7)
    assert int(cowed.page_table[0, 1]) == 7
    assert int(cowed.page_table[1, 1]) == 2     # the other owner keeps block 2
    assert int(cowed.refcount[2]) == 1 and int(cowed.refcount[7]) == 1
    for fld in ("k_codes", "k_scale", "v_codes", "v_scale",
                "feat_words", "feat_scale", "feat_zero"):
        np.testing.assert_array_equal(np.asarray(getattr(cowed, fld)[7]),
                                      np.asarray(getattr(cowed, fld)[2]))
    # The copy left block 2 with refcount 1, so BOTH writers are now
    # exclusive owners and both writes land — slot 0 into the copy, slot 1
    # into the original (the engine's last-holder-writes-in-place rule).
    stepped = append_token_paged(cowed, k, v)
    assert int(stepped.length[0]) == 7 and int(stepped.length[1]) == 7
    # The shared prefix rows (before the write cursor) are intact in both.
    np.testing.assert_array_equal(np.asarray(stepped.k_codes[7])[:, :2],
                                  before[:, :2])
    np.testing.assert_array_equal(np.asarray(stepped.k_codes[2])[:, :2],
                                  before[:, :2])


def test_free_pages_decrefs_and_double_free_is_noop(rng):
    pool = empty_paged_cache(12, 4, 3, 6, kv_heads=2, head_dim=32, r=16)
    src = _src_cache(rng, t=10)
    pool = prefill_into_pages(
        pool, src, 1, jnp.asarray(np.array([5, 2, 9, -1, -1, -1], np.int32)))
    pool = share_blocks(pool, 1, 3, 0)
    freed = free_pages(pool, 0)
    np.testing.assert_array_equal(
        np.asarray(freed.refcount), np.bincount([5, 2, 9], minlength=12))
    twice = free_pages(freed, 0)                # double free: no refcount move
    np.testing.assert_array_equal(np.asarray(twice.refcount),
                                  np.asarray(freed.refcount))
    gone = free_pages(twice, 1)
    assert int(np.asarray(gone.refcount).sum()) == 0


def test_map_block_moves_refcounts(rng):
    pool = empty_paged_cache(8, 4, 2, 4, kv_heads=2, head_dim=32, r=16)
    pool = map_block(pool, 0, 0, 3)
    assert int(pool.refcount[3]) == 1
    pool = map_block(pool, 0, 0, 6)             # remap releases the old ref
    assert int(pool.refcount[3]) == 0 and int(pool.refcount[6]) == 1


def test_engine_release_double_free_regression(model_params):
    """Host-side regression for the free-list double-free hazard: releasing
    a slot that already released (overflow finish racing a reset) must be a
    no-op, never a duplicate free-list entry."""
    engine = ServingEngine(CFG, model_params, max_seq=MAX_SEQ, slots=2,
                           paged=True, block_size=BS, num_blocks=6)
    # Simulate an admitted slot holding two blocks, one of them shared.
    engine._alloc.take(0)
    engine._alloc.take(1)
    engine._refcount[0] = 2                     # shared with another slot
    engine._refcount[1] = 1
    engine._slot_blocks[0] = [0, 1]
    engine._slot_pos[0] = 20
    engine._release_blocks(0)
    assert engine._refcount[0] == 1 and engine._refcount[1] == 0
    assert sorted(engine._free_blocks) == [1, 2, 3, 4, 5]
    engine._release_blocks(0)                   # double free: no-op
    engine._release_blocks(1)                   # never-admitted slot: no-op
    assert sorted(engine._free_blocks) == [1, 2, 3, 4, 5]
    assert engine._refcount[0] == 1
    assert len(engine._free_blocks) == len(set(engine._free_blocks))


# ---------------------------------------------------------------------------
# Property suite: random admit/share/decode/finish interleavings
# ---------------------------------------------------------------------------

NUM_BLOCKS, POOL_BS, SLOTS, POOL_MB = 12, 4, 4, 6
ADMIT_LENGTHS = (3, 4, 7, 11)       # few distinct shapes → few compilations

_j_prefill = jax.jit(prefill_into_pages)
_j_share = jax.jit(share_blocks)
_j_map = jax.jit(map_block)
_j_cow = jax.jit(cow_block)
_j_append = jax.jit(append_token_paged)
_j_free = jax.jit(free_pages)


class MiniPool:
    """Host-side mirror of the engine's block bookkeeping, driving the real
    device ops — the property-test harness. Mirrors `ServingEngine`'s
    free-list / refcount / CoW scheduling without the model forward."""

    def __init__(self, rng):
        self.pool = empty_paged_cache(NUM_BLOCKS, POOL_BS, SLOTS, POOL_MB,
                                      kv_heads=2, head_dim=32, r=16)
        self.free = list(range(NUM_BLOCKS))
        self.rc = np.zeros(NUM_BLOCKS, np.int64)
        self.blocks: dict[int, list[int]] = {}
        self.pos: dict[int, int] = {}
        self.rng = rng

    @property
    def active(self):
        return sorted(self.blocks)

    def admit(self, slot, t):
        need = -(-t // POOL_BS)
        if slot in self.blocks or need > len(self.free):
            return
        ids = [self.free.pop() for _ in range(need)]
        pages = np.full(POOL_MB, -1, np.int32)
        pages[:need] = ids
        src = _src_cache(self.rng, t, max_seq=POOL_MB * POOL_BS)
        self.pool = _j_prefill(self.pool, src, jnp.int32(slot),
                               jnp.asarray(pages))
        for b in ids:
            self.rc[b] += 1
        self.blocks[slot] = ids
        self.pos[slot] = t

    def share_admit(self, dst, src_slot, n):
        if dst in self.blocks or src_slot not in self.blocks or dst == src_slot:
            return
        n = min(n, len(self.blocks[src_slot]))
        if n == 0:
            return
        self.pool = _j_share(self.pool, jnp.int32(src_slot), jnp.int32(n),
                             jnp.int32(dst))
        ids = self.blocks[src_slot][:n]
        for b in ids:
            self.rc[b] += 1
        self.blocks[dst] = list(ids)
        self.pos[dst] = min(self.pos[src_slot], n * POOL_BS)

    def decode(self):
        """One engine tick: grow/CoW every active slot (finishing starved
        ones, as the engine's overflow path does), then one fused append."""
        for slot in list(self.blocks):
            p = self.pos[slot]
            if p >= POOL_MB * POOL_BS:
                self.finish(slot)
                continue
            lb = p // POOL_BS
            held = self.blocks[slot]
            if lb == len(held):
                if not self.free:
                    self.finish(slot)
                    continue
                b = self.free.pop()
                self.rc[b] += 1
                held.append(b)
                self.pool = _j_map(self.pool, jnp.int32(slot), jnp.int32(lb),
                                   jnp.int32(b))
            elif self.rc[held[lb]] > 1:
                if not self.free:
                    self.finish(slot)
                    continue
                b = self.free.pop()
                self.rc[b] += 1
                self.rc[held[lb]] -= 1
                self.pool = _j_cow(self.pool, jnp.int32(slot), jnp.int32(lb),
                                   jnp.int32(b))
                held[lb] = b
        if not self.blocks:
            return
        k = jnp.asarray(self.rng.normal(size=(SLOTS, 2, 32)), jnp.float32)
        v = jnp.asarray(self.rng.normal(size=(SLOTS, 2, 32)), jnp.float32)
        self.pool = _j_append(self.pool, k, v)
        for slot in self.blocks:
            self.pos[slot] += 1

    def finish(self, slot):
        ids = self.blocks.pop(slot, None)
        self.pool = _j_free(self.pool, jnp.int32(slot))
        if ids is None:
            return                   # double free exercised: must be a no-op
        for b in ids:
            self.rc[b] -= 1
            if self.rc[b] == 0:
                self.free.append(b)
        self.pos.pop(slot, None)

    def check(self):
        rc_dev = np.asarray(self.pool.refcount)
        pt = np.asarray(self.pool.page_table)
        refs = np.bincount(pt[pt >= 0], minlength=NUM_BLOCKS)
        np.testing.assert_array_equal(rc_dev, refs)   # rc == table references
        np.testing.assert_array_equal(rc_dev, self.rc)  # host mirror agrees
        mapped = set(pt[pt >= 0].tolist())
        assert not (mapped & set(self.free)), "free list ∩ mapped ≠ ∅"
        assert len(self.free) == len(set(self.free)), "free-list duplicate"
        for slot, p in self.pos.items():
            assert int(self.pool.length[slot]) == p
        for slot in range(SLOTS):
            if slot not in self.blocks:
                assert int(self.pool.length[slot]) == 0


def _interpret(mp: MiniPool, ops):
    for kind, a, b, c in ops:
        kind %= 4
        if kind == 0:
            mp.admit(a % SLOTS, ADMIT_LENGTHS[b % len(ADMIT_LENGTHS)])
        elif kind == 1 and mp.active:
            mp.share_admit(a % SLOTS, mp.active[b % len(mp.active)], c % 3 + 1)
        elif kind == 2:
            mp.decode()
        else:
            mp.finish(a % SLOTS)     # active or not: double free is a no-op
        mp.check()
    for slot in list(mp.blocks):
        mp.finish(slot)
        mp.check()
    # No block leaks after all requests finish.
    assert sorted(mp.free) == list(range(NUM_BLOCKS))
    assert int(np.asarray(mp.pool.refcount).sum()) == 0
    assert (np.asarray(mp.pool.page_table) == -1).all()


def test_interleavings_preserve_invariants_deterministic():
    """Hypothesis-free fallback (the container CI always runs this): fixed
    pseudo-random interleavings through the same harness."""
    master = np.random.default_rng(7)
    for _ in range(6):
        ops = [tuple(master.integers(0, 64, 4).tolist()) for _ in range(12)]
        _interpret(MiniPool(np.random.default_rng(int(master.integers(2**31)))),
                   ops)


if HAVE_HYPOTHESIS:
    @pytest.mark.slow
    @settings(max_examples=200, derandomize=True, deadline=None)
    @given(ops=hst.lists(
        hst.tuples(hst.integers(0, 63), hst.integers(0, 63),
                   hst.integers(0, 63), hst.integers(0, 63)),
        min_size=1, max_size=14),
        seed=hst.integers(0, 2**31 - 1))
    def test_interleavings_preserve_invariants_hypothesis(ops, seed):
        """≥200 random admit/share/decode/finish interleavings: refcounts
        equal page-table references, free ∩ mapped = ∅, no leaks at drain."""
        _interpret(MiniPool(np.random.default_rng(seed)), ops)


# ---------------------------------------------------------------------------
# Engine parity: shared admission is invisible in the outputs
# ---------------------------------------------------------------------------

def _run_engine(cfg, model_params, prompts, max_new, *, paged, share=False,
                num_blocks=None, slots=6):
    eng = ServingEngine(cfg, model_params, max_seq=MAX_SEQ, slots=slots,
                        paged=paged, block_size=BS, num_blocks=num_blocks,
                        prefix_sharing=share)
    reqs = [Request(rid=i, prompt=p.copy(), max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    stats = eng.run()
    return reqs, stats, eng


@pytest.mark.slow
@pytest.mark.parametrize("cfg", [CFG_STATIC, CFG_ORACLE],
                         ids=["sparse", "dense-oracle"])
def test_shared_prefix_parity_divergent_tails(cfg, model_params, rng):
    """N requests sharing a 48-token prefix with divergent tails: shared
    paged == unshared paged == dense slot pool, bit-identical greedy
    outputs — and sharing actually happened."""
    prefix = _prompt(rng, 48)
    prompts = [np.concatenate([prefix, _prompt(rng, 15)]) for _ in range(4)]
    r_dense, _, _ = _run_engine(cfg, model_params, prompts, 2, paged=False)
    r_plain, _, _ = _run_engine(cfg, model_params, prompts, 2, paged=True,
                                num_blocks=20)
    r_share, st, eng = _run_engine(cfg, model_params, prompts, 2, paged=True,
                                   share=True, num_blocks=20)
    for a, b, c in zip(r_dense, r_plain, r_share):
        assert a.output == b.output == c.output
    assert st.shared_blocks == 9                # 3 tail requests × 3 blocks
    assert st.prefix_hits == 3                  # the first request registers
    assert sorted(eng._free_blocks) == list(range(20))
    assert (eng._refcount == 0).all()


@pytest.mark.slow
def test_cow_triggers_mid_decode_on_first_divergent_token(model_params, rng):
    """Identical non-block-aligned prompts share every block including the
    partial one; the first decoded (divergent) token's write faults into a
    CoW copy — outputs stay bit-identical to unshared and dense runs."""
    prompts = [_prompt(rng, 40)] * 3            # 2 full blocks + 8-token tail
    prompts = [p.copy() for p in prompts]
    r_dense, _, _ = _run_engine(CFG_STATIC, model_params, prompts, 5,
                                paged=False, slots=4)
    r_plain, _, _ = _run_engine(CFG_STATIC, model_params, prompts, 5,
                                paged=True, num_blocks=16, slots=4)
    r_share, st, eng = _run_engine(CFG_STATIC, model_params, prompts, 5,
                                   paged=True, share=True, num_blocks=16,
                                   slots=4)
    for a, b, c in zip(r_dense, r_plain, r_share):
        assert a.output == b.output == c.output
    assert st.shared_blocks == 6                # 2 sharers × 3 blocks each
    assert st.cow_copies == 2                   # last holder writes in place
    assert st.summary()["effective_blocks_saved"] == 4
    assert sorted(eng._free_blocks) == list(range(16))


@pytest.mark.slow
def test_heavy_gate_disables_sharing_under_per_input_channels(model_params, rng):
    """With the paper's per-input heavy channels (default CFG), divergent
    tails derive different sets, so the gate falls back to private blocks —
    sharing reports zero and outputs still match the unshared run."""
    prefix = _prompt(rng, 48)
    prompts = [np.concatenate([prefix, _prompt(rng, 15)]) for _ in range(3)]
    r_plain, _, _ = _run_engine(CFG, model_params, prompts, 2, paged=True,
                                num_blocks=16, slots=4)
    r_share, st, _ = _run_engine(CFG, model_params, prompts, 2, paged=True,
                                 share=True, num_blocks=16, slots=4)
    for a, b in zip(r_plain, r_share):
        assert a.output == b.output
    assert st.shared_blocks == 0                # gate held
    # Identical prompts pass the gate even with per-input channels.
    same = [prompts[0].copy() for _ in range(2)]
    _, st2, _ = _run_engine(CFG, model_params, same, 2, paged=True,
                            share=True, num_blocks=16, slots=4)
    assert st2.shared_blocks == 4               # 3 full + 1 partial block
