"""Slot-pooled serving engine: pool semantics, batched decode, scheduling.

Covers the acceptance criteria of the slot-pool refactor:
  * `core.cache.write_prefill_into_slot` / `reset_slot` touch only their slot;
  * pooled masked decode leaves inactive slots bit-identical and matches a
    solo (batch=1) decode for the active slot;
  * `ServingEngine.run` issues exactly ONE jitted decode call per tick
    regardless of how many slots are active (call-counting wrapper);
  * slot reuse after completion, FIFO admission, mixed prompt/output
    lengths, stop tokens, and stats bookkeeping.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (append_token_masked, empty_cache, prefill_cache,
                        reset_slot, SalcaParams)
from repro.core.cache import write_prefill_into_slot
from repro.models import get_model
from repro.runtime.serve import Request, ServingEngine

CFG = get_config("qwen3-0.6b").reduced()
MAX_SEQ = 64


@pytest.fixture(scope="module")
def api():
    return get_model(CFG)


@pytest.fixture(scope="module")
def params(api):
    return api.init(jax.random.PRNGKey(0))


def _prompt(rng, n):
    return rng.integers(0, CFG.vocab_size, n).astype(np.int32)


# ---------------------------------------------------------------------------
# Pool primitives (cache level)
# ---------------------------------------------------------------------------

def test_write_prefill_into_slot_and_reset(rng):
    pool = empty_cache(batch=3, max_seq=32, kv_heads=2, head_dim=32, r=16)
    k = jnp.asarray(rng.normal(size=(1, 10, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 10, 2, 32)), jnp.float32)
    src = prefill_cache(k, v, max_seq=32,
                        params=SalcaParams(feature_sparsity=0.5, k=8, k_cap=8))
    pool2 = write_prefill_into_slot(pool, src, 1)
    # target slot holds the src fields, other slots untouched (still zero)
    for p2, s, p in zip(pool2, src, pool):
        np.testing.assert_array_equal(np.asarray(p2[1]), np.asarray(s[0]))
        np.testing.assert_array_equal(np.asarray(p2[0]), np.asarray(p[0]))
        np.testing.assert_array_equal(np.asarray(p2[2]), np.asarray(p[2]))
    assert int(pool2.length[1]) == 10
    pool3 = reset_slot(pool2, 1)
    assert int(pool3.length[1]) == 0
    assert int(pool3.valid_mask().sum()) == 0
    # traced slot index also works (jit-safe admission)
    pool4 = jax.jit(write_prefill_into_slot)(pool, src, jnp.int32(2))
    np.testing.assert_array_equal(np.asarray(pool4.k_codes[2]),
                                  np.asarray(src.k_codes[0]))


def test_write_prefill_into_slot_validates_shapes(rng):
    pool = empty_cache(batch=2, max_seq=32, kv_heads=2, head_dim=32, r=16)
    k = jnp.asarray(rng.normal(size=(1, 8, 2, 32)), jnp.float32)
    small = prefill_cache(k, k, max_seq=16,
                          params=SalcaParams(feature_sparsity=0.5, k=8, k_cap=8))
    with pytest.raises(ValueError):
        write_prefill_into_slot(pool, small, 0)    # max_seq mismatch


def test_slot_lifecycle_no_stale_leakage(api, params, rng):
    """Roundtrip write_into_slot → decode → reset_slot → re-admit: the
    recycled slot behaves exactly like a fresh pool (no stale tokens from
    the previous occupant leak through the valid mask)."""
    pa, pb = _prompt(rng, 20), _prompt(rng, 9)
    _, sa = api.prefill(params, {"tokens": jnp.asarray(pa[None])}, MAX_SEQ)
    _, sb = api.prefill(params, {"tokens": jnp.asarray(pb[None])}, MAX_SEQ)
    active = jnp.asarray([True, False])
    tok = jnp.asarray([4, 0], jnp.int32)
    # occupy slot 0 with request A, decode a few steps, then free it
    pool = api.init_state(2, MAX_SEQ)
    pool = api.write_into_slot(pool, sa, 0)
    for _ in range(3):
        _, pool = api.decode_step(params, pool, tok, None, active=active)
    pool = api.reset_slot(pool, 0)
    assert int(pool.pos[0]) == 0
    # re-admit request B into the recycled slot vs a never-used pool
    pool = api.write_into_slot(pool, sb, 0)
    fresh = api.write_into_slot(api.init_state(2, MAX_SEQ), sb, 0)
    for t in (7, 11, 2):
        tk = jnp.asarray([t, 0], jnp.int32)
        lr, pool = api.decode_step(params, pool, tk, None, active=active)
        lf, fresh = api.decode_step(params, fresh, tk, None, active=active)
        np.testing.assert_allclose(np.asarray(lr[0]), np.asarray(lf[0]),
                                   rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("length", [0, 1, 30, 31, 32])
def test_append_token_masked_invariants(rng, length):
    """Property-style over cursor positions near 0 and max_seq: active rows
    append at their cursor and advance (clipped at max_seq); inactive rows
    are bit-identical — under alternating active masks."""
    max_seq = 32
    cache = empty_cache(batch=4, max_seq=max_seq, kv_heads=2, head_dim=16, r=16)
    cache = cache._replace(length=jnp.full((4,), length, jnp.int32))
    lengths = np.full(4, length)               # host-tracked expectation
    active = np.asarray([True, False, True, False])
    for _ in range(3):                         # alternate the mask
        before = [np.asarray(x) for x in cache]
        k = jnp.asarray(rng.normal(size=(4, 2, 16)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(4, 2, 16)), jnp.float32)
        cache = append_token_masked(cache, k, v, jnp.asarray(active))
        after = [np.asarray(x) for x in cache]
        for row in range(4):
            if active[row]:
                cursor = lengths[row]
                lengths[row] = min(cursor + 1, max_seq)
                assert int(cache.length[row]) == lengths[row]
                if cursor < max_seq:           # in-range write landed
                    assert float(cache.k_scale[row, cursor, 0]) > 0.0
            else:                              # untouched, bit-identical
                assert int(cache.length[row]) == lengths[row]
                for b, a in zip(before, after):
                    np.testing.assert_array_equal(b[row], a[row])
        active = ~active


# ---------------------------------------------------------------------------
# Masked pooled decode (state level)
# ---------------------------------------------------------------------------

def _lm_slot_rows(state, slot):
    """All leaves of one slot's row of an LMState."""
    per = jax.tree.map(lambda x: x[:, slot], state.period_states)
    tail = jax.tree.map(lambda x: x[slot], state.tail_states)
    return [np.asarray(x) for x in jax.tree.leaves((per, tail, state.pos[slot]))]


def test_masked_decode_inactive_slot_untouched(api, params, rng):
    prompt = _prompt(rng, 12)
    _, src = api.prefill(params, {"tokens": jnp.asarray(prompt[None])}, MAX_SEQ)
    pool = api.init_state(2, MAX_SEQ)
    pool = api.write_into_slot(pool, src, 0)
    before = _lm_slot_rows(pool, 1)
    tok = jnp.asarray([3, 5], jnp.int32)
    active = jnp.asarray([True, False])
    _, pool2 = api.decode_step(params, pool, tok, None, active=active)
    after = _lm_slot_rows(pool2, 1)
    for a, b in zip(before, after):
        np.testing.assert_array_equal(a, b)
    assert int(pool2.pos[0]) == len(prompt) + 1     # active slot advanced
    assert int(pool2.pos[1]) == 0                   # inactive held


def test_pooled_decode_matches_solo(rng):
    """A slot decoded inside a pool (other slots active on other requests)
    produces the same logits as the same request decoded at batch=1, and
    its logits do not depend on what the other slots hold.

    Runs the model in f32: in bf16 the batch-3 and batch-1 programs round
    differently (one bf16 unit in the last place on some logits, with the
    slot's logits still bit-independent of its neighbours' content), which
    says nothing about slot isolation."""
    cfg = dataclasses.replace(CFG, dtype="float32")
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    pa, pb, pc = _prompt(rng, 12), _prompt(rng, 20), _prompt(rng, 20)
    _, sa = api.prefill(params, {"tokens": jnp.asarray(pa[None])}, MAX_SEQ)
    pools = []
    for other in (pb, pc):
        _, so = api.prefill(params, {"tokens": jnp.asarray(other[None])},
                            MAX_SEQ)
        pool = api.init_state(3, MAX_SEQ)
        pool = api.write_into_slot(pool, sa, 1)
        pools.append(api.write_into_slot(pool, so, 2))
    active = jnp.asarray([False, True, True])
    solo = sa
    toks = [7, 11, 2]
    for t in toks:
        logits = []
        for i, pool in enumerate(pools):
            logits_p, pools[i] = api.decode_step(
                params, pool, jnp.asarray([0, t, 9], jnp.int32), None,
                active=active)
            logits.append(np.asarray(logits_p[1]))
        logits_s, solo = api.decode_step(params, solo,
                                         jnp.asarray([t], jnp.int32))
        np.testing.assert_array_equal(logits[0], logits[1])
        np.testing.assert_allclose(logits[0], np.asarray(logits_s[0]),
                                   rtol=2e-3, atol=2e-4)


# ---------------------------------------------------------------------------
# Serving engine scheduling
# ---------------------------------------------------------------------------

def test_one_decode_call_per_tick_and_stats(params, rng):
    engine = ServingEngine(CFG, params, max_seq=MAX_SEQ, slots=4)
    for i, m in enumerate((2, 3, 4, 5)):
        engine.submit(Request(rid=i, prompt=_prompt(rng, 16), max_new_tokens=m))
    calls = 0
    orig = engine._decode

    def counting(*args):
        nonlocal calls
        calls += 1
        return orig(*args)

    engine._decode = counting
    stats = engine.run()
    # all 4 slots active from tick 1 → one fused call per tick, not per slot
    assert calls == stats.ticks == stats.decode_calls == 4
    assert stats.completed == 4
    assert stats.decode_steps == sum(m - 1 for m in (2, 3, 4, 5))
    assert stats.tokens_generated == sum((2, 3, 4, 5))
    s = stats.summary()
    assert s["decode_ms_per_tick"] > 0 and s["decode_ms_per_step"] > 0
    assert s["mean_ttft_s"] >= s["mean_queue_wait_s"] >= 0


def test_slot_reuse_and_fifo_order(params, rng):
    engine = ServingEngine(CFG, params, max_seq=MAX_SEQ, slots=1)
    reqs = [Request(rid=i, prompt=_prompt(rng, 8), max_new_tokens=2)
            for i in range(3)]
    for r in reqs:
        engine.submit(r)
    stats = engine.run()
    assert stats.completed == 3
    assert engine._free == [0] and not engine._active    # slot recycled
    # FIFO: admission (first token) strictly in submit order
    t = [r.first_token_time for r in reqs]
    assert t[0] < t[1] < t[2]
    assert all(r.done_time is not None for r in reqs)
    assert all(len(r.output) == 2 for r in reqs)


def test_mixed_prompt_and_output_lengths(params, rng):
    engine = ServingEngine(CFG, params, max_seq=MAX_SEQ, slots=2)
    specs = [(8, 3), (24, 6), (16, 1)]
    reqs = [Request(rid=i, prompt=_prompt(rng, pl), max_new_tokens=m)
            for i, (pl, m) in enumerate(specs)]
    for r in reqs:
        engine.submit(r)
    stats = engine.run()
    assert stats.completed == 3
    for r, (_, m) in zip(reqs, specs):
        assert len(r.output) == m
    # identical prompts in different slots agree token-for-token
    engine2 = ServingEngine(CFG, params, max_seq=MAX_SEQ, slots=2)
    p = _prompt(rng, 12)
    d0 = Request(rid=0, prompt=p.copy(), max_new_tokens=5)
    d1 = Request(rid=1, prompt=p.copy(), max_new_tokens=5)
    engine2.submit(d0)
    engine2.submit(d1)
    engine2.run()
    assert d0.output == d1.output


def test_stop_token_and_submit_validation(params, rng):
    engine = ServingEngine(CFG, params, max_seq=MAX_SEQ, slots=1)
    p = _prompt(rng, 8)
    probe = Request(rid=0, prompt=p.copy(), max_new_tokens=4)
    engine.submit(probe)
    engine.run()
    stop = probe.output[1]                       # first *decoded* token
    engine2 = ServingEngine(CFG, params, max_seq=MAX_SEQ, slots=1)
    req = Request(rid=1, prompt=p.copy(), max_new_tokens=16,
                  stop_token=int(stop))
    engine2.submit(req)
    stats = engine2.run()
    assert stats.completed == 1
    assert req.output[-1] == stop
    assert len(req.output) < 16
    with pytest.raises(ValueError):
        engine2.submit(Request(rid=2, prompt=_prompt(rng, MAX_SEQ),
                               max_new_tokens=8))
