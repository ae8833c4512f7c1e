"""Paged-native decode kernels: fused page-table walk vs the gather paths.

Covers the acceptance criteria of the kernel-fusion PR:

  * paged relevance scoring (`estimate_relevance_paged`, XLA ref AND Pallas
    interpret) is BIT-identical to `estimate_relevance` over the gathered
    logical feature stream — scrambled pages, unmapped-page clamping;
  * the fused exact-attention kernel (`sparse_flash_decode_paged`, ref and
    Pallas interpret) matches the gather-then-kernel path and the dense
    paged oracle — scrambled page tables, physical-block reuse after free,
    selection capacity C not divisible by the block size;
  * the fused decode tick builds no pool-wide transpose and no logical-order
    feature materialization (jaxpr scan outside the pallas_call);
  * the serving engine produces bit-identical greedy tokens fused vs
    unfused, including prefix-shared + copy-on-write blocks (CoW'd blocks
    must resolve to the writer's physical block).
"""

import dataclasses

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (
    SalcaParams, dense_decode_from_paged, empty_paged_cache, free_pages,
    prefill_cache, prefill_into_pages, salca_decode_attention,
    salca_decode_attention_paged)
from repro.core.cache import paged_logical_features
from repro.core.selection import estimate_relevance, estimate_relevance_paged
from repro.kernels.flash_decode.ops import sparse_flash_decode_paged

CFG = get_config("qwen3-0.6b").reduced()
MAX_SEQ = 64
BS = 16
MB = MAX_SEQ // BS

PARAMS = SalcaParams(feature_sparsity=0.5, k=16, k_cap=32, pool_window=7)


def _scrambled_pool(rng, t=40, slots=3, slot=1, num_blocks=20, kv=2, hd=32):
    """Contiguous prefill + the same request scattered over scrambled
    physical blocks of a paged pool. Returns (dense, pool, pages)."""
    k = jnp.asarray(rng.normal(size=(1, t, kv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, t, kv, hd)), jnp.float32)
    dense = prefill_cache(k, v, max_seq=MAX_SEQ, params=PARAMS)
    pool = empty_paged_cache(num_blocks, BS, slots, MB, kv_heads=kv,
                             head_dim=hd, r=16)
    need = -(-t // BS)
    pages = np.full(MB, -1, np.int32)
    pages[:need] = [13, 2, 7, 11][:need]
    pool = prefill_into_pages(pool, dense, slot, jnp.asarray(pages))
    return dense, pool, pages


# ---------------------------------------------------------------------------
# Relevance scoring: physical-block streaming == gathered logical view
# ---------------------------------------------------------------------------

def test_paged_scores_bitwise_parity(rng):
    """XLA-ref and Pallas-interpret paged scoring are BIT-identical to the
    flat path over `paged_logical_features` — including the unmapped pages
    that clamp to block 0 (same garbage on every path) and slots that are
    entirely unmapped."""
    _, pool, _ = _scrambled_pool(rng, t=40)
    q_feat = jnp.asarray(rng.normal(size=(3, 4, 16)), jnp.float32)
    fw, fs, fz = paged_logical_features(pool)
    flat = estimate_relevance(q_feat, fw, fs, fz, 2)
    ref = estimate_relevance_paged(q_feat, pool, 2, impl="ref")
    pal = estimate_relevance_paged(q_feat, pool, 2, impl="pallas",
                                   interpret=True)
    np.testing.assert_array_equal(np.asarray(flat), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(flat), np.asarray(pal))


def test_paged_scores_bitwise_parity_jitted(rng):
    """Bit-parity survives jit: pinned bf16 rounding in the score chain
    (`quantization.dequant_score_chain`) keeps numerics independent of how
    each caller's graph fuses."""
    _, pool, _ = _scrambled_pool(rng, t=40)
    q_feat = jnp.asarray(rng.normal(size=(3, 4, 16)), jnp.float32)
    fw, fs, fz = paged_logical_features(pool)
    flat = jax.jit(lambda qf, a, b, c: estimate_relevance(qf, a, b, c, 2))(
        q_feat, fw, fs, fz)
    ref = jax.jit(lambda qf, p: estimate_relevance_paged(qf, p, 2, impl="ref"))(
        q_feat, pool)
    np.testing.assert_array_equal(np.asarray(flat), np.asarray(ref))


# ---------------------------------------------------------------------------
# Fused exact attention: selected-block streaming == row gather == oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_fused_flash_parity_scrambled_pages(rng, impl):
    dense, pool, _ = _scrambled_pool(rng, t=40)
    q = jnp.asarray(rng.normal(size=(1, 4, 32)), jnp.float32)
    q3 = jnp.zeros((3, 4, 32), jnp.float32).at[1].set(q[0])
    o_dense, sel_d = salca_decode_attention(q, dense, PARAMS,
                                            return_selection=True)
    o_fused, sel_f = salca_decode_attention_paged(
        q3, pool, PARAMS, return_selection=True, fused=True, impl=impl,
        interpret=True)
    o_gather = salca_decode_attention_paged(q3, pool, PARAMS, fused=False)
    # identical selection (bit-identical scores) and matching attention
    np.testing.assert_array_equal(np.asarray(sel_f.indices[1]),
                                  np.asarray(sel_d.indices[0]))
    np.testing.assert_allclose(np.asarray(o_fused[1]), np.asarray(o_dense[0]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(o_fused), np.asarray(o_gather),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_fused_flash_capacity_not_divisible_by_block(rng, impl):
    """C (selection capacity) is decoupled from the block size in the fused
    kernel — the grid runs over selected physical blocks, not C-chunks."""
    p = SalcaParams(feature_sparsity=0.5, k=10, k_cap=24, pool_window=7)
    assert p.k_cap % BS != 0
    dense, pool, _ = _scrambled_pool(rng, t=40)
    q = jnp.asarray(rng.normal(size=(1, 4, 32)), jnp.float32)
    q3 = jnp.zeros((3, 4, 32), jnp.float32).at[1].set(q[0])
    o_d = salca_decode_attention(q, dense, p)
    o_f = salca_decode_attention_paged(q3, pool, p, fused=True, impl=impl,
                                       interpret=True)
    np.testing.assert_allclose(np.asarray(o_f[1]), np.asarray(o_d[0]),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_fused_flash_matches_dense_oracle_full_retention(rng, impl):
    """With k ≥ n the selection keeps every valid token, so the fused sparse
    path must reproduce the paged dense oracle (INT8-dequant attention)."""
    p = SalcaParams(feature_sparsity=0.5, k=MAX_SEQ, k_cap=MAX_SEQ,
                    pool_window=1, use_pool=False)
    _, pool, _ = _scrambled_pool(rng, t=40)
    q3 = jnp.asarray(rng.normal(size=(3, 4, 32)), jnp.float32)
    o_f = salca_decode_attention_paged(q3, pool, p, fused=True, impl=impl,
                                       interpret=True)
    o_oracle = dense_decode_from_paged(q3, pool)
    np.testing.assert_allclose(np.asarray(o_f[1]), np.asarray(o_oracle[1]),
                               rtol=1e-4, atol=1e-5)
    # fully-unmapped slots produce finite zeros, never NaN
    assert np.all(np.isfinite(np.asarray(o_f)))
    np.testing.assert_allclose(np.asarray(o_f[0]), 0.0, atol=1e-6)


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_fused_flash_block_reuse_after_free(rng, impl):
    """Physical blocks freed by one request and remapped (scrambled, in a
    different order) to another resolve through the new owner's page table —
    stale data from the previous owner never leaks into the fused fetch."""
    dense_a, pool, _ = _scrambled_pool(rng, t=40)
    pool = free_pages(pool, 1)
    t2 = 48
    k = jnp.asarray(rng.normal(size=(1, t2, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, t2, 2, 32)), jnp.float32)
    dense_b = prefill_cache(k, v, max_seq=MAX_SEQ, params=PARAMS)
    pages = np.full(MB, -1, np.int32)
    pages[:3] = [2, 13, 7]            # reuse the freed blocks, reordered
    pool = prefill_into_pages(pool, dense_b, 2, jnp.asarray(pages))
    q = jnp.asarray(rng.normal(size=(1, 4, 32)), jnp.float32)
    q3 = jnp.zeros((3, 4, 32), jnp.float32).at[2].set(q[0])
    o_d = salca_decode_attention(q, dense_b, PARAMS)
    o_f = salca_decode_attention_paged(q3, pool, PARAMS, fused=True,
                                       impl=impl, interpret=True)
    np.testing.assert_allclose(np.asarray(o_f[2]), np.asarray(o_d[0]),
                               rtol=1e-5, atol=1e-6)
    # the freed slot reads as empty through both paths
    np.testing.assert_allclose(np.asarray(o_f[1]), 0.0, atol=1e-6)


@pytest.mark.parametrize("kv,g,hd,t", [(1, 1, 32, 33), (2, 4, 64, 64),
                                       (4, 2, 32, 17)])
def test_fused_kernel_shape_sweep(rng, kv, g, hd, t):
    """Pallas-interpret fused kernel vs its XLA ref across head/shape
    combinations, through the full selection pipeline."""
    k = jnp.asarray(rng.normal(size=(1, t, kv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, t, kv, hd)), jnp.float32)
    dense = prefill_cache(k, v, max_seq=MAX_SEQ, params=PARAMS)
    pool = empty_paged_cache(12, BS, 2, MB, kv_heads=kv, head_dim=hd,
                             r=PARAMS.r(hd))
    need = -(-t // BS)
    pages = np.full(MB, -1, np.int32)
    pages[:need] = np.random.default_rng(t).choice(12, need, replace=False)
    pool = prefill_into_pages(pool, dense, 0, jnp.asarray(pages))
    q = jnp.asarray(rng.normal(size=(2, kv * g, hd)), jnp.float32)
    _, sel = salca_decode_attention_paged(q, pool, PARAMS,
                                          return_selection=True)
    out_ref = sparse_flash_decode_paged(q, pool, sel, impl="ref")
    out_pal = sparse_flash_decode_paged(q, pool, sel, impl="pallas",
                                        interpret=True)
    out_gather = sparse_flash_decode_paged(q, pool, sel, impl="gather")
    np.testing.assert_allclose(np.asarray(out_pal), np.asarray(out_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out_gather), np.asarray(out_ref),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Acceptance: no pool-wide transpose / logical feature copy in the fused tick
# ---------------------------------------------------------------------------

def _walk_jaxpr(jaxpr, banned, bad):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue                      # in-kernel streaming is the point
        for var in eqn.outvars:
            shape = tuple(getattr(var.aval, "shape", ()))
            if shape in banned:
                bad.append((eqn.primitive.name, shape))
        for val in eqn.params.values():
            # shard_map carries an OPEN Jaxpr param; pjit/scan carry Closed.
            is_jaxpr = lambda x: isinstance(x, (jex_core.Jaxpr,
                                                jex_core.ClosedJaxpr))
            for sub in jax.tree_util.tree_leaves(val, is_leaf=is_jaxpr):
                if isinstance(sub, jex_core.ClosedJaxpr):
                    _walk_jaxpr(sub.jaxpr, banned, bad)
                elif isinstance(sub, jex_core.Jaxpr):
                    _walk_jaxpr(sub, banned, bad)


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_fused_tick_has_no_pool_wide_ops(rng, impl):
    """Jaxpr scan of the fused decode attention: no op (outside the kernel
    call) produces a flat `(P·BS, KV, ·)` view/transpose of the pool or a
    logical-order `(S, L, KV, ·)` copy of the feature stream or K/V."""
    _, pool, _ = _scrambled_pool(rng, t=40)
    s = 3
    p_, kv_, bs_, hd_ = pool.k_codes.shape
    l_ = pool.max_seq
    w_ = pool.feat_words.shape[-1]
    banned = {
        (p_ * bs_, kv_, hd_), (kv_, p_ * bs_, hd_),      # flat pool (t)ranspose
        (p_ * bs_, kv_), (kv_, p_ * bs_),                # flat scale transpose
        (s, l_, kv_, w_), (s, l_, kv_, hd_), (s, l_, kv_),  # logical copies
    }
    q3 = jnp.zeros((s, 4, hd_), jnp.float32)

    def tick(q, pool):
        return salca_decode_attention_paged(q, pool, PARAMS, fused=True,
                                            impl=impl, interpret=True)

    jaxpr = jax.make_jaxpr(tick)(q3, pool)
    bad = []
    _walk_jaxpr(jaxpr.jaxpr, banned, bad)
    assert not bad, f"pool-wide ops in the fused tick: {bad}"

    # ... and the unfused (gather) tick DOES materialize logical copies —
    # the regression this PR removes stays observable in the baseline.
    def tick_unfused(q, pool):
        return salca_decode_attention_paged(q, pool, PARAMS, fused=False)

    jaxpr_u = jax.make_jaxpr(tick_unfused)(q3, pool)
    bad_u = []
    _walk_jaxpr(jaxpr_u.jaxpr, banned, bad_u)
    assert bad_u, "expected the gather path to materialize logical views"


def test_sharded_fused_tick_has_no_pool_wide_ops(rng):
    """Jaxpr scan of the SHARDED decode tick: the fully-pipelined island
    (`sp_salca_decode_paged(fused=True)`) builds no logical-order
    `(S, L, KV, ·)` copy of the feature stream or K/V and no flat pool
    transpose outside the kernel calls; the legacy gather island still
    materializes them (the per-shard O(local pool) copies this PR removes).
    """
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh
    from repro.core.sp_decode import sp_salca_decode_paged

    _, pool, _ = _scrambled_pool(rng, t=40)
    s = 3
    p_, kv_, bs_, hd_ = pool.k_codes.shape
    l_ = pool.max_seq
    w_ = pool.feat_words.shape[-1]
    banned = {
        (p_ * bs_, kv_, hd_), (kv_, p_ * bs_, hd_),      # flat pool transpose
        (p_ * bs_, kv_), (kv_, p_ * bs_),                # flat scale transpose
        (s, l_, kv_, w_), (s, l_, kv_, hd_), (s, l_, kv_),  # logical copies
    }
    q3 = jnp.zeros((s, 4, hd_), jnp.float32)
    mesh = make_mesh((1,), ("seq",))

    def island(fused):
        def f(q, pool):
            return sp_salca_decode_paged(q, pool, PARAMS, "seq", fused=fused)
        return jax.shard_map(f, mesh=mesh, in_specs=(P(), P()),
                             out_specs=P(), check_vma=False)

    bad = []
    _walk_jaxpr(jax.make_jaxpr(island(True))(q3, pool).jaxpr, banned, bad)
    assert not bad, f"pool-wide ops in the fused sharded tick: {bad}"

    bad_u = []
    _walk_jaxpr(jax.make_jaxpr(island(False))(q3, pool).jaxpr, banned, bad_u)
    assert bad_u, "expected the gather island to materialize logical views"


# ---------------------------------------------------------------------------
# Serving engine: greedy-token parity fused vs unfused (+ prefix sharing/CoW)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine_params():
    from repro.models import get_model
    return get_model(CFG).init(jax.random.PRNGKey(0))


def _prompt(rng, n):
    return rng.integers(0, CFG.vocab_size, n).astype(np.int32)


@pytest.mark.slow
def test_engine_fused_vs_unfused_greedy_parity(engine_params, rng):
    """Same mixed-length requests through a fused-decode and an unfused
    (PR 3 gather) paged engine produce bit-identical greedy tokens."""
    from repro.runtime.serve import Request, ServingEngine
    prompts = [_prompt(rng, n) for n in (12, 30, 20)]
    outs = {}
    for fused in (False, True):
        eng = ServingEngine(CFG, engine_params, max_seq=MAX_SEQ, slots=2,
                            paged=True, block_size=BS, num_blocks=8,
                            fused_decode=fused)
        reqs = [Request(rid=i, prompt=p.copy(), max_new_tokens=5)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        stats = eng.run()
        assert stats.completed == 3
        outs[fused] = [r.output for r in reqs]
    assert outs[True] == outs[False]


@pytest.mark.slow
def test_engine_fused_prefix_sharing_cow_parity(engine_params, rng):
    """Prefix-shared engines (fused vs unfused): identical prompts share all
    blocks including the partial tail block, the first decode write CoW-
    faults it, and the fused kernel must resolve the CoW'd block to the
    WRITER's private physical block — greedy tokens stay bit-identical and
    every request still matches an unshared run."""
    from repro.runtime.serve import Request, ServingEngine
    scfg = dataclasses.replace(CFG, salca_static_channels=True)
    # 40 tokens = 2 full blocks + a PARTIAL third block: identical prompts
    # share all three (exact-full-prompt partial match), so the first decode
    # write lands in a refcount-2 block and must CoW-fault.
    sys_prefix = _prompt(rng, 40)
    tails = [np.empty(0, np.int32), np.empty(0, np.int32), _prompt(rng, 8)]
    prompts = [np.concatenate([sys_prefix, t]).astype(np.int32) for t in tails]
    outs, stats = {}, {}
    for mode, (share, fused) in {
        "unshared": (False, False),
        "shared_unfused": (True, False),
        "shared_fused": (True, True),
    }.items():
        eng = ServingEngine(scfg, engine_params, max_seq=MAX_SEQ, slots=3,
                            paged=True, block_size=BS, num_blocks=12,
                            prefix_sharing=share, fused_decode=fused)
        reqs = [Request(rid=i, prompt=p.copy(), max_new_tokens=6)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        stats[mode] = eng.run()
        assert stats[mode].completed == 3
        outs[mode] = [r.output for r in reqs]
    assert outs["shared_fused"] == outs["shared_unfused"] == outs["unshared"]
    # sharing + CoW actually happened in both shared runs
    for mode in ("shared_unfused", "shared_fused"):
        assert stats[mode].shared_blocks > 0
        assert stats[mode].cow_copies > 0
