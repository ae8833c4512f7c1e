"""Serving throughput: batching sublinearity + paged-pool admission wins.

Three sweeps:

1. **Slots sweep** — the slot-pooled engine issues ONE fused decode per
   tick, so decode wall time per tick should stay ~flat as active slots grow
   (bandwidth-bound regime) instead of scaling linearly the way per-request
   dispatch does. Sweeps slots=1..16 and reports a sublinearity summary.

2. **Mixed-length sweep** — at a FIXED HBM token budget, the dense pool
   reserves a `max_seq` stripe per slot, so concurrency is capped by
   worst-case length; the paged pool allocates blocks for tokens actually
   held, so mixed short/long requests pack. Reports peak concurrent
   requests and block-pool utilization for both, plus a paged-vs-contiguous
   greedy-output parity row (the correctness anchor: same prompts, same
   tokens, block-granular pool vs dense stripes).

3. **Shared-system-prompt sweep** — N requests whose prompts share a
   75%-of-length system prefix, at the same fixed block pool. Prefix-shared
   admission maps the prefix blocks by reference and charges only the
   divergent tail, so admitted concurrency should be ≥ 2x the unshared
   paged engine — with bit-identical greedy outputs (parity row; the sweep
   RAISES on a mismatch or a gain shortfall so CI fails loudly). Uses the
   static weight-derived heavy-channel set (`salca_static_channels`), the
   request-independent mode that makes feature blocks shareable across
   divergent tails.

4. **Fused-decode sweep** — the same mixed workload through a paged engine
   with the page-table walk fused into the decode kernels
   (``fused_decode=True``) vs the PR 3 gather path (``False``). Greedy
   outputs must be bit-identical (the sweep RAISES on mismatch); the
   ms/tick rows record the decode-tick cost of each data path. On TPU the
   fused tick's pool traffic is O(active + selected) instead of O(pool);
   on CPU the two land within noise of each other (XLA folds the gather
   path's transposes), so the timing rows are informational there.

5. **Capacity sweep** — admitted concurrency at a FIXED HBM byte budget:
   the budget buys ~1.7x the pool blocks at int8 storage than at fp16, so
   with requests sized in whole blocks the int8 pool admits ≥ 2x the
   concurrent requests (RAISES below 2x) with bit-identical greedy outputs
   (RAISES on mismatch; runs at f32 logits so greedy is strictly decided).
   A third engine adds the host tier (``host_spill=True``) and lifts
   concurrency to the slot count, completing every request with zero
   overflows (RAISES otherwise) — its predicted PCIe bytes print next to
   the measured decode seconds.

6. **Multi-tenant persistent-cache sweep** — N tenant system prompts × M
   users each, every user visiting twice, requests driven strictly
   SEQUENTIALLY (submit → drain) so nothing is ever co-resident and all
   reuse is cross-request. The persistent-cache engine
   (``prefix_cache=True``) pins a finished request's prefix blocks instead
   of freeing them, so a tenant's second user admits against cached
   prefix blocks and a user's second visit admits with ZERO prefill
   (metadata-only adoption). Gates (RAISE → benchmarks/run.py exits 1):
   bit-identical greedy outputs vs the non-persistent engine, every
   second visit a zero-prefill hit, median warm TTFT ≤ 0.6x the
   non-persistent engine's on fully-cached prompts, and a clean drain
   (flush + invariant audit + full free list) with zero leaked pins.

7. **Sharded-pool sweep** — the block pool split across 1/2/4 mesh shards
   at a FIXED per-device pool size, long-context requests whose block
   count exceeds half of one shard's slice. Admitted concurrency must
   scale ~linearly with shard count (the sweep RAISES below 3x at 4
   shards) with greedy outputs bit-identical to the 1-shard engine (RAISES
   on mismatch). Needs ≥ 4 jax devices — CI runs it under
   ``XLA_FLAGS=--xla_force_host_platform_device_count=8``; with fewer
   devices the sweep reports itself skipped and gates nothing.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np


PROMPT_LEN = 64
NEW_TOKENS = 9          # 1 from prefill + 8 decode ticks
MAX_SEQ = 128
BLOCK_SIZE = 16


def _drive(engine, n_requests: int, rng) -> dict:
    """Submit n_requests and run; return the marginal decode stats."""
    from repro.runtime.serve import Request
    s0_decode, s0_ticks, s0_steps = (engine.stats.decode_s,
                                     engine.stats.ticks,
                                     engine.stats.decode_steps)
    for i in range(n_requests):
        engine.submit(Request(
            rid=i, prompt=rng.integers(0, engine.cfg.vocab_size,
                                       PROMPT_LEN).astype(np.int32),
            max_new_tokens=NEW_TOKENS))
    engine.run()
    return {
        "decode_s": engine.stats.decode_s - s0_decode,
        "ticks": engine.stats.ticks - s0_ticks,
        "steps": engine.stats.decode_steps - s0_steps,
    }


def _slots_sweep(cfg, params, rng, smoke: bool):
    from repro.runtime.serve import ServingEngine

    yield "serving,slots,ticks,decode_ms_per_tick,decode_ms_per_token,tokens_per_s"
    per_tick = {}
    sweep = (1, 2) if smoke else (1, 2, 4, 8, 16)
    for slots in sweep:
        engine = ServingEngine(cfg, params, max_seq=MAX_SEQ, slots=slots)
        _drive(engine, slots, rng)          # warmup: compiles prefill+decode
        m = _drive(engine, slots, rng)      # measured: steady-state
        ms_tick = 1e3 * m["decode_s"] / max(m["ticks"], 1)
        ms_tok = 1e3 * m["decode_s"] / max(m["steps"], 1)
        tps = m["steps"] / max(m["decode_s"], 1e-9)
        per_tick[slots] = ms_tick
        yield (f"serving,{slots},{m['ticks']},{ms_tick:.3f},"
               f"{ms_tok:.3f},{tps:.1f}")
    if not smoke:
        # Sublinearity: one resident program must NOT cost 8× at 8 slots.
        ratio = per_tick[8] / max(per_tick[1], 1e-9)
        yield (f"serving_sublinearity,slots8_vs_1x,{ratio:.2f},"
               f"{'sublinear' if ratio < 8.0 else 'LINEAR-REGRESSION'}")


def _mixed_workload(cfg, rng, smoke: bool):
    """Mixed short/long prompts: the regime where dense per-slot stripes
    waste HBM (a short request reserves the same max_seq as a long one)."""
    from repro.runtime.serve import Request
    n_short = 4 if smoke else 10
    n_long = 1 if smoke else 2
    # Shorts fit one 16-token block (12+3 writes < 16); longs take 6 blocks
    # (88+7 < 96) — so the paged pool packs every request concurrently
    # within the dense pool's HBM budget without starving block growth.
    specs = ([(12, 4)] * n_short) + ([(88, 8)] * n_long)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, pl).astype(np.int32),
                    max_new_tokens=m)
            for i, (pl, m) in enumerate(specs)]


def _mixed_sweep(cfg, params, smoke: bool):
    from repro.runtime.serve import ServingEngine

    # Fixed HBM budget: `budget_tokens` of KV storage. Dense spends it on
    # budget/max_seq uniform slots; paged splits the same bytes into blocks
    # and takes more slots (slot metadata — page table rows, recurrent
    # state — is negligible next to the KV region).
    dense_slots = 2 if smoke else 4
    budget_tokens = dense_slots * MAX_SEQ
    paged_slots = 6 if smoke else 12
    num_blocks = budget_tokens // BLOCK_SIZE
    rng = np.random.default_rng(7)
    reqs_dense = _mixed_workload(cfg, rng, smoke)
    rng = np.random.default_rng(7)
    reqs_paged = _mixed_workload(cfg, rng, smoke)

    yield ("serving_mixed,mode,slots,budget_tokens,peak_concurrent,"
           "completed,ticks,block_utilization")
    dense = ServingEngine(cfg, params, max_seq=MAX_SEQ, slots=dense_slots)
    for r in reqs_dense:
        dense.submit(r)
    sd = dense.run()
    yield (f"serving_mixed,dense,{dense_slots},{budget_tokens},"
           f"{sd.peak_active_slots},{sd.completed},{sd.ticks},n/a")
    paged = ServingEngine(cfg, params, max_seq=MAX_SEQ, slots=paged_slots,
                          paged=True, block_size=BLOCK_SIZE,
                          num_blocks=num_blocks)
    for r in reqs_paged:
        paged.submit(r)
    sp = paged.run()
    util = sp.summary().get("block_utilization", 0.0)
    yield (f"serving_mixed,paged,{paged_slots},{budget_tokens},"
           f"{sp.peak_active_slots},{sp.completed},{sp.ticks},{util}")
    gain = sp.peak_active_slots / max(sd.peak_active_slots, 1)
    yield (f"serving_mixed_gain,paged_vs_dense_concurrency,{gain:.2f},"
           f"{'paged-admits-more' if sp.peak_active_slots > sd.peak_active_slots else 'NO-GAIN'}")
    # Correctness anchor: block-granular pool must reproduce the dense
    # pool's greedy tokens exactly (paged-vs-contiguous logits parity).
    match = all(a.output == b.output for a, b in zip(reqs_dense, reqs_paged))
    yield f"serving_mixed_parity,paged_vs_dense_outputs,{'ok' if match else 'MISMATCH'}"


def _shared_workload(cfg, rng, n_requests: int):
    """Prompts sharing a 48-token system prefix (3 full blocks = 75%) with
    divergent 15-token tails. Lifetime (63 prompt + 1 stored decode token)
    fills the 4th block exactly, so no request ever needs a growth block —
    concurrency is set purely by admission, and a starved pool waits
    head-of-line instead of overflow-truncating (which would make the
    shared/unshared output comparison meaningless)."""
    from repro.runtime.serve import Request
    sys_prefix = rng.integers(0, cfg.vocab_size, 48).astype(np.int32)
    return [Request(rid=i,
                    prompt=np.concatenate(
                        [sys_prefix,
                         rng.integers(0, cfg.vocab_size, 15).astype(np.int32)]),
                    max_new_tokens=2)
            for i in range(n_requests)]


def _shared_sweep(cfg, params, smoke: bool):
    from repro.runtime.serve import ServingEngine

    # Static heavy channels: the request-independent set every request
    # agrees on, so divergent-tail feature blocks alias safely. Parameter
    # shapes don't depend on the flag, so the same params serve both modes.
    scfg = dataclasses.replace(cfg, salca_static_channels=True)
    # Pool sized so the unshared engine packs floor(num_blocks/4) requests
    # while the shared engine pays 4 blocks once + 1 divergent-tail block
    # per further request (no growth blocks — see _shared_workload).
    n_requests = 5 if smoke else 12
    num_blocks = 8 if smoke else 16
    slots = 8 if smoke else 12
    yield ("serving_shared,mode,slots,num_blocks,peak_concurrent,completed,"
           "shared_blocks,cow_copies,memory_saved_tokens")
    results = {}
    for mode, share in (("unshared", False), ("shared", True)):
        rng = np.random.default_rng(11)
        reqs = _shared_workload(scfg, rng, n_requests)
        eng = ServingEngine(scfg, params, max_seq=MAX_SEQ, slots=slots,
                            paged=True, block_size=BLOCK_SIZE,
                            num_blocks=num_blocks, prefix_sharing=share)
        for r in reqs:
            eng.submit(r)
        st = eng.run()
        results[mode] = (reqs, st)
        saved = st.summary().get("memory_saved_tokens", 0)
        yield (f"serving_shared,{mode},{slots},{num_blocks},"
               f"{st.peak_active_slots},{st.completed},{st.shared_blocks},"
               f"{st.cow_copies},{saved}")
    (ru, su), (rs, ss) = results["unshared"], results["shared"]
    gain = ss.peak_active_slots / max(su.peak_active_slots, 1)
    yield (f"serving_shared_gain,shared_vs_unshared_concurrency,{gain:.2f},"
           f"{'shared-admits-more' if gain >= 2.0 else 'BELOW-2X'}")
    match = all(a.output == b.output for a, b in zip(ru, rs))
    yield f"serving_shared_parity,shared_vs_unshared_outputs,{'ok' if match else 'MISMATCH'}"
    # Correctness/acceptance gates — raise so benchmarks/run.py exits 1.
    if not match:
        raise RuntimeError("prefix sharing broke greedy-output parity")
    if ss.shared_blocks == 0:
        raise RuntimeError("shared sweep admitted no shared blocks")
    if gain < 2.0:
        raise RuntimeError(
            f"shared-prefix admission gain {gain:.2f} < 2.0 acceptance bar")


def _multitenant_sweep(cfg, params, smoke: bool):
    """Persistent prefix cache under a multi-tenant visit pattern: N tenant
    system prompts × M users × 2 visits, driven sequentially so every reuse
    crosses a request lifetime. Compares the cache-pinned engine against
    the non-persistent prefix-sharing engine on the same trace."""
    from repro.core.performance_model import cached_prefill_bytes_avoided
    from repro.runtime.serve import Request, ServingEngine

    scfg = dataclasses.replace(cfg, salca_static_channels=True)
    n_tenants, n_users = (2, 2) if smoke else (3, 3)
    num_blocks = 24 if smoke else 32
    rng = np.random.default_rng(31)
    tenants = [rng.integers(0, scfg.vocab_size, 48).astype(np.int32)
               for _ in range(n_tenants)]
    users = [[np.concatenate(
        [t, rng.integers(0, scfg.vocab_size, 15).astype(np.int32)])
        for _ in range(n_users)] for t in tenants]
    # Visit order: tenant-major first visits, then the same sequence again —
    # first visits exercise the tenant-prefix cache hit, second visits the
    # full-prompt zero-prefill adoption.
    trace = [p for tu in users for p in tu] * 2
    second_visits = len(trace) // 2
    warm_prompt = rng.integers(0, scfg.vocab_size, 63).astype(np.int32)

    def drive(eng):
        """Sequential: one request resident at a time; returns TTFTs."""
        # Equal-shape throwaway pair amortizes jit for prefill, decode AND
        # the adopt dispatch (the repeat is a warm hit on the cache engine).
        for j in (0, 1):
            eng.submit(Request(rid=100 + j, prompt=warm_prompt.copy(),
                               max_new_tokens=4))
            eng.run()
        eng.flush_prefix_cache()
        base = (eng.stats.cache_hits, eng.stats.cache_hit_blocks,
                eng.stats.zero_prefill_hits, eng.stats.cache_evictions)
        reqs, ttfts = [], []
        for i, p in enumerate(trace):
            r = Request(rid=i, prompt=p.copy(), max_new_tokens=4)
            t0 = time.time()
            eng.submit(r)
            eng.run()
            reqs.append(r)
            ttfts.append(r.first_token_time - t0)
        d = (eng.stats.cache_hits - base[0], eng.stats.cache_hit_blocks
             - base[1], eng.stats.zero_prefill_hits - base[2],
             eng.stats.cache_evictions - base[3])
        return reqs, ttfts, d

    yield ("serving_multitenant,mode,tenants,users,requests,cache_hits,"
           "cache_hit_blocks,zero_prefill_hits,ttft_warm_median_ms")
    results = {}
    for mode, persist in (("nonpersistent", False), ("persistent", True)):
        eng = ServingEngine(scfg, params, max_seq=MAX_SEQ, slots=4,
                            paged=True, block_size=BLOCK_SIZE,
                            num_blocks=num_blocks, prefix_sharing=True,
                            prefix_cache=persist)
        reqs, ttfts, (hits, hit_blocks, zero, evictions) = drive(eng)
        warm_med = 1e3 * float(np.median(ttfts[second_visits:]))
        results[mode] = (reqs, ttfts, hits, hit_blocks, zero)
        yield (f"serving_multitenant,{mode},{n_tenants},{n_users},"
               f"{len(reqs)},{hits},{hit_blocks},{zero},{warm_med:.2f}")
        if persist:
            blocks_per_prompt = -(-63 // BLOCK_SIZE)
            hit_rate = hit_blocks / (len(trace) * blocks_per_prompt)
            saved = eng.stats.summary().get("cache_saved_tokens", 0)
            avoided = cached_prefill_bytes_avoided(
                hit_blocks, d=scfg.resolved_head_dim,
                kv_heads=scfg.num_kv_heads, block_size=BLOCK_SIZE,
                layers=scfg.num_layers)
            yield (f"serving_multitenant_reuse,block_hit_rate,{hit_rate:.2f},"
                   f"memory_saved_tokens,{saved},"
                   f"prefill_bytes_avoided,{int(avoided)}")
            # Clean drain: flushing the cache must return the pool to full
            # and leave no dangling pin, node or cold payload behind.
            eng.flush_prefix_cache()
            rep = eng.check_invariants()
            drained = (rep.ok and not eng._cached and not eng._cold_cache
                       and sorted(eng._free_blocks)
                       == list(range(num_blocks)))
            yield (f"serving_multitenant_drain,flush_clean,"
                   f"{'ok' if drained else 'LEAK'}")
            if not drained:
                raise RuntimeError(
                    f"persistent cache leaked at drain: {rep.violations}")
    (rc, tc, *_), (rw, tw, hits, hit_blocks, zero) = \
        (results["nonpersistent"], results["persistent"])
    match = all(a.output == b.output for a, b in zip(rc, rw))
    yield (f"serving_multitenant_parity,persistent_vs_cold_outputs,"
           f"{'ok' if match else 'MISMATCH'}")
    ratio = float(np.median(tw[second_visits:])
                  / max(np.median(tc[second_visits:]), 1e-9))
    yield (f"serving_multitenant_ttft,warm_vs_cold_median,{ratio:.2f},"
           f"{'cache-collapses-ttft' if ratio <= 0.6 else 'ABOVE-0.6X'}")
    # Acceptance gates — raise so benchmarks/run.py exits 1.
    if not match:
        raise RuntimeError(
            "persistent prefix cache broke greedy-output parity")
    if zero < second_visits:
        raise RuntimeError(
            f"only {zero}/{second_visits} repeat visits admitted with "
            "zero prefill")
    if hits < second_visits:
        raise RuntimeError(
            f"cache hits {hits} below the {second_visits} repeat visits")
    if ratio > 0.6:
        raise RuntimeError(
            f"warm TTFT {ratio:.2f}x cold — above the 0.6x acceptance bar")


def _fused_sweep(cfg, params, smoke: bool):
    from repro.runtime.serve import ServingEngine

    dense_slots = 2 if smoke else 4
    budget_tokens = dense_slots * MAX_SEQ
    slots = 6 if smoke else 12
    num_blocks = budget_tokens // BLOCK_SIZE
    yield "serving_fused,mode,ticks,decode_ms_per_tick,decode_ms_per_token"
    results = {}
    for mode, fused in (("gather", False), ("fused", True)):
        eng = ServingEngine(cfg, params, max_seq=MAX_SEQ, slots=slots,
                            paged=True, block_size=BLOCK_SIZE,
                            num_blocks=num_blocks, fused_decode=fused)
        rng = np.random.default_rng(7)
        warm = _mixed_workload(cfg, rng, smoke)      # compiles prefill+decode
        for r in warm:
            eng.submit(r)
        eng.run()
        s0_decode, s0_ticks, s0_steps = (eng.stats.decode_s, eng.stats.ticks,
                                         eng.stats.decode_steps)
        rng = np.random.default_rng(11)
        reqs = _mixed_workload(cfg, rng, smoke)      # measured: steady-state
        for r in reqs:
            eng.submit(r)
        st = eng.run()
        ticks = st.ticks - s0_ticks
        ms_tick = 1e3 * (st.decode_s - s0_decode) / max(ticks, 1)
        ms_tok = 1e3 * (st.decode_s - s0_decode) / max(st.decode_steps - s0_steps, 1)
        results[mode] = reqs
        yield f"serving_fused,{mode},{ticks},{ms_tick:.3f},{ms_tok:.3f}"
    match = all(a.output == b.output
                for a, b in zip(results["gather"], results["fused"]))
    yield f"serving_fused_parity,fused_vs_gather_outputs,{'ok' if match else 'MISMATCH'}"
    if not match:
        raise RuntimeError("fused paged decode broke greedy-output parity")


def _capacity_sweep(cfg, params, smoke: bool):
    """Tiered-KV capacity: admitted concurrency at a FIXED HBM byte budget,
    fp16 pool vs int8 pool vs int8 pool + host spill.

    The budget buys `num_blocks = budget_bytes // block_bytes(dtype)` pool
    blocks, so the int8 pool holds ~1.7x the blocks of the fp16 pool at the
    same bytes; with requests sized to 5 blocks over their lifetime the
    fp16 pool admits 1 concurrent request and the int8 pool 3 (integer
    block math — the ≥ 2x acceptance gate). Adding the host tier lifts
    concurrency to the slot count: demand beyond the device pool spills.
    Gates (RAISE → benchmarks/run.py exits 1): int8-vs-fp16 greedy outputs
    bit-identical, int8 gain ≥ 2x, and the spill engine completes every
    request with zero overflows. The spill row also prints the predicted
    PCIe bytes next to the measured decode tick time (perf-model term).

    Runs at dtype=float32: the bf16 default quantizes logits coarsely
    enough that EXACT top-1 ties are common at this vocab size, and a tie
    makes greedy ill-defined — any storage precision (or summation order)
    can flip it. f32 logits make every greedy decision strict, so the
    parity gate tests the int8 pool, not tie-breaking luck."""
    from repro.core.cache import block_data_bytes, empty_paged_cache
    from repro.core.performance_model import spill_pcie_traffic
    from repro.models.blocks import salca_params_for
    from repro.runtime.serve import Request, ServingEngine

    cfg = dataclasses.replace(cfg, dtype="float32")
    from repro.models import get_model
    params = get_model(cfg).init(jax.random.PRNGKey(0))

    sp = salca_params_for(cfg, MAX_SEQ)
    r = sp.r(cfg.resolved_head_dim)

    def layer_block_bytes(dt):
        probe = empty_paged_cache(1, BLOCK_SIZE, 1, MAX_SEQ // BLOCK_SIZE,
                                  cfg.num_kv_heads, cfg.resolved_head_dim, r,
                                  kv_pool_dtype=dt)
        return block_data_bytes(probe)

    # Budget = 9 fp16 blocks' worth of bytes; each request holds 5 blocks
    # over its lifetime (72-token prompt + 8 stored decode tokens = 80).
    budget_bytes = 9 * layer_block_bytes("fp16")
    blocks_per_req = 5
    n_requests = 4 if smoke else 6

    def workload():
        rng = np.random.default_rng(17)
        return [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab_size, 72)
                        .astype(np.int32),
                        max_new_tokens=9)
                for i in range(n_requests)]

    yield ("serving_capacity,mode,block_bytes,num_blocks,peak_concurrent,"
           "completed,overflows,demotions,promotions,pcie_bytes_predicted")
    results = {}
    for mode, dt, spill in (("fp16", "fp16", False), ("int8", "int8", False),
                            ("int8_spill", "int8", True)):
        bb = layer_block_bytes(dt)
        num_blocks = int(budget_bytes // bb)
        eng = ServingEngine(cfg, params, max_seq=MAX_SEQ, slots=n_requests,
                            paged=True, block_size=BLOCK_SIZE,
                            num_blocks=num_blocks, kv_pool_dtype=dt,
                            host_spill=spill, demote_after=10**6,
                            spill_keep_recent=2)
        reqs = workload()
        for req in reqs:
            eng.submit(req)
        st = eng.run()
        results[mode] = (reqs, st)
        pcie = spill_pcie_traffic(getattr(eng, "_block_bytes", 0),
                                  st.demotions, st.promotions)
        yield (f"serving_capacity,{mode},{bb},{num_blocks},"
               f"{st.peak_active_slots},{st.completed},{st.overflows},"
               f"{st.demotions},{st.promotions},{int(pcie.bytes)}")
        if spill:
            yield (f"serving_capacity_pcie,predicted_bytes,{int(pcie.bytes)},"
                   f"predicted_s,{pcie.seconds:.6f},"
                   f"measured_decode_s,{st.decode_s:.4f}")
    (rf, sf), (ri, si) = results["fp16"], results["int8"]
    rs, ss = results["int8_spill"]
    gain = si.peak_active_slots / max(sf.peak_active_slots, 1)
    yield (f"serving_capacity_gain,int8_vs_fp16_concurrency,{gain:.2f},"
           f"{'int8-admits-more' if gain >= 2.0 else 'BELOW-2X'}")
    match = all(a.output == b.output for a, b in zip(rf, ri))
    yield (f"serving_capacity_parity,int8_vs_fp16_outputs,"
           f"{'ok' if match else 'MISMATCH'}")
    spill_match = all(a.output == b.output for a, b in zip(ri, rs))
    yield (f"serving_capacity_parity,spill_vs_hot_outputs,"
           f"{'ok' if spill_match else 'diverged-while-cold'}")
    # Acceptance gates — raise so benchmarks/run.py exits 1.
    if not match:
        raise RuntimeError(
            "int8 KV pool broke greedy top-1 agreement vs the fp16 pool")
    if gain < 2.0:
        raise RuntimeError(
            f"int8-pool admission gain {gain:.2f} < 2.0 acceptance bar "
            "(fixed-HBM concurrency must at least double)")
    if ss.overflows or ss.completed != n_requests:
        raise RuntimeError(
            f"host-spill engine overflowed ({ss.overflows}) or dropped "
            f"requests ({ss.completed}/{n_requests})")


def _sharded_sweep(cfg, params, smoke: bool):
    """Admitted long-context concurrency vs pool shard count, at a fixed
    per-device pool size — the capacity claim of the sharded page pools —
    plus the sharded-vs-unsharded greedy parity gate."""
    from repro.launch.mesh import make_mesh
    from repro.models.blocks import DecodeCtx
    from repro.runtime.serve import Request, ServingEngine

    ndev = len(jax.devices())
    if ndev < 4:
        yield ("serving_sharded,skipped,need>=4_devices,"
               "set_XLA_FLAGS=--xla_force_host_platform_device_count=8")
        return
    blocks_per_shard = 8                 # FIXED per-device pool slice
    n_requests = 8
    slots = n_requests
    # Each request needs 4 blocks over its lifetime (60 prompt + 3 stored
    # decode tokens = 63 ≤ 4·16), i.e. HALF of one shard's slice: 1 shard
    # packs 2 concurrently, 4 shards pack 8 — the linear-capacity regime.
    def workload():
        rng = np.random.default_rng(13)
        return [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab_size, 60)
                        .astype(np.int32),
                        max_new_tokens=4)
                for i in range(n_requests)]

    yield ("serving_sharded,shards,num_blocks,per_shard,peak_concurrent,"
           "completed,peak_shard_blocks")
    results = {}
    for shards in ((1, 4) if smoke else (1, 2, 4)):
        ctx = None
        if shards > 1:
            mesh = make_mesh((shards,), ("seq",))
            ctx = DecodeCtx(axis="seq", mesh=mesh)
        eng = ServingEngine(cfg, params, max_seq=MAX_SEQ, slots=slots,
                            ctx=ctx, paged=True, block_size=BLOCK_SIZE,
                            num_blocks=shards * blocks_per_shard)
        reqs = workload()
        for r in reqs:
            eng.submit(r)
        st = eng.run()
        results[shards] = (reqs, st)
        peak_shard = (st.peak_shard_blocks_in_use if shards > 1
                      else st.peak_blocks_in_use)
        yield (f"serving_sharded,{shards},{shards * blocks_per_shard},"
               f"{blocks_per_shard},{st.peak_active_slots},{st.completed},"
               f"{peak_shard}")
    gain = (results[4][1].peak_active_slots
            / max(results[1][1].peak_active_slots, 1))
    yield (f"serving_sharded_gain,4shards_vs_1_concurrency,{gain:.2f},"
           f"{'linear-capacity-scaling' if gain >= 3.0 else 'BELOW-3X'}")
    match = all(a.output == b.output
                for a, b in zip(results[1][0], results[4][0]))
    yield (f"serving_sharded_parity,sharded_vs_unsharded_outputs,"
           f"{'ok' if match else 'MISMATCH'}")
    # Acceptance gates — raise so benchmarks/run.py exits 1.
    if not match:
        raise RuntimeError("sharded paged engine broke greedy-output parity")
    if gain < 3.0:
        raise RuntimeError(
            f"sharded admission gain {gain:.2f} < 3.0 acceptance bar "
            "(capacity must scale ~linearly with shard count)")


def _bursty_trace(cfg, rng, n: int):
    """Poisson-arrival mixed-length trace; half the requests share a
    one-block system prefix (so parity covers prefix sharing + CoW)."""
    from repro.runtime.serve import Request
    prefix = rng.integers(0, cfg.vocab_size, BLOCK_SIZE).astype(np.int32)
    lens = (12, 24, 48, 88)
    t, trace = 0.0, []
    for i in range(n):
        t += float(rng.exponential(6.0))        # overload: λ ≫ service rate
        pl = int(lens[int(rng.integers(len(lens)))])
        body = rng.integers(0, cfg.vocab_size, pl).astype(np.int32)
        if i % 2 == 0:
            body = np.concatenate([prefix, body[:-BLOCK_SIZE]]) \
                if pl > BLOCK_SIZE else body
        trace.append((t, Request(rid=i, prompt=body, max_new_tokens=16)))
    return trace


def _simulate_bursty(eng, trace, max_passes: int = 200_000):
    """Drive the engine pass by pass against a simulated clock: one pass
    costs (prompt tokens prefilled this pass) + 1 decode-tick unit. The
    unit charge makes head-of-line blocking measurable — a monolithic
    admission stalls every active slot for the whole prompt, a chunked
    admission for at most `prefill_chunk` tokens. Returns per-request TTFT
    and inter-token gaps in those units."""
    from collections import deque
    pending = deque(trace)
    reqs = [r for _, r in trace]
    arrive = {r.rid: at for at, r in trace}
    t = 0.0
    ttft: dict[int, float] = {}
    gaps: list[float] = []
    last_len = {r.rid: 0 for r in reqs}
    last_t: dict[int, float] = {}

    def note(now):
        for r in reqs:
            n = len(r.output)
            if n > last_len[r.rid]:
                if r.rid not in ttft:
                    ttft[r.rid] = now - arrive[r.rid]
                elif r.rid in last_t:
                    gaps.append(now - last_t[r.rid])
                last_t[r.rid] = now
                last_len[r.rid] = n
            elif n < last_len[r.rid]:           # preempted: output cleared
                last_len[r.rid] = n
                last_t.pop(r.rid, None)

    for _ in range(max_passes):
        while pending and pending[0][0] <= t:
            eng.submit(pending.popleft()[1])
        if not (eng._queue or eng._active or eng._inflight is not None):
            if not pending:
                return ttft, gaps
            t = pending[0][0]
            continue
        p0 = eng.stats.prefill_tokens
        eng._admit()
        t += float(eng.stats.prefill_tokens - p0)
        note(t)
        if eng._active:
            eng._tick()
            t += 1.0
            note(t)
    raise RuntimeError("bursty simulation did not drain")


def _bursty_sweep(cfg, params, smoke: bool):
    """Bursty Poisson arrivals against a tight block pool: the continuous-
    batching acceptance gates. Monolithic admission reserves the whole
    prompt's blocks at once — under memory pressure a long prompt waits at
    the head of the queue until enough blocks are free simultaneously,
    starving everything behind it. Chunked admission charges one chunk's
    blocks at a time, consuming frees as decode produces them, and the
    budgeted chunks bound how long any pass stalls decode. Gates (RAISE so
    benchmarks/run.py exits 1):

      * zero `overflow` stop reasons with preemption on (both engines);
      * greedy outputs bit-identical to the big-pool non-preempting paged
        engine, prefix sharing + CoW included;
      * chunked TTFT p95 strictly below the monolithic baseline.
    """
    from repro.runtime.serve import ServingEngine

    scfg = dataclasses.replace(cfg, salca_static_channels=True)
    n = 10 if smoke else 24
    slots, num_blocks, chunk = 3, 10, 8
    yield ("serving_bursty,mode,requests,ttft_p50,ttft_p95,itl_p50,itl_p95,"
           "preemptions,chunk_stalls,overflows,completed")
    results = {}
    for mode in ("reference", "monolithic", "chunked"):
        rng = np.random.default_rng(23)
        trace = _bursty_trace(scfg, rng, n)
        kw = dict(paged=True, block_size=BLOCK_SIZE, prefix_sharing=True)
        if mode == "reference":      # big pool, no preemption: parity target
            eng = ServingEngine(scfg, params, max_seq=MAX_SEQ, slots=slots,
                                num_blocks=slots * (MAX_SEQ // BLOCK_SIZE),
                                **kw)
        else:
            eng = ServingEngine(scfg, params, max_seq=MAX_SEQ, slots=slots,
                                num_blocks=num_blocks, preempt=True,
                                prefill_chunk=chunk if mode == "chunked"
                                else None, **kw)
        ttft, gaps = _simulate_bursty(eng, trace)
        st = eng.stats
        reqs = [r for _, r in trace]
        results[mode] = (reqs, st, ttft, gaps)
        tv = sorted(ttft.values())
        gv = sorted(gaps) or [0.0]
        pct = lambda v, q: v[min(int(q * len(v)), len(v) - 1)]
        yield (f"serving_bursty,{mode},{n},{pct(tv, 0.50):.0f},"
               f"{pct(tv, 0.95):.0f},{pct(gv, 0.50):.0f},{pct(gv, 0.95):.0f},"
               f"{st.preemptions},{st.chunk_stalls},{st.overflows},"
               f"{st.completed}")
    ref = results["reference"][0]
    p95 = {m: sorted(results[m][2].values())[
        min(int(0.95 * n), n - 1)] for m in results}
    ratio = p95["chunked"] / max(p95["monolithic"], 1e-9)
    yield (f"serving_bursty_ttft,chunked_vs_monolithic_p95,{ratio:.2f},"
           f"{'bounded' if ratio < 1.0 else 'ABOVE-MONOLITHIC'}")
    for mode in ("monolithic", "chunked"):
        reqs, st, _, _ = results[mode]
        match = all(a.output == b.output for a, b in zip(ref, reqs))
        yield (f"serving_bursty_parity,{mode}_vs_reference_outputs,"
               f"{'ok' if match else 'MISMATCH'}")
        # Acceptance gates — raise so benchmarks/run.py exits 1.
        if st.overflows or any(r.stop_reason == "overflow" for r in reqs):
            raise RuntimeError(
                f"bursty {mode}: overflow stop with preemption enabled")
        if not match:
            raise RuntimeError(
                f"bursty {mode}: preemption broke greedy-output parity")
        if st.completed != n:
            raise RuntimeError(f"bursty {mode}: {st.completed}/{n} completed")
    if ratio >= 1.0:
        raise RuntimeError(
            f"bursty: chunked TTFT p95 {p95['chunked']:.0f} not below "
            f"monolithic {p95['monolithic']:.0f}")


def _overload_sweep(cfg, params, smoke: bool):
    """Sustained overload (arrivals ≫ service rate) against an unbounded
    vs a `max_queue`-bounded engine on the simulated clock. An unbounded
    queue converts overload into unbounded waiting: every admitted request
    pays the whole backlog ahead of it, so TTFT p95 grows with the trace.
    A bounded queue sheds at submit (`stop_reason="rejected"`) and keeps
    the backlog — and therefore admitted-TTFT — flat. Gates (RAISE so
    benchmarks/run.py exits 1):

      * the bounded engine actually sheds (rejections > 0) but rejects
        < 30% of the trace;
      * every non-rejected request completes, zero overflow stops;
      * bounded admitted-TTFT p95 ≤ the unbounded p95 (the shed requests
        are the ones that would have blown the latency budget).
    """
    from repro.runtime.serve import ServingEngine

    scfg = dataclasses.replace(cfg, salca_static_channels=True)
    n = 12 if smoke else 24
    slots, num_blocks, cap = 3, 10, 6
    yield ("serving_overload,mode,requests,rejected,completed,ttft_p50,"
           "ttft_p95,preemptions,overflows")
    p95 = {}
    for mode in ("unbounded", "bounded"):
        rng = np.random.default_rng(29)
        trace = _bursty_trace(scfg, rng, n)
        for _, r in trace:                   # heavier overload than bursty:
            r.max_new_tokens = 24            # longer service per admission
        eng = ServingEngine(scfg, params, max_seq=MAX_SEQ, slots=slots,
                            num_blocks=num_blocks, paged=True,
                            block_size=BLOCK_SIZE, prefix_sharing=True,
                            preempt=True,
                            max_queue=cap if mode == "bounded" else None)
        ttft, _ = _simulate_bursty(eng, trace)
        st = eng.stats
        reqs = [r for _, r in trace]
        rejected = [r for r in reqs if r.stop_reason == "rejected"]
        tv = sorted(ttft.values()) or [0.0]
        pct = lambda v, q: v[min(int(q * len(v)), len(v) - 1)]
        p95[mode] = pct(tv, 0.95)
        yield (f"serving_overload,{mode},{n},{len(rejected)},{st.completed},"
               f"{pct(tv, 0.50):.0f},{p95[mode]:.0f},{st.preemptions},"
               f"{st.overflows}")
        # Acceptance gates — raise so benchmarks/run.py exits 1.
        if st.overflows:
            raise RuntimeError(f"overload {mode}: overflow stop with "
                               "preemption enabled")
        if st.rejections != len(rejected):
            raise RuntimeError(f"overload {mode}: rejections counter "
                               f"{st.rejections} != {len(rejected)} shed")
        survivors = [r for r in reqs if r.stop_reason != "rejected"]
        if not all(r.stop_reason in ("length", "stop") for r in survivors):
            raise RuntimeError(f"overload {mode}: admitted request did not "
                               "complete normally")
        if mode == "bounded":
            if not rejected:
                raise RuntimeError("overload bounded: queue cap never shed "
                                   "(trace is not overloaded)")
            if len(rejected) >= 0.30 * n:
                raise RuntimeError(
                    f"overload bounded: {len(rejected)}/{n} rejected — "
                    "shedding above the 30% acceptance bar")
        elif rejected:
            raise RuntimeError("overload unbounded: rejected without a cap")
    ratio = p95["bounded"] / max(p95["unbounded"], 1e-9)
    yield (f"serving_overload_ttft,bounded_vs_unbounded_p95,{ratio:.2f},"
           f"{'bounded' if ratio <= 1.0 else 'ABOVE-UNBOUNDED'}")
    if ratio > 1.0:
        raise RuntimeError(
            f"overload: bounded TTFT p95 {p95['bounded']:.0f} above "
            f"unbounded {p95['unbounded']:.0f} — shedding bought nothing")


def run(smoke: bool = False):
    from repro.configs import get_config
    from repro.models import get_model

    cfg = get_config("qwen3-0.6b").reduced()
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    yield from _slots_sweep(cfg, params, rng, smoke)
    yield from _mixed_sweep(cfg, params, smoke)
    yield from _shared_sweep(cfg, params, smoke)
    yield from _multitenant_sweep(cfg, params, smoke)
    yield from _fused_sweep(cfg, params, smoke)
    yield from _capacity_sweep(cfg, params, smoke)
    yield from _sharded_sweep(cfg, params, smoke)
    yield from _bursty_sweep(cfg, params, smoke)
    yield from _overload_sweep(cfg, params, smoke)


if __name__ == "__main__":
    t0 = time.time()
    for row in run():
        print(row, flush=True)
    print(f"# done in {time.time() - t0:.1f}s")
