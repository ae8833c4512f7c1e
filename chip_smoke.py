#!/usr/bin/env python3
"""Chip smoke test: the paged Salca serving path, end to end, on a TPU.

    python chip_smoke.py                      # one chip (the default)
    python chip_smoke.py --four-chips         # the sharded paged pool, 4 chips
    python chip_smoke.py --precision-control  # fused vs XLA tick, bf16 and f32

One chip: qwen3-0.6b at its published widths with seeded random weights and
static heavy channels, served through the launcher's engine builder
(`repro.launch.serve.build_engine`) on the main path — paged int8 block pool
of 32-token blocks, fused decode tick (Pallas kernels), prefix sharing +
persistent prefix cache, 512-token chunked prefill, 4 slots of 16k tokens.
Six requests of 2k–8k prompt tokens, half of them behind one 1k-token
system prefix, 16 new tokens each. Checks, in order:

* one decode attention call at the engine's pool shapes (seeded random
  K/V, int8, int4 and fp16 pools), Pallas kernels vs the XLA path:
  relevance scores bit-identical, the same selection, outputs within
  `ATTENTION_RTOL`;
* teacher-forced layers: at every decode tick that has more active slots
  than any before, the tick's 28 layers are walked on the XLA path, and at
  each layer both attention paths get the same query and the same pool —
  the same selected tokens and threshold, outputs within `ATTENTION_RTOL`;
* the engine's own checks (all requests complete, no overflow,
  decode_calls == ticks, a prefix-cache hit, Pallas kernels in the
  compiled tick);
* the same requests on an engine whose decode tick is the plain XLA path:
  the prefill token is the same (both engines share the prefill program),
  and the first decode-tick logits agree within `LOGIT_BOUND`. Later token
  divergences are printed with their logit margin.

Four chips: only the block pool sharded over a 4-device mesh, serving one
context longer than one shard's slice, against the unsharded paged engine
on one chip of the same process: teacher-forced layers (the sharded island
vs the unsharded fused attention on the same pool), the pool's blocks on all
four devices, and greedy tokens equal.

Precision control: the first decode tick of the one-chip workload through
four engines — fused and XLA tick, bf16 and f32 compute — to read how far
the decode paths drift apart end to end against how far bf16 compute
drifts from f32 (the readings `LOGIT_BOUND` is set from).

Everything runs in this one process. The script refuses to run when jax
finds no TPU. Lines tagged [setup] are set-up observations (compile and
wall times, memory), not metrics. Any failed check exits non-zero; the
last line of a passing run is one JSON object.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent
SEED = 0
ARCH = "qwen3-0.6b"
ENGINE = dict(slots=4, max_seq=16384, paged=True, block_size=32,
              prefix_cache=True, prefill_chunk=512)
PROMPT_LENGTHS = [2048, 5000, 6200, 7900, 3100, 4500]   # request 0 shortest
SHARED = {0, 4, 5}           # requests that open with the system prefix
SYSTEM_LEN = 1024
NEW_TOKENS = 16
# Four chips: 32 blocks of 32 tokens per chip; a 3500-token prompt needs 110
# blocks, more than three chips' slices, so the context spans all four.
SHARDS, PER_SHARD, SHARDED_PROMPT = 4, 32, 3500
# The attention check's pool: (slot, tokens) of 3 of its 4 slots.
ATTENTION_SLOTS = ((0, 2049), (1, 5001), (3, 7900))

# One decode attention call, two paths on the same query and pool: the same
# selection, and exact attention that differs only in f32 summation order
# (both run full-f32 matmuls) — 1e-5 relative to the output's largest
# magnitude.
ATTENTION_RTOL = 1e-5
# First decode tick of the whole model, fused vs XLA tick: max |logit diff|
# over the real vocabulary (logits here reach ~3). The two ticks agree layer
# by layer (the teacher-forced check), yet run free their ~1e-6 differences
# grow over 28 layers, in bf16 and in f32 alike. Readings on a v5e (PERF.md
# §6): fused vs XLA at most 0.217; XLA bf16 vs XLA f32, the deployment's own
# precision noise, at least 0.591. The bound sits between them, at about
# half the smaller control reading.
LOGIT_BOUND = 0.3


class SmokeFailure(AssertionError):
    """A check of the smoke failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def note(msg: str) -> None:
    print(f"[setup] {msg}", flush=True)


class TickRecorder:
    """Stands in for an engine's jitted decode tick: keeps the logits row of
    every active slot, keyed (request id, index of the token it yields), and
    the wall time of each tick (until its tokens are ready). ``probe`` runs
    on a tick's inputs before the tick consumes (donates) its state, at
    every tick with more active slots than any tick before it."""

    def __init__(self, engine, probe=None):
        self.engine, self.fn, self.probe = engine, engine._decode, probe
        self.rows: dict[tuple[int, int], object] = {}
        self.seconds: list[float] = []
        self.probed = 0
        engine._decode = self

    def __call__(self, params, state, tokens, active):
        who = {slot: (req.rid, len(req.output))
               for slot, req in self.engine._active.items()}
        act = np.asarray(active)
        if self.probe is not None and act.sum() > self.probed:
            self.probed = int(act.sum())
            self.probe(state, tokens, active)
        t0 = time.perf_counter()
        out = self.fn(params, state, tokens, active)
        out[0].block_until_ready()
        self.seconds.append(time.perf_counter() - t0)
        for slot, key in who.items():
            if act[slot]:
                self.rows[key] = out[1][slot]
        return out


def compile_tick(engine, label: str):
    """AOT-compile the engine's decode tick on its live state; returns the
    compiled text. The jitted tick compiles the same program again on its
    first call (a persistent-cache hit when the cache is on)."""
    import jax.numpy as jnp
    slots = engine.slots
    t0 = time.perf_counter()
    compiled = engine._decode.lower(
        engine.params, engine._state, jnp.zeros((slots,), jnp.int32),
        jnp.ones((slots,), bool)).compile()
    note(f"{label}: decode tick compile {time.perf_counter() - t0:.3f} s")
    mem = compiled.memory_analysis()
    if mem is not None:
        note(f"{label}: decode tick memory_analysis: args "
             f"{mem.argument_size_in_bytes} B, temps {mem.temp_size_in_bytes} B, "
             f"outputs {mem.output_size_in_bytes} B, aliased "
             f"{mem.alias_size_in_bytes} B")
    return compiled.as_text()


def serve(engine, requests, label: str, probe=None) -> TickRecorder:
    """Submit fresh copies of ``requests``, run the engine to completion."""
    from repro.runtime.serve import Request
    rec = TickRecorder(engine, probe)
    reqs = [Request(rid=r.rid, prompt=r.prompt.copy(),
                    max_new_tokens=r.max_new_tokens) for r in requests]
    t0 = time.perf_counter()
    for r in reqs:
        engine.submit(r)
    stats = engine.run()
    wall = time.perf_counter() - t0
    decode_s = sum(rec.seconds)
    note(f"{label}: run {wall:.3f} s; decode {len(rec.seconds)} ticks, median "
         f"{1e3 * float(np.median(rec.seconds)):.3f} ms/tick (first tick "
         f"{1e3 * rec.seconds[0]:.3f} ms); prefill, admission and probes "
         f"{wall - decode_s:.3f} s")
    s = stats.summary()
    for r in reqs:
        check(r.stop_reason in ("length", "stop"),
              f"{label}: request {r.rid} stopped with {r.stop_reason!r}")
        check(len(r.output) == r.max_new_tokens,
              f"{label}: request {r.rid} made {len(r.output)} tokens")
    check(s["completed"] == len(reqs) and s["overflows"] == 0,
          f"{label}: completed {s['completed']}/{len(reqs)}, "
          f"overflows {s['overflows']}")
    check(s["decode_calls"] == s["ticks"],
          f"{label}: {s['decode_calls']} decode calls for {s['ticks']} ticks")
    note(f"{label}: stats " + json.dumps(
        {k: s[k] for k in ("ticks", "decode_calls", "prefill_chunks",
                           "prefix_hits", "cache_hits", "cache_hit_blocks",
                           "zero_prefill_hits", "peak_blocks_in_use")}))
    rec.outputs = {r.rid: list(r.output) for r in reqs}
    rec.stats = s
    rec.engine = rec.fn = rec.probe = None   # let the engine (and its pool) go
    return rec


def one_chip_requests(vocab: int, new_tokens: int = NEW_TOKENS):
    """Six requests, 2k–8k prompt tokens; 0, 4 and 5 open with one shared
    1k-token system prefix. Request 0 is the shortest, so it finishes first
    and its prefix blocks are cache-pinned when request 4 is admitted."""
    from repro.runtime.serve import Request
    rng = np.random.default_rng(SEED)
    system = rng.integers(0, vocab, SYSTEM_LEN).astype(np.int32)
    reqs = []
    for rid, n in enumerate(PROMPT_LENGTHS):
        if rid in SHARED:
            tail = rng.integers(0, vocab, n - system.size).astype(np.int32)
            prompt = np.concatenate([system, tail])
        else:
            prompt = rng.integers(0, vocab, n).astype(np.int32)
        reqs.append(Request(rid=rid, prompt=prompt, max_new_tokens=new_tokens))
    return reqs


def first_step_diffs(a: TickRecorder, b: TickRecorder, vocab: int) -> dict:
    """Per request: max |logit diff| of the first decode tick (the ``vocab``
    real entries, not the padding), or None where the prefill tokens differ
    (the two ticks then decode different inputs)."""
    out = {}
    for rid, out_a in sorted(a.outputs.items()):
        if out_a[0] != b.outputs[rid][0]:
            out[rid] = None
            continue
        la = np.asarray(a.rows[(rid, 1)], np.float32)[:vocab]
        lb = np.asarray(b.rows[(rid, 1)], np.float32)[:vocab]
        check(np.isfinite(la).all() and np.isfinite(lb).all(),
              f"request {rid}: non-finite first-step logits")
        out[rid] = float(np.max(np.abs(la - lb)))
    return out


def compare(fused: TickRecorder, xla: TickRecorder, vocab: int) -> None:
    """The prefill token equal (one program on both engines); first
    decode-tick logits within `LOGIT_BOUND`; later divergences reported with
    the XLA logits' top-1/top-2 margin."""
    diffs = first_step_diffs(fused, xla, vocab)
    for rid, out_f in sorted(fused.outputs.items()):
        out_x = xla.outputs[rid]
        check(out_f[0] == out_x[0], f"request {rid}: prefill token "
              f"{out_f[0]} (fused engine) vs {out_x[0]} (xla engine)")
        top = float(np.max(np.abs(np.asarray(xla.rows[(rid, 1)],
                                             np.float32)[:vocab])))
        print(f"request {rid}: first decode tick max|logit diff| "
              f"{diffs[rid]:.6g} (bound {LOGIT_BOUND:g}, max|logit| "
              f"{top:.6g})", flush=True)
        for n in range(1, len(out_f)):
            if out_f[n] != out_x[n]:
                row = np.sort(np.asarray(xla.rows[(rid, n)], np.float32)[:vocab])
                print(f"request {rid}: tokens diverge at step {n}: fused "
                      f"{out_f[n]} vs xla {out_x[n]}; xla top-1/top-2 margin "
                      f"{float(row[-1] - row[-2]):.6g}", flush=True)
                break
        else:
            print(f"request {rid}: all {len(out_f)} tokens match", flush=True)
    worst = max(diffs.values())
    check(worst <= LOGIT_BOUND,
          f"first decode tick logits differ by {worst:.6g} > {LOGIT_BOUND:g}")


def selection_mask(sel, max_seq: int):
    """A Selection as a dense (S, KV, max_seq) int32 0/1 mask."""
    import jax.numpy as jnp
    s, kv, _ = sel.indices.shape
    return jnp.zeros((s, kv, max_seq), jnp.int32).at[
        jnp.arange(s)[:, None, None], jnp.arange(kv)[None, :, None],
        sel.indices].add(sel.mask.astype(jnp.int32))


def paged_pair(salca):
    """The one-chip attention pair: fused (Pallas) and XLA paged attention,
    each ``(q, pool) -> (out, selection mask, threshold)``."""
    import jax

    from repro.core.attention import salca_decode_attention_paged

    def make(fused):
        @jax.jit
        def fn(q, pool):
            o, sel = salca_decode_attention_paged(
                q, pool, salca, return_selection=True, fused=fused)
            return o, selection_mask(sel, pool.max_seq), sel.threshold
        return fn
    return [("fused", make(True)), ("xla", make(False))]


def teacher_forced(cfg, params, state, tokens, active, pair, label: str) -> None:
    """Walk one decode tick's layers on the XLA path (the teacher). At every
    layer, give both attention functions of ``pair(salca)`` the layer's query
    and its pool after the token's append, and check, over the active slots,
    the same selected tokens and threshold and outputs within
    `ATTENTION_RTOL`. The tick's own scan is not used, so a difference
    cannot carry from one layer into the next."""
    import jax

    from repro.flags import perf_flags
    from repro.models import blocks as B
    from repro.models.common import cdtype, embed_tokens, rmsnorm

    check(cfg.layer_pattern == "A" and not state.tail_states,
          f"{label}: teacher-forced check expects an all-attention stack")
    layers_p, layers_s = params["periods"][0], state.period_states[0]
    salca = B.salca_params_for(cfg, max(layers_s.max_seq, 128))
    fns = pair(salca)

    @jax.jit
    def step(layers_p, layers_s, i, h, pos, active):
        lp = jax.tree.map(lambda x: x[i], layers_p)
        st = jax.tree.map(lambda x: x[i], layers_s)
        q = B.attn_decode_qkv(lp["attn"], rmsnorm(lp["ln1"], h, cfg.norm_eps),
                              cfg, pos)[0]
        with perf_flags(paged_fused_decode=False):
            h, st = B.block_decode(lp, "A", h, st, cfg, pos, B.DecodeCtx(),
                                   salca, active)
        return h, st, q

    act = np.asarray(active)
    h = embed_tokens(params["embed"], tokens).astype(cdtype(cfg))
    worst, total, bad = (0.0, -1), 0, []
    t0 = time.perf_counter()
    for i in range(cfg.num_layers):
        h, pool, q = step(layers_p, layers_s, i, h, state.pos, active)
        (na, (oa, ma, ta)), (nb, (ob, mb, tb)) = [
            (name, [np.asarray(x)[act] for x in fn(q, pool)])
            for name, fn in fns]
        rel = float(np.max(np.abs(oa - ob)) / np.max(np.abs(ob)))
        worst, total = max(worst, (rel, i)), total + int(ma.sum())
        if not (np.array_equal(ta, tb) and np.array_equal(ma, mb)):
            bad.append(f"layer {i}: {na} and {nb} select different tokens "
                       f"({int(ma.sum())} vs {int(mb.sum())}, "
                       f"{int((ma != mb).sum())} differ; rel diff {rel:.3g})")
        elif rel > ATTENTION_RTOL:
            bad.append(f"layer {i}: {na} vs {nb} attention outputs differ by "
                       f"{rel:.3g} > {ATTENTION_RTOL:g}")
    print(f"{label}: teacher-forced {cfg.num_layers} layers x "
          f"{int(act.sum())} active slots: {cfg.num_layers - len(bad)} layers "
          f"with the same selection ({total} tokens) and outputs within "
          f"{ATTENTION_RTOL:g}; largest rel diff {worst[0]:.3g} (layer "
          f"{worst[1]}) [{time.perf_counter() - t0:.1f} s]", flush=True)
    for line in bad:
        print(f"{label}: teacher-forced {line}", flush=True)
    check(not bad, f"{label}: teacher-forced check failed in {len(bad)} layers")


def attention_check(cfg, kv_pool_dtype: str) -> None:
    """One paged decode attention call, Pallas kernels vs the XLA path, at
    the engine's pool shapes: 4 slots (one idle) holding 2049, 5001 and 7900
    seeded random tokens on scrambled blocks of a 1024-block pool."""
    import jax
    import jax.numpy as jnp

    from repro.core import (SalcaParams, empty_paged_cache, prefill_cache,
                            prefill_into_pages)
    from repro.core.selection import (estimate_relevance_paged,
                                      query_heavy_features)

    kv, hd, bs, slots = cfg.num_kv_heads, cfg.head_dim, ENGINE["block_size"], 4
    g, max_seq = cfg.num_heads // kv, ENGINE["max_seq"]
    mb = max_seq // bs
    num_blocks = slots * mb // 2
    params = SalcaParams.for_seq(max_seq)
    rng = np.random.default_rng(SEED)
    pool = empty_paged_cache(num_blocks, bs, slots, mb, kv, hd, params.r(hd),
                             kv_pool_dtype=kv_pool_dtype)
    free = rng.permutation(num_blocks)
    for slot, n in ATTENTION_SLOTS:
        k = jnp.asarray(rng.normal(size=(1, n, kv, hd)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, n, kv, hd)), jnp.float32)
        pages = np.full(mb, -1, np.int32)
        pages[:-(-n // bs)], free = free[:-(-n // bs)], free[-(-n // bs):]
        pool = prefill_into_pages(pool, prefill_cache(k, v, max_seq, params),
                                  slot, jnp.asarray(pages))
    q = jnp.asarray(rng.normal(size=(slots, kv * g, hd)) * 3, jnp.float32)
    qf = query_heavy_features(q, pool.heavy_idx, g)
    scores = [np.asarray(jax.jit(lambda qf, pool, impl=impl:
                                 estimate_relevance_paged(qf, pool, g,
                                                          impl=impl))(qf, pool))
              for impl in ("pallas", "ref")]
    valid = np.asarray(pool.valid_mask())[:, None, :]
    check(np.array_equal(np.where(valid, scores[0], 0),
                         np.where(valid, scores[1], 0)),
          f"attention check [{kv_pool_dtype}]: Pallas relevance scores "
          "differ from XLA")
    (o_p, m_p, t_p), (o_x, m_x, t_x) = [fn(q, pool)
                                        for _, fn in paged_pair(params)]
    check(bool(jnp.array_equal(m_p, m_x) & jnp.array_equal(t_p, t_x)),
          f"attention check [{kv_pool_dtype}]: selected tokens differ")
    rel = float(jnp.max(jnp.abs(o_p - o_x)) / jnp.max(jnp.abs(o_x)))
    print(f"attention check [{kv_pool_dtype}]: scores bit-identical, same "
          f"selection ({int(m_x.sum())} tokens), outputs rel diff {rel:.3g} "
          f"(bound {ATTENTION_RTOL:g})", flush=True)
    check(rel <= ATTENTION_RTOL,
          f"attention check [{kv_pool_dtype}]: outputs differ by {rel:.3g}")


def smoke_config(dtype: str = "bfloat16"):
    """qwen3-0.6b at published widths, static heavy channels (chunked
    prefill and zero-prefill prefix-cache hits need them)."""
    from repro.configs import get_config
    return dataclasses.replace(get_config(ARCH), salca_static_channels=True,
                               dtype=dtype)


def random_params(cfg):
    import jax

    from repro.models import get_model
    t0 = time.perf_counter()
    params = jax.jit(get_model(cfg).init)(jax.random.PRNGKey(SEED))
    jax.block_until_ready(params)
    note(f"random {ARCH} {cfg.dtype} parameters: "
         f"{time.perf_counter() - t0:.3f} s")
    return params


def run_engine(cfg, params, requests, label: str, *, fused: bool,
               probe: bool) -> TickRecorder:
    """Build one engine through the launcher's builder, check what its
    compiled tick holds, serve ``requests`` on it, release it."""
    from repro.launch.serve import build_engine
    engine = build_engine(cfg, params, fused_decode=fused, **ENGINE)
    text = compile_tick(engine, label)
    check(("tpu_custom_call" in text) == fused,
          f"{label}: Pallas kernels {'missing from' if fused else 'in'} "
          "the compiled decode tick")
    hook = None
    if probe:
        hook = lambda state, tokens, active: teacher_forced(
            cfg, params, state, tokens, active, paged_pair, label)
    rec = serve(engine, requests, label, hook)
    if probe:
        check(rec.probed > 0, f"{label}: the teacher-forced check never ran")
    del engine
    gc.collect()
    return rec


def one_chip() -> None:
    cfg = smoke_config()
    for dt in ("int8", "int4", "fp16"):
        attention_check(cfg, dt)
    params = random_params(cfg)
    requests = one_chip_requests(cfg.vocab_size)
    fused = run_engine(cfg, params, requests, "fused", fused=True, probe=True)
    check(fused.stats["cache_hits"] >= 1, "fused: no prefix-cache hit")
    xla = run_engine(cfg, params, requests, "xla", fused=False, probe=False)
    compare(fused, xla, cfg.vocab_size)


def precision_control() -> None:
    """First decode tick of the one-chip workload through the fused and the
    XLA tick, in bf16 and in f32 compute; prints the max |logit diff| of
    each pair per request. Readings only: no bound is checked here besides
    the teacher-forced layers of the f32 fused engine."""
    readings = {}
    recs = {}
    for dtype in ("bfloat16", "float32"):
        cfg = smoke_config(dtype)
        params = random_params(cfg)
        requests = one_chip_requests(cfg.vocab_size, new_tokens=2)
        for fused in (True, False):
            label = f"{'fused' if fused else 'xla'}-{dtype}"
            recs[label] = run_engine(cfg, params, requests, label, fused=fused,
                                     probe=fused and dtype == "float32")
        del params
        gc.collect()
    vocab = cfg.vocab_size
    for a, b in (("fused-bfloat16", "xla-bfloat16"),
                 ("fused-float32", "xla-float32"),
                 ("xla-bfloat16", "xla-float32")):
        readings[f"{a} vs {b}"] = first_step_diffs(recs[a], recs[b], vocab)
    for pair, diffs in readings.items():
        shown = ", ".join(f"{rid}: " + ("prefill tokens differ" if d is None
                                        else f"{d:.6g}")
                          for rid, d in diffs.items())
        print(f"first decode tick max|logit diff|, {pair}: {shown}", flush=True)


def four_chips() -> None:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.cache import PagedSalcaCache
    from repro.core.sp_decode import sp_salca_decode_paged
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import build_engine
    from repro.models.blocks import DecodeCtx, paged_cache_pspec
    from repro.runtime.serve import Request

    devices = jax.devices()
    check(len(devices) == 4, f"--four-chips needs 4 devices, found {len(devices)}")
    cfg = smoke_config()
    params = random_params(cfg)
    shards, per_shard, bs = SHARDS, PER_SHARD, ENGINE["block_size"]
    prompt = np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, SHARDED_PROMPT).astype(np.int32)
    max_seq = -(-(SHARDED_PROMPT + NEW_TOKENS) // 1024) * 1024
    engine_kw = dict(slots=1, max_seq=max_seq, paged=True, block_size=bs,
                     num_blocks=shards * per_shard)
    mesh = make_mesh((shards,), ("seq",), devices=devices)
    ctx = DecodeCtx(axis="seq", mesh=mesh)
    pspec = paged_cache_pspec(ctx)
    placed = jax.tree.map(lambda sp: NamedSharding(mesh, sp), pspec,
                          is_leaf=lambda x: isinstance(x, P))

    def island_pair(salca):
        """Unsharded fused attention on one chip vs the sharded island on
        the mesh, over the same pool."""
        rep = P(None, None, None)

        def island(q, pool):
            o, sel = sp_salca_decode_paged(q, pool, salca, "seq",
                                           return_selection=True)
            mask = jax.lax.psum(selection_mask(sel, pool.max_seq), "seq")
            return o, mask, sel.threshold

        sharded = jax.jit(jax.shard_map(
            island, mesh=mesh, in_specs=(rep, pspec),
            out_specs=(rep, rep, P(None, None)), check_vma=False))
        unsharded = dict(paged_pair(salca))["fused"]
        return [("unsharded", unsharded),
                ("sharded", lambda q, pool: sharded(
                    jax.device_put(q, NamedSharding(mesh, rep)),
                    jax.device_put(pool, placed)))]

    failures = []      # reported at the end, so one run shows every check

    def probe(state, tokens, active):
        on_one = jax.device_put((state, tokens, active), devices[0])
        try:
            teacher_forced(cfg, params, *on_one, island_pair, "sharded")
        except SmokeFailure as e:
            failures.append(str(e))

    outputs, rows = {}, {}
    for label, engine_ctx in (("sharded", ctx), ("unsharded", None)):
        engine = build_engine(cfg, params, ctx=engine_ctx, **engine_kw)
        # The shards owning the request's blocks, read from the engine's
        # host-side block lists at every decode tick.
        owners, tick = set(), engine._decode

        def spy(*args, engine=engine, tick=tick, owners=owners):
            for blocks in engine._slot_blocks.values():
                owners.update(b // per_shard for b in blocks)
            return tick(*args)

        engine._decode = spy
        rec = TickRecorder(engine, probe if engine_ctx is not None else None)
        req = Request(rid=0, prompt=prompt.copy(), max_new_tokens=NEW_TOKENS)
        t0 = time.perf_counter()
        engine.submit(req)
        stats = engine.run().summary()
        note(f"{label}: run {time.perf_counter() - t0:.3f} s, "
             f"peak blocks {stats['peak_blocks_in_use']}")
        check(req.stop_reason == "length" and len(req.output) == NEW_TOKENS,
              f"{label}: stop {req.stop_reason!r}, {len(req.output)} tokens")
        if engine_ctx is not None:
            check(rec.probed > 0, "sharded: the teacher-forced check never ran")
            pools = [x for x in jax.tree_util.tree_leaves(
                engine._state, is_leaf=lambda x: isinstance(x, PagedSalcaCache))
                if isinstance(x, PagedSalcaCache)]
            check(pools, "sharded: no paged pool in the engine state")
            for pool in pools:
                leaf = pool.k_codes
                held = {s.device for s in leaf.addressable_shards
                        if s.data.shape[-4] < leaf.shape[-4]}
                if len(held) != shards:
                    failures.append(f"sharded: pool blocks on {len(held)} "
                                    f"devices, sharding {leaf.sharding}")
                    break
            note(f"sharded: pool leaf sharding {pools[0].k_codes.sharding}; "
                 f"request blocks on shards {sorted(owners)}")
            if owners != set(range(shards)):
                failures.append(f"sharded: request blocks on shards "
                                f"{sorted(owners)} only")
        outputs[label] = list(req.output)
        rows[label] = rec.rows
        rec.engine = rec.fn = rec.probe = None
        del engine
        gc.collect()
    a, b = outputs["sharded"], outputs["unsharded"]
    for n in range(1, len(a)):
        diff = float(np.max(np.abs(
            np.asarray(rows["sharded"][(0, n)], np.float32)[:cfg.vocab_size]
            - np.asarray(rows["unsharded"][(0, n)], np.float32)[:cfg.vocab_size])))
        row = np.sort(np.asarray(rows["unsharded"][(0, n)],
                                 np.float32)[:cfg.vocab_size])
        print(f"step {n}: sharded {a[n]} unsharded {b[n]}; max|logit diff| "
              f"{diff:.6g}; unsharded top-1/top-2 margin "
              f"{float(row[-1] - row[-2]):.6g}", flush=True)
        if a[n] != b[n]:
            break
    if a != b:
        failures.append(f"greedy tokens differ: {outputs}")
    else:
        print(f"sharded vs unsharded: all {len(a)} greedy tokens match",
              flush=True)
    check(not failures, "; ".join(failures))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--four-chips", action="store_true",
                      help="run only the sharded-pool phase on 4 chips")
    mode.add_argument("--precision-control", action="store_true",
                      help="run only the fused-vs-XLA bf16/f32 readings")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (jax platform {devices[0].platform!r}); "
              "refusing to run", file=sys.stderr)
        return 2
    sys.path.insert(0, str(REPO / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    note(f"compile cache: {enable_compile_cache()}")
    note(f"devices: {len(devices)} x {devices[0].device_kind}")

    if args.four_chips:
        four_chips()
    elif args.precision_control:
        precision_control()
    else:
        one_chip()
    stats = devices[0].memory_stats() or {}
    note(f"peak_bytes_in_use (device 0): {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
