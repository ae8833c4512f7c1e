"""Decoder-only LM driver: init / train / prefill / decode over block patterns.

Heterogeneous layer patterns (gemma3 "LLLLLA", recurrentgemma "RRL") are
executed as a `jax.lax.scan` over *periods*: parameters for each pattern
position are stacked across periods, the scan body applies one full period
in order. Layers that don't fill a whole period ("tail", e.g.
recurrentgemma's final 2 of 26) run unrolled after the scan. This keeps the
compiled HLO O(pattern) instead of O(layers) while preserving per-layer
weights.

The VLM frontend stub (llava) projects precomputed patch embeddings into the
token stream; the audio stub (whisper) lives in `encdec.py`.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import blocks as B
from repro.models.common import (
    cdtype, cross_entropy, dense_init, embed_tokens, embedding_init,
    lm_logits, rmsnorm, rmsnorm_init, vocab_mask_logits)


def pattern_layout(cfg: ModelConfig) -> tuple[str, int, str]:
    """(pattern, n_periods, tail_kinds)."""
    p = cfg.layer_pattern
    n_periods = cfg.num_layers // len(p)
    tail = p[: cfg.num_layers - n_periods * len(p)]
    return p, n_periods, tail


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_lm_params(key, cfg: ModelConfig) -> dict:
    pattern, n_periods, tail = pattern_layout(cfg)
    keys = jax.random.split(key, 3 + len(tail))
    params: dict[str, Any] = {"embed": embedding_init(keys[0], cfg),
                              "ln_f": rmsnorm_init(cfg.d_model, cdtype(cfg))}
    if cfg.frontend == "vision":
        params["projector"] = dense_init(keys[1], (cfg.frontend_dim, cfg.d_model),
                                         cdtype(cfg))

    def stack_init(kind: str, base_key):
        ks = jax.random.split(base_key, n_periods)
        return jax.vmap(lambda k: B.block_init(k, kind, cfg))(ks)

    pkeys = jax.random.split(keys[2], len(pattern))
    params["periods"] = tuple(stack_init(kind, pkeys[i])
                              for i, kind in enumerate(pattern))
    params["tail"] = tuple(B.block_init(keys[3 + i], kind, cfg)
                           for i, kind in enumerate(tail))
    return params


# ---------------------------------------------------------------------------
# Embedding frontends
# ---------------------------------------------------------------------------

def embed_inputs(params: dict, cfg: ModelConfig, tokens: jax.Array,
                 patches: jax.Array | None = None) -> jax.Array:
    """tokens (B, T_text) [+ patches (B, P, F) for VLM] → (B, T, D)."""
    h = embed_tokens(params["embed"], tokens).astype(cdtype(cfg))
    if cfg.frontend == "vision" and patches is not None:
        img = (patches.astype(cdtype(cfg)) @ params["projector"])
        h = jnp.concatenate([img, h], axis=1)
    return h


# ---------------------------------------------------------------------------
# Train forward / loss
# ---------------------------------------------------------------------------

def lm_forward(params: dict, cfg: ModelConfig, tokens: jax.Array,
               patches: jax.Array | None = None, attn_impl: str = "xla"):
    """Full-sequence forward → (logits (B,T,V_pad), aux_loss)."""
    from repro.distributed.sharding import constrain, constrain_residual
    pattern, n_periods, tail = pattern_layout(cfg)
    h = constrain_residual(embed_inputs(params, cfg, tokens, patches))
    aux_total = jnp.float32(0.0)

    def run_period(h, period_params):
        aux = jnp.float32(0.0)
        for i, kind in enumerate(pattern):
            h, a = B.block_train(period_params[i], kind, h, cfg, attn_impl)
            h = constrain_residual(h)
            aux = aux + a
        return h, aux

    if n_periods > 0:
        def body(carry, period_params):
            h, aux = carry
            h, a = jax.checkpoint(run_period)(h, period_params)
            return (h, aux + a), None

        (h, aux_total), _ = jax.lax.scan(body, (h, aux_total), params["periods"])
    for i, kind in enumerate(tail):
        h, a = jax.checkpoint(
            lambda h_, p_, k_=kind: B.block_train(p_, k_, h_, cfg, attn_impl)
        )(h, params["tail"][i])
        h = constrain_residual(h)
        aux_total = aux_total + a
    h = rmsnorm(params["ln_f"], h, cfg.norm_eps)
    # Gather the sequence for the vocab-parallel head (Megatron layout).
    h = constrain(h, "dp", None, None)
    return lm_logits(params["embed"], h, cfg), aux_total


def lm_loss(params: dict, cfg: ModelConfig, tokens: jax.Array,
            labels: jax.Array, patches: jax.Array | None = None,
            attn_impl: str = "xla") -> jax.Array:
    logits, aux = lm_forward(params, cfg, tokens, patches, attn_impl)
    if cfg.frontend == "vision" and patches is not None:
        logits = logits[:, patches.shape[1]:]  # loss over text positions
    return cross_entropy(logits, labels, cfg) + 0.01 * aux


# ---------------------------------------------------------------------------
# Prefill → decode states
# ---------------------------------------------------------------------------

class LMState(NamedTuple):
    """Decode state: per-pattern-position stacked states + tail states + cursor."""
    period_states: tuple
    tail_states: tuple
    pos: jax.Array          # (B,) global lengths


def lm_prefill(params: dict, cfg: ModelConfig, tokens: jax.Array, max_seq: int,
               patches: jax.Array | None = None):
    """Run prefill, build decode states. Returns (last_logits, LMState)."""
    pattern, n_periods, tail = pattern_layout(cfg)
    h = embed_inputs(params, cfg, tokens, patches)
    t = h.shape[1]

    period_states = []
    if n_periods > 0:
        def body(h, period_params):
            states = []
            for i, kind in enumerate(pattern):
                h, st = B.block_prefill(period_params[i], kind, h, cfg, max_seq)
                states.append(st)
            return h, tuple(states)

        h, stacked_states = jax.lax.scan(body, h, params["periods"])
        period_states = stacked_states
    tail_states = []
    for i, kind in enumerate(tail):
        h, st = B.block_prefill(params["tail"][i], kind, h, cfg, max_seq)
        tail_states.append(st)
    h = rmsnorm(params["ln_f"], h, cfg.norm_eps)
    logits = vocab_mask_logits(lm_logits(params["embed"], h[:, -1], cfg), cfg)
    pos = jnp.full((h.shape[0],), t, jnp.int32)
    return logits, LMState(tuple(period_states), tuple(tail_states), pos)


# ---------------------------------------------------------------------------
# Chunked prefill: budgeted admission for the continuous-batching scheduler.
# A prompt is prefilled in C-token chunks interleaved with decode ticks; each
# chunk attends over full-precision K/V buffers carried between chunks (NOT
# the quantized pool), which keeps every activation row — and therefore the
# final logits and all streamed pool rows — bit-identical to the monolithic
# `lm_prefill` of the same prompt.
# ---------------------------------------------------------------------------

class PrefillCursor(NamedTuple):
    """In-flight chunked-prefill state for one request (batch 1).

    Per-attention-layer full-precision K/V buffers (pattern positions carry
    a stacked (n_periods, 1, T, KV, HD) pair) plus the next logical
    position. Rows [0, t0) are filled by earlier chunks; the rest are zeros,
    causally masked out by `q_offset` in the chunk's attention."""
    period_kv: tuple
    tail_kv: tuple
    t0: jax.Array           # scalar i32: logical position of the next chunk


def lm_prefill_chunk_unsupported(cfg: ModelConfig) -> str | None:
    """Why chunked prefill cannot run for this config — None when it can."""
    pattern, _, tail = pattern_layout(cfg)
    if set(pattern + tail) != {"A"}:
        return (f"layer pattern {cfg.layer_pattern!r} has non-global layers; "
                'chunked prefill supports all-"A" stacks only')
    if cfg.moe:
        return "MoE routing is not guaranteed chunk-invariant"
    if cfg.frontend != "none":
        return "modality frontends are not supported by chunked prefill"
    if not cfg.salca_static_channels:
        return ("per-input heavy-channel identification needs the full "
                "prompt's K at once; chunked prefill requires "
                "cfg.salca_static_channels")
    return None


def lm_prefill_begin(cfg: ModelConfig, t_total: int) -> PrefillCursor:
    """Fresh cursor for a prompt of `t_total` tokens (batch 1)."""
    pattern, n_periods, tail = pattern_layout(cfg)
    kvh, hd = cfg.num_kv_heads, cfg.resolved_head_dim

    def bufs(lead):
        z = jnp.zeros(lead + (1, t_total, kvh, hd), cdtype(cfg))
        return (z, z)

    period_kv = tuple(bufs((n_periods,)) for _ in pattern) if n_periods else ()
    tail_kv = tuple(bufs(()) for _ in tail)
    return PrefillCursor(period_kv, tail_kv, jnp.zeros((), jnp.int32))


def lm_prefill_chunk(params: dict, cfg: ModelConfig, pool: LMState,
                     tokens: jax.Array, cursor: PrefillCursor, slot,
                     pages: jax.Array, n_shared, max_seq: int, *,
                     final: bool):
    """Advance an in-flight chunked prefill by one chunk of tokens.

    `tokens`: (1, C) token ids for logical positions [t0, t0+C). Streams the
    chunk's K/V into the paged pool at `slot` (which the engine keeps masked
    off until the final chunk) and carries the full-precision buffers
    forward. Returns (logits, pool', cursor'): `logits` is the (1, V)
    next-token distribution on the final chunk and None otherwise. On the
    final chunk the pool's `pos[slot]` is set so decode resumes exactly
    where `lm_prefill` + `lm_write_into_slot` would have left it.

    Its ops run under the ``prefill.chunk`` scope, opened inside the layer
    scan's body and around it (never around the scan: a trace would then
    count the loop and its body twice).
    """
    from repro.core.cache import prefill_chunk_into_pages
    pattern, n_periods, tail = pattern_layout(cfg)
    reason = lm_prefill_chunk_unsupported(cfg)
    if reason is not None:
        raise ValueError(f"chunked prefill unsupported: {reason}")
    with jax.named_scope("prefill.chunk"):
        h = embed_inputs(params, cfg, tokens)
    t0 = cursor.t0
    sp = B.salca_params_for(cfg, max_seq)

    period_kv, period_states = (), ()
    if n_periods > 0:
        def body(h, xs):
            pps, kvs, psts = xs
            new_kvs, new_psts = [], []
            with jax.named_scope("prefill.chunk"):
                for i, _ in enumerate(pattern):
                    kb, vb = kvs[i]
                    h, kb, vb, k, v = B.block_prefill_chunk(pps[i], h, kb, vb,
                                                            t0, cfg)
                    heavy = B.static_heavy_idx(pps[i]["attn"], cfg, sp, 1)
                    new_psts.append(prefill_chunk_into_pages(
                        psts[i], k, v, heavy, slot, pages, t0, n_shared))
                    new_kvs.append((kb, vb))
            return h, (tuple(new_kvs), tuple(new_psts))

        h, (period_kv, period_states) = jax.lax.scan(
            body, h, (params["periods"], cursor.period_kv, pool.period_states))

    tail_kv, tail_states = [], list(pool.tail_states)
    with jax.named_scope("prefill.chunk"):
        for i, _ in enumerate(tail):
            kb, vb = cursor.tail_kv[i]
            h, kb, vb, k, v = B.block_prefill_chunk(params["tail"][i], h, kb,
                                                    vb, t0, cfg)
            heavy = B.static_heavy_idx(params["tail"][i]["attn"], cfg, sp, 1)
            tail_states[i] = prefill_chunk_into_pages(
                tail_states[i], k, v, heavy, slot, pages, t0, n_shared)
            tail_kv.append((kb, vb))

        c = tokens.shape[1]
        if final:
            hn = rmsnorm(params["ln_f"], h, cfg.norm_eps)
            logits = vocab_mask_logits(
                lm_logits(params["embed"], hn[:, -1], cfg), cfg)
            pos = pool.pos.at[slot].set(t0 + c)
        else:
            logits, pos = None, pool.pos
    new_pool = LMState(period_states, tuple(tail_states), pos)
    return logits, new_pool, PrefillCursor(period_kv, tuple(tail_kv), t0 + c)


def lm_static_heavy(params: dict, cfg: ModelConfig, max_seq: int):
    """Static heavy-channel sets per attention layer, in the same
    (periods..., tail...) order and stacked shapes as a batch=1 prefill
    state's cache `heavy_idx` leaves — the serving engine hashes these for
    radix-map registration when a chunked prefill installs without ever
    materializing a dense source cache. None unless the config uses the
    static (weight-derived) selection."""
    if not cfg.salca_static_channels:
        return None
    pattern, n_periods, tail = pattern_layout(cfg)
    sp = B.salca_params_for(cfg, max_seq)
    parts = []
    for i, kind in enumerate(pattern):
        if kind in ("A", "L") and n_periods:
            parts.append(jax.vmap(
                lambda p: B.static_heavy_idx(p["attn"], cfg, sp, 1)
            )(params["periods"][i]))
    for i, kind in enumerate(tail):
        if kind in ("A", "L"):
            parts.append(B.static_heavy_idx(params["tail"][i]["attn"],
                                            cfg, sp, 1))
    return tuple(parts)


def lm_adopt_pages(params: dict, cfg: ModelConfig, pool: LMState, slot,
                   pages: jax.Array, length) -> LMState:
    """Zero-prefill warm admission: map an ALREADY-WRITTEN (cache-pinned)
    prefix into row `slot` of every paged layer without touching data rows.

    The metadata-only counterpart of `lm_write_into_slot`: per-layer page
    table row, refcounts, cursor, and the slot's heavy-channel set — the
    static set the retained rows were encoded against, which is why adoption
    requires `cfg.salca_static_channels` (each layer's set differs, so the
    per-layer sets are recomputed here rather than mapped uniformly).
    `pages` (max_blocks,) int32 must cover exactly the prompt's blocks
    (-1 beyond); `slot`, `pages` and `length` may be traced."""
    if not cfg.salca_static_channels:
        raise ValueError("adopt_pages requires cfg.salca_static_channels: "
                         "retained rows were encoded against the static "
                         "heavy-channel set")
    from repro.core.cache import adopt_pages
    pattern, n_periods, tail = pattern_layout(cfg)
    max_seq = None
    for st in list(pool.period_states) + list(pool.tail_states):
        if isinstance(st, B.PagedSalcaCache):
            max_seq = int(st.max_seq)
            break
    if max_seq is None:
        raise ValueError("adopt_pages requires a paged pool state")
    sp = B.salca_params_for(cfg, max_seq)
    with jax.named_scope("prefill.adopt"):
        ln = jnp.asarray(length, jnp.int32)
        periods = tuple(
            jax.vmap(lambda st, p: adopt_pages(
                st, slot, pages, ln, B.static_heavy_idx(p["attn"], cfg, sp, 1)
            ))(pp, params["periods"][i])
            if isinstance(pp, B.PagedSalcaCache) else pp
            for i, pp in enumerate(pool.period_states))
        tails = tuple(
            adopt_pages(st, slot, pages, ln,
                        B.static_heavy_idx(params["tail"][i]["attn"], cfg, sp,
                                           1))
            if isinstance(st, B.PagedSalcaCache) else st
            for i, st in enumerate(pool.tail_states))
        return LMState(periods, tails, pool.pos.at[slot].set(ln))


def lm_calibrate_static_heavy(params: dict, cfg: ModelConfig,
                              tokens: jax.Array) -> dict:
    """Calibration-based static heavy-channel selection: run a prefill over
    a sample batch, accumulate per-layer K-activation channel salience
    Σ_{b,t} |K[b,t,·,·]| from the caches (dequantized, valid rows only), and
    install it as a ``calib_salience`` leaf next to each attention layer's
    weights. `blocks.static_heavy_idx` prefers that leaf over the
    weight-derived Σ|W_k| mass, so hit rates track the deployed prompt
    distribution instead of the weights alone. Returns a NEW params tree;
    the input params (and the weight-derived default) are untouched.

    `tokens` (B, T) is the calibration batch — a few representative prompts
    suffice; salience is r-robust because top-r is taken at use time."""
    pattern, n_periods, tail = pattern_layout(cfg)
    t = int(tokens.shape[1])
    _, state = lm_prefill(params, cfg, tokens, max_seq=t)

    def sal_of(st):
        # (B, S, KV, HD) int8 codes × (B, S, KV) per-token scales → |K| mass
        # over valid rows, summed over batch and tokens → (KV, HD) f32.
        k = st.k_codes.astype(jnp.float32) * st.k_scale[..., None]
        valid = (jnp.arange(k.shape[1])[None, :]
                 < st.length[:, None]).astype(jnp.float32)
        return jnp.sum(jnp.abs(k) * valid[..., None, None], axis=(0, 1))

    new = dict(params)
    new_periods = []
    for i, kind in enumerate(pattern):
        pp = params["periods"][i]
        st = state.period_states[i] if i < len(state.period_states) else None
        if isinstance(st, B.SalcaCache):
            sal = jax.vmap(sal_of)(st)          # (n_periods, KV, HD)
            pp = {**pp, "attn": {**pp["attn"], "calib_salience": sal}}
        new_periods.append(pp)
    new["periods"] = tuple(new_periods)
    new_tail = []
    for i, kind in enumerate(tail):
        tp = params["tail"][i]
        st = state.tail_states[i]
        if isinstance(st, B.SalcaCache):
            tp = {**tp, "attn": {**tp["attn"], "calib_salience": sal_of(st)}}
        new_tail.append(tp)
    new["tail"] = tuple(new_tail)
    return new


def lm_init_state(cfg: ModelConfig, batch: int, max_seq: int,
                  prefill_len: int | jax.Array = 0) -> LMState:
    """Empty (or cursor-advanced) decode state, used for dry-run specs."""
    pattern, n_periods, tail = pattern_layout(cfg)

    def stack(kind):
        st = B.block_init_state(kind, batch, max_seq, cfg)
        return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n_periods,) + x.shape), st)

    period_states = tuple(stack(kind) for kind in pattern) if n_periods else ()
    tail_states = tuple(B.block_init_state(kind, batch, max_seq, cfg) for kind in tail)
    pos = jnp.full((batch,), prefill_len, jnp.int32)
    return LMState(period_states, tail_states, pos)


def lm_init_paged_state(cfg: ModelConfig, slots: int, max_seq: int,
                        block_size: int, num_blocks: int) -> LMState:
    """Pooled decode state whose full-context attention caches are paged:
    one `(num_blocks, block_size, ·)` physical pool per layer plus per-slot
    page tables, instead of dense `(slots, max_seq, ·)` stripes."""
    pattern, n_periods, tail = pattern_layout(cfg)

    def init(kind):
        return B.block_init_paged_state(kind, slots, max_seq, cfg,
                                        block_size, num_blocks)

    def stack(kind):
        st = init(kind)
        return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n_periods,) + x.shape), st)

    period_states = tuple(stack(kind) for kind in pattern) if n_periods else ()
    tail_states = tuple(init(kind) for kind in tail)
    return LMState(period_states, tail_states, jnp.zeros((slots,), jnp.int32))


# ---------------------------------------------------------------------------
# Slot pool: write a per-request (batch=1) prefill state into one row of a
# pooled (batch=slots) LMState, and reset a row on completion. Both are
# jit-safe with a traced slot index — the serving engine compiles each once.
# ---------------------------------------------------------------------------

def _write_substate_into_slot(pool_st, src_st, slot, pages=None, n_shared=0):
    from repro.core.cache import prefill_into_pages, write_prefill_into_slot
    if isinstance(pool_st, B.PagedSalcaCache):
        if pages is None:
            raise ValueError("paged cache substate requires a pages array "
                             "(use write_into_pages)")
        return prefill_into_pages(pool_st, src_st, slot, pages, n_shared)
    if isinstance(pool_st, B.SalcaCache):
        return write_prefill_into_slot(pool_st, src_st, slot)
    # Recurrent states (SSM / RG-LRU): batch-leading leaves, plain row write.
    return jax.tree.map(lambda p, s: p.at[slot].set(s[0].astype(p.dtype)),
                        pool_st, src_st)


def _reset_substate_slot(st, slot):
    from repro.core.cache import free_pages, reset_slot
    if isinstance(st, B.PagedSalcaCache):
        return free_pages(st, slot)
    if isinstance(st, B.SalcaCache):
        return reset_slot(st, slot)
    return jax.tree.map(lambda x: x.at[slot].set(jnp.zeros((), x.dtype)), st)


def lm_write_into_slot(pool: LMState, src: LMState, slot, pages=None,
                       n_shared=0) -> LMState:
    """Install a batch=1 prefilled `src` state into row `slot` of `pool`.

    Period states carry a leading n_periods axis; the per-cache write is
    vmapped over it so `core.cache.write_prefill_into_slot` /
    `prefill_into_pages` stay the single definition of the slot-write
    semantics. `pages` (max_blocks,) int32 names the physical blocks the
    engine allocated for this request — required when the pool's attention
    caches are paged (the same block ids apply to every layer's pool), and
    must be None for dense pools. `n_shared` marks the leading entries of
    `pages` as prefix-shared: mapped and refcounted in every paged layer,
    but not written (see `core.cache.prefill_into_pages`).
    """
    periods = tuple(
        jax.vmap(lambda p, s: _write_substate_into_slot(p, s, slot, pages,
                                                        n_shared))(pp, sp)
        for pp, sp in zip(pool.period_states, src.period_states))
    tails = tuple(_write_substate_into_slot(p, s, slot, pages, n_shared)
                  for p, s in zip(pool.tail_states, src.tail_states))
    return LMState(periods, tails, pool.pos.at[slot].set(src.pos[0]))


def lm_reset_slot(pool: LMState, slot) -> LMState:
    """Free row `slot`: caches marked empty (length 0, page tables unmapped
    for paged pools), recurrent states and the position cursor zeroed. O(1)
    per cache — data rows are left for the next admission to overwrite."""
    periods = tuple(jax.vmap(lambda p: _reset_substate_slot(p, slot))(pp)
                    for pp in pool.period_states)
    tails = tuple(_reset_substate_slot(p, slot) for p in pool.tail_states)
    return LMState(periods, tails, pool.pos.at[slot].set(0))


def _map_paged_substates(pool: LMState, fn) -> LMState:
    """Apply `fn` to every paged attention cache in the state (vmapped over
    the period axis); every other substate passes through unchanged."""
    def sub(st):
        return fn(st) if isinstance(st, B.PagedSalcaCache) else st

    periods = tuple(
        jax.vmap(fn)(pp) if isinstance(pp, B.PagedSalcaCache) else pp
        for pp in pool.period_states)
    tails = tuple(sub(st) for st in pool.tail_states)
    return LMState(periods, tails, pool.pos)


def lm_map_block(pool: LMState, slot, logical_block, page) -> LMState:
    """On-demand growth: map `logical_block` of `slot` to physical block
    `page` in every layer's paged pool (the engine allocates one block id
    from its free list and it applies to all layers). Non-paged substates
    pass through unchanged."""
    from repro.core.cache import map_block
    return _map_paged_substates(
        pool, lambda st: map_block(st, slot, logical_block, page))


def lm_share_blocks(pool: LMState, src_slot, n_blocks, dst_slot) -> LMState:
    """Prefix sharing: alias the first `n_blocks` logical blocks of
    `src_slot` into `dst_slot` in every layer's paged pool (same block ids
    in every layer — the engine's free list is layer-agnostic). Dense
    substates (sliding-window rings, recurrent states) pass through
    unchanged: they are per-slot O(window)/O(state) and are populated by the
    admission prefill write, not by sharing."""
    from repro.core.cache import share_blocks
    return _map_paged_substates(
        pool, lambda st: share_blocks(st, src_slot, n_blocks, dst_slot))


def lm_cow_block(pool: LMState, slot, logical_block, new_page) -> LMState:
    """Copy-on-write service for every layer's paged pool: copy the shared
    block mapped at (`slot`, `logical_block`) into `new_page` and remap only
    this slot's page-table entry (see `core.cache.cow_block`)."""
    from repro.core.cache import cow_block
    return _map_paged_substates(
        pool, lambda st: cow_block(st, slot, logical_block, new_page))


def lm_read_block(pool: LMState, page) -> tuple:
    """Host-spill transport, read side: the data rows of physical block
    `page` from EVERY paged layer (period pools keep their leading
    n_periods axis — `leaf[:, page]` — so the payload round-trips through
    `lm_write_block` unchanged). Returns a tuple of per-cache row tuples in
    the state's paged-cache order; the values are STORAGE-format (quantized
    codes + scales), so a demote→promote cycle is bit-exact. Jit this with
    a traced `page` — the engine compiles it once."""
    from repro.core.cache import read_block_rows
    pg = jnp.asarray(page, jnp.int32)
    out = []
    for pp in pool.period_states:
        if isinstance(pp, B.PagedSalcaCache):
            out.append(jax.vmap(lambda st: read_block_rows(st, pg))(pp))
    for st in pool.tail_states:
        if isinstance(st, B.PagedSalcaCache):
            out.append(read_block_rows(st, pg))
    return tuple(out)


def lm_write_block(pool: LMState, page, payload: tuple) -> LMState:
    """Host-spill transport, write side: install a payload captured by
    `lm_read_block` into physical block `page` of every paged layer (the
    promotion's `jax.device_put` target). Page tables / refcounts are the
    engine's job (`lm_map_block`); this moves data only."""
    from repro.core.cache import write_block_rows
    pg = jnp.asarray(page, jnp.int32)
    it = iter(payload)
    periods = tuple(
        jax.vmap(lambda st, rows: write_block_rows(st, pg, rows))(pp, next(it))
        if isinstance(pp, B.PagedSalcaCache) else pp
        for pp in pool.period_states)
    tails = tuple(
        write_block_rows(st, pg, next(it))
        if isinstance(st, B.PagedSalcaCache) else st
        for st in pool.tail_states)
    return LMState(periods, tails, pool.pos)


def lm_selection_hist(pool: LMState) -> jax.Array:
    """Cumulative selected-token counts per (slot, logical block), summed
    over every paged attention layer — the relevance histogram the engine's
    demotion policy diffs per tick (a block no layer has selected for
    `demote_after` consecutive ticks is cold). Returns (slots, MB) i32."""
    total = None
    for pp in pool.period_states:
        if isinstance(pp, B.PagedSalcaCache):
            h = jnp.sum(pp.sel_hist, axis=0)     # sum the period axis
            total = h if total is None else total + h
    for st in pool.tail_states:
        if isinstance(st, B.PagedSalcaCache):
            total = st.sel_hist if total is None else total + st.sel_hist
    return total


# ---------------------------------------------------------------------------
# Decode step
# ---------------------------------------------------------------------------

def lm_decode_step(params: dict, cfg: ModelConfig, state: LMState,
                   token: jax.Array, ctx: B.DecodeCtx | None = None,
                   active: jax.Array | None = None):
    """One decode step. token (B,) int32 → (logits (B, V_pad), new state).

    `active` is an optional (B,) bool mask over pooled request slots: every
    slot flows through the same fused program (shapes stay static for
    jit/pjit), but inactive slots write nothing, hold their cursor, and their
    logits are garbage the caller must ignore. One call therefore advances
    *all* active slots at once — the serving engine's per-tick step.
    """
    pattern, n_periods, tail = pattern_layout(cfg)
    ctx = ctx or B.DecodeCtx()
    with jax.named_scope("dense"):
        h = embed_tokens(params["embed"], token).astype(cdtype(cfg))
    pos = state.pos

    # max_seq for salca params: derive from any attention cache in the state.
    def _max_seq():
        for st in list(state.period_states) + list(state.tail_states):
            if isinstance(st, B.PagedSalcaCache):
                return st.max_seq        # logical capacity (negative-index safe)
            if isinstance(st, B.SalcaCache):
                return st.k_codes.shape[-3]
        return 0

    salca = B.salca_params_for(cfg, max(_max_seq(), 128))

    if n_periods > 0:
        def body(h, xs):
            period_params, period_states = xs
            new_states = []
            for i, kind in enumerate(pattern):
                h, st = B.block_decode(period_params[i], kind, h,
                                       period_states[i], cfg, pos, ctx, salca,
                                       active)
                new_states.append(st)
            return h, tuple(new_states)

        h, new_period_states = jax.lax.scan(
            body, h, (params["periods"], state.period_states))
    else:
        new_period_states = ()
    new_tail = []
    for i, kind in enumerate(tail):
        h, st = B.block_decode(params["tail"][i], kind, h, state.tail_states[i],
                               cfg, pos, ctx, salca, active)
        new_tail.append(st)
    with jax.named_scope("dense"):
        h = rmsnorm(params["ln_f"], h, cfg.norm_eps)
        logits = vocab_mask_logits(lm_logits(params["embed"], h, cfg), cfg)
    new_pos = pos + 1 if active is None else pos + active.astype(jnp.int32)
    return logits, LMState(new_period_states, tuple(new_tail), new_pos)
