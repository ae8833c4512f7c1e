"""Layer blocks: assembly of mixers + FFNs per block kind.

Block kinds (ModelConfig.layer_pattern):
    "A" — global attention (+ FFN).   Salca decode when cfg.salca.
    "L" — local sliding-window attention (+ FFN). Dense SP decode.
    "S" — Mamba2 SSD mixer (no FFN; mamba block layout).
    "R" — RG-LRU recurrent block (+ FFN).

Each kind provides init / train / prefill / decode with a uniform state
protocol so the transformer driver can scan heterogeneous patterns.
Decode runs inside shard_map with the KV cache sequence-sharded; recurrent
states are batch-sharded only.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.cache import (
    PagedSalcaCache, SalcaCache, append_token_paged, prefill_cache)
from repro.core.selection import SalcaParams
from repro.core.sp_decode import (
    local_lengths, sp_append_token, sp_dense_decode, sp_salca_decode)
from repro.core.attention import (
    dense_decode_from_cache, dense_decode_from_paged, salca_decode_attention,
    salca_decode_attention_paged)
from repro.models import ssm, rglru
from repro.models.attention import attention_init, attention_train, qkv_project
from repro.models.common import glu_init, glu_apply, rmsnorm, rmsnorm_init, rope, cdtype
from repro.models.moe import moe_init, moe_apply


class DecodeCtx(NamedTuple):
    """How decode attention is distributed.

    axis: mesh axis name (or tuple of names) the cache *sequence* dim is
        sharded over, or None for single-device execution.
    mesh: the Mesh for the shard_map island (required when axis is set).
    batch_axes: mesh axis name(s) the batch dim is sharded over (or None).
    """
    axis: Any = None
    mesh: Any = None
    batch_axes: Any = None
    self_axis: Any = None    # enc-dec: separate (shorter) self-cache seq axis


def cache_pspec(ctx: "DecodeCtx", axis: Any = None):
    """PartitionSpec pytree for a sequence-sharded SalcaCache."""
    from jax.sharding import PartitionSpec as P
    ba, sa = ctx.batch_axes, (axis if axis is not None else ctx.axis)
    return SalcaCache(
        k_codes=P(ba, sa, None, None), k_scale=P(ba, sa, None),
        v_codes=P(ba, sa, None, None), v_scale=P(ba, sa, None),
        feat_words=P(ba, sa, None, None), feat_scale=P(ba, sa, None),
        feat_zero=P(ba, sa, None),
        heavy_idx=P(ba, None, None), length=P(ba))


def paged_cache_pspec(ctx: "DecodeCtx", axis: Any = None):
    """PartitionSpec pytree for a block-sharded PagedSalcaCache.

    The physical block dim of every data leaf splits over the decode
    sequence axes (shard i owns global block ids [i·P_local, (i+1)·P_local)
    — `core.cache.local_block_range`); the per-slot metadata AND the
    refcount stay replicated: `append_token_paged` reads the refcount of the
    cursor's block on every shard to keep the CoW-fault test and the length
    advance replicated-consistent, and the page table is the (tiny) shared
    routing structure each shard filters down to its owned entries. Slots
    are replicated rather than batch-sharded — the pool is one shared
    structure, so the slot dim of a paged pool cannot split without
    splitting the free list too (a non-goal: the engine already charges
    whole slots to shards host-side)."""
    from jax.sharding import PartitionSpec as P
    sa = axis if axis is not None else ctx.axis
    return PagedSalcaCache(
        k_codes=P(sa, None, None, None), k_scale=P(sa, None, None),
        v_codes=P(sa, None, None, None), v_scale=P(sa, None, None),
        feat_words=P(sa, None, None, None), feat_scale=P(sa, None, None),
        feat_zero=P(sa, None, None),
        heavy_idx=P(None, None, None), length=P(None),
        page_table=P(None, None), refcount=P(None),
        sel_hist=P(None, None))


def salca_params_for(cfg: ModelConfig, seq_len: int) -> SalcaParams:
    k = max(128, min(int(seq_len * cfg.salca_retention), cfg.salca_max_k, seq_len))
    k_cap = min(((int(k * 1.25) + 127) // 128) * 128, seq_len)
    return SalcaParams(
        feature_sparsity=cfg.salca_feature_sparsity, k=k, k_cap=k_cap,
        pool_window=cfg.salca_pool_window, use_pool=cfg.salca_use_pool)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _ffn_init(key, cfg: ModelConfig) -> dict:
    if cfg.moe:
        k1, k2 = jax.random.split(key)
        p = {"moe": moe_init(k1, cfg)}
        if cfg.dense_residual:
            p["dense"] = glu_init(k2, cfg.d_model, cfg.d_ff, cdtype(cfg))
        return p
    return {"glu": glu_init(key, cfg.d_model, cfg.d_ff, cdtype(cfg))}


def block_init(key, kind: str, cfg: ModelConfig) -> dict:
    dtype = cdtype(cfg)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    if kind in ("A", "L"):
        return {"ln1": rmsnorm_init(cfg.d_model, dtype),
                "attn": attention_init(k1, cfg),
                "ln2": rmsnorm_init(cfg.d_model, dtype),
                "ffn": _ffn_init(k2, cfg)}
    if kind == "S":
        return {"ln1": rmsnorm_init(cfg.d_model, dtype),
                "ssd": ssm.ssd_init(k1, cfg)}
    if kind == "R":
        return {"ln1": rmsnorm_init(cfg.d_model, dtype),
                "rglru": rglru.rglru_init(k1, cfg),
                "ln2": rmsnorm_init(cfg.d_model, dtype),
                "ffn": _ffn_init(k2, cfg)}
    raise ValueError(f"unknown block kind {kind!r}")


# ---------------------------------------------------------------------------
# FFN apply (dense GLU / MoE / arctic hybrid)
# ---------------------------------------------------------------------------

def ffn_apply(params: dict, x: jax.Array, cfg: ModelConfig):
    aux = jnp.float32(0.0)
    if cfg.moe:
        squeeze = x.ndim == 2
        x3 = x[:, None] if squeeze else x
        out, aux = moe_apply(params["moe"], x3, cfg)
        if cfg.dense_residual:
            out = out + glu_apply(params["dense"], x3, cfg.act)
        return (out[:, 0] if squeeze else out), aux
    return glu_apply(params["glu"], x, cfg.act), aux


# ---------------------------------------------------------------------------
# Train (full-sequence) forward
# ---------------------------------------------------------------------------

def block_train(params: dict, kind: str, x: jax.Array, cfg: ModelConfig,
                attn_impl: str = "xla"):
    """x: (B, T, D) → (x, aux_loss)."""
    aux = jnp.float32(0.0)
    if kind in ("A", "L"):
        window = cfg.local_window if kind == "L" else 0
        h = attention_train(params["attn"], rmsnorm(params["ln1"], x, cfg.norm_eps),
                            cfg, window=window, impl=attn_impl)
        x = x + h
        f, aux = ffn_apply(params["ffn"], rmsnorm(params["ln2"], x, cfg.norm_eps), cfg)
        return x + f, aux
    if kind == "S":
        h = ssm.ssd_train(params["ssd"], rmsnorm(params["ln1"], x, cfg.norm_eps), cfg)
        return x + h, aux
    if kind == "R":
        h = rglru.rglru_train(params["rglru"], rmsnorm(params["ln1"], x, cfg.norm_eps), cfg)
        x = x + h
        f, aux = ffn_apply(params["ffn"], rmsnorm(params["ln2"], x, cfg.norm_eps), cfg)
        return x + f, aux
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Prefill: train-forward + state extraction
# ---------------------------------------------------------------------------

def ring_size(cfg: ModelConfig, kind: str, max_seq: int) -> int:
    """Effective cache length for a block: sliding-window ("L") layers keep
    a `window`-slot ring instead of the full context (§Perf it-10)."""
    from repro.flags import PERF
    if (kind == "L" and PERF.ring_local_cache and cfg.local_window > 0
            and cfg.local_window < max_seq):
        return cfg.local_window
    return max_seq


def static_heavy_idx(attn_params: dict, cfg: ModelConfig, sp: SalcaParams,
                     batch: int) -> jax.Array | None:
    """Request-independent heavy-channel set (cfg.salca_static_channels):
    per-kv-head top-r channels by key-projection weight mass Σ_d |W_k[d,·,j]|
    — the Loki-style offline selection. When the layer carries a
    ``calib_salience`` leaf (installed by ``lm_calibrate_static_heavy`` from
    K-activation statistics over a sample batch), that salience replaces the
    weight-derived mass; the weight-derived path stays the default. Returns
    (B, KV, R) broadcast over the batch, or None to keep the paper's
    per-input identification. A static set is what makes prefix-shared
    feature blocks valid across requests whose prompts (and hence per-input
    sets) diverge."""
    if not cfg.salca_static_channels:
        return None
    sal = attn_params.get("calib_salience")
    if sal is None:
        sal = jnp.sum(jnp.abs(attn_params["wk"].astype(jnp.float32)), axis=0)
    else:
        sal = sal.astype(jnp.float32)
    _, idx = jax.lax.top_k(sal, sp.r(cfg.resolved_head_dim))    # (KV, R)
    idx = jnp.sort(idx, axis=-1).astype(jnp.int32)
    return jnp.broadcast_to(idx[None], (batch,) + idx.shape)


def block_prefill(params: dict, kind: str, x: jax.Array, cfg: ModelConfig,
                  max_seq: int, attn_impl: str = "xla"):
    """Returns (x_out, state) where state feeds block_decode."""
    if kind in ("A", "L"):
        window = cfg.local_window if kind == "L" else 0
        xn = rmsnorm(params["ln1"], x, cfg.norm_eps)
        positions = jnp.arange(x.shape[1])
        q, k, v = qkv_project(params["attn"], xn, cfg, positions)
        from repro.models.attention import flash_attention_xla
        o = flash_attention_xla(q, k, v, causal=True, window=window)
        x = x + o.reshape(x.shape[0], x.shape[1], -1) @ params["attn"]["wo"]
        f, _ = ffn_apply(params["ffn"], rmsnorm(params["ln2"], x, cfg.norm_eps), cfg)
        sp = salca_params_for(cfg, max_seq)
        w_ring = ring_size(cfg, kind, max_seq)
        t = k.shape[1]
        if w_ring < max_seq and t >= w_ring:
            # keep the last `window` tokens at their canonical ring slots
            # (token j lives at slot j % W, so decode's wrap stays aligned)
            base = t - w_ring
            slot_tok = base + ((jnp.arange(w_ring) - base) % w_ring)
            k, v = k[:, slot_tok], v[:, slot_tok]
        cache = prefill_cache(k, v, max_seq=w_ring if w_ring < max_seq else max_seq,
                              params=sp,
                              heavy_idx=static_heavy_idx(params["attn"], cfg, sp,
                                                         x.shape[0]))
        return x + f, cache
    if kind == "S":
        h, st = ssm.ssd_train(params["ssd"], rmsnorm(params["ln1"], x, cfg.norm_eps),
                              cfg, return_state=True)
        return x + h, st
    if kind == "R":
        h, st = rglru.rglru_train(params["rglru"],
                                  rmsnorm(params["ln1"], x, cfg.norm_eps), cfg,
                                  return_state=True)
        x = x + h
        f, _ = ffn_apply(params["ffn"], rmsnorm(params["ln2"], x, cfg.norm_eps), cfg)
        return x + f, st
    raise ValueError(kind)


def block_prefill_chunk(params: dict, x: jax.Array, kbuf: jax.Array,
                        vbuf: jax.Array, t0, cfg: ModelConfig):
    """One layer's chunked-prefill step — global-attention ("A") blocks only.

    `x`: (1, C, D) chunk activations; `kbuf`/`vbuf`: (1, T, KV, HD)
    full-precision K/V carried across chunks (rows [0, t0) filled by earlier
    chunks, the rest zero); `t0` is the chunk's first logical position (may
    be traced; C and T are static).

    Bit-identity with `block_prefill`: the chunk's queries run through the
    same `flash_attention_xla` over the same T-length key axis (q_offset
    shifts the causal mask), and masked key contributions are exact zeros in
    that kernel — so each output row equals the monolithic forward's row at
    the same position, bit for bit. Returns (x_out, kbuf, vbuf, k, v); the
    raw chunk k/v feed the streaming pool install
    (`cache.prefill_chunk_into_pages`).
    """
    xn = rmsnorm(params["ln1"], x, cfg.norm_eps)
    positions = jnp.asarray(t0) + jnp.arange(x.shape[1])
    q, k, v = qkv_project(params["attn"], xn, cfg, positions)
    t0 = jnp.asarray(t0, jnp.int32)
    kbuf = jax.lax.dynamic_update_slice(kbuf, k.astype(kbuf.dtype), (0, t0, 0, 0))
    vbuf = jax.lax.dynamic_update_slice(vbuf, v.astype(vbuf.dtype), (0, t0, 0, 0))
    from repro.models.attention import flash_attention_xla
    o = flash_attention_xla(q, kbuf, vbuf, causal=True, q_offset=t0)
    x = x + o.reshape(x.shape[0], x.shape[1], -1) @ params["attn"]["wo"]
    f, _ = ffn_apply(params["ffn"], rmsnorm(params["ln2"], x, cfg.norm_eps), cfg)
    return x + f, kbuf, vbuf, k, v


# ---------------------------------------------------------------------------
# Decode: one token
# ---------------------------------------------------------------------------

def attn_decode_qkv(params: dict, x: jax.Array, cfg: ModelConfig,
                    pos: jax.Array):
    """One decode token's projections: q (B, H, HD) f32 and k, v (B, KV, HD)
    in the compute dtype, qk-normed and rotated to position ``pos``."""
    q = jnp.einsum("bd,dhk->bhk", x, params["wq"])
    k = jnp.einsum("bd,dhk->bhk", x, params["wk"])
    v = jnp.einsum("bd,dhk->bhk", x, params["wv"])
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(params["k_norm"], k, cfg.norm_eps)
    q = rope(q[:, None], pos[:, None], cfg.rope_theta)[:, 0]
    k = rope(k[:, None], pos[:, None], cfg.rope_theta)[:, 0]
    return q.astype(jnp.float32), k, v


def _attn_decode(params: dict, x: jax.Array, cache: SalcaCache, cfg: ModelConfig,
                 pos: jax.Array, window: int, use_salca: bool,
                 ctx: DecodeCtx, salca: SalcaParams,
                 active: jax.Array | None = None):
    """x: (B, D); cache sequence-sharded when ctx.axis is set.

    Ring semantics (§Perf it-10): when a sliding-window layer's cache was
    allocated at `window` slots (< full context), the write cursor wraps
    (pos % W) and exactly the last min(pos+1, W) tokens are valid — no
    window masking needed, and the full-context buffer never exists.

    Masked-slot semantics: `active` is an optional (B,) bool mask over pooled
    request slots. Inactive slots still flow through the whole datapath (the
    batch shape stays static for jit), but their K/V write is forced
    out-of-range (dropped) and their valid length is pinned to 0, so the
    slot's cache region is bit-identical afterwards and its attention output
    is a well-defined finite value the engine discards.
    """
    b, d = x.shape
    h, hd = cfg.num_heads, cfg.resolved_head_dim
    q, k, v = attn_decode_qkv(params, x, cfg, pos)

    paged = isinstance(cache, PagedSalcaCache)
    ring = (not paged) and window > 0 and cache.max_seq <= window
    if ring:
        write_pos = pos % cache.max_seq
        valid_len = jnp.minimum(pos + 1, cache.max_seq)
        window = 0          # the ring holds exactly the window
    else:
        write_pos = pos
        valid_len = pos + 1
    if active is not None:
        # Inactive slots: drop the write, treat the slot as holding 0 tokens.
        # (Non-sharded scatters wrap negative indices, so force OOB with
        # max_seq; the sharded paths use -1, which sp_append_token and the
        # paged cursor walk (cur >= 0) reject explicitly on every shard.)
        oob = -1 if ctx.axis is not None else cache.max_seq
        write_pos = jnp.where(active, write_pos, jnp.int32(oob))
        valid_len = jnp.where(active, valid_len, 0)

    if paged and ctx.axis is not None:
        # Block-sharded paged pool: each shard holds num_blocks/n_shards
        # physical blocks (metadata replicated — see `paged_cache_pspec`).
        # The island appends shard-locally (unowned writes drop; the cursor
        # walk is replicated-consistent) and decodes with the two-collective
        # sharded tick: psum'd additive histograms give one global Top-K
        # threshold, each shard exactly-attends over its locally-mapped
        # blocks, and the partials merge with the online-softmax psum/pmax
        # (`sp_decode.sp_salca_decode_paged`). Selection is bit-identical to
        # the unsharded paged tick; batch stays replicated across the island.
        # PERF.sharded_fused_decode picks the tick's data path inside:
        # fused (default) streams each shard's owned physical blocks through
        # the scalar-prefetched paged kernels; baseline re-materializes the
        # PR 5 O(local pool) logical gathers.
        from jax.sharding import PartitionSpec as P
        from repro.core.cache import local_block_range
        from repro.core.sp_decode import (
            sp_dense_decode_paged, sp_salca_decode_paged)
        sa = ctx.axis

        def paged_island(q_, k_, v_, wp_, vl_, pos_, pool_):
            pool_ = append_token_paged(
                pool_._replace(length=wp_), k_, v_,
                block_range=local_block_range(pool_, sa))
            pool_ = pool_._replace(length=vl_)
            if use_salca:
                o_ = sp_salca_decode_paged(q_, pool_, salca, sa)
            else:
                o_ = sp_dense_decode_paged(q_, pool_, sa, window=window,
                                           global_pos=pos_)
            return o_, pool_

        rep3 = P(None, None, None)
        pspec = paged_cache_pspec(ctx)
        o, cache = jax.shard_map(
            paged_island, mesh=ctx.mesh,
            in_specs=(rep3, rep3, rep3, P(None), P(None), P(None), pspec),
            out_specs=(rep3, pspec),
            check_vma=False,
        )(q, k, v, write_pos, valid_len, pos, cache)
    elif paged:
        # Paged block pool: the write cursor resolves through the slot's page
        # table (unmapped / out-of-capacity writes are dropped, no silent
        # clip — the engine grows or overflow-finishes first).
        cache = append_token_paged(cache._replace(length=write_pos), k, v)
        cache = cache._replace(length=valid_len)
        if use_salca:
            # Fused vs gather data path is chosen inside (PERF.paged_fused_
            # decode): fused streams physical blocks through the page table
            # in-kernel; gather rebuilds logical views (the PR 3 baseline).
            o, sel = salca_decode_attention_paged(q, cache, salca,
                                                  return_selection=True)
            # Relevance history for the host-spill tier: count each tick's
            # selected tokens per logical block (O(S·KV·C) scatter-add; the
            # engine diffs snapshots host-side to find cold blocks).
            from repro.core.cache import record_selection
            cache = record_selection(cache, sel.indices, sel.mask)
        else:
            valid = cache.valid_mask()
            if window > 0:
                p = jnp.arange(cache.max_seq)[None, :]
                valid = valid & (p > (pos[:, None] - window))
            o = dense_decode_from_paged(q, cache, valid)
    elif ctx.axis is None:
        from repro.core.cache import append_token
        cache = append_token(cache._replace(length=write_pos), k, v)
        cache = cache._replace(length=valid_len)
        if use_salca:
            o = salca_decode_attention(q, cache, salca)
        else:
            valid = cache.valid_mask()
            if window > 0:
                p = jnp.arange(cache.max_seq)[None, :]
                valid = valid & (p > (pos[:, None] - window))
            kd = cache.k_codes.astype(jnp.float32) * cache.k_scale[..., None]
            vd = cache.v_codes.astype(jnp.float32) * cache.v_scale[..., None]
            from repro.core.attention import dense_decode_attention
            o = dense_decode_attention(q, kd, vd, valid)
    else:
        from jax.sharding import PartitionSpec as P
        ba, sa = ctx.batch_axes, ctx.axis

        def island(q_, k_, v_, wp_, vl_, cache_):
            # Never trust the carried length field across the global/local
            # boundary: recompute this shard's span from the write cursor,
            # then mask attention to the valid length (ring-aware).
            cache_ = cache_._replace(
                length=local_lengths(wp_, cache_.max_seq, sa))
            cache_ = sp_append_token(cache_, k_, v_, wp_, sa)
            cache_ = cache_._replace(
                length=local_lengths(vl_, cache_.max_seq, sa))
            if use_salca:
                o_ = sp_salca_decode(q_, cache_, salca, sa)
            else:
                o_ = sp_dense_decode(q_, cache_, sa, window=window,
                                     global_len=vl_)
            return o_, cache_

        rep3 = P(ba, None, None)
        o, cache = jax.shard_map(
            island, mesh=ctx.mesh,
            in_specs=(rep3, rep3, rep3, P(ba), P(ba), cache_pspec(ctx)),
            out_specs=(rep3, cache_pspec(ctx)),
            check_vma=False,
        )(q, k, v, write_pos, valid_len, cache)
    o = o.astype(x.dtype).reshape(b, h * hd)
    return o @ params["wo"], cache


def merge_masked_state(new_state, old_state, active: jax.Array):
    """Per-slot select: keep `new_state` where active, `old_state` where not.

    Used for recurrent (SSM / RG-LRU) decode states, which are small
    batch-leading pytrees; attention caches gate their own writes instead
    (see `_attn_decode`), which avoids copying the whole pooled cache.
    """
    def sel(n, o):
        a = active.reshape(active.shape + (1,) * (n.ndim - 1))
        return jnp.where(a, n, o)

    return jax.tree.map(sel, new_state, old_state)


def block_decode(params: dict, kind: str, x: jax.Array, state, cfg: ModelConfig,
                 pos: jax.Array, ctx: DecodeCtx, salca: SalcaParams,
                 active: jax.Array | None = None):
    """x: (B, D) single token; returns (x, new_state). `active` (B,) bool
    masks pooled request slots: inactive slots compute (static shapes) but
    their state carries through unchanged."""
    if kind in ("A", "L"):
        window = cfg.local_window if kind == "L" else 0
        use_salca = cfg.salca and kind == "A"
        h, state = _attn_decode(params["attn"],
                                rmsnorm(params["ln1"], x, cfg.norm_eps),
                                state, cfg, pos, window, use_salca, ctx, salca,
                                active)
        x = x + h
        f, _ = ffn_apply(params["ffn"], rmsnorm(params["ln2"], x, cfg.norm_eps), cfg)
        return x + f, state
    if kind == "S":
        h, new = ssm.ssd_decode(params["ssd"],
                                rmsnorm(params["ln1"], x, cfg.norm_eps), state, cfg)
        if active is not None:
            new = merge_masked_state(new, state, active)
        return x + h, new
    if kind == "R":
        h, new = rglru.rglru_decode(params["rglru"],
                                    rmsnorm(params["ln1"], x, cfg.norm_eps), state, cfg)
        x = x + h
        f, _ = ffn_apply(params["ffn"], rmsnorm(params["ln2"], x, cfg.norm_eps), cfg)
        if active is not None:
            new = merge_masked_state(new, state, active)
        return x + f, new
    raise ValueError(kind)


def block_init_state(kind: str, batch: int, max_seq: int, cfg: ModelConfig):
    """Empty decode state for one block (used when decoding from scratch or
    for building ShapeDtypeStructs in the dry-run)."""
    if kind in ("A", "L"):
        from repro.core.cache import empty_cache
        sp = salca_params_for(cfg, max_seq)
        return empty_cache(batch, ring_size(cfg, kind, max_seq),
                           cfg.num_kv_heads, cfg.resolved_head_dim,
                           sp.r(cfg.resolved_head_dim))
    if kind == "S":
        return ssm.ssd_init_state(batch, cfg)
    if kind == "R":
        return rglru.rglru_init_state(batch, cfg)
    raise ValueError(kind)


def block_init_paged_state(kind: str, slots: int, max_seq: int, cfg: ModelConfig,
                           block_size: int, num_blocks: int):
    """Empty decode state for one block with attention caches backed by a
    paged block pool instead of dense per-slot stripes.

    Sliding-window layers whose ring cache is already bounded by the window
    keep the dense per-slot stripe (a ring is O(window) per slot — paging it
    buys nothing and complicates the wrap); full-context caches become one
    shared `(num_blocks, block_size, ·)` pool with a per-slot page table.
    Recurrent states are per-slot dense as before.
    """
    if kind in ("A", "L"):
        from repro.core.cache import empty_cache, empty_paged_cache
        sp = salca_params_for(cfg, max_seq)
        r = sp.r(cfg.resolved_head_dim)
        w_ring = ring_size(cfg, kind, max_seq)
        if w_ring < max_seq:
            return empty_cache(slots, w_ring, cfg.num_kv_heads,
                               cfg.resolved_head_dim, r)
        max_blocks = -(-max_seq // block_size)
        return empty_paged_cache(num_blocks, block_size, slots, max_blocks,
                                 cfg.num_kv_heads, cfg.resolved_head_dim, r,
                                 kv_pool_dtype=cfg.kv_pool_dtype)
    return block_init_state(kind, slots, max_seq, cfg)
