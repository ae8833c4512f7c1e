"""Sequence-parallel (sharded-KV) Salca decode. Beyond-paper contribution.

For `long_500k` (batch=1) and CP archs, the KV cache is sharded along the
*sequence* dimension across mesh axes. The paper's O(n) selection
distributes perfectly — unlike exact Top-K, which would need a distributed
sort:

1. each shard computes local relevance scores;
2. score→INT8 binning needs a *global* affine map: one (min, max) pair per
   (batch, kv-head) is combined with `pmin`/`pmax` (tiny);
3. the 256-bin histograms are **additive**: one 256-int `psum` yields the
   exact global histogram, hence the same threshold everywhere;
4. maxpool windows crossing shard boundaries are fixed with a halo exchange
   (`ppermute` of `window//2` edge columns) — the TPU analogue of the
   paper's shift-register continuity;
5. each shard gathers its local selection and computes a partial attention
   (m, l, acc); partials merge with the online-softmax identity under
   `pmax`/`psum`.

Total collective traffic per layer per step: O(256 + head_dim) floats per
(batch, kv-head) — independent of sequence length.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import histogram_topk as ht
from repro.core import quantization as qz
from repro.core.cache import (
    PagedSalcaCache, SalcaCache, _encode_tokens, _resolve_pages,
    gather_selected_paged, local_block_range)
from repro.core.maxpool import maxpool1d_blocked_halo, maxpool1d_reuse
from repro.core.selection import (
    SalcaParams, estimate_relevance, estimate_relevance_paged_bounds,
    query_heavy_features)
from repro.core.attention import gather_selected, NEG_INF

_EPS = 1e-6


def _halo_exchange(x: jax.Array, halo: int, axis_name) -> jax.Array:
    """Concatenate `halo` columns from both sequence-neighbour shards.

    x: (..., n_local). Returns (..., n_local + 2*halo) with edge fill 0
    (the minimum INT8 bin) at the global boundaries.
    """
    n_shards = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    left_edge = x[..., -halo:]    # what our LEFT neighbour needs on its right
    right_edge = x[..., :halo]
    perm_fwd = [(i, (i + 1) % n_shards) for i in range(n_shards)]
    perm_bwd = [(i, (i - 1) % n_shards) for i in range(n_shards)]
    from_left = jax.lax.ppermute(left_edge, axis_name, perm_fwd)
    from_right = jax.lax.ppermute(right_edge, axis_name, perm_bwd)
    zeros = jnp.zeros_like(from_left)
    from_left = jnp.where(idx == 0, zeros, from_left)
    from_right = jnp.where(idx == n_shards - 1, zeros, from_right)
    return jnp.concatenate([from_left, x, from_right], axis=-1)


def local_lengths(global_len: jax.Array, n_local: int, axis_name) -> jax.Array:
    """Per-shard valid lengths of a sequence-sharded cache.

    global_len: (B,) int32 cursor; shard i owns [i·n_local, (i+1)·n_local).
    """
    off = jax.lax.axis_index(axis_name) * n_local
    return jnp.clip(global_len - off, 0, n_local)


def sp_append_token(cache: SalcaCache, k: jax.Array, v: jax.Array,
                    global_len: jax.Array, axis_name) -> SalcaCache:
    """Append one token's K/V into a sequence-sharded cache.

    The write cursor lands in exactly one shard; other shards' scatters fall
    out of range and are dropped. `cache.length` holds *local* lengths and
    is updated consistently. k, v: (B, KV, HD)."""
    b = k.shape[0]
    n_local = cache.max_seq
    off = jax.lax.axis_index(axis_name) * n_local
    idx = global_len - off                                     # may be OOB
    in_range = (idx >= 0) & (idx < n_local)
    safe_idx = jnp.where(in_range, idx, n_local)               # force drop
    k8, v8, words, fs, fz = _encode_tokens(k[:, None], v[:, None], cache.heavy_idx)

    def upd(buf, val):
        bidx = jnp.arange(b)
        return buf.at[bidx, safe_idx].set(val[:, 0], mode="drop")

    return cache._replace(
        k_codes=upd(cache.k_codes, k8.codes), k_scale=upd(cache.k_scale, k8.scale),
        v_codes=upd(cache.v_codes, v8.codes), v_scale=upd(cache.v_scale, v8.scale),
        feat_words=upd(cache.feat_words, words),
        feat_scale=upd(cache.feat_scale, fs), feat_zero=upd(cache.feat_zero, fz),
        length=jnp.clip(global_len + 1 - off, 0, n_local).astype(jnp.int32),
    )


def sp_dense_decode(q: jax.Array, cache: SalcaCache, axis_name,
                    window: int = 0, global_len: jax.Array | None = None) -> jax.Array:
    """Dense (no selection) decode over a sequence-sharded INT8 cache.

    Used by sliding-window layers (gemma3 local, recurrentgemma attention,
    whisper self-attention) and as the ASIC_D-style dense baseline. Same
    online-softmax psum merge as the Salca path, no filtering. ``window``>0
    restricts to the trailing window (global positions)."""
    b, h, hd = q.shape
    kv = cache.num_kv_heads
    groups = h // kv
    n_local = cache.max_seq
    valid = cache.valid_mask()                                  # (B, n_local)
    if window > 0:
        assert global_len is not None
        off = jax.lax.axis_index(axis_name) * n_local
        pos = off + jnp.arange(n_local, dtype=jnp.int32)[None, :]
        valid = valid & (pos > (global_len[:, None] - window))
    k = cache.k_codes.astype(jnp.float32) * cache.k_scale[..., None]
    v = cache.v_codes.astype(jnp.float32) * cache.v_scale[..., None]
    qg = q.reshape(b, kv, groups, hd).astype(jnp.float32)
    kk = k.transpose(0, 2, 1, 3)                                # (B,KV,S,HD)
    vv = v.transpose(0, 2, 1, 3)
    s = jnp.einsum("bkgd,bksd->bkgs", qg, kk) / jnp.sqrt(hd).astype(jnp.float32)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m_l = jnp.max(s, axis=-1)
    m_g = jax.lax.pmax(m_l, axis_name)
    p = jnp.exp(s - m_g[..., None])
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    l_g = jax.lax.psum(jnp.sum(p, axis=-1), axis_name)
    acc_g = jax.lax.psum(jnp.einsum("bkgs,bksd->bkgd", p, vv), axis_name)
    out = acc_g / jnp.maximum(l_g, 1e-20)[..., None]
    return out.reshape(b, h, hd)


def sp_salca_decode(q: jax.Array, cache: SalcaCache, params: SalcaParams,
                    axis_name, shard_cap: int | None = None) -> jax.Array:
    """Salca decode attention with sequence-sharded cache, inside shard_map.

    q: (B, H, HD) replicated across `axis_name`. `cache` holds this shard's
    slice of the sequence; `cache.length` must hold *local* valid lengths.
    `shard_cap` is the per-shard index-buffer capacity (defaults to
    4×(k_cap / n_shards), clipped to the local length).
    """
    b, h, hd = q.shape
    kv = cache.num_kv_heads
    groups = h // kv
    n_local = cache.max_seq
    n_shards = jax.lax.axis_size(axis_name)
    if shard_cap is None:
        shard_cap = min(n_local, max(128, (4 * params.k_cap) // max(n_shards, 1)))

    # --- Phase 1: local relevance scores --------------------------------
    q_feat = query_heavy_features(q, cache.heavy_idx, groups)
    scores = estimate_relevance(q_feat, cache.feat_words, cache.feat_scale,
                                cache.feat_zero, groups)          # (B,KV,n_local)
    valid = cache.valid_mask()[:, None, :]                        # (B,1,n_local)
    masked = jnp.where(valid, scores, NEG_INF)

    # --- Phase 2: globally-consistent INT8 binning ----------------------
    lo_l = jnp.min(jnp.where(valid, scores, jnp.inf), axis=-1)
    hi_l = jnp.max(masked, axis=-1)
    lo = jax.lax.pmin(lo_l, axis_name)                            # (B,KV)
    hi = jax.lax.pmax(hi_l, axis_name)
    lo = jnp.where(jnp.isfinite(lo), lo, 0.0)
    scale = jnp.maximum((hi - lo) / 254.0, _EPS)
    bins = jnp.clip(jnp.round((scores - lo[..., None]) / scale[..., None]) + 1.0,
                    1.0, 255.0)
    bins = jnp.where(valid, bins, 0.0).astype(jnp.uint8)

    if params.use_pool and params.pool_window > 1:
        halo = params.pool_window // 2
        padded = _halo_exchange(bins, halo, axis_name)
        pooled = maxpool1d_reuse(padded, params.pool_window)[..., halo:-halo]
        pooled = jnp.where(valid, pooled, jnp.uint8(0))
    else:
        pooled = bins

    # --- Phase 3: additive histogram → global threshold -----------------
    hist = ht.histogram256(pooled)                                # (B,KV,256)
    hist = jax.lax.psum(hist, axis_name)
    t = ht.locate_threshold(hist, params.k)                       # (B,KV)
    keep = pooled >= t[..., None].astype(pooled.dtype)
    indices, mask, count = ht.compact_indices(keep, shard_cap)
    sel = ht.Selection(indices, mask, count, t)

    # --- Phase 4: local partial attention + online-softmax merge --------
    kc, ks, vc, vs = gather_selected(cache, sel)                  # (B,KV,C,·)
    qh = q.reshape(b, kv, groups, hd).astype(jnp.float32)
    s = jnp.einsum("bkgd,bkcd->bkgc", qh, kc.astype(jnp.float32))
    s = s * ks[:, :, None, :] / jnp.sqrt(hd).astype(jnp.float32)
    s = jnp.where(mask[:, :, None, :], s, NEG_INF)
    m_l = jnp.max(s, axis=-1)                                     # (B,KV,G)
    m_g = jax.lax.pmax(m_l, axis_name)
    p = jnp.exp(s - m_g[..., None])
    p = jnp.where(mask[:, :, None, :], p, 0.0)
    l_l = jnp.sum(p, axis=-1)
    v = vc.astype(jnp.float32) * vs[..., None]
    acc_l = jnp.einsum("bkgc,bkcd->bkgd", p, v)
    l_g = jax.lax.psum(l_l, axis_name)
    acc_g = jax.lax.psum(acc_l, axis_name)
    out = acc_g / jnp.maximum(l_g, 1e-20)[..., None]
    return out.reshape(b, h, hd)


# ---------------------------------------------------------------------------
# Block-sharded paged pool: the physical block dim of a PagedSalcaCache is
# split across the mesh (shard i owns global block ids [i·P_local,
# (i+1)·P_local)); page tables, lengths, heavy sets and the refcount stay
# replicated. A decode tick runs fully shard-locally — each shard scores,
# bins, pools and exactly-attends over only the blocks it physically holds —
# around two tiny collective phases:
#
#   (1) threshold: pmin/pmax of the binning bounds, a psum of the pre-pool
#       block-edge columns (the blocked-maxpool halo), a psum of the
#       ADDITIVE 256-bin histograms (→ one global Top-K threshold), and a
#       psum of per-block kept counts (→ the global selection rank that
#       reproduces the flat index-buffer capacity truncation exactly);
#   (2) merge: the per-shard partial attention (m, l, acc) combined with the
#       online-softmax pmax/psum identity.
#
# Every payload is O(max_blocks + 256 + head_dim) per (slot, kv-head) —
# independent of context length. The SELECTED TOKEN SET is bit-identical to
# the unsharded paged decode by construction (exact reductions end to end);
# outputs differ only by float summation order in the softmax merge, so
# greedy tokens match (gated by tests/_sharded_pool_check.py).
# ---------------------------------------------------------------------------


def _shard_pool_view(pool: PagedSalcaCache, axis_name):
    """This shard's ownership view of a block-sharded pool.

    Returns (block_range, owned_blk (S, MB) bool, local_pt (S, MB) int32):
    which page-table entries resolve into locally-held blocks, and the table
    translated to local block ids (unowned/unmapped clamp to local block 0 —
    callers mask through `owned_blk`)."""
    lo, hi = local_block_range(pool, axis_name)
    pt = pool.page_table
    owned_blk = (pt >= lo) & (pt < hi)
    local_pt = jnp.where(owned_blk, pt - lo, 0)
    return (lo, hi), owned_blk, local_pt


def _local_logical(pool: PagedSalcaCache, local_pt: jax.Array):
    """Gather a block-indexed pool leaf into logical order from the LOCAL
    pool: buf (P_local, KV, BS, ·) → (S, L, KV, ·). Unowned blocks read
    local block 0 (masked by the caller); owned blocks land bit-identical
    to the flat `paged_logical_features` gather."""
    s, mb = local_pt.shape
    l = mb * pool.block_size

    def logical(buf):
        g = buf[local_pt].swapaxes(2, 3)                    # (S, MB, BS, KV, ·)
        return g.reshape((s, l, buf.shape[1]) + buf.shape[3:])

    return logical


def sp_salca_decode_paged(q: jax.Array, pool: PagedSalcaCache,
                          params: SalcaParams, axis_name,
                          shard_cap: int | None = None,
                          return_selection: bool = False,
                          fused: bool | None = None,
                          impl: str | None = None,
                          interpret: bool | None = None):
    """Salca decode attention over a block-sharded paged pool, in shard_map.

    q: (S, H, HD) replicated; `pool` holds this shard's physical blocks plus
    replicated metadata (see `models.blocks.paged_cache_pspec`). The
    selection (token set, threshold, capacity truncation) is bit-identical
    to `attention.salca_decode_attention_paged` on the unsharded pool.

    Two implementations of the same tick:

    * ``fused=True`` (default via `PERF.sharded_fused_decode`) — the
      fully-pipelined island: scoring streams each locally-owned physical
      feature block once while accumulating the binning bounds, the fused
      bin/pool/hist pass consumes the scores in place, and exact attention
      walks only the shard-local selected blocks. Per-shard per-tick pool
      traffic is O(owned-active + owned-selected) blocks. ``impl`` steers
      the kernel legs ("pallas"/"ref"/"gather", default per platform).
    * ``fused=False`` — the PR 5 logical-gather island: O(local pool)
      feature/KV copies re-materialize through the page table each tick.
      Kept as the structural baseline (same selection bit-for-bit — that is
      the regression test).

    `shard_cap` is the per-shard index-buffer capacity; it defaults to the
    full `params.k_cap` so that even a maximally skewed placement (every
    selected block on one shard) drops exactly the tokens the flat path
    drops, keeping parity unconditional.
    """
    if fused is None:
        from repro.flags import PERF
        fused = PERF.sharded_fused_decode
    if shard_cap is None:
        shard_cap = params.k_cap
    if fused:
        return _sp_salca_decode_paged_fused(q, pool, params, axis_name,
                                            shard_cap, return_selection,
                                            impl, interpret)
    return _sp_salca_decode_paged_gather(q, pool, params, axis_name,
                                         shard_cap, return_selection)


def _sp_salca_decode_paged_gather(q: jax.Array, pool: PagedSalcaCache,
                                  params: SalcaParams, axis_name,
                                  shard_cap: int,
                                  return_selection: bool = False):
    """The PR 5 logical-gather island (see `sp_salca_decode_paged`)."""
    s_, h, hd = q.shape
    kv = pool.num_kv_heads
    groups = h // kv
    bs, mb = pool.block_size, pool.max_blocks
    n = pool.max_seq
    block_range, owned_blk, local_pt = _shard_pool_view(pool, axis_name)
    own = jnp.broadcast_to(owned_blk[..., None],
                           owned_blk.shape + (bs,)).reshape(s_, n)   # (S, L)
    mask3 = (pool.valid_mask() & own)[:, None, :]                    # (S, 1, L)

    # --- Phase 1: relevance scores over locally-held feature blocks -----
    q_feat = query_heavy_features(q, pool.heavy_idx, groups)
    qg = q.reshape(s_, kv, groups, hd).astype(jnp.float32)   # phase-4 operand
    logical = _local_logical(pool, local_pt)
    scores = estimate_relevance(q_feat, logical(pool.feat_words),
                                logical(pool.feat_scale),
                                logical(pool.feat_zero), groups)     # (S,KV,L)

    # --- Phase 2: globally-consistent INT8 binning ----------------------
    # Same arithmetic as qz.quantize_scores_uint8, with the raw per-shard
    # bounds pmin/pmax-merged first (min/max are exact ⇒ identical bounds
    # ⇒ bit-identical bins at every owned position).
    sm = qz.masked_scores(scores, mask3)
    lo_l, hi_l = qz.score_bounds(sm)                                 # (S, KV)
    lo = jax.lax.pmin(lo_l, axis_name)
    hi = jax.lax.pmax(hi_l, axis_name)
    bins = qz.bins_from_bounds(sm, lo, hi, mask3)                    # (S,KV,L)

    # --- Phase 2b: blocked maxpool with psum'd inter-block halos --------
    if params.use_pool and params.pool_window > 1:
        w = params.pool_window
        halo = w // 2
        blocked = bins.reshape(s_, kv, mb, bs)
        # Each block's edge columns are nonzero only on its owner, so one
        # psum reconstructs every block's true pre-pool edges everywhere.
        edges = jnp.stack([blocked[..., -halo:], blocked[..., :halo]])
        edges = jax.lax.psum(edges.astype(jnp.int32), axis_name)
        left, right = edges[0].astype(bins.dtype), edges[1].astype(bins.dtype)
        zero = jnp.zeros(blocked.shape[:-2] + (1, halo), bins.dtype)
        from_left = jnp.concatenate([zero, left[..., :-1, :]], axis=-2)
        from_right = jnp.concatenate([right[..., 1:, :], zero], axis=-2)
        pooled = maxpool1d_blocked_halo(blocked, w, from_left, from_right)
        pooled = pooled.reshape(s_, kv, n)
        pooled = jnp.where(mask3, pooled, jnp.uint8(0))
    else:
        pooled = bins
    if params.sink_tokens or params.recent_tokens:
        pos = jnp.arange(n)
        forced = jnp.zeros((n,), bool)
        if params.sink_tokens:
            forced |= pos < params.sink_tokens
        if params.recent_tokens:
            vm3 = pool.valid_mask()[:, None, :]
            length = jnp.sum(vm3.astype(jnp.int32), axis=-1, keepdims=True)
            forced = forced | (pos >= (length - params.recent_tokens))
        pooled = jnp.where(forced & mask3, jnp.uint8(255), pooled)

    # --- Phase 3: additive histogram psum → threshold; global rank ------
    hist = jax.lax.psum(ht.histogram256(pooled), axis_name)
    t = ht.locate_threshold(hist, params.k)                          # (S, KV)
    keep = pooled >= t[..., None].astype(pooled.dtype)
    # Flat compact_indices drops selections past k_cap by GLOBAL prefix
    # rank; reproduce it exactly from psum'd per-block kept counts (each
    # block's count is nonzero only on its owner) + the local within-block
    # prefix sum.
    kb = keep.reshape(s_, kv, mb, bs)
    blk_counts = jax.lax.psum(jnp.sum(kb.astype(jnp.int32), axis=-1),
                              axis_name)                             # (S,KV,MB)
    base = jnp.cumsum(blk_counts, axis=-1) - blk_counts              # exclusive
    within = jnp.cumsum(kb.astype(jnp.int32), axis=-1) - 1
    grank = (base[..., None] + within).reshape(s_, kv, n)
    keep = keep & (grank < params.k_cap)
    indices, mask, count = ht.compact_indices(keep, shard_cap)
    sel = ht.Selection(indices, mask, count, t)

    # --- Phase 4: local partial attention + online-softmax merge --------
    kc, ks, vc, vs = gather_selected_paged(pool, sel, block_range)
    s = jnp.einsum("bkgd,bkcd->bkgc", qg, kc.astype(jnp.float32))
    s = s * ks[:, :, None, :] / jnp.sqrt(hd).astype(jnp.float32)
    s = jnp.where(mask[:, :, None, :], s, NEG_INF)
    m_l = jnp.max(s, axis=-1)                                        # (S,KV,G)
    m_g = jax.lax.pmax(m_l, axis_name)
    p = jnp.exp(s - m_g[..., None])
    p = jnp.where(mask[:, :, None, :], p, 0.0)
    l_l = jnp.sum(p, axis=-1)
    v = vc.astype(jnp.float32) * vs[..., None]
    acc_l = jnp.einsum("bkgc,bkcd->bkgd", p, v)
    l_g = jax.lax.psum(l_l, axis_name)
    acc_g = jax.lax.psum(acc_l, axis_name)
    out = (acc_g / jnp.maximum(l_g, 1e-20)[..., None]).reshape(s_, h, hd)
    if return_selection:
        return out, sel
    return out


def _sp_salca_decode_paged_fused(q: jax.Array, pool: PagedSalcaCache,
                                 params: SalcaParams, axis_name,
                                 shard_cap: int,
                                 return_selection: bool = False,
                                 impl: str | None = None,
                                 interpret: bool | None = None):
    """The fully-pipelined sharded island (see `sp_salca_decode_paged`).

    A sharded decode tick is two kernel sweeps over the shard's owned pool
    blocks bracketing two collective phases:

      kernel 1  scoring+bounds: each owned feature block streams HBM→VMEM
                once; sentinel-masked scores and the raw (lo, hi) binning
                bounds come out of the same pass.
      psums  1  pmin/pmax the bounds; psum the pre-pool block-edge bin
                columns (the blocked-maxpool halo, O(MB·halo) u8) and —
                after kernel 2 — the additive 256-bin histogram and the
                per-block kept counts (the flat capacity-truncation rank).
      kernel 2  fused selection: INT8 binning (global-bounds affine) +
                stride-1 maxpool (psum'd halos) + histogram accumulation,
                consuming the scores without re-reading the pool.
      kernel 3  exact attention over the shard-local selected-block plan
                (each selected owned block streams once).
      psums  2  the online-softmax (m, l, acc) pmax/psum merge.

    Selection set, threshold and capacity truncation are bit-identical to
    the gather island AND the flat paged path: the scores share the dequant
    chain, min/max/histogram/rank are exact integer/order-independent
    reductions, and the binning affine is the same expression tree
    (`quantization.binning_affine`) everywhere.
    """
    from repro.kernels.common import paged_impl_default
    from repro.kernels.flash_decode.ops import sparse_flash_decode_paged_partials
    from repro.kernels.selection_fused.ops import paged_fused_select
    s_, h, hd = q.shape
    kv = pool.num_kv_heads
    groups = h // kv
    bs, mb = pool.block_size, pool.max_blocks
    n = pool.max_seq
    with jax.named_scope("salca.score"):
        block_range, owned_blk, local_pt = _shard_pool_view(pool, axis_name)
        pos_blk = jnp.arange(n, dtype=jnp.int32).reshape(mb, bs)
        stored = pos_blk[None] < pool.length[:, None, None]            # (S,MB,BS)
        blk_valid = owned_blk[..., None] & stored                      # (S,MB,BS)
        mask3 = blk_valid.reshape(s_, 1, n)

        # --- Kernel 1: streaming scores + raw bounds over owned blocks ------
        q_feat = query_heavy_features(q, pool.heavy_idx, groups)
        qg = q.reshape(s_, kv, groups, hd).astype(jnp.float32)   # phase-4 operand
        sm, lo_l, hi_l = estimate_relevance_paged_bounds(
            q_feat, pool, groups, blk_valid, pages=local_pt,
            impl=impl, interpret=interpret)                          # (S,KV,L)

    with jax.named_scope("salca.select"):
        # --- Collective 1a: merged binning bounds + pre-pool halo columns ---
        lo = jax.lax.pmin(lo_l, axis_name)
        hi = jax.lax.pmax(hi_l, axis_name)
        blocked = sm.reshape(s_, kv, mb, bs)
        use_pool = params.use_pool and params.pool_window > 1
        w = params.pool_window if use_pool else 1
        if use_pool:
            halo = w // 2
            # Bin ONLY each block's edge columns in XLA (O(MB·halo) work) with
            # the merged global affine — bit-identical to slicing the full bins,
            # which kernel 2 computes in VMEM. Each column is nonzero only on
            # its owner, so one psum reconstructs every block's true edges.
            edge_s = jnp.concatenate([blocked[..., -halo:],
                                      blocked[..., :halo]], axis=-1)
            edge_v = jnp.concatenate([blk_valid[..., -halo:],
                                      blk_valid[..., :halo]], axis=-1)[:, None]
            edge_bins = qz.bins_from_bounds(
                edge_s.reshape(s_, kv, mb * 2 * halo), lo, hi,
                edge_v.reshape(s_, 1, mb * 2 * halo)).reshape(s_, kv, mb, 2 * halo)
            edges = jax.lax.psum(edge_bins.astype(jnp.int32), axis_name)
            left, right = edges[..., :halo], edges[..., halo:]
            zero = jnp.zeros(left.shape[:-2] + (1, halo), jnp.int32)
            from_left = jnp.concatenate([zero, left[..., :-1, :]],
                                        axis=-2).astype(jnp.uint8)
            from_right = jnp.concatenate([right[..., 1:, :], zero],
                                         axis=-2).astype(jnp.uint8)
        else:
            from_left = jnp.zeros((s_, kv, mb, 1), jnp.uint8)
            from_right = jnp.zeros((s_, kv, mb, 1), jnp.uint8)
        if params.sink_tokens or params.recent_tokens:
            pos = jnp.arange(n)
            forced = jnp.zeros((n,), bool)
            if params.sink_tokens:
                forced |= pos < params.sink_tokens
            if params.recent_tokens:
                length = jnp.sum(pool.valid_mask().astype(jnp.int32), axis=-1,
                                 keepdims=True)
                forced = forced[None, :] | (pos[None, :]
                                            >= (length - params.recent_tokens))
            force = jnp.broadcast_to(forced, (s_, n)).reshape(s_, mb, bs)
        else:
            force = jnp.zeros((s_, mb, bs), jnp.bool_)

        # --- Kernel 2: fused bin/pool/hist, scores consumed in place --------
        pooled4, hist_l = paged_fused_select(
            blocked, lo, hi, from_left, from_right, blk_valid, force,
            window=w, impl=impl, interpret=interpret)
        pooled = pooled4.reshape(s_, kv, n)

        # --- Collective 1b: histogram psum → threshold; global rank ---------
        # Identical XLA to the gather island from here to the Selection.
        hist = jax.lax.psum(hist_l, axis_name)
        t = ht.locate_threshold(hist, params.k)                          # (S, KV)
        keep = pooled >= t[..., None].astype(pooled.dtype)
        kb = keep.reshape(s_, kv, mb, bs)
        blk_counts = jax.lax.psum(jnp.sum(kb.astype(jnp.int32), axis=-1),
                                  axis_name)                             # (S,KV,MB)
        base = jnp.cumsum(blk_counts, axis=-1) - blk_counts              # exclusive
        within = jnp.cumsum(kb.astype(jnp.int32), axis=-1) - 1
        grank = (base[..., None] + within).reshape(s_, kv, n)
        keep = keep & (grank < params.k_cap)
        indices, mask, count = ht.compact_indices(keep, shard_cap)
        sel = ht.Selection(indices, mask, count, t)

    # --- Kernel 3 + collective 2: sharded exact attention ---------------
    phase4 = impl
    if phase4 is None:
        phase4 = "pallas" if paged_impl_default() == "pallas" else "gather"
    if phase4 != "gather":
        # Names its own stages: salca.plan and salca.attend.
        acc_l, m_l, l_l = sparse_flash_decode_paged_partials(
            q, pool, sel, block_range=block_range, impl=phase4,
            interpret=interpret)                                 # (S,KV,G,·)
    with jax.named_scope("salca.attend"):
        if phase4 == "gather":
            # Row-gather + einsum partials with the gather island's merge
            # (pmax BEFORE exp) — bitwise that path's phase 4, making the
            # platform-default fused tick fully bitwise vs the gather island.
            kc, ks, vc, vs = gather_selected_paged(pool, sel, block_range)
            s = jnp.einsum("bkgd,bkcd->bkgc", qg, kc.astype(jnp.float32))
            s = s * ks[:, :, None, :] / jnp.sqrt(hd).astype(jnp.float32)
            s = jnp.where(mask[:, :, None, :], s, NEG_INF)
            m_g = jax.lax.pmax(jnp.max(s, axis=-1), axis_name)
            p = jnp.exp(s - m_g[..., None])
            p = jnp.where(mask[:, :, None, :], p, 0.0)
            l_g = jax.lax.psum(jnp.sum(p, axis=-1), axis_name)
            v = vc.astype(jnp.float32) * vs[..., None]
            acc_g = jax.lax.psum(jnp.einsum("bkgc,bkcd->bkgd", p, v), axis_name)
        else:
            m_g = jax.lax.pmax(m_l, axis_name)
            corr = jnp.exp(m_l - m_g)
            l_g = jax.lax.psum(l_l * corr, axis_name)
            acc_g = jax.lax.psum(acc_l * corr[..., None], axis_name)
        out = (acc_g / jnp.maximum(l_g, 1e-20)[..., None]).reshape(s_, h, hd)
    if return_selection:
        return out, sel
    return out


def sp_dense_decode_paged(q: jax.Array, pool: PagedSalcaCache, axis_name,
                          window: int = 0,
                          global_pos: jax.Array | None = None) -> jax.Array:
    """Dense (no selection) decode over a block-sharded paged pool.

    The paged analogue of `sp_dense_decode`: each shard dequantizes only the
    K/V blocks it holds (unowned logical positions are masked) and the
    partials merge with the same online-softmax psum. ``window``>0 restricts
    to the trailing window of ``global_pos`` (per-slot positions) — the
    sliding-window / dense-oracle path over a sharded pool.

    The fetch goes through the row-gather resolve (`cache._resolve_pages`):
    one advanced-index gather per field straight into the (S, KV, L, ·)
    attention layout — no (S, L, KV, ·) logical pool copy and no pool-wide
    transpose (the previous form materialized both, per field, every tick).
    Works for all three `kv_pool_dtype` modes (the old path was int8-only).
    """
    s_, h, hd = q.shape
    kv = pool.num_kv_heads
    groups = h // kv
    n = pool.max_seq
    block_range = local_block_range(pool, axis_name)
    idx = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None, :], (s_, n))
    pg, off, owned = _resolve_pages(pool, idx, block_range)        # (S, L)
    valid = pool.valid_mask() & owned
    if window > 0:
        assert global_pos is not None
        pos = jnp.arange(n, dtype=jnp.int32)[None, :]
        valid = valid & (pos > (global_pos[:, None] - window))
    pgk, offk = pg[:, None, :], off[:, None, :]                    # (S, 1, L)
    kvb = jnp.arange(kv)[None, :, None]                            # (1, KV, 1)
    kc, vc = pool.k_codes[pgk, kvb, offk], pool.v_codes[pgk, kvb, offk]
    mode = pool.kv_pool_dtype
    if mode == "int4":
        kc, vc = qz.unpack_int4(kc), qz.unpack_int4(vc)
    soff = offk if mode == "int8" else jnp.zeros_like(offk)
    ks, vs = pool.k_scale[pgk, kvb, soff], pool.v_scale[pgk, kvb, soff]
    kk = kc.astype(jnp.float32) * ks[..., None]                    # (S,KV,L,HD)
    vv = vc.astype(jnp.float32) * vs[..., None]
    qg = q.reshape(s_, kv, groups, hd).astype(jnp.float32)
    s = jnp.einsum("bkgd,bksd->bkgs", qg, kk) / jnp.sqrt(hd).astype(jnp.float32)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m_g = jax.lax.pmax(jnp.max(s, axis=-1), axis_name)
    p = jnp.exp(s - m_g[..., None])
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    l_g = jax.lax.psum(jnp.sum(p, axis=-1), axis_name)
    acc_g = jax.lax.psum(jnp.einsum("bkgs,bksd->bkgd", p, vv), axis_name)
    out = acc_g / jnp.maximum(l_g, 1e-20)[..., None]
    return out.reshape(s_, h, hd)
