"""Exact sparse attention over the selected tokens (paper Algorithm 1, phase 4).

Fetch (gather) the INT8 K/V rows named by the selection, compute scaled
dot-product scores with running-max tracking, online softmax, and the
weighted Value sum. The Pallas `flash_decode` kernel implements the same
computation blocked over the capacity dim; this module is the XLA reference
path (used in the distributed steps) plus the dense oracles.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import quantization as qz
from repro.core.cache import (
    PagedSalcaCache, SalcaCache, gather_selected_paged, paged_logical_features,
    paged_logical_kv)
from repro.core.histogram_topk import Selection, compact_indices
from repro.core.selection import (
    SalcaParams, estimate_relevance, estimate_relevance_paged,
    query_heavy_features, salca_select, select_sparse_pattern_blocked)

NEG_INF = -1e30


def gather_selected(cache: SalcaCache, sel: Selection):
    """Gather selected K/V rows per (batch, kv-head).

    sel.indices: (B, KV, C). Returns int8 k/v codes (B, KV, C, HD) and
    scales (B, KV, C).
    """
    idx = sel.indices  # (B, KV, C)

    def take_codes(codes):  # (B,S,KV,HD) -> (B,KV,C,HD)
        c = codes.transpose(0, 2, 1, 3)                       # (B,KV,S,HD)
        return jnp.take_along_axis(c, idx[..., None], axis=2)

    def take_scale(scale):  # (B,S,KV) -> (B,KV,C)
        s = scale.transpose(0, 2, 1)
        return jnp.take_along_axis(s, idx, axis=2)

    return (take_codes(cache.k_codes), take_scale(cache.k_scale),
            take_codes(cache.v_codes), take_scale(cache.v_scale))


def exact_sparse_attention(q: jax.Array, k_codes: jax.Array, k_scale: jax.Array,
                           v_codes: jax.Array, v_scale: jax.Array,
                           mask: jax.Array) -> jax.Array:
    """Attention of q over gathered INT8 K/V.

    q: (B, H, HD); k/v codes: (B, KV, C, HD) int8 with (B, KV, C) scales;
    mask: (B, KV, C) bool. Returns (B, H, HD) f32.

    Score uses the int8 codes directly on the contraction (MXU int path on
    TPU) and applies the per-token scale afterwards — exactly what the
    paper's QK-mul stage does with its dequant-after-accumulate datapath.
    """
    b, h, hd = q.shape
    kv = k_codes.shape[1]
    g = h // kv
    qg = q.reshape(b, kv, g, hd).astype(jnp.float32)
    # Full f32 matmuls: the TPU default for f32 operands rounds them to bf16.
    hi = jax.lax.Precision.HIGHEST
    s_int = jnp.einsum("bkgd,bkcd->bkgc", qg, k_codes.astype(jnp.float32),
                       precision=hi)
    s = s_int * k_scale[:, :, None, :] / jnp.sqrt(hd).astype(jnp.float32)
    s = jnp.where(mask[:, :, None, :], s, NEG_INF)
    # Safe softmax with global-max tracking (paper's qk_max mechanism).
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.maximum(m, NEG_INF)  # guard all-masked rows
    p = jnp.exp(s - m)
    p = jnp.where(mask[:, :, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    v = v_codes.astype(jnp.float32) * v_scale[..., None]      # (B,KV,C,HD)
    o = jnp.einsum("bkgc,bkcd->bkgd", p, v, precision=hi) / jnp.maximum(l, 1e-20)
    return o.reshape(b, h, hd)


def fused_select_flat(scores: jax.Array, length: jax.Array,
                      params: SalcaParams, impl: str = "pallas",
                      interpret: bool | None = None) -> Selection:
    """Phases 2-3 through the fused `selection_fused` kernel (one HBM pass).

    scores: (B, KV, N) f32; length: (B,) valid-prefix lengths. Bitwise-
    identical Selection to `select_sparse_pattern` without sink/recent
    forcing: the bounds are cleaned through `binning_affine` BEFORE they
    reach the kernel (whose affine uses its `lo` operand raw), so the
    in-kernel bins match `bins_from_bounds`, the integer maxpool is exact,
    and the in-kernel reverse-prefix scan is `locate_threshold` verbatim.
    """
    from repro.kernels.selection_fused.ops import fused_bin_pool_threshold
    b, kv, n = scores.shape
    valid = jnp.arange(n)[None, :] < length[:, None]                # (B, N)
    s = qz.masked_scores(scores, valid[:, None, :])
    lo, hi = qz.score_bounds(s)                                     # (B, KV)
    offset, _ = qz.binning_affine(lo, hi)
    w = params.pool_window if params.use_pool else 1
    pooled, _, thr = fused_bin_pool_threshold(
        s.reshape(b * kv, n), offset.reshape(-1), hi.reshape(-1),
        jnp.full((b * kv,), params.k, jnp.int32),
        jnp.broadcast_to(length[:, None], (b, kv)).reshape(-1),
        window=w, impl=impl, interpret=interpret)
    keep = pooled >= thr[:, None].astype(pooled.dtype)
    indices, mask, count = compact_indices(keep.reshape(b, kv, n),
                                           params.k_cap)
    return Selection(indices, mask, count, thr.reshape(b, kv))


def salca_decode_attention(q: jax.Array, cache: SalcaCache, params: SalcaParams,
                           return_selection: bool = False,
                           impl: str | None = None,
                           interpret: bool | None = None):
    """Full Salca decode attention for one step.

    q: (B, H, HD) current query (post-RoPE). Returns (B, H, HD) f32 output
    (and optionally the Selection for introspection).

    ``impl`` routes selection phases 2-3: None/"xla" chains the library
    primitives (`salca_select`); "pallas"/"ref" runs the fused
    bin→pool→histogram→threshold kernel — same Selection bit-for-bit.
    Sink/recent forcing bends the histogram before the threshold, which the
    fused kernel doesn't model, so those configs stay on the XLA chain.
    """
    h = q.shape[1]
    kv = cache.num_kv_heads
    groups = h // kv
    q_feat = query_heavy_features(q, cache.heavy_idx, groups)
    fused = (impl in ("pallas", "ref")
             and not (params.sink_tokens or params.recent_tokens))
    if fused:
        scores = estimate_relevance(q_feat, cache.feat_words, cache.feat_scale,
                                    cache.feat_zero, groups)
        sel = fused_select_flat(scores, cache.length, params, impl=impl,
                                interpret=interpret)
    else:
        sel = salca_select(q_feat, cache.feat_words, cache.feat_scale,
                           cache.feat_zero, groups, params,
                           valid_mask=cache.valid_mask())
    kc, ks, vc, vs = gather_selected(cache, sel)
    out = exact_sparse_attention(q, kc, ks, vc, vs, sel.mask)
    if return_selection:
        return out, sel
    return out


def salca_decode_attention_paged(q: jax.Array, pool: PagedSalcaCache,
                                 params: SalcaParams,
                                 return_selection: bool = False,
                                 fused: bool | None = None,
                                 impl: str | None = None,
                                 interpret: bool | None = None):
    """Full Salca decode attention over a paged block pool.

    Identical math to `salca_decode_attention` on the contiguous cache, in
    one of two data paths:

    * **fused** (default, `flags.PERF.paged_fused_decode`): the page-table
      walk is fused into the kernels — relevance scoring streams *physical*
      feature blocks (`selection.estimate_relevance_paged`) and exact
      attention fetches only the physical blocks the selection touches
      (`kernels.flash_decode.sparse_flash_decode_paged`). No logical copy of
      the pool and no pool-wide transpose exist in the tick; per-tick HBM
      traffic is O(active tokens + selected blocks) instead of O(pool).
    * **unfused** (the PR 3 path, kept as the baseline/fallback): the
      feature stream is gathered into logical (page) order and the
      exact-attention gather fetches each selected row individually.

    Both paths share the query quantization, the blocked selection (additive
    per-block histograms), and the page-table clamping rules, so the
    selection — and hence the attended token set — is bit-identical between
    them; outputs differ only by float summation order.
    """
    from repro.flags import PERF
    if fused is None:
        fused = PERF.paged_fused_decode
    h = q.shape[1]
    kv = pool.num_kv_heads
    groups = h // kv
    q_feat = query_heavy_features(q, pool.heavy_idx, groups)
    if fused:
        scores = estimate_relevance_paged(q_feat, pool, groups, impl=impl,
                                          interpret=interpret)
    else:
        fw, fs, fz = paged_logical_features(pool)
        scores = estimate_relevance(q_feat, fw, fs, fz, groups)
    # mapped_valid_mask: identical to valid_mask unless the engine demoted a
    # cold block to host memory (page_table -1 below the cursor) — a spilled
    # block must be unselectable, not garbage-read, until promoted back.
    sel = select_sparse_pattern_blocked(scores, params,
                                        pool.mapped_valid_mask()[:, None, :],
                                        pool.block_size)
    if fused:
        from repro.kernels.flash_decode.ops import sparse_flash_decode_paged
        out = sparse_flash_decode_paged(q, pool, sel, impl=impl,
                                        interpret=interpret)
    else:
        kc, ks, vc, vs = gather_selected_paged(pool, sel)
        out = exact_sparse_attention(q, kc, ks, vc, vs, sel.mask)
    if return_selection:
        return out, sel
    return out


# ---------------------------------------------------------------------------
# Dense oracles (for accuracy benchmarks and tests)
# ---------------------------------------------------------------------------

def dense_decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           valid_mask: jax.Array | None = None) -> jax.Array:
    """Full-precision dense decode attention oracle.

    q: (B, H, HD); k, v: (B, S, KV, HD); valid_mask: (B, S).

    Masked-slot contract (slot-pooled serving): a row whose valid mask is
    all-False — an inactive pool slot holding 0 tokens — returns exact
    zeros, never NaN and never an average over stale rows.
    """
    b, h, hd = q.shape
    kv = k.shape[2]
    g = h // kv
    qg = q.reshape(b, kv, g, hd).astype(jnp.float32)
    kk = k.transpose(0, 2, 1, 3).astype(jnp.float32)          # (B,KV,S,HD)
    vv = v.transpose(0, 2, 1, 3).astype(jnp.float32)
    s = jnp.einsum("bkgd,bksd->bkgs", qg, kk) / jnp.sqrt(hd)
    if valid_mask is not None:
        s = jnp.where(valid_mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if valid_mask is not None:
        # No-op for partially-masked rows (those probs are already ~0);
        # zeroes the uniform softmax a fully-masked row would produce.
        p = p * valid_mask[:, None, None, :]
    o = jnp.einsum("bkgs,bksd->bkgd", p, vv)
    return o.reshape(b, h, hd)


def dense_decode_from_cache(q: jax.Array, cache: SalcaCache) -> jax.Array:
    """Dense attention over the INT8 cache (isolates selection error from
    quantization error when compared against `salca_decode_attention`)."""
    k = cache.k_codes.astype(jnp.float32) * cache.k_scale[..., None]
    v = cache.v_codes.astype(jnp.float32) * cache.v_scale[..., None]
    return dense_decode_attention(q, k, v, cache.valid_mask())


def dense_decode_from_paged(q: jax.Array, pool: PagedSalcaCache,
                            valid_mask: jax.Array | None = None) -> jax.Array:
    """Dense attention over a paged pool's logical view (sliding-window
    layers and the paged-vs-contiguous parity oracle). Mode-generic via
    `paged_logical_kv`; the default mask excludes host-spilled (unmapped)
    blocks like the sparse path does."""
    k, v = paged_logical_kv(pool)
    return dense_decode_attention(
        q, k, v, pool.mapped_valid_mask() if valid_mask is None else valid_mask)
