"""Pure-jnp oracle for the score-estimation kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import quantization as qz


def score_estimate_ref(q_codes: jax.Array, q_scale: jax.Array, words: jax.Array,
                       feat_scale: jax.Array, feat_zero: jax.Array) -> jax.Array:
    """Same contract as `score_estimate_pallas`, built from jnp primitives."""
    bh, g, r = q_codes.shape
    codes = qz.unpack2bit(words, r)                           # (BH, N, r) int8
    int_dot = jnp.einsum("bgr,bnr->bgn", q_codes.astype(jnp.int32),
                         codes.astype(jnp.int32))
    qsum = jnp.sum(q_codes.astype(jnp.int32), axis=-1)        # (BH, G)
    a = feat_scale[:, None, :]                                # (BH, 1, N)
    z = feat_zero[:, None, :]
    s = q_scale[..., None] * (a * int_dot.astype(jnp.float32)
                              + z * qsum[..., None].astype(jnp.float32))
    return jnp.sum(s, axis=1)                                 # (BH, N)


def paged_score_estimate_ref(q_codes: jax.Array, q_scale: jax.Array,
                             q_sums: jax.Array, feat_words: jax.Array,
                             feat_scale: jax.Array, feat_zero: jax.Array,
                             pages: jax.Array, bf16: bool = True) -> jax.Array:
    """Same contract as `paged_score_estimate_pallas`, from jnp primitives.

    The feature stream is fetched block-decomposed through the (clamped)
    page table — one gather per field keyed on physical block ids; the
    widest temporaries carry the (S, MB, BS, ·) block axes, never a flat
    `(S, L, ·)` logical copy. The elementwise dequant chain mirrors
    `selection.estimate_relevance` op for op (same acc dtype, same
    expression tree), so the scores are bit-identical to running it over
    `cache.paged_logical_features`.
    """
    s, kv, g, r = q_codes.shape
    mb = pages.shape[1]
    bs = feat_words.shape[2]
    fw = feat_words[pages]                                    # (S, MB, KV, BS, W)
    # kv-head leading on both operands → a clean batched int matmul (the
    # mixed-order contraction lowers ~3× slower on CPU).
    codes = qz.unpack2bit(fw, r).transpose(0, 2, 1, 3, 4)     # (S, KV, MB, BS, r)
    int_dot = jnp.einsum("skgr,skmnr->skgmn", q_codes, codes,
                         preferred_element_type=jnp.int32)    # (S, KV, G, MB, BS)
    a = feat_scale[pages].transpose(0, 2, 1, 3)[:, :, None]
    z = feat_zero[pages].transpose(0, 2, 1, 3)[:, :, None]
    scores = qz.dequant_score_chain(q_scale[..., None, None], a, z, int_dot,
                                    q_sums[..., None, None], bf16)
    return jnp.sum(scores, axis=2, dtype=jnp.float32).reshape(s, kv, mb * bs)


def paged_score_bounds_ref(q_codes: jax.Array, q_scale: jax.Array,
                           q_sums: jax.Array, feat_words: jax.Array,
                           feat_scale: jax.Array, feat_zero: jax.Array,
                           pages: jax.Array, blk_valid: jax.Array,
                           bf16: bool = True):
    """Same contract as `paged_score_bounds_pallas`, from jnp primitives.

    Blocked scoring (`paged_score_estimate_ref` — widest temporaries carry
    the (S, MB, BS, ·) block axes) followed by the library's sentinel mask
    and raw bounds reduction, so the (scores, lo, hi) triple is bit-identical
    to the kernel AND to the legacy `masked_scores`/`score_bounds` chain.
    """
    s, kv = q_codes.shape[:2]
    mb, bs = blk_valid.shape[1], blk_valid.shape[2]
    scores = paged_score_estimate_ref(q_codes, q_scale, q_sums, feat_words,
                                      feat_scale, feat_zero, pages, bf16=bf16)
    valid = (blk_valid != 0).reshape(s, 1, mb * bs)
    sm = qz.masked_scores(scores, valid)
    lo, hi = qz.score_bounds(sm)
    return sm, lo, hi
