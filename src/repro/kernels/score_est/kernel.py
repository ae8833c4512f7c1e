"""Pallas TPU kernel: dual-compressed relevance estimation (paper Alg. 1, phase 1).

Computes, per (batch·kv-head) row and per key block,

    S[n] = Σ_g s_q[g] · ( a[n] · Σ_j q̂[g,j]·ĉ[n,j]  +  z[n] · Σ_j q̂[g,j] )

where ĉ are 2-bit key-feature codes stored **packed 16-per-uint32 in HBM**
(so the HBM→VMEM stream is the true 0.5-byte/feature footprint the paper
fights for), unpacked to int8 in VMEM, and contracted on the MXU against
the 3-bit query codes riding in int8 lanes.

Block layout: grid = (B·KV, N/BN). Each step streams one (BN, r/16) word
tile + its (BN,) scale/zero rows; the (G, r) query tile stays resident.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import interpret_default

DEFAULT_BLOCK_N = 512


def _kernel(q_codes_ref, q_scale_ref, words_ref, a_ref, z_ref, out_ref, *, r: int):
    # q_codes: (1, G, r) int8; words: (1, BN, r//16) uint32; a,z: (1, BN) f32
    g = q_codes_ref.shape[1]
    words = words_ref[0]                                   # (BN, r//16)
    shifts = (2 * jax.lax.broadcasted_iota(jnp.uint32, (1, 1, 16), 2))
    codes = (words[:, :, None] >> shifts) & jnp.uint32(0x3)
    codes = codes.reshape(words.shape[0], r).astype(jnp.int8)      # (BN, r)
    q = q_codes_ref[0]                                      # (G, r) int8
    # MXU integer contraction: (BN, r) x (r, G) -> (BN, G)
    int_dot = jax.lax.dot_general(
        codes.astype(jnp.int32), q.astype(jnp.int32),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    qsum = jnp.sum(q.astype(jnp.int32), axis=1)             # (G,)
    a = a_ref[0][:, None]                                   # (BN, 1)
    z = z_ref[0][:, None]
    sq = q_scale_ref[0][None, :]                            # (1, G)
    scores = sq * (a * int_dot.astype(jnp.float32)
                   + z * qsum[None, :].astype(jnp.float32))  # (BN, G)
    out_ref[0] = jnp.sum(scores, axis=1)                    # group sum -> (BN,)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def score_estimate_pallas(q_codes: jax.Array, q_scale: jax.Array,
                          words: jax.Array, feat_scale: jax.Array,
                          feat_zero: jax.Array, *, block_n: int = DEFAULT_BLOCK_N,
                          interpret: bool | None = None) -> jax.Array:
    """q_codes (BH, G, r) int8; q_scale (BH, G) f32; words (BH, N, r//16)
    uint32; feat_scale/zero (BH, N) f32 → scores (BH, N) f32."""
    if interpret is None:
        interpret = interpret_default()
    bh, g, r = q_codes.shape
    n = words.shape[1]
    bn = min(block_n, n)
    assert n % bn == 0, f"N={n} not divisible by block {bn}"
    grid = (bh, n // bn)
    return pl.pallas_call(
        functools.partial(_kernel, r=r),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, g, r), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, g), lambda b, j: (b, 0)),
            pl.BlockSpec((1, bn, r // 16), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, bn), lambda b, j: (b, j)),
            pl.BlockSpec((1, bn), lambda b, j: (b, j)),
        ],
        out_specs=pl.BlockSpec((1, bn), lambda b, j: (b, j)),
        out_shape=jax.ShapeDtypeStruct((bh, n), jnp.float32),
        interpret=interpret,
    )(q_codes, q_scale, words, feat_scale, feat_zero)


# ---------------------------------------------------------------------------
# Paged-native variant: the page table is scalar-prefetched and drives the
# BlockSpec index_map, so each grid step streams one PHYSICAL feature block
# HBM→VMEM — the logical-order copy of the feature stream never exists.
#
# TPU tiling: the last two dims of every block are full or (8k, 128k)-
# aligned. So the query codes arrive split per packed word
# (S, KV, W, G, 16), the per-token scale/zero rows as (P, KV, 1, BS), and
# the scores leave block-major (S, MB, KV, BS) — the wrappers restore the
# (S, KV, MB·BS) logical order, an O(scores) transpose.
# ---------------------------------------------------------------------------


def _paged_block_scores(qc_ref, qs_ref, qsum_ref, words_ref, fs_ref, fz_ref,
                        *, bf16: bool):
    """(KV, BS) group-summed scores of one physical feature block.

    The 2-bit codes unpack word by word (16 channels each) and contract on
    the MXU per word; the codes (0..3) and 3-bit query codes are exact in
    bf16 and the partial sums are small integers, so the f32 sum over words
    equals the integer dot bit for bit."""
    from repro.core.quantization import dequant_score_chain
    words = words_ref[0]                                       # (KV, BS, W)
    shifts = 2 * jax.lax.broadcasted_iota(jnp.uint32, (1, 1, 16), 2)
    int_dot = 0.0
    for w in range(words.shape[2]):
        codes = ((words[:, :, w:w + 1] >> shifts) & jnp.uint32(0x3))
        int_dot = int_dot + jax.lax.dot_general(
            qc_ref[0, :, w], codes.astype(jnp.int32).astype(jnp.float32),
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)                # (KV, G, BS)
    # Shared dequant chain (pinned bf16 rounding) — bit-identical to the
    # flat `selection.estimate_relevance` path by construction.
    scores = dequant_score_chain(qs_ref[0], fs_ref[0], fz_ref[0], int_dot,
                                 qsum_ref[0], bf16)            # (KV, G, BS)
    return jnp.sum(scores, axis=1, dtype=jnp.float32)          # (KV, BS)


def _paged_kernel(pt_ref, qc_ref, qs_ref, qsum_ref, words_ref, fs_ref, fz_ref,
                  out_ref, *, bf16: bool):
    del pt_ref  # consumed by the index_maps
    out_ref[0, 0] = _paged_block_scores(qc_ref, qs_ref, qsum_ref, words_ref,
                                        fs_ref, fz_ref, bf16=bf16)


def _paged_bounds_kernel(pt_ref, qc_ref, qs_ref, qsum_ref, words_ref, fs_ref,
                         fz_ref, valid_ref, out_ref, lo_ref, hi_ref,
                         lo_acc, hi_acc, *, bf16: bool, mb: int):
    """`_paged_kernel` + masking + running (lo, hi) bounds accumulation.

    The sharded fused tick's phase 1: scores leave the kernel already masked
    to the binning sentinel (`quantization.SCORE_NEG_INF`) and the per-row
    raw score bounds — the operands of the cross-shard pmin/pmax — accumulate
    in VMEM across the block grid, so the selection pipeline never re-reads
    the feature stream. min/max are exact, so blockwise accumulation lands on
    the same bounds as the flat `quantization.score_bounds` reduction."""
    del pt_ref  # consumed by the index_maps
    j = pl.program_id(1)
    from repro.core.quantization import SCORE_NEG_INF

    @pl.when(j == 0)
    def _init():
        lo_acc[...] = jnp.full_like(lo_acc, jnp.inf)
        hi_acc[...] = jnp.full_like(hi_acc, -jnp.inf)

    s = _paged_block_scores(qc_ref, qs_ref, qsum_ref, words_ref, fs_ref,
                            fz_ref, bf16=bf16)                 # (KV, BS)
    valid = valid_ref[0, 0] != 0                               # (1, BS)
    sm = jnp.where(valid, s, jnp.float32(SCORE_NEG_INF))
    out_ref[0, 0] = sm
    lo_acc[...] = jnp.minimum(
        lo_acc[...], jnp.min(jnp.where(valid, s, jnp.inf), axis=1,
                             keepdims=True))
    hi_acc[...] = jnp.maximum(hi_acc[...], jnp.max(sm, axis=1, keepdims=True))

    @pl.when(j == mb - 1)
    def _finalize():
        lo_ref[0] = lo_acc[...]
        hi_ref[0] = hi_acc[...]


def _paged_operands(q_codes, q_scale, q_sums, feat_words, feat_scale,
                    feat_zero):
    """Kernel operands + their BlockSpecs (grid (S, MB), prefetched table)."""
    s, kv, g, r = q_codes.shape
    p, _, bs, w = feat_words.shape
    qc = q_codes.astype(jnp.float32).reshape(s, kv, g, w, 16).transpose(
        0, 1, 3, 2, 4)                                         # (S, KV, W, G, 16)
    col = lambda x, dt: x.astype(dt).reshape(s, kv, g, 1)
    row = lambda x: x.reshape(p, kv, 1, bs)
    slot = lambda i, j, pt: (i, 0, 0, 0)
    blk = lambda i, j, pt: (pt[i, j], 0, 0, 0)
    specs = [pl.BlockSpec((1, kv, w, g, 16), lambda i, j, pt: (i, 0, 0, 0, 0)),
             pl.BlockSpec((1, kv, g, 1), slot),
             pl.BlockSpec((1, kv, g, 1), slot),
             pl.BlockSpec((1, kv, bs, w), blk),
             pl.BlockSpec((1, kv, 1, bs), blk),
             pl.BlockSpec((1, kv, 1, bs), blk)]
    ops = (qc, col(q_scale, jnp.float32), col(q_sums, jnp.int32), feat_words,
           row(feat_scale), row(feat_zero))
    return ops, specs


def _logical_scores(blocked):
    """(S, MB, KV, BS) block-major kernel output → (S, KV, MB·BS)."""
    s, mb, kv, bs = blocked.shape
    return blocked.transpose(0, 2, 1, 3).reshape(s, kv, mb * bs)


@functools.partial(jax.jit, static_argnames=("bf16", "interpret"))
def paged_score_bounds_pallas(q_codes: jax.Array, q_scale: jax.Array,
                              q_sums: jax.Array, feat_words: jax.Array,
                              feat_scale: jax.Array, feat_zero: jax.Array,
                              pages: jax.Array, blk_valid: jax.Array,
                              *, bf16: bool = True,
                              interpret: bool | None = None):
    """Sentinel-masked relevance scores + raw per-row score bounds, one pass.

    Same operands as `paged_score_estimate_pallas` plus ``blk_valid``
    (S, MB, BS) int8 — the per-block validity columns (owned ∧ stored for the
    sharded tick). Returns (scores (S, KV, MB·BS) f32 with invalid positions
    at `SCORE_NEG_INF`, lo (S, KV) f32, hi (S, KV) f32) where (lo, hi) are
    the raw `quantization.score_bounds` partials ready for pmin/pmax.
    """
    if interpret is None:
        interpret = interpret_default()
    s, kv = q_codes.shape[:2]
    bs = feat_words.shape[2]
    mb = pages.shape[1]
    ops, specs = _paged_operands(q_codes, q_scale, q_sums, feat_words,
                                 feat_scale, feat_zero)
    bound = pl.BlockSpec((1, kv, 1), lambda i, j, pt: (i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(s, mb),
        in_specs=specs + [
            pl.BlockSpec((1, 1, 1, bs), lambda i, j, pt: (i, j, 0, 0))],
        out_specs=[pl.BlockSpec((1, 1, kv, bs), lambda i, j, pt: (i, j, 0, 0)),
                   bound, bound],
        scratch_shapes=[pltpu.VMEM((kv, 1), jnp.float32),
                        pltpu.VMEM((kv, 1), jnp.float32)],
    )
    scores, lo, hi = pl.pallas_call(
        functools.partial(_paged_bounds_kernel, bf16=bf16, mb=mb),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((s, mb, kv, bs), jnp.float32),
            jax.ShapeDtypeStruct((s, kv, 1), jnp.float32),
            jax.ShapeDtypeStruct((s, kv, 1), jnp.float32),
        ],
        interpret=interpret,
    )(pages, *ops, blk_valid.astype(jnp.int32).reshape(s, mb, 1, bs))
    return _logical_scores(scores), lo[..., 0], hi[..., 0]


@functools.partial(jax.jit, static_argnames=("bf16", "interpret"))
def paged_score_estimate_pallas(q_codes: jax.Array, q_scale: jax.Array,
                                q_sums: jax.Array, feat_words: jax.Array,
                                feat_scale: jax.Array, feat_zero: jax.Array,
                                pages: jax.Array, *, bf16: bool = True,
                                interpret: bool | None = None) -> jax.Array:
    """Relevance scores straight off the physical block pool.

    q_codes (S, KV, G, r) int8 + q_scale (S, KV, G) f32 + q_sums (S, KV, G)
    int32 (precomputed code sums); feat_words (P, KV, BS, r//16) uint32 with
    feat_scale/zero (P, KV, BS) f32 — the SHARED pool, not a logical copy;
    pages (S, MB) int32 page table with unmapped entries already clamped to
    block 0 (`PagedSalcaCache.clamped_pages`). Returns (S, MB·BS, ·)-ordered
    scores (S, KV, L) f32. Grid = (S, MB); step (s, j) streams physical
    block ``pages[s, j]`` — per-tick feature traffic is the mapped blocks,
    with repeated (clamped) indices coalesced by the pipeline.
    """
    if interpret is None:
        interpret = interpret_default()
    s, kv = q_codes.shape[:2]
    bs = feat_words.shape[2]
    mb = pages.shape[1]
    ops, specs = _paged_operands(q_codes, q_scale, q_sums, feat_words,
                                 feat_scale, feat_zero)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(s, mb),
        in_specs=specs,
        out_specs=pl.BlockSpec((1, 1, kv, bs), lambda i, j, pt: (i, j, 0, 0)),
    )
    scores = pl.pallas_call(
        functools.partial(_paged_kernel, bf16=bf16),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, mb, kv, bs), jnp.float32),
        interpret=interpret,
    )(pages, *ops)
    return _logical_scores(scores)
