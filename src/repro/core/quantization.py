"""Ultra-low-precision quantization primitives for Salca (paper §3.1).

Implements the paper's dual-compression bit widths:

* **2-bit asymmetric** Key-feature quantization (codes in {0..3}, per-token
  per-head scale + zero point — the paper's "two FP16 quantization factors").
* **3-bit symmetric** Query quantization (codes in {-3..3}; the scale is
  shared across all keys of a head so it never changes ranking and can be
  dropped, but we keep it for interpretable dequantized scores).
* **INT8 symmetric** K/V quantization for the exact-attention phase (the
  paper executes attention under 8-bit quantization).
* **INT8 score binning** for the histogram filter (§3.2) — scores map to
  uint8 "addresses" in [0, 255].
* **Sub-byte packing**: 2-bit codes are packed 16-per-int32 so that HBM
  traffic in the dry-run/roofline reflects the true 2-bit footprint.

Every function is shape-polymorphic over leading batch dims and jit-safe.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

# Number of levels used by each scheme.
KEY2_LEVELS = 4          # 2-bit codes {0,1,2,3}
QUERY3_MAXABS = 3        # 3-bit symmetric codes {-3..3}
INT8_MAXABS = 127
INT4_MAXABS = 7          # 4-bit symmetric codes {-7..7} (nibble-packed)

_EPS = 1e-6


class AsymQuant(NamedTuple):
    """Asymmetrically quantized tensor: ``x ≈ scale * codes + zero``."""

    codes: jax.Array   # integer codes, int8 carrier
    scale: jax.Array   # per-row scale, f32
    zero: jax.Array    # per-row zero point (= row min), f32


class SymQuant(NamedTuple):
    """Symmetrically quantized tensor: ``x ≈ scale * codes``."""

    codes: jax.Array   # integer codes, int8 carrier
    scale: jax.Array   # per-row scale, f32


# ---------------------------------------------------------------------------
# Generic helpers
# ---------------------------------------------------------------------------

def asym_quantize(x: jax.Array, bits: int, axis: int = -1) -> AsymQuant:
    """Asymmetric quantization along ``axis`` with ``2**bits`` levels.

    ``codes = round((x - min) / scale)`` with ``scale = (max - min) / (2^b-1)``.
    """
    levels = (1 << bits) - 1
    x32 = x.astype(jnp.float32)
    lo = jnp.min(x32, axis=axis, keepdims=True)
    hi = jnp.max(x32, axis=axis, keepdims=True)
    scale = (hi - lo) / levels
    safe = jnp.maximum(scale, _EPS)
    codes = jnp.clip(jnp.round((x32 - lo) / safe), 0, levels).astype(jnp.int8)
    return AsymQuant(codes, jnp.squeeze(safe, axis), jnp.squeeze(lo, axis))


def sym_quantize(x: jax.Array, bits: int, axis: int = -1) -> SymQuant:
    """Symmetric quantization along ``axis``; codes in ``[-(2^(b-1)-1), ...]``."""
    maxabs_code = (1 << (bits - 1)) - 1
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=axis, keepdims=True)
    scale = jnp.maximum(amax / maxabs_code, _EPS)
    codes = jnp.clip(jnp.round(x32 / scale), -maxabs_code, maxabs_code)
    return SymQuant(codes.astype(jnp.int8), jnp.squeeze(scale, axis))


def sym_quantize_axes(x: jax.Array, bits: int,
                      axes: tuple[int, ...]) -> tuple[jax.Array, jax.Array]:
    """Symmetric quantization with ONE shared scale over ``axes``.

    The per-block, per-head scheme of the tiered KV pool: for a physical
    block ``(BS, KV, HD)``, ``axes=(-3, -1)`` shares a scale across the
    block's tokens and head channels while keeping kv-heads independent.
    Returns ``(codes int8, scale f32)`` with the reduced axes KEPT as size-1
    dims so the scale broadcasts straight back against ``codes``.
    """
    maxabs_code = (1 << (bits - 1)) - 1
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=axes, keepdims=True)
    scale = jnp.maximum(amax / maxabs_code, _EPS)
    codes = jnp.clip(jnp.round(x32 / scale), -maxabs_code, maxabs_code)
    return codes.astype(jnp.int8), scale


def sym_dequantize_axes(codes: jax.Array, scale: jax.Array) -> jax.Array:
    """Inverse of :func:`sym_quantize_axes` (scale keeps its size-1 dims)."""
    return codes.astype(jnp.float32) * scale


def asym_dequantize(q: AsymQuant, axis: int = -1) -> jax.Array:
    scale = jnp.expand_dims(q.scale, axis)
    zero = jnp.expand_dims(q.zero, axis)
    return q.codes.astype(jnp.float32) * scale + zero


def sym_dequantize(q: SymQuant, axis: int = -1) -> jax.Array:
    return q.codes.astype(jnp.float32) * jnp.expand_dims(q.scale, axis)


# ---------------------------------------------------------------------------
# Paper-specific schemes
# ---------------------------------------------------------------------------

def quantize_key_features(k_feat: jax.Array) -> AsymQuant:
    """2-bit asymmetric quantization of heavy-channel Key features.

    ``k_feat``: (..., r) FP key features; quantized per row (= per token per
    kv-head), matching the paper's two-FP16-factors-per-key layout.
    """
    return asym_quantize(k_feat, bits=2)


def quantize_query_features(q_feat: jax.Array) -> SymQuant:
    """3-bit symmetric quantization of heavy-channel Query features."""
    return sym_quantize(q_feat, bits=3)


def quantize_kv_int8(x: jax.Array) -> SymQuant:
    """INT8 symmetric per-token quantization of K or V for exact attention."""
    return sym_quantize(x, bits=8)


def estimate_scores(q3: SymQuant, k2: AsymQuant) -> jax.Array:
    """Dequantized relevance scores from dual-compressed features.

    ``q3.codes``: (..., H, r) int8; ``k2.codes``: (..., N, r) int8.
    Returns (..., H, N) f32 scores:

        S = Σ_j q_j * (a*c_j + z) = s_q * (a * Σ q̂_j c_j + z * Σ q̂_j)

    The integer dot product ``Σ q̂ c`` is the MXU-friendly part; the
    correction uses the precomputed code-sum of q.
    """
    qi = q3.codes.astype(jnp.int32)
    ki = k2.codes.astype(jnp.int32)
    int_dot = jax.lax.dot_general(
        qi, ki,
        dimension_numbers=(((qi.ndim - 1,), (ki.ndim - 1,)),
                           (tuple(range(qi.ndim - 2)), tuple(range(ki.ndim - 2)))),
        preferred_element_type=jnp.int32,
    )  # (..., H, N)
    qsum = jnp.sum(qi, axis=-1)                       # (..., H)
    a = k2.scale[..., None, :]                        # (..., 1, N)
    z = k2.zero[..., None, :]
    s_q = q3.scale[..., None]                         # (..., H, 1)
    return s_q * (a * int_dot.astype(jnp.float32) + z * qsum[..., None].astype(jnp.float32))


def dequant_score_chain(q_scale: jax.Array, a: jax.Array, z: jax.Array,
                        int_dot: jax.Array, q_sums: jax.Array,
                        bf16: bool) -> jax.Array:
    """Shared phase-1 dequant chain: ``s_q · (a · Σq̂ĉ + z · Σq̂)``.

    All relevance-score producers (flat XLA, paged XLA, paged Pallas kernel)
    run THIS function so their scores are bit-identical by construction.
    When ``bf16`` (§Perf it-6) the chain emulates bf16 arithmetic in f32 by
    rounding to bf16 precision after every op (`round_to_bf16`): a plain
    bf16 dtype chain rounds per-op in eager mode but XLA fusion may elide the
    intermediate rounding, making numerics depend on the surrounding graph —
    integer bit rounding is never elided, so the rounding points are pinned
    no matter how each caller's graph compiles, and it lowers inside a TPU
    kernel too. Operands must be pre-broadcast; returns f32.
    """
    d = int_dot.astype(jnp.float32)
    qm = q_sums.astype(jnp.float32)
    a = a.astype(jnp.float32)
    z = z.astype(jnp.float32)
    qs = q_scale.astype(jnp.float32)
    if not bf16:
        return qs * (a * d + z * qm)

    rp = round_to_bf16
    return rp(rp(qs) * rp(rp(rp(a) * rp(d)) + rp(rp(z) * rp(qm))))


def round_to_bf16(x: jax.Array) -> jax.Array:
    """Round f32 to the nearest bf16 value (ties to even), kept in f32.

    Equal to ``lax.reduce_precision(x, 8, 7)`` on finite inputs, written with
    integer ops on the bit pattern because Pallas TPU kernels have no
    lowering for reduce_precision."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    b = (b + 0x7FFF + ((b >> 16) & 1)) & jnp.int32(-65536)
    return jax.lax.bitcast_convert_type(b, jnp.float32)


SCORE_NEG_INF = -3.0e38     # masked-score sentinel for the binning affine map


def masked_scores(scores: jax.Array, valid_mask: jax.Array | None) -> jax.Array:
    """f32 scores with masked positions at the binning sentinel."""
    s = scores.astype(jnp.float32)
    if valid_mask is not None:
        s = jnp.where(valid_mask, s, jnp.float32(SCORE_NEG_INF))
    return s


def score_bounds(s: jax.Array, axis: int = -1) -> tuple[jax.Array, jax.Array]:
    """Raw per-row (lo, hi) of masked scores, keepdims dropped.

    `lo` ignores sentinel-masked positions (all-masked rows give +inf — the
    cleanup lives in `bins_from_bounds` so the distributed path can pmin/pmax
    these raw partials FIRST and still land on identical bounds: min/max are
    exact, so a shard-wise reduction of raw bounds == the flat bounds."""
    lo = jnp.min(jnp.where(s <= SCORE_NEG_INF / 2, jnp.inf, s), axis=axis)
    hi = jnp.max(s, axis=axis)
    return lo, hi


def binning_affine(lo: jax.Array, hi: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Global-bounds binning affine: (lo, hi) → (offset, scale) so that
    ``bin = clip(round((s - offset)/scale) + 1, 1, 255)``.

    THE definition of the INT8 binning arithmetic — `bins_from_bounds`, the
    fused-selection kernels and their refs all derive bins from this exact
    pair, so paths that merge raw per-shard bounds first (pmin/pmax) and
    paths that bin locally land on bit-identical bins. All-masked rows (+inf
    lo from `score_bounds`) clean up to offset 0 here."""
    offset = jnp.where(jnp.isfinite(lo), lo, 0.0)
    scale = jnp.maximum((hi - offset) / 254.0, _EPS)
    return offset, scale


def bins_from_bounds(s: jax.Array, lo: jax.Array, hi: jax.Array,
                     valid_mask: jax.Array | None = None) -> jax.Array:
    """Affine-map masked scores to uint8 bins given (possibly globally
    reduced) bounds; masked positions land on bin 0. The single definition
    of the binning arithmetic for the flat AND the sequence-sharded paths —
    identical bounds in, bit-identical bins out."""
    offset, scale = binning_affine(lo, hi)
    offset, scale = offset[..., None], scale[..., None]
    bins = jnp.clip(jnp.round((s - offset) / scale) + 1.0, 1.0, 255.0)
    if valid_mask is not None:
        bins = jnp.where(valid_mask, bins, 0.0)
    return bins.astype(jnp.uint8)


def quantize_scores_uint8(scores: jax.Array, valid_mask: jax.Array | None = None,
                          axis: int = -1) -> jax.Array:
    """Map FP scores to INT8 bins [0,255] per row (paper §3.2 phase 1).

    Monotone affine map ⇒ relative ordering preserved; masked (invalid)
    positions map to bin 0 so they can never pass a threshold ≥ 1.
    """
    if axis != -1:
        if valid_mask is not None:
            # Broadcast to the full scores shape BEFORE moving the axis: a
            # broadcast-shaped mask (e.g. (B, 1, N) against (B, KV, N) with
            # axis=1) would otherwise have the wrong dimension moved and
            # misalign silently.
            valid_mask = jnp.moveaxis(
                jnp.broadcast_to(valid_mask, scores.shape), axis, -1)
        scores = jnp.moveaxis(scores, axis, -1)
    s = masked_scores(scores, valid_mask)
    lo, hi = score_bounds(s)
    bins = bins_from_bounds(s, lo, hi, valid_mask)
    if axis != -1:
        bins = jnp.moveaxis(bins, -1, axis)
    return bins


# ---------------------------------------------------------------------------
# Sub-byte packing (2-bit codes <-> int32 words, 16 codes per word)
# ---------------------------------------------------------------------------

CODES_PER_WORD = 16


def pack2bit(codes: jax.Array) -> jax.Array:
    """Pack 2-bit codes (int8 in {0..3}, last dim divisible by 16) to uint32."""
    *lead, r = codes.shape
    assert r % CODES_PER_WORD == 0, f"feature dim {r} not divisible by 16"
    c = codes.astype(jnp.uint32).reshape(*lead, r // CODES_PER_WORD, CODES_PER_WORD)
    shifts = (2 * jnp.arange(CODES_PER_WORD, dtype=jnp.uint32))
    return jnp.sum(c << shifts, axis=-1, dtype=jnp.uint32)


def unpack2bit(words: jax.Array, r: int) -> jax.Array:
    """Inverse of :func:`pack2bit`; returns int8 codes of feature dim ``r``.

    §Perf it-5: unpack byte-wise — bitcast each uint32 to 4 uint8 lanes and
    shift in uint8, so the widest intermediate is 1 byte/code instead of the
    naive 4 (uint32) — a 4× cut of this stage's HBM-bytes in the XLA path
    (the Pallas kernel unpacks in VMEM where this never hits HBM).
    """
    *lead, nw = words.shape
    assert nw * CODES_PER_WORD == r
    from repro.flags import PERF
    if not PERF.hist_scatter_add:   # baseline variant: plain uint32 unpack
        shifts = (2 * jnp.arange(CODES_PER_WORD, dtype=jnp.uint32))
        c = (words[..., None] >> shifts) & jnp.uint32(0x3)
        return c.reshape(*lead, r).astype(jnp.int8)
    bytes_ = jax.lax.bitcast_convert_type(words, jnp.uint8)  # (..., nw, 4)
    shifts8 = jnp.arange(0, 8, 2, dtype=jnp.uint8)
    c = (bytes_[..., None] >> shifts8) & jnp.uint8(0x3)       # (..., nw, 4, 4)
    return c.reshape(*lead, r).astype(jnp.int8)


# 4-bit nibble packing (two signed int4 codes per int8 byte, along the last
# dim): even channels in the low nibble, odd channels in the high nibble.
# The unpack is pure shift arithmetic — `(b << 4) >> 4` sign-extends the low
# nibble because int8 right shift is arithmetic — so it runs unchanged
# inside a Pallas VMEM block.

def pack_int4(codes: jax.Array) -> jax.Array:
    """Pack int4 codes (int8 in [-7, 7], even last dim) two-per-byte."""
    *lead, d = codes.shape
    assert d % 2 == 0, f"head dim {d} not divisible by 2 for int4 packing"
    c = codes.astype(jnp.int8).reshape(*lead, d // 2, 2)
    even, odd = c[..., 0], c[..., 1]
    return ((odd << 4) | (even & jnp.int8(0x0F))).astype(jnp.int8)


def unpack_int4(packed: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_int4`; returns int8 codes, last dim doubled."""
    b = packed.astype(jnp.int8)
    lo = (b << 4) >> 4            # arithmetic shift sign-extends the nibble
    hi = b >> 4
    return jnp.stack([lo, hi], axis=-1).reshape(*b.shape[:-1], 2 * b.shape[-1])


# Alternate schemes used only by the design-space exploration benchmarks
# (paper Table 7): 1-bit sign, 2/3-bit sym/asym, MSB-truncated INT8.

def quantize_sign(x: jax.Array) -> jax.Array:
    """1-bit sign-only quantization (Table 7 row ``k_1``)."""
    return jnp.where(x >= 0, 1.0, -1.0).astype(jnp.float32)


def quantize_msb(x: jax.Array, keep_bits: int, axis: int = -1) -> jax.Array:
    """INT8-then-MSB-truncate (Table 7 rows ``k_msb{2,3}``), Energon-style.

    Quantizes symmetrically to int8 then keeps the top ``keep_bits`` bits
    (zeroing the rest), returning the dequantized approximation.
    """
    q = sym_quantize(x, bits=8, axis=axis)
    drop = 8 - 1 - keep_bits  # of the 7 magnitude bits keep the top `keep_bits`
    codes = q.codes.astype(jnp.int32)
    trunc = (codes >> drop) << drop
    return trunc.astype(jnp.float32) * jnp.expand_dims(q.scale, axis)
