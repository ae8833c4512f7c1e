"""Pallas TPU kernel: exact sparse attention over gathered INT8 K/V
(paper §4.2.4, Fig. 7).

Two fused stages, blocked over the selection-capacity dim C:

* stage 1 — segmented INT8 dot products with running ``qk_max`` tracking
  (the paper accumulates partial sums across cycles because one HBM PC
  yields a partial key per cycle; here one grid step consumes one C-block);
* stage 2 — online softmax + Value accumulation:
  ``o = Σ e^{s_i − qk_max} V_i / Σ e^{s_i − qk_max}`` with the usual
  rescale-on-new-max correction, carried in VMEM scratch across the grid.

Inputs are the *gathered* rows (the gather itself is XLA's job — on TPU a
row gather from HBM is a dynamic-slice stream the compiler already
pipelines; the kernel owns the compute-bound part).

Grid = (B·KV, C/BC); scratch: m (G, 1), l (G, 1), acc (G, HD) —
double-buffered K/V blocks stream HBM→VMEM while the MXU consumes the
previous block.

TPU tiling: the last two dims of every block are either (8k, 128k)-aligned
or the array's own, so per-row vectors — scales, masks, per-block lists —
travel as (·, 1, N) with the unit axis second-to-last, and the Value scale
folds into the probabilities (``(p·s_v) @ v``) instead of a column
broadcast over the codes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import interpret_default

DEFAULT_BLOCK_C = 256
NEG_INF = -1e30
_F32 = jax.lax.Precision.HIGHEST


def _kernel(q_ref, kc_ref, ks_ref, vc_ref, vs_ref, mask_ref, out_ref,
            m_ref, l_ref, acc_ref, *, scale: float, nblocks: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _online_softmax_step((q_ref[0],), (kc_ref[0],), ks_ref[0], (vc_ref[0],),
                         vs_ref[0], mask_ref[0] != 0, m_ref, l_ref, (acc_ref,),
                         scale=scale)

    @pl.when(j == nblocks - 1)
    def _finalize():
        out_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)


def _online_softmax_step(q_parts, k_parts, ks, v_parts, vs, mask, m_ref, l_ref,
                         acc_refs, *, scale: float):
    """One key tile of the two fused stages, shared by every flash-decode
    kernel. The head dim comes in one part, or in the even/odd channel
    halves of an int4 pool: q parts (G, D), k/v code parts (T, D), scales
    ks/vs (1, T) or (1, 1), mask (1, T) bool; scratch m/l (G, 1) and one
    (G, D) f32 accumulator per part."""
    # Stage 1: segmented dot product; dequant applied post-accumulate. Both
    # matmuls run at full f32 precision (the TPU default for f32 operands
    # rounds them to bf16 first).
    s = None
    for q, k in zip(q_parts, k_parts):
        d = jax.lax.dot_general(q.astype(jnp.float32), k.astype(jnp.float32),
                                (((1,), (1,)), ((), ())), precision=_F32,
                                preferred_element_type=jnp.float32)  # (G, T)
        s = d if s is None else s + d
    s = jnp.where(mask, s * ks * scale, NEG_INF)
    # Stage 2: online softmax with qk_max tracking; the Value scale folds
    # into the probabilities.
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    for acc_ref, v in zip(acc_refs, v_parts):
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p * vs, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            precision=_F32, preferred_element_type=jnp.float32)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = m_new


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def sparse_flash_decode_pallas(q: jax.Array, k_codes: jax.Array, k_scale: jax.Array,
                               v_codes: jax.Array, v_scale: jax.Array,
                               mask: jax.Array, *, block_c: int = DEFAULT_BLOCK_C,
                               interpret: bool | None = None) -> jax.Array:
    """q (BH, G, HD); k/v codes (BH, C, HD) int8 + scales (BH, C) f32;
    mask (BH, C) bool → out (BH, G, HD) f32."""
    if interpret is None:
        interpret = interpret_default()
    bh, g, hd = q.shape
    c = k_codes.shape[1]
    bc = min(block_c, c)
    assert c % bc == 0, f"C={c} not divisible by block {bc}"
    nblocks = c // bc
    scale = 1.0 / (hd ** 0.5)
    codes = pl.BlockSpec((1, bc, hd), lambda b, j: (b, j, 0))
    row = pl.BlockSpec((1, 1, bc), lambda b, j: (b, 0, j))
    as_row = lambda x, dt: x.astype(dt).reshape(bh, 1, c)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, nblocks=nblocks),
        grid=(bh, nblocks),
        in_specs=[pl.BlockSpec((1, g, hd), lambda b, j: (b, 0, 0)),
                  codes, row, codes, row, row],
        out_specs=pl.BlockSpec((1, g, hd), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, g, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((g, 1), jnp.float32)] * 2
        + [pltpu.VMEM((g, hd), jnp.float32)],
        interpret=interpret,
    )(q, k_codes, as_row(k_scale, jnp.float32), v_codes,
      as_row(v_scale, jnp.float32), as_row(mask, jnp.int32))


# ---------------------------------------------------------------------------
# Paged-native variant: instead of consuming pre-gathered (BH, C, ·) rows,
# the kernel walks a per-(slot, kv-head) list of PHYSICAL blocks — the
# selection's logical indices resolved through the page table on the host
# side of the trace — and the scalar-prefetched list drives the BlockSpec
# index_map, so each grid step streams one physical K/V block HBM→VMEM.
# The (P·BS, KV, ·) flat transpose of the pool that the gather path builds
# never exists; per-tick exact-attention traffic is the selected blocks.
# ---------------------------------------------------------------------------


def _split_nibbles(codes):
    """In-VMEM int4 dequant-to-int: the signed low and high nibbles of each
    packed byte (arithmetic shifts sign-extend) — head channels 2j and 2j+1.
    The two halves stay separate so no lane interleave runs in the kernel;
    the query and the output travel split into even/odd channels instead."""
    c = codes.astype(jnp.int32)
    return (c << 28) >> 28, c >> 4


def _f16_bits_to_f32(bits):
    """In-VMEM fp16 decode: the int16 bit patterns of an fp16 pool's codes
    to their exact f32 values (the v5e vector unit has no f16 loads, so the
    codes arrive bitcast to int16). Normal values rebias the exponent,
    subnormals are ``mantissa · 2^-24``, inf/nan keep an all-ones exponent."""
    h = bits.astype(jnp.int32) & 0xFFFF
    exp, mant = (h >> 10) & 0x1F, h & 0x3FF
    mag = jnp.where(exp == 0x1F, 0xFF << 23, (exp + 112) << 23) | (mant << 13)
    val = jnp.where(exp == 0, mant.astype(jnp.float32) * 2.0 ** -24,
                    jax.lax.bitcast_convert_type(mag, jnp.float32))
    return jnp.where(h >= 0x8000, -val, val)


def _paged_step(cnt_ref, q_refs, kc_ref, ks_ref, vc_ref, vs_ref, mask_ref,
                m_ref, l_ref, acc_refs, *, scale: float):
    """One (b, n) grid step of the paged online softmax — shared between the
    normalizing kernel and the partials (sharded-merge) kernel. ``q_refs`` /
    ``acc_refs`` hold one (G, HD) part, or the even/odd (G, HD//2) channel
    halves of an int4 pool."""
    b = pl.program_id(0)
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        for acc_ref in acc_refs:
            acc_ref[...] = jnp.zeros_like(acc_ref)

    # Padded list entries (n ≥ count) revisit a clamped block; skip the math.
    @pl.when(n < cnt_ref[b])
    def _compute():
        kc, vc = kc_ref[0, 0], vc_ref[0, 0]                # (BS, HD | HD//2)
        # int4: s = q_even·lo + q_odd·hi and acc_even/odd += (p·s_v) @ lo/hi.
        if len(q_refs) == 2:
            split = _split_nibbles
        elif kc.dtype == jnp.int16:
            split = lambda c: (_f16_bits_to_f32(c),)
        else:
            split = lambda c: (c,)
        _online_softmax_step([r[0] for r in q_refs], split(kc), ks_ref[0, 0],
                             split(vc), vs_ref[0, 0], mask_ref[0, 0] != 0,
                             m_ref, l_ref, acc_refs, scale=scale)


def _paged_kernel(pblk_ref, cnt_ref, *refs, scale: float, nsb: int,
                  nparts: int, partials: bool):
    del pblk_ref  # consumed by the index_maps
    q_refs, (kc_ref, ks_ref, vc_ref, vs_ref, mask_ref) = (
        refs[:nparts], refs[nparts:nparts + 5])
    rest = refs[nparts + 5:]
    n_out = nparts + 2 if partials else nparts
    outs, (m_ref, l_ref), acc_refs = (
        rest[:n_out], rest[n_out:n_out + 2], rest[n_out + 2:])
    _paged_step(cnt_ref, q_refs, kc_ref, ks_ref, vc_ref, vs_ref, mask_ref,
                m_ref, l_ref, acc_refs, scale=scale)

    @pl.when(pl.program_id(1) == nsb - 1)
    def _finalize():
        if partials:
            for out_ref, acc_ref in zip(outs[:nparts], acc_refs):
                out_ref[0] = acc_ref[...]
            outs[nparts][0] = m_ref[...]
            outs[nparts + 1][0] = l_ref[...]
        else:
            for out_ref, acc_ref in zip(outs, acc_refs):
                out_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)


def _paged_call(q, k_codes, k_scale, v_codes, v_scale, pblk, counts,
                blk_mask, *, num_kv: int, kv_dtype: str, partials: bool,
                interpret: bool | None):
    """Shared pallas_call of the two paged kernels (see their docstrings).

    Block layout (TPU tiling: the last two block dims are full or aligned):
    codes (P, KV, BS, HD') stream as (1, 1, BS, HD') — ONE kv head of one
    physical block; scales travel as (P, KV, 1, SB) and masks as
    (BH, NSB, 1, BS). int4 queries/outputs are split into even/odd channels;
    fp16 codes travel as their int16 bit patterns (a free bitcast).
    """
    if interpret is None:
        interpret = interpret_default()
    bh, g, hd = q.shape
    p, kv, bs, hdc = k_codes.shape
    sb = k_scale.shape[-1]            # scale entries per block (BS or 1)
    nsb = pblk.shape[1]
    scale = 1.0 / (hd ** 0.5)
    int4 = kv_dtype == "int4"
    if kv_dtype == "fp16":
        k_codes = jax.lax.bitcast_convert_type(k_codes, jnp.int16)
        v_codes = jax.lax.bitcast_convert_type(v_codes, jnp.int16)
    q_parts = (q[..., 0::2], q[..., 1::2]) if int4 else (q,)
    hp = q_parts[0].shape[-1]
    nparts = len(q_parts)
    qspec = pl.BlockSpec((1, g, hp), lambda b, n, pb, ct: (b, 0, 0))
    cspec = pl.BlockSpec((1, 1, bs, hdc),
                         lambda b, n, pb, ct: (pb[b, n], b % num_kv, 0, 0))
    sspec = pl.BlockSpec((1, 1, 1, sb),
                         lambda b, n, pb, ct: (pb[b, n], b % num_kv, 0, 0))
    mspec = pl.BlockSpec((1, 1, 1, bs), lambda b, n, pb, ct: (b, n, 0, 0))
    out_shape = [jax.ShapeDtypeStruct((bh, g, hp), jnp.float32)] * nparts
    out_specs = [qspec] * nparts
    if partials:
        col = pl.BlockSpec((1, g, 1), lambda b, n, pb, ct: (b, 0, 0))
        out_shape += [jax.ShapeDtypeStruct((bh, g, 1), jnp.float32)] * 2
        out_specs += [col, col]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bh, nsb),
        in_specs=[qspec] * nparts + [cspec, sspec, cspec, sspec, mspec],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((g, 1), jnp.float32)] * 2
        + [pltpu.VMEM((g, hp), jnp.float32)] * nparts,
    )
    outs = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, nsb=nsb, nparts=nparts,
                          partials=partials),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(pblk, counts, *q_parts, k_codes, k_scale.reshape(p, kv, 1, sb),
      v_codes, v_scale.reshape(p, kv, 1, sb),
      blk_mask.astype(jnp.int32).reshape(bh, nsb, 1, bs))
    acc = outs[:nparts]
    acc = jnp.stack(acc, axis=-1).reshape(bh, g, hd) if int4 else acc[0]
    if partials:
        return acc, outs[nparts][..., 0], outs[nparts + 1][..., 0]
    return acc


@functools.partial(jax.jit, static_argnames=("num_kv", "kv_dtype", "interpret"))
def sparse_flash_decode_paged_pallas(q: jax.Array, k_codes: jax.Array,
                                     k_scale: jax.Array, v_codes: jax.Array,
                                     v_scale: jax.Array, pblk: jax.Array,
                                     counts: jax.Array, blk_mask: jax.Array,
                                     *, num_kv: int, kv_dtype: str = "int8",
                                     interpret: bool | None = None) -> jax.Array:
    """Exact sparse attention straight off the physical block pool.

    q (BH, G, HD) with BH = slots·num_kv (kv-major rows, kv = row % num_kv);
    k/v codes (P, KV, BS, HD) int8 + scales (P, KV, BS) f32 — the SHARED
    pool; pblk (BH, NSB) int32 physical ids of the blocks the selection
    touches (padded entries clamped, elided by the pipeline); counts (BH,)
    int32 live-entry counts; blk_mask (BH, NSB, BS) selected-token masks per
    listed block. Returns (BH, G, HD) f32. Grid = (BH, NSB); step (b, n)
    streams the (BS, HD) K and V tiles of physical block ``pblk[b, n]`` for
    row b's kv head — the only pool bytes the tick touches.

    ``kv_dtype`` is the pool's storage precision. "fp16"/"int4" pools stream
    ONE scale word per block alongside the block's codes (the extra scale
    operand of the tiered-pool design); int4 codes arrive packed
    (BS, HD//2) and split into their two nibbles in VMEM before the MXU dots,
    fp16 codes arrive as int16 bit patterns and decode to f32 in VMEM.
    """
    return _paged_call(q, k_codes, k_scale, v_codes, v_scale, pblk, counts,
                       blk_mask, num_kv=num_kv, kv_dtype=kv_dtype,
                       partials=False, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("num_kv", "kv_dtype", "interpret"))
def sparse_flash_decode_paged_partials_pallas(
        q: jax.Array, k_codes: jax.Array, k_scale: jax.Array,
        v_codes: jax.Array, v_scale: jax.Array, pblk: jax.Array,
        counts: jax.Array, blk_mask: jax.Array, *, num_kv: int,
        kv_dtype: str = "int8", interpret: bool | None = None):
    """`sparse_flash_decode_paged_pallas` that stops before normalizing.

    Same contract, but returns the raw online-softmax state
    ``(acc (BH, G, HD), m (BH, G), l (BH, G))`` instead of ``acc / l`` —
    the shard-local partials of the sharded fused tick, merged across chips
    afterwards with the standard flash rescale
    (``m* = pmax(m); out = psum(acc·e^{m−m*}) / psum(l·e^{m−m*})``).
    Rows with ``counts == 0`` (shard owns nothing the selection touched)
    come back as (0, NEG_INF, 0) and vanish in the merge.
    """
    return _paged_call(q, k_codes, k_scale, v_codes, v_scale, pblk, counts,
                       blk_mask, num_kv=num_kv, kv_dtype=kv_dtype,
                       partials=True, interpret=interpret)
