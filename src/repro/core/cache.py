"""Salca KV cache: INT8 K/V + packed 2-bit heavy-channel feature stream.

Mirrors the paper's HBM layout logically:

* Region "core features": contiguous per-token packed 2-bit heavy-channel
  codes (16/int32 word) + the two FP quantization factors per key — the
  sequentially-streamed store that the pre-computing stage reads.
* Region "K/V": INT8 K and V with per-token scales — the randomly gathered
  store read by exact attention.

The cache is a NamedTuple (= pytree), so it flows through jit/scan/shard_map
and can be sharded: batch on "data", kv-heads on "model" (TP archs) or
sequence on "model"/"data" (CP archs, long_500k).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quantization as qz
from repro.core import heavy_channels as hc
from repro.core.selection import SalcaParams


class SalcaCache(NamedTuple):
    k_codes: jax.Array     # (B, S, KV, HD) int8 — symmetric INT8 keys
    k_scale: jax.Array     # (B, S, KV) f32
    v_codes: jax.Array     # (B, S, KV, HD) int8
    v_scale: jax.Array     # (B, S, KV) f32
    feat_words: jax.Array  # (B, S, KV, R//16) uint32 — packed 2-bit features
    feat_scale: jax.Array  # (B, S, KV) f32 — asymmetric scale a
    feat_zero: jax.Array   # (B, S, KV) f32 — asymmetric zero z
    heavy_idx: jax.Array   # (B, KV, R) int32 — frozen heavy-channel set
    length: jax.Array      # (B,) int32 — tokens currently stored

    @property
    def max_seq(self) -> int:
        return self.k_codes.shape[1]

    @property
    def num_kv_heads(self) -> int:
        return self.k_codes.shape[2]

    @property
    def head_dim(self) -> int:
        return self.k_codes.shape[3]

    def valid_mask(self) -> jax.Array:
        """(B, S) bool — True where a real token is stored."""
        pos = jnp.arange(self.max_seq, dtype=jnp.int32)
        return pos[None, :] < self.length[:, None]


def empty_cache(batch: int, max_seq: int, kv_heads: int, head_dim: int,
                r: int, dtype=jnp.int8) -> SalcaCache:
    del dtype
    zeros = lambda shape, dt: jnp.zeros(shape, dt)
    return SalcaCache(
        k_codes=zeros((batch, max_seq, kv_heads, head_dim), jnp.int8),
        k_scale=zeros((batch, max_seq, kv_heads), jnp.float32),
        v_codes=zeros((batch, max_seq, kv_heads, head_dim), jnp.int8),
        v_scale=zeros((batch, max_seq, kv_heads), jnp.float32),
        feat_words=zeros((batch, max_seq, kv_heads, r // qz.CODES_PER_WORD), jnp.uint32),
        feat_scale=zeros((batch, max_seq, kv_heads), jnp.float32),
        feat_zero=zeros((batch, max_seq, kv_heads), jnp.float32),
        heavy_idx=zeros((batch, kv_heads, r), jnp.int32),
        length=zeros((batch,), jnp.int32),
    )


def _encode_tokens(k: jax.Array, v: jax.Array, heavy_idx: jax.Array):
    """Quantize a block of K/V tokens into cache fields.

    k, v: (B, T, KV, HD); heavy_idx: (B, KV, R). Returns the per-token cache
    field values for those T positions.
    """
    k8 = qz.quantize_kv_int8(k)
    v8 = qz.quantize_kv_int8(v)
    # Extract heavy channels: (B, T, KV, R)
    r = heavy_idx.shape[-1]
    idx = jnp.broadcast_to(heavy_idx[:, None], k.shape[:3] + (r,))
    k_feat = jnp.take_along_axis(k.astype(jnp.float32), idx, axis=-1)
    f2 = qz.quantize_key_features(k_feat)
    words = qz.pack2bit(f2.codes)
    return k8, v8, words, f2.scale, f2.zero


def prefill_cache(k: jax.Array, v: jax.Array, max_seq: int,
                  params: SalcaParams,
                  heavy_idx: jax.Array | None = None) -> SalcaCache:
    """Build a cache from prefill K/V.

    k, v: (B, T, KV, HD) full-precision prefill keys/values. Heavy channels
    are identified here (once per input, per kv head — paper §3.1) and then
    frozen for the whole decode. Pass `heavy_idx` (B, KV, R) to override
    with a precomputed (e.g. static weight-derived) channel set — required
    request-independent for prefix-shared feature blocks.
    """
    b, t, kv, hd = k.shape
    r = params.r(hd)
    if heavy_idx is None:
        # Per-kv-head salience over tokens: reduce |K| along T.
        heavy_idx = hc.heavy_channel_indices(
            k.transpose(0, 2, 1, 3).reshape(b, kv, t, hd), r)   # (B, KV, R)
    k8, v8, words, fs, fz = _encode_tokens(k, v, heavy_idx)
    pad = max_seq - t
    assert pad >= 0, f"prefill length {t} exceeds cache capacity {max_seq}"
    pad4 = ((0, 0), (0, pad), (0, 0), (0, 0))
    pad3 = ((0, 0), (0, pad), (0, 0))
    return SalcaCache(
        k_codes=jnp.pad(k8.codes, pad4), k_scale=jnp.pad(k8.scale, pad3),
        v_codes=jnp.pad(v8.codes, pad4), v_scale=jnp.pad(v8.scale, pad3),
        feat_words=jnp.pad(words, pad4), feat_scale=jnp.pad(fs, pad3),
        feat_zero=jnp.pad(fz, pad3),
        heavy_idx=heavy_idx,
        length=jnp.full((b,), t, jnp.int32),
    )


def append_token(cache: SalcaCache, k: jax.Array, v: jax.Array) -> SalcaCache:
    """Append one decoded token's K/V (B, KV, HD) at each sequence's cursor."""
    b = k.shape[0]
    k8, v8, words, fs, fz = _encode_tokens(k[:, None], v[:, None], cache.heavy_idx)

    def upd(buf, val):  # dynamic per-batch-row scatter at cursor `length`
        bidx = jnp.arange(b)
        return buf.at[bidx, cache.length].set(val[:, 0], mode="drop")

    return cache._replace(
        k_codes=upd(cache.k_codes, k8.codes), k_scale=upd(cache.k_scale, k8.scale),
        v_codes=upd(cache.v_codes, v8.codes), v_scale=upd(cache.v_scale, v8.scale),
        feat_words=upd(cache.feat_words, words),
        feat_scale=upd(cache.feat_scale, fs), feat_zero=upd(cache.feat_zero, fz),
        length=jnp.minimum(cache.length + 1, cache.max_seq),
    )


# ---------------------------------------------------------------------------
# Slot pool: the serving engine keeps ONE persistent cache per layer whose
# leading `batch` dimension is a pool of request slots. Admission prefills a
# request (batch=1) and writes the result into a free slot; completion resets
# the slot. Both operations are jit-safe with a traced `slot` index, so the
# engine pays one compiled program regardless of which slot turns over.
# ---------------------------------------------------------------------------

def write_prefill_into_slot(pool: SalcaCache, src: SalcaCache, slot) -> SalcaCache:
    """Write a batch=1 prefilled cache into row `slot` of a pooled cache.

    `src` must have batch 1 and match `pool` on every trailing dimension
    (same max_seq / kv heads / head_dim / r). `slot` may be a Python int or a
    traced int32 scalar. Every field — including the frozen per-request
    heavy-channel set and the length cursor — is replaced for that slot;
    other slots are untouched.
    """
    if src.k_codes.shape[0] != 1:
        raise ValueError(f"src cache must have batch 1, got {src.k_codes.shape[0]}")
    if pool.k_codes.shape[1:] != src.k_codes.shape[1:]:
        raise ValueError(
            f"slot shape mismatch: pool {pool.k_codes.shape[1:]} "
            f"vs src {src.k_codes.shape[1:]}")
    return SalcaCache(*[p.at[slot].set(s[0].astype(p.dtype))
                        for p, s in zip(pool, src)])


def reset_slot(pool: SalcaCache, slot) -> SalcaCache:
    """Mark a slot empty (length 0). The K/V rows are left in place — the
    valid mask gates every read, and admission overwrites the whole region —
    so reset is O(1) instead of O(max_seq)."""
    return pool._replace(length=pool.length.at[slot].set(0))


def append_token_masked(cache: SalcaCache, k: jax.Array, v: jax.Array,
                        active: jax.Array | None) -> SalcaCache:
    """`append_token` under an active-slot mask: inactive slots drop the
    write (cursor forced out of range, scatter mode="drop") and keep their
    stored length — the single definition of the masked-append invariant for
    length-cursor caches (the pos-cursor attention path gates its own
    cursors in `models.blocks._attn_decode`)."""
    if active is None:
        return append_token(cache, k, v)
    old_len = cache.length
    gated = cache._replace(
        length=jnp.where(active, old_len, jnp.int32(cache.max_seq)))
    return append_token(gated, k, v)._replace(
        length=jnp.where(active, jnp.minimum(old_len + 1, cache.max_seq),
                         old_len))


def cache_bytes(cache: SalcaCache) -> dict[str, int]:
    """Physical bytes by region (for the performance model / roofline)."""
    def nbytes(x):
        return int(x.size) * x.dtype.itemsize
    kv = nbytes(cache.k_codes) + nbytes(cache.v_codes) + nbytes(cache.k_scale) + nbytes(cache.v_scale)
    feats = nbytes(cache.feat_words) + nbytes(cache.feat_scale) + nbytes(cache.feat_zero)
    return {"kv_region": kv, "feature_region": feats, "total": kv + feats}


# ---------------------------------------------------------------------------
# Paged block pool: the serving-scale cache substrate. One shared physical
# pool per layer holds `num_blocks` blocks of `block_size` tokens for all
# seven cache fields; each request slot owns a *page table* mapping its
# logical block j to a physical block id (-1 = unmapped). HBM is therefore
# allocated at the granularity of tokens actually held — a 256-token request
# costs 256/block_size blocks, not a dense max_seq stripe — and the engine's
# free list packs mixed 1k/100k requests into one pool.
#
# Logical order is recovered by gathering blocks through the page table, so
# the paper's streaming selection (per-block relevance + additive histograms)
# maps directly onto page order; the exact-attention gather resolves logical
# token indices to physical rows (page * block_size + offset) before fetching
# K/V. All shapes are static, all ops jit-safe with traced slots/pages.
#
# Prefix sharing: identical prompt prefixes map the SAME physical blocks from
# multiple page tables. A per-block `refcount` tracks how many page-table
# entries reference each block; every mapping op maintains it (`map_block` /
# `share_blocks` / `prefill_into_pages` incref, `free_pages` / `cow_block`
# decref). Shared blocks are copy-on-write: `append_token_paged` treats a
# write into a block with refcount > 1 as a write fault (the write is DROPPED
# and the cursor held — a shared block is never mutated in place); the engine
# services the fault by allocating a fresh block and calling `cow_block`,
# which copies all seven cache fields of the block, remaps only the writer's
# page-table entry, and moves one reference from the old block to the copy.
#
# Sequence sharding: the physical block dim can be split across a mesh axis —
# shard i owns the contiguous global-id range [i·P_local, (i+1)·P_local)
# (`local_block_range`), while the page table (global ids), lengths and
# heavy sets stay replicated. Every pool primitive takes an optional
# `block_range=(lo, hi)`: with it set, the op sees a LOCAL pool (data leaves
# hold only this shard's blocks) and resolutions/writes whose physical block
# falls outside [lo, hi) are dropped (writes) or flagged unowned (reads) —
# the local-or-sentinel rule `_resolve_pages` implements once for every
# caller. A decode tick composed of these shard-local ops touches only local
# HBM until the two tiny collectives in `sp_decode.sp_salca_decode_paged`.
# ---------------------------------------------------------------------------

PAGE_UNMAPPED = -1


class PagedSalcaCache(NamedTuple):
    # Physical pool, shared by all slots (no batch dim). The K/V region is
    # stored at `kv_pool_dtype` precision (inferred from the leaves, see
    # below); the feature stream is always the packed 2-bit layout:
    # The kv-head axis leads the token axis in every data leaf, so one
    # (block, head) pair is a (BS, ·) tile whose last two dims a TPU kernel
    # can stream alone — the exact-attention kernel fetches only the heads a
    # selection named, never the whole block.
    k_codes: jax.Array     # (P, KV, BS, HD) int8 | f16 | (P, KV, BS, HD//2) int4-packed
    k_scale: jax.Array     # (P, KV, BS) f32 per-token | (P, KV, 1) per-block
    v_codes: jax.Array     # (P, KV, BS, HD) int8 | f16 | (P, KV, BS, HD//2) int4-packed
    v_scale: jax.Array     # (P, KV, BS) f32 per-token | (P, KV, 1) per-block
    feat_words: jax.Array  # (P, KV, BS, R//16) uint32
    feat_scale: jax.Array  # (P, KV, BS) f32
    feat_zero: jax.Array   # (P, KV, BS) f32
    # Per-slot request state:
    heavy_idx: jax.Array   # (S, KV, R) int32 — frozen heavy-channel set
    length: jax.Array      # (S,) int32 — tokens currently stored
    page_table: jax.Array  # (S, MB) int32 — logical block → physical block, -1 unmapped
    # Per-block sharing state:
    refcount: jax.Array    # (P,) int32 — page-table entries referencing each block
    # Relevance history (host-spill demotion signal):
    sel_hist: jax.Array    # (S, MB) int32 — cumulative selected-token count
                           # per logical block (scatter-added each tick)

    # Shape properties use negative indices so they stay correct on stacked
    # (n_periods-leading) instances inside scanned model states.
    @property
    def num_blocks(self) -> int:
        return self.k_codes.shape[-4]

    @property
    def block_size(self) -> int:
        return self.k_codes.shape[-2]

    @property
    def kv_pool_dtype(self) -> str:
        """K/V storage precision, inferred from the leaves (kept out of the
        pytree so the NamedTuple stays a plain jit-safe container):

        * ``float16`` codes → "fp16" (unit scales, shape (P, KV, 1))
        * int8 codes with per-token scales (scale dim == block_size) → "int8"
        * int8 codes with per-block scales (scale dim == 1) → "int4"
          (two signed nibbles per byte along head_dim)

        Non-int8 pools require block_size > 1 (enforced at construction) so
        the scale-dim test is unambiguous."""
        if self.k_codes.dtype == jnp.float16:
            return "fp16"
        if self.k_scale.shape[-1] == self.k_codes.shape[-2]:
            return "int8"
        return "int4"

    @property
    def num_slots(self) -> int:
        return self.page_table.shape[-2]

    @property
    def max_blocks(self) -> int:
        return self.page_table.shape[-1]

    @property
    def max_seq(self) -> int:
        """Logical per-slot capacity (tokens)."""
        return self.max_blocks * self.block_size

    @property
    def num_kv_heads(self) -> int:
        return self.k_codes.shape[-3]

    @property
    def head_dim(self) -> int:
        hd = self.k_codes.shape[-1]
        return 2 * hd if self.kv_pool_dtype == "int4" else hd

    def valid_mask(self) -> jax.Array:
        """(S, L) bool over the logical view — True where a real token is stored."""
        pos = jnp.arange(self.max_seq, dtype=jnp.int32)
        return pos[None, :] < self.length[:, None]

    def mapped_valid_mask(self) -> jax.Array:
        """(S, L) bool — stored AND resident: `valid_mask` further gated to
        positions whose covering block is currently mapped. Identical to
        `valid_mask` when no block is unmapped below the cursor (the only
        engine that creates that state is host spill, which demotes cold
        blocks to `page_table == -1` while `length` keeps counting them);
        every read path uses THIS mask so a demoted block is invisible — not
        garbage-read — until the engine promotes it back."""
        pos = jnp.arange(self.max_seq, dtype=jnp.int32)
        resident = jnp.repeat(self.page_table >= 0, self.block_size, axis=-1)
        return (pos[None, :] < self.length[:, None]) & resident

    def clamped_pages(self) -> jax.Array:
        """Page table with unmapped entries clamped to block 0 for gathers.

        Gathered garbage at unmapped positions is gated by `valid_mask` (a
        mapped logical position is always < length or beyond it, and reads
        are masked to pos < length)."""
        return jnp.where(self.page_table >= 0, self.page_table, 0)

    def check_invariants(self, free_blocks=None, host_refcount=None,
                         allow_holes: bool = False,
                         cache_pinned=None) -> "InvariantReport":
        """Runtime integrity audit of this pool's bookkeeping.

        The invariants the hypothesis batteries check offline become a
        production self-check the engine can run every ``audit_every``
        ticks. Verified here (host-side numpy; one device sync for the
        three metadata leaves):

        * ``refcount[b]`` equals the number of page-table entries mapping
          block ``b``, for every block — no leaked or phantom references.
        * refcounts are non-negative; page-table entries are ``-1`` or a
          valid physical id; ``0 <= length <= max_seq`` (cursor bounds).
        * ``free_blocks`` (the engine's free list), when given, is
          duplicate-free, in range, and disjoint from every mapped block —
          free ∩ mapped = ∅ — and covers exactly the unreferenced blocks.
        * ``host_refcount`` (the engine's numpy mirror), when given,
          matches the device refcount bit-for-bit.
        * ``cache_pinned`` (the engine's persistent prefix cache), when
          given, names blocks retained by the ENGINE after their last
          resident owner released: each must be fully unreferenced
          (derived refcount 0), off the free list, and is excluded from
          the leak check — a pin IS its accounting.
        * per-slot mapped entries are contiguous from logical 0 with no
          holes below the cursor, unless ``allow_holes`` (host spill
          legitimately unmaps cold blocks below the cursor).

        Stack-aware: on instances carrying leading layer/period dims
        (states inside scanned models), every layer is audited and all
        layers must agree — the engine maps blocks into every layer's
        page table in lockstep, so divergence is corruption.

        Returns an `InvariantReport`; never raises on violation (the
        caller decides whether an unclean report is fatal).
        """
        pt = np.asarray(self.page_table)
        rc = np.asarray(self.refcount)
        ln = np.asarray(self.length)
        mb, s = self.max_blocks, self.num_slots
        p = self.num_blocks
        pt = pt.reshape(-1, s, mb)
        rc = rc.reshape(-1, p)
        ln = ln.reshape(-1, s)
        layers = pt.shape[0]
        rep = InvariantReport(
            checked={"layers": layers, "slots": s, "blocks": p,
                     "max_blocks": mb})

        # Cross-layer agreement: the engine updates every layer in lockstep.
        if layers > 1:
            if not (pt == pt[0]).all():
                rep.fail("page tables diverge across layers")
            if not (rc == rc[0]).all():
                rep.fail("refcounts diverge across layers")
            if not (ln == ln[0]).all():
                rep.fail("lengths diverge across layers")
        pt0, rc0, ln0 = pt[0], rc[0], ln[0]

        if ((ln0 < 0) | (ln0 > self.max_seq)).any():
            bad = np.where((ln0 < 0) | (ln0 > self.max_seq))[0]
            rep.fail(f"length out of [0, {self.max_seq}] at slots {bad.tolist()}")
        if (rc0 < 0).any():
            rep.fail(f"negative refcount at blocks "
                     f"{np.where(rc0 < 0)[0].tolist()}")
        if ((pt0 < PAGE_UNMAPPED) | (pt0 >= p)).any():
            rep.fail("page-table entry outside [-1, num_blocks)")
            pt0 = np.clip(pt0, PAGE_UNMAPPED, p - 1)

        # refcount[b] == number of page-table references to b.
        mapped = pt0[pt0 >= 0]
        derived = np.bincount(mapped, minlength=p).astype(rc0.dtype)
        if not (derived == rc0).all():
            bad = np.where(derived != rc0)[0]
            rep.fail(f"refcount mismatch at blocks {bad.tolist()[:8]}: "
                     f"device={rc0[bad].tolist()[:8]} "
                     f"page-table={derived[bad].tolist()[:8]}")

        if host_refcount is not None:
            hrc = np.asarray(host_refcount)
            if hrc.shape != (p,) or not (hrc == rc0).all():
                bad = np.where(hrc != rc0)[0] if hrc.shape == (p,) else []
                rep.fail(f"host refcount mirror diverges from device at "
                         f"blocks {list(bad)[:8]}")

        pinned_mask = np.zeros((p,), bool)
        if cache_pinned is not None:
            pins = list(cache_pinned)
            rep.checked["cache_pinned"] = len(pins)
            if len(set(pins)) != len(pins):
                rep.fail("duplicate ids in the cache-pin set")
            pa = np.asarray(pins, dtype=np.int64) if pins else \
                np.zeros((0,), np.int64)
            if pa.size and ((pa < 0) | (pa >= p)).any():
                rep.fail("cache-pinned id outside the pool")
                pa = pa[(pa >= 0) & (pa < p)]
            pinned_mask[pa] = True
            clash = pinned_mask & (derived > 0)
            if clash.any():
                rep.fail(f"cache-pinned ∩ mapped ≠ ∅: blocks "
                         f"{np.where(clash)[0].tolist()[:8]} (a pin holds "
                         f"zero page-table references by definition)")

        if free_blocks is not None:
            free = list(free_blocks)
            if len(set(free)) != len(free):
                rep.fail("duplicate ids in the free list")
            fa = np.asarray(free, dtype=np.int64) if free else \
                np.zeros((0,), np.int64)
            if fa.size and ((fa < 0) | (fa >= p)).any():
                rep.fail("free-list id outside the pool")
            else:
                free_mask = np.zeros((p,), bool)
                free_mask[fa] = True
                clash = free_mask & (derived > 0)
                if clash.any():
                    rep.fail(f"free ∩ mapped ≠ ∅: blocks "
                             f"{np.where(clash)[0].tolist()[:8]}")
                clash = free_mask & pinned_mask
                if clash.any():
                    rep.fail(f"cache-pinned ∩ free ≠ ∅: blocks "
                             f"{np.where(clash)[0].tolist()[:8]}")
                orphan = ~free_mask & ~pinned_mask & (derived == 0)
                if orphan.any():
                    rep.fail(f"leaked blocks (unreferenced, not free, not "
                             f"cache-pinned): "
                             f"{np.where(orphan)[0].tolist()[:8]}")

        if not allow_holes:
            # Mapped entries must be contiguous from logical 0: a hole
            # below a mapped block means a write landed past an unmapped
            # region (only host spill creates that state on purpose).
            is_mapped = pt0 >= 0
            first_unmapped = np.where(is_mapped.any(axis=1),
                                      np.argmin(is_mapped, axis=1), mb)
            first_unmapped[is_mapped.all(axis=1)] = mb
            tail_mapped = is_mapped & (np.arange(mb)[None, :]
                                       >= first_unmapped[:, None])
            if tail_mapped.any():
                rep.fail(f"page-table hole below a mapped block at slots "
                         f"{np.where(tail_mapped.any(axis=1))[0].tolist()}")
        return rep


@dataclass
class InvariantReport:
    """Structured result of a `PagedSalcaCache.check_invariants` audit (or
    the engine-level audit composing several of them)."""
    violations: list = field(default_factory=list)
    checked: dict = field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return not self.violations

    def fail(self, msg: str) -> None:
        self.violations.append(msg)

    def merge(self, other: "InvariantReport", prefix: str = "") -> None:
        for v in other.violations:
            self.violations.append(f"{prefix}{v}" if prefix else v)
        for k, v in other.checked.items():
            self.checked.setdefault(k, v)

    def __repr__(self) -> str:
        state = "ok" if self.ok else f"{len(self.violations)} violation(s)"
        body = "".join(f"\n  - {v}" for v in self.violations)
        return f"InvariantReport({state}, checked={self.checked}){body}"


def empty_paged_cache(num_blocks: int, block_size: int, slots: int,
                      max_blocks: int, kv_heads: int, head_dim: int,
                      r: int, kv_pool_dtype: str = "int8") -> PagedSalcaCache:
    zeros = lambda shape, dt: jnp.zeros(shape, dt)
    if kv_pool_dtype == "int8":
        code_shape = (num_blocks, kv_heads, block_size, head_dim)
        code_dt = jnp.int8
        # Per-token scales, zero-init (never read before written).
        scale = zeros((num_blocks, kv_heads, block_size), jnp.float32)
    elif kv_pool_dtype == "fp16":
        assert block_size > 1, "fp16 pool needs block_size > 1 (mode inference)"
        code_shape = (num_blocks, kv_heads, block_size, head_dim)
        code_dt = jnp.float16
        # Unit per-block scales: the dequant paths multiply by them blindly,
        # so they MUST be ones (and nothing ever rewrites them).
        scale = jnp.ones((num_blocks, kv_heads, 1), jnp.float32)
    elif kv_pool_dtype == "int4":
        assert block_size > 1, "int4 pool needs block_size > 1 (mode inference)"
        assert head_dim % 2 == 0, f"head_dim {head_dim} not packable to int4"
        code_shape = (num_blocks, kv_heads, block_size, head_dim // 2)
        code_dt = jnp.int8
        scale = zeros((num_blocks, kv_heads, 1), jnp.float32)
    else:
        raise ValueError(f"unknown kv_pool_dtype {kv_pool_dtype!r}")
    return PagedSalcaCache(
        k_codes=zeros(code_shape, code_dt),
        k_scale=scale,
        v_codes=zeros(code_shape, code_dt),
        v_scale=scale,
        feat_words=zeros((num_blocks, kv_heads, block_size, r // qz.CODES_PER_WORD),
                         jnp.uint32),
        feat_scale=zeros((num_blocks, kv_heads, block_size), jnp.float32),
        feat_zero=zeros((num_blocks, kv_heads, block_size), jnp.float32),
        heavy_idx=zeros((slots, kv_heads, r), jnp.int32),
        length=zeros((slots,), jnp.int32),
        page_table=jnp.full((slots, max_blocks), PAGE_UNMAPPED, jnp.int32),
        refcount=zeros((num_blocks,), jnp.int32),
        sel_hist=zeros((slots, max_blocks), jnp.int32),
    )


def local_block_range(pool: PagedSalcaCache, axis_name) -> tuple:
    """This shard's global physical-block id range ``(lo, hi)``.

    Call INSIDE a shard_map island whose in_specs shard the pool's data
    leaves over ``axis_name`` on the block dim (metadata replicated):
    ``pool.num_blocks`` is then the LOCAL block count and shard i owns the
    contiguous global ids [i·P_local, (i+1)·P_local). Feed the result to the
    ``block_range`` parameter of the pool primitives below."""
    p_local = pool.num_blocks
    lo = jax.lax.axis_index(axis_name) * p_local
    return lo, lo + p_local


def _localize_pages(pages: jax.Array, block_range) -> jax.Array:
    """Translate global physical block ids to the shard-local coordinate.

    Owned ids map to [0, P_local); unowned (and unmapped -1) ids map to the
    unmapped sentinel, so downstream refcount scatters / data writes drop
    them — the shard-aware "unowned writes drop" rule in one place."""
    if block_range is None:
        return pages
    lo, hi = block_range
    owned = (pages >= lo) & (pages < hi)
    return jnp.where(owned, pages - lo, jnp.int32(PAGE_UNMAPPED))


def _refcount_add(refcount: jax.Array, pages: jax.Array, delta: int,
                  valid: jax.Array | None = None) -> jax.Array:
    """Scatter `delta` onto `refcount` at every valid page id. Unmapped (-1)
    entries — and entries where `valid` is False — are redirected out of
    bounds and dropped, so the op is safe (and idempotent for -1 rows)."""
    p = refcount.shape[-1]
    ok = pages >= 0 if valid is None else (pages >= 0) & valid
    tgt = jnp.where(ok, pages, p)
    return refcount.at[tgt].add(jnp.int32(delta), mode="drop")


def prefill_into_pages(pool: PagedSalcaCache, src: SalcaCache, slot,
                       pages: jax.Array, n_shared=0) -> PagedSalcaCache:
    """Write a batch=1 contiguous prefilled cache into the physical blocks
    named by `pages` and install the page table for `slot`.

    `pages`: (max_blocks,) int32 — physical block id for logical block j, or
    -1 for blocks the engine did not allocate (their writes are dropped; the
    src rows there are padding anyway). `slot` and `pages` may be traced, so
    the engine compiles this once. Unallocated physical blocks keep whatever
    stale data a freed request left — every read path is gated to
    pos < length, so reuse is safe.

    Prefix sharing: the first `n_shared` entries of `pages` name blocks that
    ALREADY hold this prompt's prefix (another request wrote them). Those
    blocks are mapped — installed in the page table and refcounted — but NOT
    written: the divergent tail is the only data transfer. `n_shared` may be
    traced. The slot must be unmapped (fresh or freed) before this call, or
    the refcount bookkeeping double-counts.
    """
    if src.k_codes.shape[0] != 1:
        raise ValueError(f"src cache must have batch 1, got {src.k_codes.shape[0]}")
    if (pool.num_kv_heads, pool.head_dim) != src.k_codes.shape[2:]:
        raise ValueError(
            f"kv-head/head-dim mismatch: pool "
            f"{(pool.num_kv_heads, pool.head_dim)} vs src "
            f"{src.k_codes.shape[2:]}")
    if src.max_seq > pool.max_seq:
        raise ValueError(
            f"src length {src.max_seq} exceeds paged logical capacity "
            f"{pool.max_seq} (= {pool.max_blocks} blocks × {pool.block_size})")
    bs, mb, p = pool.block_size, pool.max_blocks, pool.num_blocks
    pad = pool.max_seq - src.max_seq
    # Shared-prefix blocks are mapped but never (re)written — their content
    # is the prefix by construction; rewriting would race the other owners.
    writable = jnp.arange(mb) >= jnp.asarray(n_shared, jnp.int32)
    safe_pages = jnp.where((pages >= 0) & writable, pages, p)  # → OOB → dropped

    def to_blocks(val):  # val: (1, src_seq, KV, ·) → (MB, KV, BS, ·)
        v = jnp.pad(val[0], ((0, pad),) + ((0, 0),) * (val.ndim - 2))
        return v.reshape((mb, bs) + v.shape[1:]).swapaxes(1, 2)

    def upd(buf, blocks):
        return buf.at[safe_pages].set(blocks.astype(buf.dtype), mode="drop")

    # Transcode the K/V region into the pool's storage precision. The dense
    # prefill cache always carries per-token int8 (the paper's exact-attention
    # operands); fp16/int4 pools re-encode those values — fp16 holds them
    # verbatim (unit per-block scales), int4 requantizes each block with one
    # shared per-block, per-head scale.
    mode = pool.kv_pool_dtype
    if mode == "int8":
        kc, ks = to_blocks(src.k_codes), to_blocks(src.k_scale)
        vc, vs = to_blocks(src.v_codes), to_blocks(src.v_scale)
    else:
        k = to_blocks(src.k_codes).astype(jnp.float32) * to_blocks(src.k_scale)[..., None]
        v = to_blocks(src.v_codes).astype(jnp.float32) * to_blocks(src.v_scale)[..., None]
        if mode == "fp16":
            kc, vc = k, v                               # cast to f16 in `upd`
            ks = vs = jnp.ones((mb, pool.num_kv_heads, 1), jnp.float32)
        else:                                           # int4
            kq, ks = qz.sym_quantize_axes(k, bits=4, axes=(2, 3))
            vq, vs = qz.sym_quantize_axes(v, bits=4, axes=(2, 3))
            kc, vc = qz.pack_int4(kq), qz.pack_int4(vq)
            ks, vs = ks[..., 0], vs[..., 0]             # (MB, KV, 1)

    return pool._replace(
        k_codes=upd(pool.k_codes, kc),
        k_scale=upd(pool.k_scale, ks),
        v_codes=upd(pool.v_codes, vc),
        v_scale=upd(pool.v_scale, vs),
        feat_words=upd(pool.feat_words, to_blocks(src.feat_words)),
        feat_scale=upd(pool.feat_scale, to_blocks(src.feat_scale)),
        feat_zero=upd(pool.feat_zero, to_blocks(src.feat_zero)),
        heavy_idx=pool.heavy_idx.at[slot].set(src.heavy_idx[0]),
        length=pool.length.at[slot].set(src.length[0]),
        page_table=pool.page_table.at[slot].set(pages.astype(jnp.int32)),
        refcount=_refcount_add(pool.refcount, pages, +1),
        sel_hist=pool.sel_hist.at[slot].set(0),
    )


def adopt_pages(pool: PagedSalcaCache, slot, pages: jax.Array, length,
                heavy_idx: jax.Array) -> PagedSalcaCache:
    """Map an ALREADY-WRITTEN prefix into `slot` without touching data rows.

    The zero-prefill warm path of the persistent prefix cache: every block
    named by `pages` still holds the prompt's rows (written by the original
    cold prefill and retained under the engine's cache pin), so admission
    only needs the metadata side of `prefill_into_pages` — install the page
    table row, bump refcounts, set the cursor to the prompt length and the
    slot's heavy-channel set to the static set (1, KV, R) the retained rows
    were encoded against. `slot`, `pages` and `length` may be traced, so the
    engine compiles this once. The slot must be unmapped (fresh or freed)
    before this call, exactly like `prefill_into_pages`."""
    return pool._replace(
        heavy_idx=pool.heavy_idx.at[slot].set(heavy_idx[0]),
        length=pool.length.at[slot].set(jnp.asarray(length, jnp.int32)),
        page_table=pool.page_table.at[slot].set(pages.astype(jnp.int32)),
        refcount=_refcount_add(pool.refcount, pages, +1),
        sel_hist=pool.sel_hist.at[slot].set(0),
    )


def prefill_chunk_into_pages(pool: PagedSalcaCache, k: jax.Array, v: jax.Array,
                             heavy_idx: jax.Array, slot, pages: jax.Array,
                             start, n_shared=0) -> PagedSalcaCache:
    """Stream one prefill chunk's raw K/V into a partially-filled paged slot.

    The chunked-prefill cursor: `k`/`v` are (1, C, KV, HD) full-precision
    chunk projections for logical positions [start, start+C), `heavy_idx` is
    the (1, KV, R) static heavy-channel set (chunked prefill requires
    `cfg.salca_static_channels` — the paper's per-input identification needs
    the whole prompt's K at once, so it cannot stream). Encoding is per-token
    (`_encode_tokens`), hence invariant to chunk boundaries: the pool rows a
    chunked prefill writes are bitwise identical to a monolithic
    `prefill_into_pages` install of the same prompt.

    `pages` is the page row mapped SO FAR: the first `n_shared` entries are
    always set (the shared prefix is pinned at admission), entries for every
    fresh logical block covered through THIS chunk are physical ids, later
    entries -1. The page table row is replaced wholesale; the refcount
    increments every shared block on the first chunk and each fresh block on
    the chunk that first covers it — so at any preemption point `free_pages`
    on the row undoes precisely what has been charged. `start` and `slot`
    may be traced; `C` is static.

    The first `n_shared` logical blocks are mapped but never written (prefix
    sharing); `length` is set to start+C absolutely — decode ticks clobber
    pool.length from LMState.pos each tick (masked slots read valid_len 0),
    so the engine threads the cursor through `start`, never through the pool.
    int4 pools are rejected: their per-block requantization folds a whole
    block's statistics into one scale, which is not chunk-incremental.
    """
    bs, mb, p = pool.block_size, pool.max_blocks, pool.num_blocks
    mode = pool.kv_pool_dtype
    if mode == "int4":
        raise ValueError("chunked prefill does not support int4 pools "
                         "(per-block requantization is not chunk-incremental)")
    if k.shape[0] != 1:
        raise ValueError(f"chunk must have batch 1, got {k.shape[0]}")
    c = k.shape[1]
    start = jnp.asarray(start, jnp.int32)
    k8, v8, words, fs, fz = _encode_tokens(k, v, heavy_idx)

    rows = start + jnp.arange(c, dtype=jnp.int32)               # (C,) logical
    blk = jnp.clip(rows // bs, 0, mb - 1)
    pg = pages[blk]
    writable = (pg >= 0) & (blk >= jnp.asarray(n_shared, jnp.int32))
    tgt_pg = jnp.where(writable, pg, p)                          # OOB → dropped
    off = rows % bs
    kvb = jnp.arange(pool.num_kv_heads)[None, :]

    def upd(buf, vals):  # vals: (1, C, KV, ·) per-token field values
        return buf.at[tgt_pg[:, None], kvb, off[:, None]].set(
            vals[0].astype(buf.dtype), mode="drop")

    if mode == "int8":
        kc, ks = k8.codes, k8.scale
        vc, vs = v8.codes, v8.scale
        k_scale = upd(pool.k_scale, ks)
        v_scale = upd(pool.v_scale, vs)
    else:  # fp16: store dequantized int8 values verbatim; per-block scales
        #        stay the unit ones `empty_paged_cache` installed.
        kc = k8.codes.astype(jnp.float32) * k8.scale[..., None]
        vc = v8.codes.astype(jnp.float32) * v8.scale[..., None]
        k_scale, v_scale = pool.k_scale, pool.v_scale

    # Charge fresh blocks exactly when this chunk first covers them (block
    # j is covered once start+C > j·BS, so the newly covered range is
    # [ceil(start/BS), ceil((start+C)/BS))). Shared-prefix blocks are all
    # charged up front on the FIRST chunk: they are pinned at admission —
    # lazily increfing them as chunks arrive would let the radix owner
    # finish mid-prefill and free a block this prefill still plans to map.
    bidx = jnp.arange(mb, dtype=jnp.int32)
    cdiv = lambda n: (n + bs - 1) // bs
    nsh = jnp.asarray(n_shared, jnp.int32)
    first = start == 0
    newly = ((bidx >= nsh) & (bidx >= cdiv(start)) & (bidx < cdiv(start + c))
             | (bidx < nsh) & first)
    return pool._replace(
        k_codes=upd(pool.k_codes, kc),
        k_scale=k_scale,
        v_codes=upd(pool.v_codes, vc),
        v_scale=v_scale,
        feat_words=upd(pool.feat_words, words),
        feat_scale=upd(pool.feat_scale, fs),
        feat_zero=upd(pool.feat_zero, fz),
        heavy_idx=pool.heavy_idx.at[slot].set(
            jnp.where(first, heavy_idx[0], pool.heavy_idx[slot])),
        length=pool.length.at[slot].set(start + c),
        page_table=pool.page_table.at[slot].set(pages.astype(jnp.int32)),
        refcount=_refcount_add(pool.refcount, pages, +1, valid=newly),
        sel_hist=pool.sel_hist.at[slot].set(
            jnp.where(first, 0, pool.sel_hist[slot])),
    )


def append_token_paged(pool: PagedSalcaCache, k: jax.Array, v: jax.Array,
                       block_range=None) -> PagedSalcaCache:
    """Append one decoded token's K/V (S, KV, HD) at each slot's cursor.

    The cursor (`pool.length`) resolves through the page table: block =
    table[slot, cursor // BS], physical row = block·BS + cursor % BS. Writes
    to unmapped blocks or past the logical capacity are DROPPED and the
    cursor does not advance — there is no silent clip; the engine is
    responsible for growing the slot's page list (or finishing the request
    with an overflow stop) before the write lands.

    Copy-on-write fault: a write into a block with refcount > 1 is likewise
    DROPPED with the cursor held — a shared block is never mutated in place.
    The engine services the fault before the tick by allocating a fresh block
    and calling `cow_block` (copy all seven fields, remap only the writer's
    page-table entry, move one reference), after which the write is private
    and lands normally.

    Sharded form (``block_range`` set, inside shard_map): the cursor walk,
    the CoW-fault test and the length advance run identically on every shard
    (page table and refcount are replicated), but the data write lands only
    on the shard owning the resolved block — unowned writes drop, so each
    token's K/V is stored exactly once across the mesh.
    """
    s = k.shape[0]
    bs, mb, p = pool.block_size, pool.max_blocks, pool.num_blocks
    cur = pool.length
    blk = jnp.clip(cur // bs, 0, mb - 1)
    sidx = jnp.arange(s)
    page = pool.page_table[sidx, blk]                          # (S,) global id
    rc = pool.refcount[jnp.where(page >= 0, page, 0)]          # (S,)
    ok = (cur >= 0) & (cur < pool.max_seq) & (page >= 0) & (rc <= 1)
    local = _localize_pages(page, block_range)                 # unowned → -1
    pg = jnp.where(ok & (local >= 0), local, p)                # OOB → drop
    off = cur % bs
    k8, v8, words, fs, fz = _encode_tokens(k[:, None], v[:, None], pool.heavy_idx)

    kvb = jnp.arange(pool.num_kv_heads)[None, :]

    def upd(buf, val):  # scatter each slot's rows at (block, head, offset)
        # directly — no flat (P·BS, ·) reshape of the pool enters the decode
        # tick. Indexing all three leading dims keeps the scatter in the
        # pool's own layout (a (block, :, offset) scatter makes XLA relayout
        # the whole pool around it).
        return buf.at[pg[:, None], kvb, off[:, None]].set(
            val[:, 0].astype(buf.dtype), mode="drop")

    mode = pool.kv_pool_dtype
    if mode == "int8":
        kv_fields = dict(
            k_codes=upd(pool.k_codes, k8.codes), k_scale=upd(pool.k_scale, k8.scale),
            v_codes=upd(pool.v_codes, v8.codes), v_scale=upd(pool.v_scale, v8.scale))
    elif mode == "fp16":
        # Raw rows at f16; the unit per-block scales are never rewritten.
        kv_fields = dict(k_codes=upd(pool.k_codes, k[:, None]),
                         v_codes=upd(pool.v_codes, v[:, None]))
    else:  # int4: per-block scale → a streaming append requantizes the block
        kc, ks = _int4_block_append(pool.k_codes, pool.k_scale, k, pg, off)
        vc, vs = _int4_block_append(pool.v_codes, pool.v_scale, v, pg, off)
        kv_fields = dict(k_codes=kc, k_scale=ks, v_codes=vc, v_scale=vs)

    return pool._replace(
        feat_words=upd(pool.feat_words, words),
        feat_scale=upd(pool.feat_scale, fs), feat_zero=upd(pool.feat_zero, fz),
        length=jnp.where(ok, cur + 1, cur),
        **kv_fields,
    )


def _int4_block_append(codes_buf, scale_buf, tok, pg, off):
    """One token's int4 append for K or V: grow the target block's shared
    per-block, per-head scale monotonically (`new = max(old, amax/7)`),
    rescale the block's existing codes into the new scale, set the token's
    row and scatter the block back. At ``off == 0`` the scale RESETS to the
    token's own range instead — a freshly mapped (or reused) block must not
    inherit a stale scale, or visible codes would depend on pool history.
    ``pg`` carries the out-of-bounds drop sentinel for gated slots; gathers
    clamp it to 0 (their result is discarded by the dropped scatter)."""
    p, bs = codes_buf.shape[0], codes_buf.shape[2]
    pg_safe = jnp.where(pg < p, pg, 0)
    old_codes = qz.unpack_int4(codes_buf[pg_safe])             # (S, KV, BS, HD)
    old_scale = scale_buf[pg_safe, :, 0]                       # (S, KV)
    t32 = tok.astype(jnp.float32)                              # (S, KV, HD)
    amax = jnp.max(jnp.abs(t32), axis=-1)                      # (S, KV)
    reset = (off == 0)[:, None]
    base = jnp.where(reset, 0.0, old_scale)
    new_scale = jnp.maximum(jnp.maximum(base, amax / qz.INT4_MAXABS), 1e-6)
    ratio = jnp.where(reset, 0.0, old_scale / new_scale)
    m = qz.INT4_MAXABS
    rescaled = jnp.clip(jnp.round(old_codes.astype(jnp.float32)
                                  * ratio[:, :, None, None]), -m, m)
    tok_codes = jnp.clip(jnp.round(t32 / new_scale[..., None]), -m, m)
    row = jnp.arange(bs)[None, None, :, None] == off[:, None, None, None]
    merged = jnp.where(row, tok_codes[:, :, None], rescaled).astype(jnp.int8)
    kvb = jnp.arange(scale_buf.shape[1])[None, :]
    return (codes_buf.at[pg].set(qz.pack_int4(merged), mode="drop"),
            scale_buf.at[pg[:, None], kvb, 0].set(new_scale, mode="drop"))


def map_block(pool: PagedSalcaCache, slot, logical_block, page,
              block_range=None) -> PagedSalcaCache:
    """Map one logical block of `slot` to physical block `page` (on-demand
    growth: the engine allocates a block from its free list when a slot's
    cursor crosses a block boundary). All args may be traced.

    Refcounts move with the mapping: the new page gains a reference, and a
    previously mapped entry (remap) releases one.

    ``block_range``: for a fully-sharded metadata layout where the refcount
    leaf holds only this shard's blocks — the page-table write (replicated
    metadata) applies everywhere, but refcount deltas land only on the shard
    owning the block; unowned deltas drop and are applied by the owner. The
    per-shard results concatenate to the global op (property-tested)."""
    page = jnp.asarray(page, jnp.int32)
    old = pool.page_table[slot, logical_block]
    rc = _refcount_add(pool.refcount, _localize_pages(page[None], block_range), +1)
    rc = _refcount_add(rc, _localize_pages(old[None], block_range), -1)
    return pool._replace(
        page_table=pool.page_table.at[slot, logical_block].set(page),
        refcount=rc)


def share_blocks(pool: PagedSalcaCache, src_slot, n_blocks,
                 dst_slot) -> PagedSalcaCache:
    """Map the first `n_blocks` logical blocks of `src_slot` into `dst_slot`
    — the prefix-sharing primitive. No data moves: `dst_slot`'s page table
    aliases `src_slot`'s physical blocks and each gains a reference, making
    them copy-on-write for BOTH slots. `dst_slot` also adopts `src_slot`'s
    frozen heavy-channel set (the shared feature blocks are encoded with it)
    and a length covering the shared tokens (min(src length, n_blocks·BS)).
    `dst_slot` must be unmapped beforehand. All args may be traced.
    """
    mb, bs = pool.max_blocks, pool.block_size
    take = jnp.arange(mb) < jnp.asarray(n_blocks, jnp.int32)
    src_row = pool.page_table[src_slot]
    dst_row = jnp.where(take, src_row, pool.page_table[dst_slot])
    shared_len = jnp.minimum(pool.length[src_slot],
                             jnp.asarray(n_blocks, jnp.int32) * bs)
    return pool._replace(
        page_table=pool.page_table.at[dst_slot].set(dst_row),
        heavy_idx=pool.heavy_idx.at[dst_slot].set(pool.heavy_idx[src_slot]),
        length=pool.length.at[dst_slot].set(shared_len),
        refcount=_refcount_add(pool.refcount, src_row, +1, valid=take),
        sel_hist=pool.sel_hist.at[dst_slot].set(0),
    )


def cow_block(pool: PagedSalcaCache, slot, logical_block,
              new_page) -> PagedSalcaCache:
    """Copy-on-write service: copy ALL SEVEN cache fields of the block
    currently mapped at (`slot`, `logical_block`) into the fresh physical
    block `new_page`, remap ONLY this slot's page-table entry, and move one
    reference from the source block to the copy (the source stays alive for
    its remaining owners). A no-op if the entry is unmapped. All args may be
    traced — the engine compiles this once.
    """
    p = pool.num_blocks
    old = pool.page_table[slot, logical_block]
    mapped = old >= 0
    src = jnp.where(mapped, old, 0)
    tgt = jnp.where(mapped, jnp.asarray(new_page, jnp.int32), p)  # OOB → drop

    def copy(buf):
        return buf.at[tgt].set(buf[src], mode="drop")

    rc = _refcount_add(pool.refcount, old[None], -1)
    rc = _refcount_add(rc, jnp.where(mapped, tgt, -1)[None], +1)
    return pool._replace(
        k_codes=copy(pool.k_codes), k_scale=copy(pool.k_scale),
        v_codes=copy(pool.v_codes), v_scale=copy(pool.v_scale),
        feat_words=copy(pool.feat_words), feat_scale=copy(pool.feat_scale),
        feat_zero=copy(pool.feat_zero),
        page_table=pool.page_table.at[slot, logical_block].set(
            jnp.where(mapped, jnp.asarray(new_page, jnp.int32), old)),
        refcount=rc)


def free_pages(pool: PagedSalcaCache, slot, block_range=None) -> PagedSalcaCache:
    """Release a slot: decrement the refcount of every block it maps, unmap
    its page table row and zero its length. Blocks whose refcount reaches 0
    return to the engine's free list (host side); their data rows are left
    in place — every read is gated by the valid mask, and the next owner
    overwrites them. Freeing an already-freed slot is a no-op (its row is
    all -1, so no refcount moves) — the double-free hazard lives here.

    ``block_range``: sharded-refcount form (see `map_block`) — each shard
    decrements only the counts of the blocks it owns; the page-table unmap
    and length zero are replicated metadata and apply everywhere."""
    return pool._replace(
        length=pool.length.at[slot].set(0),
        page_table=pool.page_table.at[slot].set(jnp.int32(PAGE_UNMAPPED)),
        refcount=_refcount_add(
            pool.refcount,
            _localize_pages(pool.page_table[slot], block_range), -1),
        sel_hist=pool.sel_hist.at[slot].set(0),
    )


def paged_logical_features(pool: PagedSalcaCache):
    """Gather the feature stream into logical order: (S, L, KV, ·).

    This is the paper's sequentially-streamed pre-computing read, resolved
    through the page table — the per-block gathers arrive in page order, so
    the result is logically contiguous and all downstream selection math is
    unchanged. Unmapped pages clamp to block 0; the valid mask gates them.
    """
    pt = pool.clamped_pages()                                   # (S, MB)
    s, l = pt.shape[0], pool.max_seq

    def logical(buf):  # (P, KV, BS, ·) → (S, L, KV, ·)
        g = buf[pt].swapaxes(2, 3)                              # (S, MB, BS, KV, ·)
        return g.reshape((s, l, buf.shape[1]) + buf.shape[3:])

    return (logical(pool.feat_words), logical(pool.feat_scale),
            logical(pool.feat_zero))


def paged_logical_kv(pool: PagedSalcaCache):
    """Dequantized dense logical K/V view (S, L, KV, HD) f32 — the dense
    oracle / sliding-window read over a paged pool. O(S·L) transient; use
    the selected-gather path for the sparse decode.

    Mode-generic: int4 codes unpack first, and the scale gather broadcasts
    whether it is per-token ``(·, KV, BS)`` or per-block ``(·, KV, 1)`` —
    the fp16 pool's unit scales make the multiply an exact identity."""
    pt = pool.clamped_pages()
    s, l = pt.shape[0], pool.max_seq
    unpack = qz.unpack_int4 if pool.kv_pool_dtype == "int4" else (lambda x: x)

    def logical(codes, scale):  # (S, MB, KV, BS, HD) → (S, L, KV, HD)
        x = unpack(codes[pt]).astype(jnp.float32) * scale[pt][..., None]
        return x.swapaxes(2, 3).reshape(s, l, pool.num_kv_heads, -1)

    return logical(pool.k_codes, pool.k_scale), logical(pool.v_codes, pool.v_scale)


def _resolve_pages(pool: PagedSalcaCache, idx: jax.Array, block_range=None):
    """Walk the page table for logical token indices (S, ...).

    Returns (page, offset, mapped): the physical block id, the within-block
    row, and whether the entry was mapped. Unmapped resolutions clamp to
    (block 0, offset 0) — callers mask them. The single definition of the
    logical→physical rule for every gather path.

    Sharded form: with ``block_range=(lo, hi)`` the resolution is
    local-or-sentinel — `page` comes back in the LOCAL coordinate
    (global − lo) and `mapped` is True only when this shard owns the block,
    so composing the per-shard resolutions over all shards reproduces the
    flat resolution exactly (property-tested)."""
    bs = pool.block_size
    blk = jnp.clip(idx // bs, 0, pool.max_blocks - 1)
    # page[s, ...] = page_table[s, blk[s, ...]]
    pt = pool.page_table.reshape(
        (pool.page_table.shape[0],) + (1,) * (idx.ndim - 2) + (pool.max_blocks,))
    page = _localize_pages(jnp.take_along_axis(pt, blk, axis=-1), block_range)
    mapped = page >= 0
    return (jnp.where(mapped, page, 0), jnp.where(mapped, idx % bs, 0), mapped)


def resolve_logical_rows(pool: PagedSalcaCache, idx: jax.Array) -> jax.Array:
    """Resolve logical token indices (S, ..., ) to physical rows in the flat
    (P·BS) pool through the page table. Unmapped resolutions clamp to row 0
    (callers mask them)."""
    page, off, _ = _resolve_pages(pool, idx)
    return page * pool.block_size + off


def gather_selected_paged(pool: PagedSalcaCache, sel, block_range=None) -> tuple:
    """Gather selected K/V rows per (slot, kv-head), resolving the selection's
    logical indices through the page table before fetching from the pool.

    sel.indices: (S, KV, C) logical. Returns int8 k/v codes (S, KV, C, HD)
    and scales (S, KV, C) — the same contract as `attention.gather_selected`.

    The page-table resolution is computed ONCE and each field is fetched with
    a single (block, kv-head, offset) advanced-index gather straight off the
    `(P, KV, BS, ·)` pool — no `(P·BS, KV, ·)` flattening and no pool-wide
    transpose ever materializes (the PR 3 form transposed all four pool
    buffers every decode tick). Unmapped resolutions clamp to (block 0,
    offset 0); callers mask them.

    Sharded form: with ``block_range`` the gather reads the LOCAL pool —
    indices resolving off-shard clamp like unmapped ones, so a shard fetches
    exactly the selected rows it physically holds (callers mask via the
    selection mask, whose entries are shard-local by construction).
    """
    pg, off, _ = _resolve_pages(pool, sel.indices, block_range)  # (S, KV, C)
    kvb = jnp.arange(pool.num_kv_heads)[None, :, None]           # (1, KV, 1)

    mode = pool.kv_pool_dtype
    if mode == "int8":
        return (pool.k_codes[pg, kvb, off], pool.k_scale[pg, kvb, off],
                pool.v_codes[pg, kvb, off], pool.v_scale[pg, kvb, off])
    # Per-block scales: one scale row per block, fetched at scale-offset 0
    # and broadcast across the block's gathered tokens; int4 codes unpack to
    # full head_dim so the consumer contract is unchanged.
    soff = jnp.zeros_like(off)
    kc, vc = pool.k_codes[pg, kvb, off], pool.v_codes[pg, kvb, off]
    if mode == "int4":
        kc, vc = qz.unpack_int4(kc), qz.unpack_int4(vc)
    return (kc, pool.k_scale[pg, kvb, soff],
            vc, pool.v_scale[pg, kvb, soff])


def record_selection(pool: PagedSalcaCache, sel_indices: jax.Array,
                     sel_mask: jax.Array) -> PagedSalcaCache:
    """Scatter-add this tick's selected tokens into the per-logical-block
    relevance history (`sel_hist`) — the signal the host-spill engine reads
    to find blocks the filter has stopped selecting. ``sel_indices`` /
    ``sel_mask``: the (S, KV, C) logical selection a decode tick produced.
    O(S·KV·C) — never pool-shaped."""
    bs, mb = pool.block_size, pool.max_blocks
    blk = jnp.clip(sel_indices // bs, 0, mb - 1)
    tgt = jnp.where(sel_mask, blk, mb)                         # masked → drop
    sidx = jnp.arange(tgt.shape[0])[:, None, None]
    return pool._replace(
        sel_hist=pool.sel_hist.at[sidx, tgt].add(jnp.int32(1), mode="drop"))


# Block read/write rows: the host-spill transport. `read_block_rows` pulls
# one physical block's data fields in STORAGE format (codes stay packed /
# quantized, scales ride along), so a demote→promote round trip through host
# memory is bit-exact by construction — no transcode on either side.

_BLOCK_DATA_FIELDS = ("k_codes", "k_scale", "v_codes", "v_scale",
                      "feat_words", "feat_scale", "feat_zero")


def read_block_rows(pool: PagedSalcaCache, page) -> tuple:
    """The seven data-field rows of physical block `page` (traced-safe)."""
    pg = jnp.asarray(page, jnp.int32)
    return tuple(getattr(pool, f)[pg] for f in _BLOCK_DATA_FIELDS)


def write_block_rows(pool: PagedSalcaCache, page, rows: tuple) -> PagedSalcaCache:
    """Install rows captured by :func:`read_block_rows` into block `page`."""
    pg = jnp.asarray(page, jnp.int32)
    upd = {f: getattr(pool, f).at[pg].set(r.astype(getattr(pool, f).dtype))
           for f, r in zip(_BLOCK_DATA_FIELDS, rows)}
    return pool._replace(**upd)


def block_data_bytes(pool: PagedSalcaCache) -> int:
    """Bytes of ONE physical block across the seven data fields — the unit
    of PCIe traffic for a host-spill demotion or promotion."""
    total = 0
    for f in _BLOCK_DATA_FIELDS:
        buf = getattr(pool, f)
        total += int(buf[0].size) * buf.dtype.itemsize
    return total


def paged_cache_bytes(pool: PagedSalcaCache) -> dict[str, int]:
    """Physical bytes by region, plus the page-table + refcount overhead."""
    def nbytes(x):
        return int(x.size) * x.dtype.itemsize
    kv = (nbytes(pool.k_codes) + nbytes(pool.v_codes)
          + nbytes(pool.k_scale) + nbytes(pool.v_scale))
    feats = (nbytes(pool.feat_words) + nbytes(pool.feat_scale)
             + nbytes(pool.feat_zero))
    table = (nbytes(pool.page_table) + nbytes(pool.refcount)
             + nbytes(pool.sel_hist))
    return {"kv_region": kv, "feature_region": feats, "page_table": table,
            "total": kv + feats + table}
