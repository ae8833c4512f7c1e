"""Jit'd public wrappers for exact sparse attention over INT8 K/V.

Two front-ends share the kernel math:

* `sparse_flash_decode` — the flat form over pre-gathered (BH, C, ·) rows.
* `sparse_flash_decode_paged` — the paged-native form: the selection's
  logical indices are resolved to physical blocks on the host side of the
  trace (`_selected_block_plan`), and the kernel/oracle fetches only those
  blocks from the shared pool. ``impl="gather"`` keeps the PR 3 behaviour
  (gather every selected row into a dense (S, KV, C, ·) buffer, then run
  the flat kernel) for parity tests and benchmarks.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import paged_impl_default
from repro.kernels.flash_decode.kernel import (
    sparse_flash_decode_paged_pallas, sparse_flash_decode_paged_partials_pallas,
    sparse_flash_decode_pallas)
from repro.kernels.flash_decode.ref import (
    sparse_flash_decode_paged_partials_ref, sparse_flash_decode_paged_ref,
    sparse_flash_decode_ref)


def sparse_flash_decode(q: jax.Array, k_codes: jax.Array, k_scale: jax.Array,
                        v_codes: jax.Array, v_scale: jax.Array, mask: jax.Array,
                        *, impl: str = "pallas", interpret: bool | None = None) -> jax.Array:
    """Exact attention of q (BH, G, HD) over gathered INT8 K/V (BH, C, ·)."""
    if impl == "pallas":
        return sparse_flash_decode_pallas(q, k_codes, k_scale, v_codes, v_scale,
                                          mask, interpret=interpret)
    return sparse_flash_decode_ref(q, k_codes, k_scale, v_codes, v_scale, mask)


def _selected_block_plan(pool, sel, block_range=None):
    """Resolve a Selection to per-(slot, kv-head) physical block lists.

    Host-of-the-trace prep for the paged-native kernel: the C selected
    logical token indices collapse to the ≤ min(MB, C) logical blocks they
    touch, compacted (ascending) into a fixed NSB-capacity list and resolved
    through the page table. Returns

    * pblk  (S·KV, NSB) int32 — physical block ids (padding clamped to the
      last real entry's neighbourhood via block 0; consecutive repeats are
      coalesced by the kernel pipeline),
    * counts (S·KV,) int32 — live entries per row,
    * bmask (S·KV, NSB, BS) bool — which tokens of each listed block the
      selection actually picked (False everywhere on padding).

    Unmapped resolutions clamp to block 0; a well-formed selection (gated to
    pos < length) never lands there, and padding is masked out regardless.

    With ``block_range`` (inside a sharded island) the plan is SHARD-LOCAL:
    only selected blocks this shard owns are listed, with their ids in the
    local coordinate — each shard's kernel leg touches exactly the selected
    blocks resident in its pool slice, and a shard owning none of a row's
    selection gets counts == 0 (its partials vanish in the merge).
    """
    from repro.core.cache import _localize_pages
    from repro.core.histogram_topk import compact_indices
    s, kv, c = sel.indices.shape
    bs, mb, l = pool.block_size, pool.max_blocks, pool.max_seq
    nsb = max(1, min(mb, c))
    bh = s * kv
    idx = jnp.clip(sel.indices, 0, l - 1).reshape(bh, c)
    m = sel.mask.reshape(bh, c)
    rows = jnp.arange(bh)[:, None]
    tok = jnp.zeros((bh, l), jnp.bool_).at[rows, idx].max(m)
    blk_active = jnp.zeros((bh, mb), jnp.bool_).at[rows, idx // bs].max(m)
    if block_range is None:
        pt = pool.clamped_pages()                               # (S, MB)
    else:
        local = _localize_pages(pool.page_table, block_range)   # (S, MB)
        blk_active &= jnp.repeat(local >= 0, kv, axis=0)
        pt = jnp.where(local >= 0, local, 0)
    lblk, lmask, cnt = compact_indices(blk_active, nsb)         # (BH, NSB)
    pblk = jnp.take_along_axis(jnp.repeat(pt, kv, axis=0), lblk, axis=1)
    bmask = jnp.take_along_axis(tok.reshape(bh, mb, bs),
                                lblk[:, :, None], axis=1)       # (BH, NSB, BS)
    return pblk.astype(jnp.int32), cnt.astype(jnp.int32), bmask & lmask[:, :, None]


def sparse_flash_decode_paged(q: jax.Array, pool, sel, *, impl: str | None = None,
                              interpret: bool | None = None) -> jax.Array:
    """Paged front-end: exact attention over the tokens a Selection names.

    q: (S, H, HD); pool: `core.cache.PagedSalcaCache`; sel: Selection with
    (S, KV, C) logical indices. Returns (S, H, HD) f32.

    impl picks the fetch strategy (all three are value-equivalent):

    * "pallas" — the fused kernel: the selection's physical-block list is
      scalar-prefetched and drives the index_map, each grid step streaming
      one selected block HBM→VMEM (the TPU hot path);
    * "ref"    — the kernel's pure-jnp oracle over the same per-block
      operands (parity tests; its static NSB·BS padding makes it slow);
    * "gather" — resolve each selected row through the page table and fetch
      it with ONE advanced-index gather (no pool-wide transpose), then run
      the flat flash-decode kernel on TPU or `exact_sparse_attention` on
      CPU. O(C) rows moved — the fastest XLA lowering, so it is the CPU
      serving default.

    Default: pallas on TPU, gather elsewhere.
    """
    s, h, hd = q.shape
    kv = pool.num_kv_heads
    g = h // kv
    on_tpu = paged_impl_default() == "pallas"
    if impl is None:
        impl = "pallas" if on_tpu else "gather"
    if impl == "gather":
        from repro.core.attention import exact_sparse_attention
        from repro.core.cache import gather_selected_paged
        with jax.named_scope("salca.attend"):
            kc, ks, vc, vs = gather_selected_paged(pool, sel)  # (S, KV, C, ·)
            if on_tpu:
                # Gathered rows through the flat flash-decode kernel (the PR
                # 2/3 TPU fallback path).
                c = kc.shape[2]
                out = sparse_flash_decode(
                    q.reshape(s * kv, g, hd),
                    kc.reshape(s * kv, c, hd), ks.reshape(s * kv, c),
                    vc.reshape(s * kv, c, hd), vs.reshape(s * kv, c),
                    sel.mask.reshape(s * kv, c), impl="pallas",
                    interpret=interpret)
                return out.reshape(s, h, hd)
            return exact_sparse_attention(q, kc, ks, vc, vs, sel.mask)
    if impl not in ("pallas", "ref"):
        raise ValueError(f"unknown impl {impl!r} "
                         "(expected 'pallas', 'ref' or 'gather')")
    with jax.named_scope("salca.plan"):
        pblk, counts, bmask = _selected_block_plan(pool, sel)
    with jax.named_scope("salca.attend"):
        qr = q.reshape(s * kv, g, hd)
        if impl == "pallas":
            out = sparse_flash_decode_paged_pallas(
                qr, pool.k_codes, pool.k_scale, pool.v_codes, pool.v_scale,
                pblk, counts, bmask, num_kv=kv, kv_dtype=pool.kv_pool_dtype,
                interpret=interpret)
        else:
            out = sparse_flash_decode_paged_ref(
                qr, pool.k_codes, pool.k_scale, pool.v_codes, pool.v_scale,
                pblk, bmask, kv, kv_dtype=pool.kv_pool_dtype)
        return out.reshape(s, h, hd)


def sparse_flash_decode_paged_partials(q: jax.Array, pool, sel, *,
                                       block_range=None, impl: str | None = None,
                                       interpret: bool | None = None):
    """Shard-local leg of the sharded exact-attention phase.

    Same inputs as `sparse_flash_decode_paged` plus ``block_range`` (the
    island's `local_block_range`), but returns the UNNORMALIZED online-
    softmax state ``(acc (S, KV, G, HD), m (S, KV, G), l (S, KV, G))`` over
    the shard-local selected-block plan; the caller merges across chips with
    the flash rescale (pmax on m, psum on corrected l/acc). impl: "pallas"
    (scalar-prefetched kernel) or "ref" (blocked oracle); default follows
    `paged_impl_default`.
    """
    s, h, hd = q.shape
    kv = pool.num_kv_heads
    g = h // kv
    if impl is None:
        impl = paged_impl_default()
    if impl not in ("pallas", "ref"):
        raise ValueError(f"unknown impl {impl!r} (expected 'pallas' or 'ref')")
    with jax.named_scope("salca.plan"):
        pblk, counts, bmask = _selected_block_plan(pool, sel, block_range)
    with jax.named_scope("salca.attend"):
        qr = q.reshape(s * kv, g, hd)
        if impl == "pallas":
            acc, m, l = sparse_flash_decode_paged_partials_pallas(
                qr, pool.k_codes, pool.k_scale, pool.v_codes, pool.v_scale,
                pblk, counts, bmask, num_kv=kv, kv_dtype=pool.kv_pool_dtype,
                interpret=interpret)
        else:
            acc, m, l = sparse_flash_decode_paged_partials_ref(
                qr, pool.k_codes, pool.k_scale, pool.v_codes, pool.v_scale,
                pblk, bmask, kv, kv_dtype=pool.kv_pool_dtype)
        return (acc.reshape(s, kv, g, hd), m.reshape(s, kv, g),
                l.reshape(s, kv, g))
