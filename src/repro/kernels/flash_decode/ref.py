"""Pure-jnp oracle for the sparse flash-decode kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def sparse_flash_decode_ref(q: jax.Array, k_codes: jax.Array, k_scale: jax.Array,
                            v_codes: jax.Array, v_scale: jax.Array,
                            mask: jax.Array) -> jax.Array:
    """Same contract as the kernel: q (BH,G,HD), codes (BH,C,HD) int8."""
    hd = q.shape[-1]
    s = jnp.einsum("bgd,bcd->bgc", q.astype(jnp.float32),
                   k_codes.astype(jnp.float32))
    s = s * k_scale[:, None, :] / jnp.sqrt(hd)
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(mask[:, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    v = v_codes.astype(jnp.float32) * v_scale[..., None]
    return jnp.einsum("bgc,bcd->bgd", p, v) / jnp.maximum(l, 1e-20)


def sparse_flash_decode_paged_ref(q: jax.Array, k_codes: jax.Array,
                                  k_scale: jax.Array, v_codes: jax.Array,
                                  v_scale: jax.Array, pblk: jax.Array,
                                  blk_mask: jax.Array,
                                  num_kv: int,
                                  kv_dtype: str = "int8") -> jax.Array:
    """Paged-native oracle: same contract as the scalar-prefetch kernel.

    Fetches each row's listed physical blocks with one (block, kv-head,
    token) advanced-index gather per field — O(selected blocks), never a
    flat (P·BS, ·) view of the pool — then runs the flat oracle over the
    flattened (BH, NSB·BS) block stream.

    ``kv_dtype`` names the pool's storage precision: "fp16"/"int4" pools
    carry ONE scale row per block (fetched at scale-offset 0 and broadcast
    over the block's tokens), and int4 codes unpack nibble-wise before the
    flat oracle sees them.
    """
    bh = q.shape[0]
    bs = k_codes.shape[2]
    nsb = pblk.shape[1]
    kvb = (jnp.arange(bh) % num_kv)[:, None, None]             # (BH, 1, 1)
    tok = jnp.arange(bs)[None, None, :]                        # (1, 1, BS)
    pb = pblk[:, :, None]                                      # (BH, NSB, 1)
    kc = k_codes[pb, kvb, tok]                                 # (BH, NSB, BS, ·)
    vc = v_codes[pb, kvb, tok]
    if kv_dtype == "int4":
        from repro.core import quantization as qz
        kc, vc = qz.unpack_int4(kc), qz.unpack_int4(vc)
    kc = kc.reshape(bh, nsb * bs, -1)
    vc = vc.reshape(bh, nsb * bs, -1)
    stok = tok if kv_dtype == "int8" else jnp.zeros_like(tok)
    ks = k_scale[pb, kvb, stok].reshape(bh, nsb * bs)
    vs = v_scale[pb, kvb, stok].reshape(bh, nsb * bs)
    return sparse_flash_decode_ref(q, kc, ks, vc, vs,
                                   blk_mask.reshape(bh, nsb * bs))


def sparse_flash_decode_partials_ref(q: jax.Array, k_codes: jax.Array,
                                     k_scale: jax.Array, v_codes: jax.Array,
                                     v_scale: jax.Array, mask: jax.Array):
    """Flat oracle stopping before normalization: returns (acc, m, l).

    All-masked rows come back as (0, NEG_INF, 0), matching the partials
    kernel's counts == 0 rows, so they vanish in the cross-shard merge."""
    hd = q.shape[-1]
    s = jnp.einsum("bgd,bcd->bgc", q.astype(jnp.float32),
                   k_codes.astype(jnp.float32))
    s = s * k_scale[:, None, :] / jnp.sqrt(hd)
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)          # all-masked rows: exactly NEG_INF
    p = jnp.exp(s - m[..., None])
    p = jnp.where(mask[:, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1)
    v = v_codes.astype(jnp.float32) * v_scale[..., None]
    acc = jnp.einsum("bgc,bcd->bgd", p, v)
    return acc, m, l


def sparse_flash_decode_paged_partials_ref(q: jax.Array, k_codes: jax.Array,
                                           k_scale: jax.Array,
                                           v_codes: jax.Array,
                                           v_scale: jax.Array,
                                           pblk: jax.Array,
                                           blk_mask: jax.Array,
                                           num_kv: int,
                                           kv_dtype: str = "int8"):
    """Paged partials oracle: `sparse_flash_decode_paged_ref`'s gather
    followed by the unnormalized flat oracle — the reference for the
    shard-local leg of the sharded fused tick."""
    bh = q.shape[0]
    bs = k_codes.shape[2]
    nsb = pblk.shape[1]
    kvb = (jnp.arange(bh) % num_kv)[:, None, None]
    tok = jnp.arange(bs)[None, None, :]
    pb = pblk[:, :, None]
    kc = k_codes[pb, kvb, tok]
    vc = v_codes[pb, kvb, tok]
    if kv_dtype == "int4":
        from repro.core import quantization as qz
        kc, vc = qz.unpack_int4(kc), qz.unpack_int4(vc)
    kc = kc.reshape(bh, nsb * bs, -1)
    vc = vc.reshape(bh, nsb * bs, -1)
    stok = tok if kv_dtype == "int8" else jnp.zeros_like(tok)
    ks = k_scale[pb, kvb, stok].reshape(bh, nsb * bs)
    vs = v_scale[pb, kvb, stok].reshape(bh, nsb * bs)
    return sparse_flash_decode_partials_ref(q, kc, ks, vc, vs,
                                            blk_mask.reshape(bh, nsb * bs))
