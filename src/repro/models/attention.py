"""GQA attention layer: train/prefill (chunked flash) + Salca/SP decode.

Train/prefill attention is the memory-lean chunked-scan flash form (online
softmax over K blocks) so the compiled step stays within activation budget;
the Pallas `flash_prefill` kernel implements the identical tiling for real
TPU runs (`impl="pallas"`).

Decode goes through the sequence-parallel Salca path (`repro.core.sp_decode`)
— the KV cache is sharded on the token dim, which sidesteps the
kv_heads < model-axis divisibility problem for every assigned arch and is
the layout the paper's O(n) selection distributes over (DESIGN.md §4).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import dense_init, rmsnorm, rmsnorm_init, rope, cdtype

NEG_INF = -1e30

# When True, the flash K-chunk loop unrolls (python loop) instead of
# lax.scan. XLA cost_analysis counts scan bodies ONCE, so roofline
# (layer-granularity) compiles flip this on for honest FLOP/byte counts;
# production steps keep the scan (compile speed, identical math).
UNROLL_KV_CHUNKS = False


def attention_init(key, cfg: ModelConfig) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    dtype = cdtype(cfg)
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, h, hd), dtype, fan_in=d),
        "wk": dense_init(ks[1], (d, kv, hd), dtype, fan_in=d),
        "wv": dense_init(ks[2], (d, kv, hd), dtype, fan_in=d),
        "wo": dense_init(ks[3], (h * hd, d), dtype, fan_in=h * hd),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd, dtype)
        p["k_norm"] = rmsnorm_init(hd, dtype)
    return p


def qkv_project(params: dict, x: jax.Array, cfg: ModelConfig,
                positions: jax.Array, use_rope: bool = True):
    """x (B, T, D) → q (B,T,H,HD), k/v (B,T,KV,HD), post-norm post-RoPE."""
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"])
    k = jnp.einsum("btd,dhk->bthk", x, params["wk"])
    v = jnp.einsum("btd,dhk->bthk", x, params["wv"])
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(params["k_norm"], k, cfg.norm_eps)
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def flash_attention_xla(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: int = 0,
                        q_offset: jax.Array | int = 0,
                        chunk: int = 1024) -> jax.Array:
    """Chunked-scan flash attention (XLA path; GQA via KV head repeat).

    q: (B, T, H, HD); k, v: (B, S, KV, HD). ``q_offset`` shifts query
    positions (cross-chunk prefill). Returns (B, T, H, HD) in q dtype.
    A key length S that ``chunk`` does not divide is padded to the next
    multiple with masked keys (their contributions are exact zeros).
    """
    b, t, h, hd = q.shape
    s = k.shape[1]
    kv = k.shape[2]
    if kv != h:
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
    chunk = min(chunk, s)
    pad = -s % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = (s + pad) // chunk
    kc = k.reshape(b, nc, chunk, h, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nc, chunk, h, hd).transpose(1, 0, 2, 3, 4)
    qpos = jnp.asarray(q_offset) + jnp.arange(t)
    scale = 1.0 / (hd ** 0.5)
    from repro.flags import PERF
    if PERF.bf16_collectives:
        # §Perf it-4: cast at the MXU (f32 accumulation), not before the K
        # stream — operands cross resharding boundaries in bf16, halving
        # all-gather/all-to-all wire bytes.
        qf = q
    else:
        qf = q.astype(jnp.float32)

    def body(carry, inp):
        m, l, acc = carry
        kb, vb, ci = inp
        if PERF.bf16_collectives:
            sc = jnp.einsum("bthd,bshd->bhts", qf, kb,
                            preferred_element_type=jnp.float32) * scale
        else:
            sc = jnp.einsum("bthd,bshd->bhts", qf, kb.astype(jnp.float32)) * scale
        kpos = ci * chunk + jnp.arange(chunk)
        mask = jnp.broadcast_to(kpos[None, :] < s, (t, chunk))
        if causal:
            mask &= qpos[:, None] >= kpos[None, :]
        if window > 0:
            mask &= kpos[None, :] > qpos[:, None] - window
        sc = jnp.where(mask[None, None], sc, NEG_INF)
        m2 = jnp.maximum(m, sc.max(-1))
        p = jnp.exp(sc - m2[..., None])
        p = jnp.where(mask[None, None], p, 0.0)
        corr = jnp.exp(m - m2)
        l2 = l * corr + p.sum(-1)
        acc2 = acc * corr[..., None] + jnp.einsum(
            "bhts,bshd->bhtd", p, vb.astype(jnp.float32))
        return (m2, l2, acc2), None

    m0 = jnp.full((b, h, t), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, t), jnp.float32)
    a0 = jnp.zeros((b, h, t, hd), jnp.float32)
    if UNROLL_KV_CHUNKS:
        carry = (m0, l0, a0)
        for ci in range(nc):
            carry, _ = body(carry, (kc[ci], vc[ci], jnp.asarray(ci)))
        m, l, acc = carry
    else:
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                      (kc, vc, jnp.arange(nc)))
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return out.astype(q.dtype).transpose(0, 2, 1, 3)


def flash_attention_pallas_wrap(q, k, v, *, causal=True, window=0):
    """(B,T,H,HD) adapter over the Pallas flash kernel's (BH,T,HD) layout."""
    from repro.kernels.flash_prefill import flash_attention as _fa
    b, t, h, hd = q.shape
    kv = k.shape[2]
    if kv != h:
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], hd)
    out = _fa(fold(q), fold(k), fold(v), causal=causal, window=window)
    return out.reshape(b, h, t, hd).transpose(0, 2, 1, 3)


def attention_train(params: dict, x: jax.Array, cfg: ModelConfig, *,
                    window: int = 0, impl: str = "xla",
                    causal: bool = True) -> jax.Array:
    """Self-attention over a full (training/prefill) sequence.

    x: (B, T, D) → (B, T, D). ``window`` > 0 selects sliding-window masking
    (gemma3 local layers / recurrentgemma attention blocks); ``causal=False``
    gives the bidirectional form (whisper encoder).
    """
    from repro.distributed.sharding import constrain_qkv
    b, t, _ = x.shape
    positions = jnp.arange(t)
    q, k, v = qkv_project(params, x, cfg, positions)
    q, k, v = constrain_qkv(q, k, v)
    if impl == "pallas":
        o = flash_attention_pallas_wrap(q, k, v, causal=causal, window=window)
    else:
        o = flash_attention_xla(q, k, v, causal=causal, window=window)
    return o.reshape(b, t, -1) @ params["wo"]
