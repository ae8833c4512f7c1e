"""Whisper-style encoder–decoder (audio family).

The conv audio frontend is a STUB: `input_specs()` delivers precomputed
frame embeddings (B, T_enc, D) — post-conv, pre-encoder (per the
assignment: "the modality frontend is a STUB; input_specs() provides
precomputed frame embeddings").

Decoder blocks: causal self-attention (short target stream, ≤
cfg.decoder_max_len) + cross-attention over the encoder states + GLU FFN.
**Salca applies to the cross-attention stream** — decode reads a 32k/500k
frame context per step, which is exactly the paper's bandwidth-bound
regime; the self-attention cache is window-bounded and uses the dense SP
path. Simplification noted in DESIGN.md: RoPE replaces whisper's
learned/sinusoidal positions (self-attention only; cross-attention is
position-free as in the original).
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.cache import SalcaCache, empty_cache, prefill_cache
from repro.core.attention import salca_decode_attention, dense_decode_attention
from repro.core.sp_decode import local_lengths, sp_append_token, sp_dense_decode, sp_salca_decode
from repro.models import blocks as B
from repro.models.attention import attention_init, attention_train, flash_attention_xla, qkv_project
from repro.models.common import (
    cdtype, cross_entropy, embed_tokens, embedding_init, glu_apply, glu_init,
    lm_logits, rmsnorm, rmsnorm_init, rope, vocab_mask_logits)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _enc_layer_init(key, cfg: ModelConfig) -> dict:
    k1, k2 = jax.random.split(key)
    return {"ln1": rmsnorm_init(cfg.d_model, cdtype(cfg)),
            "attn": attention_init(k1, cfg),
            "ln2": rmsnorm_init(cfg.d_model, cdtype(cfg)),
            "glu": glu_init(k2, cfg.d_model, cfg.d_ff, cdtype(cfg))}


def _dec_layer_init(key, cfg: ModelConfig) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {"ln1": rmsnorm_init(cfg.d_model, cdtype(cfg)),
            "self_attn": attention_init(k1, cfg),
            "ln_x": rmsnorm_init(cfg.d_model, cdtype(cfg)),
            "cross_attn": attention_init(k2, cfg),
            "ln2": rmsnorm_init(cfg.d_model, cdtype(cfg)),
            "glu": glu_init(k3, cfg.d_model, cfg.d_ff, cdtype(cfg))}


def init_encdec_params(key, cfg: ModelConfig) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    enc_keys = jax.random.split(k1, cfg.encoder_layers)
    dec_keys = jax.random.split(k2, cfg.num_layers)
    return {
        "embed": embedding_init(k3, cfg),
        "enc": jax.vmap(lambda k: _enc_layer_init(k, cfg))(enc_keys),
        "dec": jax.vmap(lambda k: _dec_layer_init(k, cfg))(dec_keys),
        "ln_enc": rmsnorm_init(cfg.d_model, cdtype(cfg)),
        "ln_f": rmsnorm_init(cfg.d_model, cdtype(cfg)),
    }


# ---------------------------------------------------------------------------
# Encoder
# ---------------------------------------------------------------------------

def encode(params: dict, cfg: ModelConfig, frames: jax.Array) -> jax.Array:
    """frames: (B, T_enc, D) stub embeddings → encoder states (B, T_enc, D)."""
    from repro.distributed.sharding import constrain_residual
    h = constrain_residual(frames.astype(cdtype(cfg)))

    def body(h, lp):
        def blk(h_, lp_):
            a = attention_train(lp_["attn"], rmsnorm(lp_["ln1"], h_, cfg.norm_eps),
                                cfg, causal=False)
            h_ = h_ + a
            f = glu_apply(lp_["glu"], rmsnorm(lp_["ln2"], h_, cfg.norm_eps), cfg.act)
            return h_ + f

        return constrain_residual(jax.checkpoint(blk)(h, lp)), None

    h, _ = jax.lax.scan(body, h, params["enc"])
    return rmsnorm(params["ln_enc"], h, cfg.norm_eps)


# ---------------------------------------------------------------------------
# Decoder (training / prefill, teacher-forced)
# ---------------------------------------------------------------------------

def _cross_attention_full(lp: dict, x: jax.Array, enc: jax.Array,
                          cfg: ModelConfig) -> jax.Array:
    """Full cross-attention (B, Td, D) x (B, Te, D), no positions."""
    q = jnp.einsum("btd,dhk->bthk", x, lp["wq"])
    k = jnp.einsum("btd,dhk->bthk", enc, lp["wk"])
    v = jnp.einsum("btd,dhk->bthk", enc, lp["wv"])
    o = flash_attention_xla(q, k, v, causal=False)
    return o.reshape(x.shape[0], x.shape[1], -1) @ lp["wo"]


def decode_train(params: dict, cfg: ModelConfig, tokens: jax.Array,
                 enc: jax.Array) -> jax.Array:
    """Teacher-forced decoder forward → logits (B, Td, V_pad)."""
    from repro.distributed.sharding import constrain, constrain_residual
    h = constrain_residual(embed_tokens(params["embed"], tokens).astype(cdtype(cfg)))

    def body(h, lp):
        def blk(h_, lp_):
            a = attention_train(lp_["self_attn"],
                                rmsnorm(lp_["ln1"], h_, cfg.norm_eps), cfg, causal=True)
            h_ = h_ + a
            c = _cross_attention_full(lp_["cross_attn"],
                                      rmsnorm(lp_["ln_x"], h_, cfg.norm_eps), enc, cfg)
            h_ = h_ + c
            f = glu_apply(lp_["glu"], rmsnorm(lp_["ln2"], h_, cfg.norm_eps), cfg.act)
            return h_ + f

        return constrain_residual(jax.checkpoint(blk)(h, lp)), None

    h, _ = jax.lax.scan(body, h, params["dec"])
    h = rmsnorm(params["ln_f"], h, cfg.norm_eps)
    h = constrain(h, "dp", None, None)
    return lm_logits(params["embed"], h, cfg)


def encdec_loss(params: dict, cfg: ModelConfig, frames: jax.Array,
                tokens: jax.Array, labels: jax.Array) -> jax.Array:
    enc = encode(params, cfg, frames)
    logits = decode_train(params, cfg, tokens, enc)
    return cross_entropy(logits, labels, cfg)


# ---------------------------------------------------------------------------
# Serving: prefill + single-token decode
# ---------------------------------------------------------------------------

class EncDecState(NamedTuple):
    self_caches: Any      # stacked SalcaCache (L, B, S_self, ...)
    cross_caches: Any     # stacked SalcaCache (L, B, T_enc, ...)
    pos: jax.Array        # (B,) decoder cursor


def encdec_prefill(params: dict, cfg: ModelConfig, frames: jax.Array,
                   tokens: jax.Array, self_max: int | None = None):
    """Encode + teacher-forced decoder prefill; build both cache stacks."""
    self_max = self_max or cfg.decoder_max_len
    enc = encode(params, cfg, frames)
    h = embed_tokens(params["embed"], tokens).astype(cdtype(cfg))
    t_enc = enc.shape[1]
    sp_cross = B.salca_params_for(cfg, t_enc)
    sp_self = B.salca_params_for(cfg, self_max)

    def body(h, lp):
        xn = rmsnorm(lp["ln1"], h, cfg.norm_eps)
        positions = jnp.arange(h.shape[1])
        q, k, v = qkv_project(lp["self_attn"], xn, cfg, positions)
        o = flash_attention_xla(q, k, v, causal=True)
        h = h + o.reshape(h.shape[0], h.shape[1], -1) @ lp["self_attn"]["wo"]
        self_cache = prefill_cache(k, v, max_seq=self_max, params=sp_self)
        xn = rmsnorm(lp["ln_x"], h, cfg.norm_eps)
        kx = jnp.einsum("btd,dhk->bthk", enc, lp["cross_attn"]["wk"])
        vx = jnp.einsum("btd,dhk->bthk", enc, lp["cross_attn"]["wv"])
        qx = jnp.einsum("btd,dhk->bthk", xn, lp["cross_attn"]["wq"])
        ox = flash_attention_xla(qx, kx, vx, causal=False)
        h = h + ox.reshape(h.shape[0], h.shape[1], -1) @ lp["cross_attn"]["wo"]
        cross_cache = prefill_cache(kx, vx, max_seq=t_enc, params=sp_cross)
        f = glu_apply(lp["glu"], rmsnorm(lp["ln2"], h, cfg.norm_eps), cfg.act)
        return h + f, (self_cache, cross_cache)

    h, (self_caches, cross_caches) = jax.lax.scan(body, h, params["dec"])
    h = rmsnorm(params["ln_f"], h, cfg.norm_eps)
    logits = vocab_mask_logits(lm_logits(params["embed"], h[:, -1], cfg), cfg)
    pos = jnp.full((h.shape[0],), tokens.shape[1], jnp.int32)
    return logits, EncDecState(self_caches, cross_caches, pos)


def encdec_init_state(cfg: ModelConfig, batch: int, enc_len: int,
                      prefill_len: int | jax.Array = 0,
                      self_max: int | None = None) -> EncDecState:
    """Empty decode state (dry-run ShapeDtypeStruct source)."""
    self_max = self_max or cfg.decoder_max_len
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    r = B.salca_params_for(cfg, enc_len).r(hd)
    L = cfg.num_layers

    def stack(c):
        return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (L,) + x.shape), c)

    return EncDecState(
        self_caches=stack(empty_cache(batch, self_max, kv, hd, r)),
        cross_caches=stack(empty_cache(batch, enc_len, kv, hd, r)),
        pos=jnp.full((batch,), prefill_len, jnp.int32))


def encdec_write_into_slot(pool: EncDecState, src: EncDecState, slot) -> EncDecState:
    """Install a batch=1 prefilled state into row `slot` of a pooled state.

    Cache stacks carry a leading layer axis; the per-cache write is vmapped
    over it (see `core.cache.write_prefill_into_slot`)."""
    from repro.core.cache import write_prefill_into_slot
    wr = lambda p, s: write_prefill_into_slot(p, s, slot)
    return EncDecState(
        self_caches=jax.vmap(wr)(pool.self_caches, src.self_caches),
        cross_caches=jax.vmap(wr)(pool.cross_caches, src.cross_caches),
        pos=pool.pos.at[slot].set(src.pos[0]))


def encdec_reset_slot(pool: EncDecState, slot) -> EncDecState:
    """Free row `slot`: both cache stacks marked empty, cursor zeroed."""
    from repro.core.cache import reset_slot
    rs = lambda c: reset_slot(c, slot)
    return EncDecState(
        self_caches=jax.vmap(rs)(pool.self_caches),
        cross_caches=jax.vmap(rs)(pool.cross_caches),
        pos=pool.pos.at[slot].set(0))


def encdec_decode_step(params: dict, cfg: ModelConfig, state: EncDecState,
                       token: jax.Array, ctx: B.DecodeCtx | None = None,
                       active: jax.Array | None = None):
    """One decoder step. Salca runs on the cross-attention stream.

    `active` (B,) bool masks pooled request slots: inactive slots compute
    (static shapes) but append nothing to their self-cache and hold their
    cursor; their logits are garbage the caller ignores."""
    ctx = ctx or B.DecodeCtx()
    h = embed_tokens(params["embed"], token).astype(cdtype(cfg))
    pos = state.pos
    t_enc = state.cross_caches.k_codes.shape[-3]
    sp_cross = B.salca_params_for(cfg, t_enc)

    def body(h, xs):
        lp, self_cache, cross_cache = xs
        # --- causal self-attention over the short target stream ---------
        xn = rmsnorm(lp["ln1"], h, cfg.norm_eps)
        q = jnp.einsum("bd,dhk->bhk", xn, lp["self_attn"]["wq"])
        k = jnp.einsum("bd,dhk->bhk", xn, lp["self_attn"]["wk"])
        v = jnp.einsum("bd,dhk->bhk", xn, lp["self_attn"]["wv"])
        q = rope(q[:, None], pos[:, None], cfg.rope_theta)[:, 0].astype(jnp.float32)
        k = rope(k[:, None], pos[:, None], cfg.rope_theta)[:, 0]
        if ctx.axis is None:
            from repro.core.cache import append_token_masked
            self_cache = append_token_masked(self_cache, k, v, active)
            kd = self_cache.k_codes.astype(jnp.float32) * self_cache.k_scale[..., None]
            vd = self_cache.v_codes.astype(jnp.float32) * self_cache.v_scale[..., None]
            o = dense_decode_attention(q, kd, vd, self_cache.valid_mask())
        else:
            from jax.sharding import PartitionSpec as P
            ba = ctx.batch_axes
            sa = ctx.self_axis if ctx.self_axis is not None else ctx.axis
            rep3 = P(ba, None, None)
            # Sharded path: -1 cursor ⇒ every shard drops the write and
            # recomputes a 0 valid length for the slot.
            cursor = pos if active is None else jnp.where(active, pos, -1)

            def island(q_, k_, v_, pos_, c_):
                c_ = c_._replace(length=local_lengths(pos_, c_.max_seq, sa))
                c_ = sp_append_token(c_, k_, v_, pos_, sa)
                return sp_dense_decode(q_, c_, sa, global_len=pos_ + 1), c_

            o, self_cache = jax.shard_map(
                island, mesh=ctx.mesh,
                in_specs=(rep3, rep3, rep3, P(ba), B.cache_pspec(ctx, sa)),
                out_specs=(rep3, B.cache_pspec(ctx, sa)), check_vma=False,
            )(q, k, v, cursor, self_cache)
        h = h + (o.astype(h.dtype).reshape(h.shape[0], -1)
                 @ lp["self_attn"]["wo"])

        # --- Salca cross-attention over the encoder stream ---------------
        xn = rmsnorm(lp["ln_x"], h, cfg.norm_eps)
        qx = jnp.einsum("bd,dhk->bhk", xn, lp["cross_attn"]["wq"]).astype(jnp.float32)
        if ctx.axis is None:
            if cfg.salca:
                ox = salca_decode_attention(qx, cross_cache, sp_cross)
            else:
                kd = cross_cache.k_codes.astype(jnp.float32) * cross_cache.k_scale[..., None]
                vd = cross_cache.v_codes.astype(jnp.float32) * cross_cache.v_scale[..., None]
                ox = dense_decode_attention(qx, kd, vd, cross_cache.valid_mask())
        else:
            from jax.sharding import PartitionSpec as P
            ba, sa = ctx.batch_axes, ctx.axis
            rep3 = P(ba, None, None)
            enc_len_arr = jnp.full((qx.shape[0],), t_enc, jnp.int32)

            def island_x(q_, el_, c_):
                c_ = c_._replace(length=local_lengths(el_, c_.max_seq, sa))
                if cfg.salca:
                    return sp_salca_decode(q_, c_, sp_cross, sa)
                return sp_dense_decode(q_, c_, sa, global_len=el_)

            ox = jax.shard_map(
                island_x, mesh=ctx.mesh,
                in_specs=(rep3, P(ba), B.cache_pspec(ctx)),
                out_specs=rep3, check_vma=False,
            )(qx, enc_len_arr, cross_cache)
        h = h + (ox.astype(h.dtype).reshape(h.shape[0], -1)
                 @ lp["cross_attn"]["wo"])
        f = glu_apply(lp["glu"], rmsnorm(lp["ln2"], h, cfg.norm_eps), cfg.act)
        return h + f, self_cache

    h, new_self = jax.lax.scan(
        body, h, (params["dec"], state.self_caches, state.cross_caches))
    h = rmsnorm(params["ln_f"], h, cfg.norm_eps)
    logits = vocab_mask_logits(lm_logits(params["embed"], h, cfg), cfg)
    new_pos = pos + 1 if active is None else pos + active.astype(jnp.int32)
    return logits, EncDecState(new_self, state.cross_caches, new_pos)
