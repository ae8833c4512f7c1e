"""Pallas TPU kernel: dense causal flash attention (prefill / training path).

The paper accelerates *decoding*; prefill remains dense and compute-bound
("prefilling executes matrix multiplication, fully exploiting parallel
capability"). This kernel is the compute hot-spot of that phase — a
standard flash-attention tiling shaped for the TPU memory hierarchy:

* grid = (B·H, T/BQ, S/BK), K-dim innermost so the (BQ, HD) query tile and
  the (BQ,) online-softmax state stay VMEM-resident across the K stream;
* BQ/BK default to 512/512 with HD up to 256: working set ≈
  q(512·256·4) + k/v(2·512·256·2) + p(512·512·4) ≈ 1.8 MB ≪ VMEM,
  leaving room for the double-buffered next K/V tile;
* MXU-aligned tiles (multiples of 128 lanes / 8 sublanes);
* causal blocks above the diagonal are skipped via ``pl.when`` (no work,
  no HBM read of the masked K/V tile: the index map never advances there —
  skipping is done with a zero-contribution guard to keep the pipeline
  static, the standard TPU trade).

Supports an optional sliding window (gemma3 local layers, recurrentgemma
local attention) via ``window``; window==0 means full causal.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import interpret_default

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, out_ref, m_ref, l_ref, acc_ref,
            *, scale: float, bq: int, bk: int, nk: int, causal: bool,
            window: int, q_offset: int):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # q_offset shifts the queries' absolute positions (chunked prefill: a
    # C-token chunk attends over the whole-prompt K/V buffer); the causal
    # band test and the mask iotas both use the shifted coordinate.
    q_start = q_offset + iq * bq
    k_start = ik * bk

    # Work only when the block intersects the (windowed) causal band.
    live = True
    if causal:
        live = k_start <= q_start + bq - 1
    if window > 0:
        live = jnp.logical_and(live, k_start + bk - 1 >= q_start - window + 1) \
            if causal else live

    @pl.when(live)
    def _work():
        q = q_ref[0].astype(jnp.float32) * scale          # (BQ, HD)
        k = k_ref[0].astype(jnp.float32)                  # (BK, HD)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (BQ, BK)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask = qpos >= kpos
        if window > 0:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        v = v_ref[0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        out_ref[0] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-20)[:, None]).astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "block_q", "block_k",
                                    "q_offset", "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                           *, causal: bool = True, window: int = 0,
                           q_offset: int = 0,
                           block_q: int = DEFAULT_BLOCK_Q,
                           block_k: int = DEFAULT_BLOCK_K,
                           interpret: bool | None = None) -> jax.Array:
    """q (BH, T, HD), k/v (BH, S, HD) → out (BH, T, HD) (q dtype).

    ``q_offset`` is the chunked-prefill entry: queries sit at absolute
    positions [q_offset, q_offset+T) over keys [0, S). It is static — the
    engine calls with offsets that are multiples of a fixed chunk size, so
    the compile cache stays small.
    """
    if interpret is None:
        interpret = interpret_default()
    bh, t, hd = q.shape
    s_len = k.shape[1]
    bq = min(block_q, t)
    bk = min(block_k, s_len)
    assert t % bq == 0 and s_len % bk == 0
    nq, nk = t // bq, s_len // bk
    scale = 1.0 / (hd ** 0.5)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, bq=bq, bk=bk, nk=nk,
                          causal=causal, window=window, q_offset=q_offset),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        name="flash_attention_pallas",
        interpret=interpret,
    )(q, k, v)
