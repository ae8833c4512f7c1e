"""Plain float32 reference: a Qwen3 decoder with Salca sparse decode attention.

Follows the published descriptions, not the program's code, and imports
nothing of it:

* Qwen3 (HF ``Qwen3ForCausalLM``): RMSNorm before attention and MLP, q/k
  RMSNorm per head, rotate-half RoPE, grouped-query attention, SwiGLU MLP,
  final RMSNorm, tied or untied LM head.
* Prefill (every prompt position) attends densely and causally.
* Decode (every position after the prompt) attends with Salca (the paper's
  Algorithm 1): keys are stored as int8 rows (one symmetric scale per token
  and head) plus 2-bit asymmetric codes of r heavy channels, chosen per kv
  head as the r largest of sum_d |W_k[d, kv, :]| (the configuration's static
  channels). The query's heavy channels are summed over the heads of a kv
  group, quantized to 3 bits, and scored against the 2-bit codes; scores
  over the valid positions map to bins 1..255 through their min and max; a
  stride-1 max-pool of ``pool_window`` spreads them; the largest bin whose
  count from above reaches k is the threshold, and the first ``k_cap``
  positions at or above it are attended exactly over the int8 rows.
  k = max(128, min(floor(retention * max_seq), max_k, max_seq)) and
  k_cap = min(roundup(floor(1.25 k), 128), max_seq).

Every matmul runs in float32 at ``Precision.HIGHEST`` (TPUs otherwise round
f32 operands to bf16). ``precision="fp8"`` is the control: the same
computation with every matmul operand rounded to float8 e4m3 (activations
scaled per row, weights per tensor) — the precision below the
configuration's bfloat16.

Weights come as the benchmark makes them: the parameter tree of
``bench/harness/weights.py`` (``periods[0]`` stacks the layers).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def _fp8(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis`` (None: one
    scale for the tensor)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.maximum(amax, 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, fp8: bool):
    """a (..., K) @ w (K, M) in float32 (or fp8-rounded operands)."""
    a, w = a.astype(jnp.float32), w.astype(jnp.float32)
    if fp8:
        a, w = _fp8(a, -1), _fp8(w, None)
    return jnp.matmul(a, w, precision=HI)


def _rms(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, pos, theta):
    """x (N, heads, hd), rotate-half RoPE at integer positions ``pos``."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / x.shape[-1])
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attend(q, k, v, mask, fp8):
    """q (R, KV, G, hd), k/v (S, KV, hd), mask (R, S) or (R, KV, S)."""
    if fp8:
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, -1)
    s = jnp.einsum("rkgd,skd->rkgs", q, k, precision=HI) / math.sqrt(q.shape[-1])
    m = mask[:, None, None, :] if mask.ndim == 2 else mask[:, :, None, :]
    s = jnp.where(m, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(m, p, 0.0)
    if fp8:
        p = _fp8(p, -1)
    return jnp.einsum("rkgs,skd->rkgd", p, v, precision=HI)


def _causal(q, k, v, fp8, groups=8, rows=256):
    """Dense causal attention of all N rows, in ``groups`` row groups that
    each see only the keys up to their last row."""
    n = q.shape[0]
    per = n // groups
    sub = math.gcd(rows, per)
    out = []
    for g in range(groups):
        hi_key = (g + 1) * per
        kk, vv = k[:hi_key], v[:hi_key]

        def block(i, g=g, kk=kk, vv=vv):
            r0 = g * per + i * sub
            qb = jax.lax.dynamic_slice_in_dim(q, r0, sub, 0)
            mask = (r0 + jnp.arange(sub))[:, None] >= jnp.arange(kk.shape[0])[None, :]
            return _attend(qb, kk, vv, mask, fp8)

        out.append(jax.lax.map(block, jnp.arange(per // sub)).reshape(
            (per,) + q.shape[1:]))
    return jnp.concatenate(out, 0)


def _int8_rows(x):
    """Symmetric int8 per (token, head) over the head dim, dequantized."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True) / 127.0, 1e-6)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _salca(qd, k, v, qpos, wk, sal, fp8):
    """Salca attention of D decode rows at positions ``qpos`` over all keys
    at positions <= their own. qd (D, KV, G, hd); k, v (S, KV, hd)."""
    d_rows, kvh, g, hd = qd.shape
    s_len = k.shape[0]
    r, k_top, k_cap, window = sal["r"], sal["k"], sal["k_cap"], sal["window"]
    weight = jnp.sum(jnp.abs(wk.astype(jnp.float32)), axis=0)        # (KV, hd)
    idx = jnp.sort(jax.lax.top_k(weight, r)[1], axis=-1)              # (KV, r)
    # keys: 2-bit asymmetric codes of the heavy channels, per token and head
    kf = jnp.take_along_axis(k, jnp.broadcast_to(idx[None], (s_len, kvh, r)), -1)
    lo = jnp.min(kf, -1)
    step = jnp.maximum((jnp.max(kf, -1) - lo) / 3.0, 1e-6)
    kc = jnp.clip(jnp.round((kf - lo[..., None]) / step[..., None]), 0, 3)
    # query: heavy channels summed over the group, 3-bit symmetric
    qf = jnp.take_along_axis(qd, jnp.broadcast_to(idx[None, :, None, :],
                                                  (d_rows, kvh, g, r)), -1)
    qf = jnp.sum(qf, axis=2)                                          # (D, KV, r)
    qs = jnp.maximum(jnp.max(jnp.abs(qf), -1) / 3.0, 1e-6)
    qc = jnp.clip(jnp.round(qf / qs[..., None]), -3, 3)
    dot = jnp.einsum("dkr,skr->dks", qc, kc, precision=HI)
    score = qs[..., None] * (step.T[None] * dot
                             + lo.T[None] * jnp.sum(qc, -1)[..., None])
    valid = jnp.arange(s_len)[None, :] <= qpos[:, None]               # (D, S)
    vm = valid[:, None, :]
    smin = jnp.min(jnp.where(vm, score, jnp.inf), -1, keepdims=True)
    smax = jnp.max(jnp.where(vm, score, -jnp.inf), -1, keepdims=True)
    width = jnp.maximum((smax - smin) / 254.0, 1e-6)
    bins = jnp.where(vm, jnp.clip(jnp.round((score - smin) / width) + 1, 1, 255), 0)
    half = window // 2
    padded = jnp.pad(bins, ((0, 0), (0, 0), (half, half)))
    pooled = functools.reduce(jnp.maximum, [padded[..., i:i + s_len]
                                            for i in range(window)])
    pooled = jnp.where(vm, pooled, 0).astype(jnp.int32)
    # the threshold: the largest bin b with at least k positions at or above
    # it (found by bisection over the 256 bins), and at least 1
    lo, hi = jnp.zeros(pooled.shape[:-1], jnp.int32), jnp.full(pooled.shape[:-1], 256)
    for _ in range(8):
        mid = (lo + hi) // 2
        enough = jnp.sum(pooled >= mid[..., None], -1) >= k_top
        lo, hi = jnp.where(enough, mid, lo), jnp.where(enough, hi, mid)
    keep = pooled >= jnp.maximum(lo, 1)[..., None]
    # the first k_cap kept positions: those before the smallest position p
    # with k_cap kept positions before it (bisection over positions)
    pos = jnp.arange(s_len)
    lo, hi = jnp.zeros(keep.shape[:-1], jnp.int32), jnp.full(keep.shape[:-1], s_len)
    for _ in range(max(s_len, 2).bit_length()):
        mid = (lo + hi + 1) // 2
        few = jnp.sum(keep & (pos < mid[..., None]), -1) <= k_cap
        lo, hi = jnp.where(few, mid, lo), jnp.where(few, hi, mid - 1)
    keep &= pos < lo[..., None]
    return _attend(qd, _int8_rows(k), _int8_rows(v), keep, fp8)


@functools.partial(jax.jit, static_argnames=("dims", "sal", "rows", "fp8"))
def _layer(h, w, i, t_prompt, *, dims, sal, rows, fp8):
    """One layer over the whole padded sequence h (N, d)."""
    n = h.shape[0]
    heads, kvh, hd, eps, theta = dims
    lw = jax.tree.map(lambda x: x[i], w)
    x = _rms(h, lw["ln1"], eps)
    q = _mm(x, lw["wq"].reshape(x.shape[-1], -1), fp8).reshape(n, heads, hd)
    k = _mm(x, lw["wk"].reshape(x.shape[-1], -1), fp8).reshape(n, kvh, hd)
    v = _mm(x, lw["wv"].reshape(x.shape[-1], -1), fp8).reshape(n, kvh, hd)
    q, k = _rms(q, lw["qn"], eps), _rms(k, lw["kn"], eps)
    pos = jnp.arange(n)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    qg = q.reshape(n, kvh, heads // kvh, hd)
    o = _causal(qg, k, v, fp8)
    qd = jax.lax.dynamic_slice_in_dim(qg, t_prompt, rows, 0)
    od = _salca(qd, k, v, t_prompt + jnp.arange(rows), lw["wk"], dict(sal), fp8)
    o = jax.lax.dynamic_update_slice_in_dim(o, od, t_prompt, 0)
    h = h + _mm(o.reshape(n, heads * hd), lw["wo"], fp8)
    x = _rms(h, lw["ln2"], eps)
    f = jax.nn.silu(_mm(x, lw["wg"], fp8)) * _mm(x, lw["wu"], fp8)
    return h + _mm(f, lw["wd"], fp8)


@functools.partial(jax.jit, static_argnames=("rows", "eps", "vocab", "fp8"))
def _logits(h, ln_f, head, t_prompt, *, rows, eps, vocab, fp8):
    hs = jax.lax.dynamic_slice_in_dim(h, t_prompt - 1, rows, 0)
    return _mm(_rms(hs, ln_f, eps), head, fp8)[:, :vocab]


def _stacked(params) -> dict:
    p = params["periods"][0]
    a, f = p["attn"], p["ffn"]["glu"]
    return {"ln1": p["ln1"]["scale"], "wq": a["wq"], "wk": a["wk"],
            "wv": a["wv"], "wo": a["wo"], "qn": a["q_norm"]["scale"],
            "kn": a["k_norm"]["scale"], "ln2": p["ln2"]["scale"],
            "wg": f["w_gate"], "wu": f["w_up"], "wd": f["w_down"]}


def salca_sizes(config: dict, max_seq: int) -> tuple:
    s = config["serving"]
    hd = config["head_dim"]
    k = max(128, min(int(max_seq * s["salca_retention"]), s["salca_max_k"], max_seq))
    k_cap = min((int(k * 1.25) + 127) // 128 * 128, max_seq)
    r = max(16, int(s["salca_feature_sparsity"] * hd) // 16 * 16)
    return (("r", r), ("k", k), ("k_cap", k_cap), ("window", s["salca_pool_window"]))


def logits(params, config: dict, max_seq: int, prompt, served,
           precision: str = "f32") -> jax.Array:
    """Logits (len(served), vocab) that predict each served token: row i is
    the reference's next-token distribution after ``prompt`` followed by
    ``served[:i]``. The sequence is padded up to a multiple of an eighth of
    ``max_seq``, so a cell's requests share a few compiled layers."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    t, n = len(prompt), len(served)
    if t + n > max_seq:
        raise ValueError(f"prompt {t} + served {n} exceeds max_seq {max_seq}")
    rows = -(-n // 128) * 128
    quantum = max(256, max_seq // 8)
    n_pad = -(-(t + rows) // quantum) * quantum
    seq = np.zeros(n_pad, np.int32)
    seq[:t], seq[t:t + n - 1] = prompt, served[:-1]
    fp8 = precision == "fp8"
    dims = (config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], float(config["rms_norm_eps"]),
            float(config["rope_theta"]))
    emb = params["embed"]["tok"]
    h = jnp.take(emb, jnp.asarray(seq), axis=0).astype(jnp.float32)
    w = _stacked(params)
    sal = salca_sizes(config, max_seq)
    for i in range(config["num_hidden_layers"]):
        h = _layer(h, w, jnp.int32(i), jnp.int32(t), dims=dims, sal=sal,
                   rows=rows, fp8=fp8)
    head = emb.T if config["tie_word_embeddings"] else params["embed"]["head"]
    out = _logits(h, params["ln_f"]["scale"], head, jnp.int32(t), rows=rows,
                  eps=dims[3], vocab=config["vocab_size"], fp8=fp8)
    return out[:n]
