"""Subprocess check: sequence-parallel Salca decode == single-device decode.

Run by test_sp_decode.py with 8 forced host devices (the XLA flag must be
set before jax initializes, hence the separate process).
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

import jax

from repro.launch.mesh import make_mesh
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import SalcaParams, prefill_cache, salca_decode_attention
from repro.core.sp_decode import (
    local_lengths, sp_append_token, sp_dense_decode, sp_salca_decode)
from repro.core.attention import dense_decode_from_cache


def main() -> int:
    assert len(jax.devices()) == 8, jax.devices()
    mesh = make_mesh((2, 4), ("data", "model"))
    rng = np.random.default_rng(0)
    B, T, H, KV, HD = 2, 512, 8, 4, 64
    G = H // KV
    q = jnp.asarray(rng.normal(size=(B, H, HD)), jnp.float32)
    k = rng.normal(size=(B, T, KV, HD)).astype(np.float32)
    qg = np.asarray(q).reshape(B, KV, G, HD).mean(2)
    for b in range(B):
        for h in range(KV):
            sel = rng.choice(T, size=20, replace=False)
            k[b, sel, h] += 3.0 * qg[b, h] / np.linalg.norm(qg[b, h]) * np.sqrt(HD)
    k = jnp.asarray(k * (1 + 4 * (rng.random(HD) < 0.25)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, KV, HD)), jnp.float32)

    params = SalcaParams.for_seq(T, retention=0.1, use_pool=True)
    cache = prefill_cache(k, v, max_seq=T, params=params)

    # --- single-device reference -----------------------------------------
    ref = salca_decode_attention(q, cache, params)
    ref_dense = dense_decode_from_cache(q, cache)

    # --- sequence-parallel over "model" (4 shards) ------------------------
    cspec = type(cache)(
        k_codes=P(None, "model", None, None), k_scale=P(None, "model", None),
        v_codes=P(None, "model", None, None), v_scale=P(None, "model", None),
        feat_words=P(None, "model", None, None), feat_scale=P(None, "model", None),
        feat_zero=P(None, "model", None), heavy_idx=P(None, None, None),
        length=P(None))
    glen = cache.length

    def island(q_, gl_, c_):
        c_ = c_._replace(length=local_lengths(gl_, c_.max_seq, "model"))
        out_salca = sp_salca_decode(q_, c_, params, "model",
                                    shard_cap=params.k_cap)
        out_dense = sp_dense_decode(q_, c_, "model", global_len=gl_)
        return out_salca, out_dense

    f = jax.jit(jax.shard_map(
        island, mesh=mesh,
        in_specs=(P(None, None, None), P(None), cspec),
        out_specs=(P(None, None, None), P(None, None, None)),
        check_vma=False))
    out_salca, out_dense = f(q, glen, cache)

    err_dense = float(jnp.max(jnp.abs(out_dense - ref_dense)))
    print("sp_dense max err vs single-device:", err_dense)
    assert err_dense < 1e-4, err_dense

    rel = float(jnp.linalg.norm(out_salca - ref) / jnp.linalg.norm(ref))
    print("sp_salca rel err vs single-device salca:", rel)
    # selections may differ slightly at shard boundaries (per-shard capacity
    # + halo pooling); outputs must still agree closely on concentrated data
    assert rel < 0.05, rel

    # --- distributed histogram == global histogram ------------------------
    from repro.core.histogram_topk import histogram256, locate_threshold
    from repro.core.selection import estimate_relevance
    idx = jnp.broadcast_to(cache.heavy_idx[:, :, None, :], (B, KV, G, 64 // 2))
    qg_j = q.reshape(B, KV, G, HD).astype(jnp.float32)
    q_feat = jnp.take_along_axis(qg_j, idx, axis=-1).reshape(B, H, -1)
    scores = estimate_relevance(q_feat, cache.feat_words, cache.feat_scale,
                                cache.feat_zero, G)
    from repro.core.quantization import quantize_scores_uint8
    bins = quantize_scores_uint8(scores, cache.valid_mask()[:, None, :])
    t_global = locate_threshold(histogram256(bins), params.k)

    def hist_island(bins_):
        h = histogram256(bins_)
        h = jax.lax.psum(h, "model")
        return locate_threshold(h, params.k)

    t_sp = jax.jit(jax.shard_map(
        hist_island, mesh=mesh, in_specs=P(None, None, "model"),
        out_specs=P(None, None), check_vma=False))(bins)
    np.testing.assert_array_equal(np.asarray(t_sp), np.asarray(t_global))
    print("distributed histogram threshold == global: OK")

    # --- sp append lands in exactly one shard ------------------------------
    k_new = jnp.asarray(rng.normal(size=(B, KV, HD)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(B, KV, HD)), jnp.float32)
    short = jnp.asarray([100, 300], jnp.int32)   # cursors in shards 0 and 2

    def app_island(c_, k_, v_, gl_):
        c_ = c_._replace(length=local_lengths(gl_, c_.max_seq, "model"))
        return sp_append_token(c_, k_, v_, gl_, "model")

    new_cache = jax.jit(jax.shard_map(
        app_island, mesh=mesh,
        in_specs=(cspec, P(None, None, None), P(None, None, None), P(None)),
        out_specs=cspec, check_vma=False))(cache, k_new, v_new, short)
    deq = np.asarray(new_cache.k_codes[0, 100].astype(jnp.float32)
                     * new_cache.k_scale[0, 100, :, None])
    np.testing.assert_allclose(deq, np.asarray(k_new[0]), atol=0.05, rtol=0.1)
    deq2 = np.asarray(new_cache.k_codes[1, 300].astype(jnp.float32)
                      * new_cache.k_scale[1, 300, :, None])
    np.testing.assert_allclose(deq2, np.asarray(k_new[1]), atol=0.05, rtol=0.1)
    print("sp_append writes at global cursor across shards: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
