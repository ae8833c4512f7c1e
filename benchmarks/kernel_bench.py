"""Kernel microbenchmarks: XLA reference-path wall times on the host.

interpret=True Pallas timing is emulation (meaningless for TPU), so the
wall numbers here time the XLA paths these kernels replace, sized to the
paper's decode workload; the TPU-relevant throughput claims come from the
dry-run roofline instead. Derived column = bytes touched / time (GB/s proxy).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import time_call
from repro.core import quantization as qz
from repro.core.histogram_topk import histogram_topk
from repro.core.maxpool import maxpool1d_reuse
from repro.kernels.flash_decode.ref import sparse_flash_decode_ref
from repro.kernels.score_est.ref import score_estimate_ref


def _paged_decode_rows(rng, n: int, k: int, pool_factor: int = 64,
                       gate: bool = False) -> list[str]:
    """Paged decode tick: PR 3 pool-wide gather vs the paged-native path.

    One slot holds ``n`` active tokens (logical capacity 2n) inside a pool
    ``pool_factor``·n tokens large — the serving regime, where the shared
    pool backs many other resident requests and dwarfs any one slot's
    context. Three full ticks (scoring + selection + exact attention):

    * ``pr3_gather``  — the shipped PR 3 path, reconstructed inline: the
      exact-attention fetch transposes all four (P·BS, KV, ·) pool buffers
      every tick, so its cost grows with the POOL, not the request;
    * ``gather``      — the cleaned-up fallback (single advanced-index
      row gather, no pool transpose — O(selected) rows moved);
    * ``fused``       — the paged-native path (physical-block streaming on
      TPU; blocked scoring + the row gather on CPU).

    The derived column is the bytes-moved model for the exact-attention
    fetch: pool bytes touched (pr3) vs selected-block bytes (fused) — the
    structural claim; on TPU the transposes are physical data movement. On
    CPU, XLA folds the pr3 transposes into the gather, so tick wall-clock
    mostly reflects how well each whole graph fuses, not bytes. A fifth
    column prices the same tick on a 4-way block-sharded pool
    (`performance_model.sharded_salca_bytes_per_token`): collective psum
    bytes vs per-shard HBM stream. ``gate=True``
    (the --smoke CI run) hard-fails when the fused tick exceeds the pr3 tick
    by >50% at the smoke shapes — a regression tripwire for the fused path
    (it caught two real 6–20× blowups during development), with headroom for
    XLA fusion drift and scheduler noise; the non-smoke run just reports.
    """
    from repro.core import (SalcaParams, empty_paged_cache, prefill_cache,
                            prefill_into_pages)
    from repro.core.attention import (exact_sparse_attention,
                                      salca_decode_attention_paged)
    from repro.core.cache import paged_logical_features, resolve_logical_rows
    from repro.core.selection import (estimate_relevance,
                                      estimate_relevance_paged,
                                      select_sparse_pattern_blocked)
    from repro.kernels.flash_decode.ops import _selected_block_plan

    bsz, kv, hd = 64, 2, 128
    params = SalcaParams(k=k, k_cap=max(((int(k * 1.25) + 127) // 128) * 128, 128),
                         pool_window=7)
    num_blocks = pool_factor * n // bsz
    mb_slot = 2 * n // bsz                 # per-slot logical capacity: 2n
    kk = jnp.asarray(rng.normal(size=(1, n, kv, hd)), jnp.float32)
    vv = jnp.asarray(rng.normal(size=(1, n, kv, hd)), jnp.float32)
    dense = prefill_cache(kk, vv, max_seq=mb_slot * bsz, params=params)
    pool = empty_paged_cache(num_blocks, bsz, 1, mb_slot, kv, hd,
                             params.r(hd))
    need = n // bsz
    pages = np.full(mb_slot, -1, np.int32)
    pages[:need] = rng.choice(num_blocks, need, replace=False)
    pool = prefill_into_pages(pool, dense, 0, jnp.asarray(pages))
    q = jnp.asarray(rng.normal(size=(1, 2 * kv, hd)), jnp.float32)

    def pr3_gather(pool, sel):  # the four pool-wide transposes, verbatim
        phys = resolve_logical_rows(pool, sel.indices)

        def take_codes(codes):  # (P, KV, BS, HD) → flat (P·BS, KV, HD)
            flat = codes.swapaxes(1, 2).reshape((-1,) + codes.shape[1:2]
                                                + codes.shape[3:])
            f = flat.transpose(1, 0, 2)
            return jnp.take_along_axis(f[None], phys[..., None], axis=2)

        def take_scale(scale):
            flat = scale.swapaxes(1, 2).reshape(-1, scale.shape[1])
            f = flat.transpose(1, 0)
            return jnp.take_along_axis(f[None], phys, axis=2)

        return (take_codes(pool.k_codes), take_scale(pool.k_scale),
                take_codes(pool.v_codes), take_scale(pool.v_scale))

    def pr3_tick(q, pool):
        b, h, _ = q.shape
        groups = h // pool.num_kv_heads
        r_ = pool.heavy_idx.shape[-1]
        idx = jnp.broadcast_to(pool.heavy_idx[:, :, None, :],
                               (b, pool.num_kv_heads, groups, r_))
        qg = q.reshape(b, pool.num_kv_heads, groups, hd).astype(jnp.float32)
        q_feat = jnp.take_along_axis(qg, idx, axis=-1).reshape(b, h, r_)
        fw, fs, fz = paged_logical_features(pool)
        scores = estimate_relevance(q_feat, fw, fs, fz, groups)
        sel = select_sparse_pattern_blocked(scores, params,
                                            pool.valid_mask()[:, None, :],
                                            pool.block_size)
        kc, ks, vc, vs = pr3_gather(pool, sel)
        return exact_sparse_attention(q, kc, ks, vc, vs, sel.mask)

    ticks = {
        "paged_decode_pr3_gather": jax.jit(pr3_tick),
        "paged_decode_gather": jax.jit(
            lambda q, p: salca_decode_attention_paged(q, p, params, fused=False)),
        "paged_decode_fused": jax.jit(
            lambda q, p: salca_decode_attention_paged(q, p, params, fused=True)),
    }
    # Bytes-moved model for the exact-attention fetch (codes + scales, K+V):
    pool_bytes = (pool.k_codes.size + pool.v_codes.size
                  + 4 * pool.k_scale.size + 4 * pool.v_scale.size)

    @jax.jit
    def selection_only(q, pool):  # scoring + selection, no attention
        b, h, _ = q.shape
        groups = h // pool.num_kv_heads
        r_ = pool.heavy_idx.shape[-1]
        idx = jnp.broadcast_to(pool.heavy_idx[:, :, None, :],
                               (b, pool.num_kv_heads, groups, r_))
        qg = q.reshape(b, pool.num_kv_heads, groups, hd).astype(jnp.float32)
        q_feat = jnp.take_along_axis(qg, idx, axis=-1).reshape(b, h, r_)
        scores = estimate_relevance_paged(q_feat, pool, groups)
        return select_sparse_pattern_blocked(scores, params,
                                             pool.valid_mask()[:, None, :],
                                             pool.block_size)

    sel = selection_only(q, pool)
    _, counts, _ = _selected_block_plan(pool, sel)
    sel_blocks = int(np.asarray(counts).sum())
    sel_bytes = sel_blocks * bsz * (2 * hd + 8)    # per-head block K+V bytes
    model = {"paged_decode_pr3_gather": f"{pool_bytes/1e6:.1f}MB_pool_fetch",
             "paged_decode_gather": "O(selected)_row_fetch",
             "paged_decode_fused":
                 f"{sel_bytes/1e6:.2f}MB_selected({pool_bytes/max(sel_bytes,1):.0f}x_less)"}
    # Interconnect column: what the same tick costs in COLLECTIVE bytes when
    # the pool is sharded 4 ways (psum'd histogram threshold + halo + rank +
    # the (m, l, o) softmax merge — context-length-independent) next to the
    # per-shard HBM stream. The ratio is the headroom argument for the
    # sharded engine: the mesh term stays O(max_blocks + 256 + d) while the
    # streamed slice keeps growing with context.
    from repro.core.performance_model import sharded_salca_bytes_per_token
    sh = sharded_salca_bytes_per_token(
        n=n, d=hd, kv_heads=kv, groups=2, s_f=0.5, retention=k / n,
        n_shards=4, block_size=bsz)
    shard_col = (f"shard4:{sh.interconnect/1e3:.1f}KB_psum_vs_"
                 f"{sh.local_total/1e6:.2f}MB_local"
                 f"({100 * sh.interconnect_ratio:.1f}%)")
    rows, us = [], {}
    for name, fn in ticks.items():
        us[name] = time_call(fn, q, pool)
        rows.append(f"kernel_bench,{name},{us[name]:.1f},{model[name]},"
                    f"{shard_col}")
    # Ratio gate with an absolute-delta floor: a loaded CI runner can stretch
    # a ~2ms median by tens of percent, but a real fused-path regression (the
    # 6–20× class this tripwire caught in development) blows past both.
    if gate and (us["paged_decode_fused"] > 1.5 * us["paged_decode_pr3_gather"]
                 and us["paged_decode_fused"]
                 > us["paged_decode_pr3_gather"] + 2000):
        raise RuntimeError(
            f"paged-native decode tick ({us['paged_decode_fused']:.0f}us) is "
            f"slower than the pool-wide gather tick "
            f"({us['paged_decode_pr3_gather']:.0f}us) at pool="
            f"{num_blocks * bsz} tokens — the fusion regressed")
    return rows


def _sharded_decode_rows(rng, n: int, k: int, gate: bool = False) -> list[str]:
    """Sharded island tick: the PR 5 gather island vs the fully-pipelined
    fused island, on a 1-way mesh (CPU wall times; the structural claim is
    the bytes-moved model column).

    * ``sharded_island_legacy`` — `sp_salca_decode_paged(fused=False)`:
      every tick re-materializes capacity-shaped logical copies of all seven
      pool leaves (`performance_model.sharded_gather_bytes_per_token`);
    * ``sharded_island_fused``  — the fused island: two kernel passes over
      owned-active blocks + the selected-block fetch, two psums
      (`performance_model.sharded_fused_bytes_per_token`).

    ``gate=True`` (the --smoke CI run) hard-fails if (a) the per-shard
    bytes-moved model ratio falls under 10× at a 4-way shard, or (b) the
    fused tick's measured wall time regresses past the legacy tick with the
    same ratio+absolute-delta noise guard the paged gate uses.
    """
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh
    from repro.core import (SalcaParams, empty_paged_cache, prefill_cache,
                            prefill_into_pages)
    from repro.core.performance_model import (
        sharded_fused_bytes_per_token, sharded_gather_bytes_per_token)
    from repro.core.sp_decode import sp_salca_decode_paged

    bsz, kv, hd = 64, 2, 128
    params = SalcaParams(k=k, k_cap=max(((int(k * 1.25) + 127) // 128) * 128,
                                        128), pool_window=7)
    mb_slot = 2 * n // bsz                 # per-slot logical capacity: 2n
    num_blocks = 4 * n // bsz
    kk = jnp.asarray(rng.normal(size=(1, n, kv, hd)), jnp.float32)
    vv = jnp.asarray(rng.normal(size=(1, n, kv, hd)), jnp.float32)
    dense = prefill_cache(kk, vv, max_seq=mb_slot * bsz, params=params)
    pool = empty_paged_cache(num_blocks, bsz, 1, mb_slot, kv, hd,
                             params.r(hd))
    need = n // bsz
    pages = np.full(mb_slot, -1, np.int32)
    pages[:need] = rng.choice(num_blocks, need, replace=False)
    pool = prefill_into_pages(pool, dense, 0, jnp.asarray(pages))
    q = jnp.asarray(rng.normal(size=(1, 2 * kv, hd)), jnp.float32)
    mesh = make_mesh((1,), ("seq",))

    def island(fused):
        def f(q_, p_):
            return sp_salca_decode_paged(q_, p_, params, "seq", fused=fused)
        return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P(), P()),
                                     out_specs=P(), check_vma=False))

    leg = sharded_gather_bytes_per_token(
        n=n, d=hd, kv_heads=kv, groups=2, s_f=0.5, retention=k / n,
        n_shards=4, block_size=bsz, max_blocks=mb_slot, slots=1)
    fus = sharded_fused_bytes_per_token(
        n=n, d=hd, kv_heads=kv, groups=2, s_f=0.5, retention=k / n,
        n_shards=4, block_size=bsz)
    ratio = leg.local_total / max(fus.local_total, 1e-9)
    model = {
        "sharded_island_legacy":
            f"shard4:{leg.local_total/1e6:.2f}MB_capacity_copies",
        "sharded_island_fused":
            f"shard4:{fus.local_total/1e3:.1f}KB_owned+selected"
            f"({ratio:.0f}x_less)",
    }
    rows, us = [], {}
    for name, fused in (("sharded_island_legacy", False),
                        ("sharded_island_fused", True)):
        us[name] = time_call(island(fused), q, pool)
        rows.append(f"kernel_bench,{name},{us[name]:.1f},{model[name]}")
    if gate:
        if ratio < 10.0:
            raise RuntimeError(
                f"sharded fused bytes-moved model ratio {ratio:.1f}x < 10x "
                f"at n={n} — the fused island's traffic model regressed")
        if (us["sharded_island_fused"] > 1.5 * us["sharded_island_legacy"]
                and us["sharded_island_fused"]
                > us["sharded_island_legacy"] + 2000):
            raise RuntimeError(
                f"fused sharded tick ({us['sharded_island_fused']:.0f}us) is "
                f"slower than the legacy gather tick "
                f"({us['sharded_island_legacy']:.0f}us) — the island fusion "
                f"regressed")
    return rows


def run(n: int = 32768, bh: int = 8, r: int = 64, k: int = 1024,
        paged_gate: bool = False) -> list[str]:
    rng = np.random.default_rng(0)
    rows = ["kernel_bench,name,us_per_call,derived"]

    kf = jnp.asarray(rng.normal(size=(bh, n, r)), jnp.float32)
    k2 = qz.quantize_key_features(kf)
    words = qz.pack2bit(k2.codes)
    qf = jnp.asarray(rng.normal(size=(bh, 4, r)), jnp.float32)
    q3 = qz.quantize_query_features(qf)
    f = jax.jit(score_estimate_ref)
    us = time_call(f, q3.codes, q3.scale, words, k2.scale, k2.zero)
    bytes_read = words.size * 4 + k2.scale.size * 8
    rows.append(f"kernel_bench,score_est,{us:.1f},{bytes_read/us/1e3:.2f}GB/s")

    bins = jnp.asarray(rng.integers(1, 256, size=(bh, n)), jnp.uint8)
    f = jax.jit(lambda b: histogram_topk(b, k, k_cap=int(k * 1.25) // 128 * 128))
    us = time_call(f, bins)
    rows.append(f"kernel_bench,hist_topk,{us:.1f},{bins.size/us/1e3:.2f}Gelem/s")

    f = jax.jit(lambda b: maxpool1d_reuse(b, 7))
    us = time_call(f, bins)
    rows.append(f"kernel_bench,maxpool_w7,{us:.1f},{bins.size/us/1e3:.2f}Gelem/s")

    c = int(k * 1.25) // 128 * 128
    kc = jnp.asarray(rng.integers(-127, 128, size=(bh, c, 128)), jnp.int8)
    vc = jnp.asarray(rng.integers(-127, 128, size=(bh, c, 128)), jnp.int8)
    ks = jnp.asarray(rng.random((bh, c)), jnp.float32)
    vs = jnp.asarray(rng.random((bh, c)), jnp.float32)
    mask = jnp.ones((bh, c), bool)
    qd = jnp.asarray(rng.normal(size=(bh, 4, 128)), jnp.float32)
    f = jax.jit(sparse_flash_decode_ref)
    us = time_call(f, qd, kc, ks, vc, vs, mask)
    rows.append(f"kernel_bench,flash_decode,{us:.1f},{(kc.size+vc.size)/us/1e3:.2f}GB/s")

    # end-to-end salca decode step vs dense decode (XLA, host CPU)
    from repro.core import SalcaParams, prefill_cache, salca_decode_attention
    from repro.core.attention import dense_decode_from_cache
    B, T, H, KV, HD = 1, n, 8, 8, 128
    kk = jnp.asarray(rng.normal(size=(B, T, KV, HD)), jnp.float32)
    vv = jnp.asarray(rng.normal(size=(B, T, KV, HD)), jnp.float32)
    params = SalcaParams.for_seq(T, retention=0.05)
    cache = prefill_cache(kk, vv, max_seq=T, params=params)
    q = jnp.asarray(rng.normal(size=(B, H, HD)), jnp.float32)
    f_salca = jax.jit(lambda q, c: salca_decode_attention(q, c, params))
    f_dense = jax.jit(dense_decode_from_cache)
    us_s = time_call(f_salca, q, cache)
    us_d = time_call(f_dense, q, cache)
    rows.append(f"kernel_bench,salca_decode_e2e,{us_s:.1f},{us_d/us_s:.2f}x_vs_dense")
    rows.append(f"kernel_bench,dense_decode_e2e,{us_d:.1f},1.00x")

    # paged decode tick: PR 3 pool-wide gather vs the paged-native fused path
    # (paged_gate=True — the --smoke CI run — hard-fails if the fused tick
    # regresses past the pool-wide gather tick)
    rows.extend(_paged_decode_rows(rng, n=min(n, 4096), k=k, gate=paged_gate))
    # sharded island tick: legacy capacity-shaped gather vs the fully-
    # pipelined fused island (paged_gate=True also hard-fails if the model
    # bytes ratio drops under 10x or the fused tick regresses past legacy)
    rows.extend(_sharded_decode_rows(rng, n=min(n, 2048), k=min(k, 512),
                                     gate=paged_gate))
    return rows


def main() -> None:
    for row in run():
        print(row)


if __name__ == "__main__":
    main()
