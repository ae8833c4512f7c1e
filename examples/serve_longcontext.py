"""End-to-end serving driver: batched requests through prefill + Salca decode.

    PYTHONPATH=src python examples/serve_longcontext.py [--arch qwen3-0.6b]

Runs the reduced config of a real arch through the ServingEngine
(continuous batching: slots admit queued requests as sequences finish) and
reports the phase split the paper's Fig. 1 is about — prefill vs decode
time — plus per-request latency.

``--shards N`` forces N host devices and shards the paged block pool across
them (each device owns ``--blocks-per-shard`` physical blocks). The demo
then runs one long-context request twice: against a 1-shard pool (the same
per-device budget — it overflows) and against the N-shard pool (the blocks
span devices and the request completes) — the capacity argument for
sequence-sharded page pools. Argument parsing happens before jax imports
because the XLA device-count flag must precede jax initialization.
"""

import argparse
import os


def parse_args():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--requests", type=int, default=5)
    ap.add_argument("--prompt-len", type=int, default=192)
    ap.add_argument("--new-tokens", type=int, default=12)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--paged", action="store_true",
                    help="paged block-pool KV cache instead of dense slots")
    ap.add_argument("--block-size", type=int, default=32)
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="pool size in blocks (0 = dense-equivalent budget)")
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="refcounted copy-on-write prefix sharing (paged "
                         "only); requests share a system prompt below")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="tokens of shared system prompt per request "
                         "(default: 75%% of prompt-len when sharing)")
    ap.add_argument("--kv-dtype", choices=("fp16", "int8", "int4"),
                    default=None,
                    help="block pool exact-K/V storage precision (paged "
                         "only; in-kernel dequant)")
    ap.add_argument("--host-spill", action="store_true",
                    help="tiered-KV demo (implies --paged): a context whose "
                         "block footprint overflows an fp16 pool completes "
                         "on an int8 pool of the same byte budget with cold "
                         "blocks spilled to host memory")
    ap.add_argument("--shards", type=int, default=1,
                    help="shard the paged block pool across N forced host "
                         "devices (implies --paged); demos a context that "
                         "overflows 1 shard but completes on N")
    ap.add_argument("--blocks-per-shard", type=int, default=8,
                    help="per-device pool slice for the --shards demo")
    return ap


def main() -> None:
    ap = parse_args()
    args = ap.parse_args()
    if args.shards > 1:
        # Must land before jax initializes (hence before the imports below).
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.shards}")

    import dataclasses
    import time

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models import get_model
    from repro.runtime.serve import Request, ServingEngine

    cfg = get_config(args.arch).reduced()
    if args.prefix_sharing:
        # Static weight-derived heavy channels: the request-independent set
        # that lets divergent-tail requests alias feature blocks.
        cfg = dataclasses.replace(cfg, salca_static_channels=True)
    print(f"arch={args.arch} (reduced: {cfg.num_layers}L d={cfg.d_model}, "
          f"salca={'on' if cfg.salca else 'off — ' + cfg.family})")
    api = get_model(cfg)
    t0 = time.time()
    params = api.init(jax.random.PRNGKey(0))
    print(f"init {time.time()-t0:.1f}s, params "
          f"{sum(x.size for x in jax.tree.leaves(params))/1e6:.1f}M")

    if args.shards > 1:
        _sharded_demo(args, cfg, params)
        return
    if args.host_spill:
        _spill_demo(args, cfg, params)
        return

    max_seq = ((args.prompt_len + args.new_tokens + 127) // 128) * 128
    engine = ServingEngine(cfg, params, max_seq=max_seq, slots=args.slots,
                           paged=args.paged, block_size=args.block_size,
                           num_blocks=args.num_blocks or None,
                           prefix_sharing=args.prefix_sharing,
                           kv_pool_dtype=args.kv_dtype)
    rng = np.random.default_rng(0)
    shared_len = 0
    shared = np.zeros((0,), np.int32)
    if args.prefix_sharing:
        shared_len = args.shared_prefix or (3 * args.prompt_len) // 4
        if not 0 < shared_len < args.prompt_len:
            ap.error(f"--shared-prefix {shared_len} must be in "
                     f"(0, prompt-len {args.prompt_len}) — requests need a "
                     "divergent tail")
        shared = rng.integers(0, cfg.vocab_size, shared_len).astype(np.int32)
    for i in range(args.requests):
        tail = rng.integers(0, cfg.vocab_size,
                            args.prompt_len - shared_len).astype(np.int32)
        engine.submit(Request(
            rid=i, prompt=np.concatenate([shared, tail]),
            max_new_tokens=args.new_tokens))
    stats = engine.run()
    s = stats.summary()
    print(f"completed {s['completed']} requests | prefill {s['prefill_s']}s "
          f"decode {s['decode_s']}s over {s['ticks']} ticks "
          f"({s['decode_calls']} fused decode calls, "
          f"{s['decode_ms_per_tick']} ms/tick, "
          f"{s['decode_ms_per_step']} ms/token)")
    print(f"latency: mean TTFT {s['mean_ttft_s']}s "
          f"(queue wait {s['mean_queue_wait_s']}s)")
    if args.paged:
        print(f"block pool: {s['peak_blocks_in_use']}/{s['block_pool_size']} "
              f"blocks at peak (utilization {s['block_utilization']}), "
              f"{s['overflows']} overflows")
    if args.prefix_sharing:
        print(f"prefix sharing: {s['shared_blocks']} blocks shared across "
              f"{s['prefix_hits']} hits, {s['cow_copies']} CoW copies, "
              f"{s['memory_saved_tokens']} tokens of HBM saved")
    print("decode/(prefill+decode) time share: "
          f"{s['decode_s']/(s['prefill_s']+s['decode_s']):.1%} "
          "(the paper's Fig.1 regime: decode dominates long-context serving)")


def _spill_demo(args, cfg, params) -> None:
    """Tiered KV memory at a fixed HBM byte budget: the same long-context
    request is rejected by an fp16 pool, rejected by a plain int8 pool
    (still one block short), and COMPLETES on the int8 pool once cold
    blocks may spill to the host tier (wave admission + histogram-driven
    demote/promote)."""
    import numpy as np

    from repro.core import empty_paged_cache
    from repro.core.cache import block_data_bytes
    from repro.models.blocks import salca_params_for
    from repro.runtime.serve import Request, ServingEngine

    bs = args.block_size
    need = 7                                    # request lifetime in blocks
    prompt_len = need * bs - args.new_tokens
    max_seq = ((prompt_len + args.new_tokens + 127) // 128) * 128
    r = salca_params_for(cfg, max_seq).r(cfg.resolved_head_dim)

    def bb(dt):
        return block_data_bytes(empty_paged_cache(
            1, bs, 1, max_seq // bs, cfg.num_kv_heads, cfg.resolved_head_dim,
            r, kv_pool_dtype=dt))

    budget = 4 * bb("fp16")                     # an fp16 pool of 4 blocks
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
    want_dt = args.kv_dtype or "int8"
    print(f"\ntiered-KV demo: {prompt_len}-token context needs {need} "
          f"blocks x {bs} tokens; HBM budget {budget} B per layer "
          f"(= 4 fp16 blocks)")
    for tag, dt, spill in (("fp16", "fp16", False), (want_dt, want_dt, False),
                           (f"{want_dt}+spill", want_dt, True)):
        blocks = int(budget // bb(dt))
        engine = ServingEngine(cfg, params, max_seq=max_seq, slots=1,
                               paged=True, block_size=bs, num_blocks=blocks,
                               kv_pool_dtype=dt, host_spill=spill)
        req = Request(rid=0, prompt=prompt.copy(),
                      max_new_tokens=args.new_tokens)
        try:
            engine.submit(req)
        except ValueError as e:                 # pool can never hold it
            print(f"  {tag}: pool {blocks} blocks — rejected at submit ({e})")
            continue
        st = engine.run()
        s = st.summary()
        line = (f"  {tag}: pool {blocks} blocks — "
                f"stop_reason={req.stop_reason}, "
                f"{len(req.output)}/{args.new_tokens} tokens, "
                f"{s['overflows']} overflows")
        if spill:
            line += (f", {s['demotions']} demotions / {s['promotions']} "
                     f"promotions, peak cold {s['peak_cold_blocks']} blocks, "
                     f"{s['pcie_bytes']} PCIe bytes")
        print(line)
    print("  → the byte budget that rejects the request at fp16 (and still "
          "at int8) serves it once rarely-selected blocks demote to host "
          "memory and resurrect on demand.")


def _sharded_demo(args, cfg, params) -> None:
    """One long-context request vs a fixed per-device pool: overflows on a
    1-shard pool, completes when the block pool spans --shards devices."""
    import jax
    import numpy as np

    from repro.launch.mesh import make_mesh
    from repro.models.blocks import DecodeCtx
    from repro.runtime.serve import Request, ServingEngine

    bs = args.block_size
    per_shard = args.blocks_per_shard
    # A context needing ~2 shard-slices of blocks: too big for one device's
    # pool, comfortable across args.shards of them.
    prompt_len = 2 * per_shard * bs - args.new_tokens
    max_seq = ((prompt_len + args.new_tokens + 127) // 128) * 128
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
    print(f"\nsharded-pool demo: {prompt_len}-token context, "
          f"{per_shard} blocks x {bs} tokens per device "
          f"({len(jax.devices())} forced host devices)")

    for shards in (1, args.shards):
        ctx = None
        if shards > 1:
            mesh = make_mesh((shards,), ("seq",))
            ctx = DecodeCtx(axis="seq", mesh=mesh)
        engine = ServingEngine(cfg, params, max_seq=max_seq, slots=1,
                               ctx=ctx, paged=True, block_size=bs,
                               num_blocks=shards * per_shard)
        req = Request(rid=0, prompt=prompt.copy(),
                      max_new_tokens=args.new_tokens)
        try:
            engine.submit(req)
        except ValueError as e:                 # pool can never hold it
            print(f"  shards={shards}: pool {shards * per_shard} blocks — "
                  f"rejected at submit ({e})")
            continue
        st = engine.run()
        s = st.summary()
        print(f"  shards={shards}: pool {shards * per_shard} blocks — "
              f"stop_reason={req.stop_reason}, "
              f"{len(req.output)}/{args.new_tokens} tokens, "
              f"peak blocks {s['peak_blocks_in_use']}"
              + (f", hottest shard {s['peak_shard_blocks_in_use']}"
                 f"/{per_shard}" if shards > 1 else ""))
    print("  → the same per-device budget that overflows one device "
          "completes when the page tables resolve across the mesh.")


if __name__ == "__main__":
    main()
