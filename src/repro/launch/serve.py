"""Serving launcher: batched long-context decoding with Salca.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --local \
        --requests 4 --prompt-len 192 --new-tokens 16 \
        --paged --block-size 32 --static-channels --prefix-cache \
        --prefill-chunk 64
"""

from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models import get_model
from repro.runtime.serve import Request, ServingEngine


def build_engine(cfg: ModelConfig, params, *, slots: int, max_seq: int,
                 paged: bool = False, block_size: int = 32,
                 num_blocks: int | None = None, prefix_cache: bool = False,
                 prefill_chunk: int | None = None, **engine_kw) -> ServingEngine:
    """The serving engine as the launcher builds it, for ``cfg`` as given.

    ``prefix_cache`` turns on prefix sharing too (the cache retains shared
    blocks). Chunked prefill needs ``cfg.salca_static_channels`` (the
    engine refuses it otherwise), and the prefix cache admits with zero
    prefill only with it. ``engine_kw`` passes any other `ServingEngine`
    option (``ctx``, ``fused_decode``, ...)."""
    return ServingEngine(cfg, params, max_seq=max_seq, slots=slots,
                         paged=paged, block_size=block_size,
                         num_blocks=num_blocks, prefix_sharing=prefix_cache,
                         prefix_cache=prefix_cache,
                         prefill_chunk=prefill_chunk, **engine_kw)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--local", action="store_true",
                    help="the arch's reduced (CPU-sized) config")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=192)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=0)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--paged", action="store_true",
                    help="paged block pool (the main serving path)")
    ap.add_argument("--block-size", type=int, default=32)
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="pool size in blocks (default: slots x max_seq)")
    ap.add_argument("--static-channels", action="store_true",
                    help="request-independent heavy channels "
                         "(cfg.salca_static_channels; chunked prefill needs it)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="prefix sharing + persistent prefix cache (paged)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked-prefill budget in tokens (paged)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.local:
        cfg = cfg.reduced()
    if args.static_channels:
        cfg = dataclasses.replace(cfg, salca_static_channels=True)
    max_seq = args.max_seq or (args.prompt_len + args.new_tokens + 8)
    # round up for clean sharding
    max_seq = ((max_seq + 127) // 128) * 128

    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(args.seed))
    engine = build_engine(cfg, params, slots=args.slots, max_seq=max_seq,
                          paged=args.paged, block_size=args.block_size,
                          num_blocks=args.num_blocks or None,
                          prefix_cache=args.prefix_cache,
                          prefill_chunk=args.prefill_chunk or None)
    rng = np.random.default_rng(args.seed)
    for i in range(args.requests):
        prompt = rng.integers(0, cfg.vocab_size,
                              size=(args.prompt_len,)).astype(np.int32)
        engine.submit(Request(rid=i, prompt=prompt,
                              max_new_tokens=args.new_tokens))
    stats = engine.run()
    print("serve stats:", stats.summary())


if __name__ == "__main__":
    main()
