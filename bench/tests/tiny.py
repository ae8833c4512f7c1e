"""A tiny checkout for the harness's CPU tests: BENCHMARK.json with one
closed-loop cell of a 2-layer Qwen3-shaped model, the benchmark's own
metric readers, references and harness, and the program's ``src``.

    root = tiny.make_root(tmp_path)
    run.main([...], root=root, require_chip=False)
"""

from __future__ import annotations

import json
import os
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
CELL = "tiny.decode"

# The share of served tokens whose logit lies more than 0.15 below the
# reference's best (the tiny model's logits spread about a third as wide as
# qwen3-0.6b's): sound tiny runs on the CPU read 0.0077-0.0175 (4 seeds,
# every request sampled), the fp8 control 0.104-0.208 (test_reference.py,
# 4 seeds), the planted faults of test_faults.py 0.46-0.98.
TINY_LIMIT = 0.05


def tiny_config() -> dict:
    c = json.loads((BENCH / "configs" / "qwen3-0.6b.json").read_text())
    sizes = {"hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 2,
             "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
             "vocab_size": 512}
    c.update(sizes)
    c["program"] = {"arch": "qwen3-0.6b", "set": {
        "d_model": 128, "d_ff": 256, "num_layers": 2, "num_heads": 4,
        "num_kv_heads": 2, "head_dim": 32, "vocab_size": 512,
        "salca_static_channels": True, "kv_pool_dtype": "int8",
        "dtype": "bfloat16"}}
    c["serving"] = dict(c["serving"], block_size=16)
    c["check"] = {"sample_requests": 64, "gap_statistic": "over0.15", "gap_limit": TINY_LIMIT}
    return c


TINY_TRAFFIC = {"loop": "closed",
                "engine": {"slots": 2, "max_seq": 256, "num_blocks": 32,
                           "prefill_chunk": 128},
                "contexts": [160, 192], "warm_ticks": 2}


def make_root(tmp: Path, config=None, traffic=None) -> Path:
    """A checkout under ``tmp`` whose BENCHMARK.json has the one tiny cell
    and every metric of the real BENCHMARK.json."""
    root = tmp / "checkout"
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir(parents=True)
    (root / "src").symlink_to(REPO / "src")
    for d in ("e2e_metrics", "layer_metrics", "references", "harness"):
        (root / "bench" / d).symlink_to(BENCH / d)
    for f in ("peaks.json", "kernel_names.json"):
        (root / "bench" / f).symlink_to(BENCH / f)
    (root / "bench" / "configs" / "tiny.json").write_text(
        json.dumps(config or tiny_config()))
    (root / "bench" / "traffic" / "tiny-decode.json").write_text(
        json.dumps(traffic or TINY_TRAFFIC))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": "test",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "test"}]
    spec["workloads"] = [{"name": CELL, "config": "tiny",
                          "traffic": "tiny-decode", "chips": 1, "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root
