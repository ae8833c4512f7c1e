"""The work a decode step needs, and the least time the chip could take.

Byte and operation counts are the algorithm's need, computed from the
configuration's sizes and the contexts each tick served, never from the
program's own model of itself (the Salca byte formulas of
`core/performance_model.py` are copied here with the pool's real storage:
f32 feature factors and f32 K/V scales). Peaks come from ``peaks.json``,
keyed by the device kind JAX reports; an unknown kind is an error.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


@dataclass(frozen=True)
class Peak:
    flops: float            # bf16 FLOP/s
    bytes: float            # HBM bytes/s


def peak_for(device_kind: str, path: Path = PEAKS) -> Peak:
    table = json.loads(path.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    row = table[device_kind]
    return Peak(flops=float(row["bf16_flops"]), bytes=float(row["hbm_bytes_per_s"]))


@dataclass(frozen=True)
class Sizes:
    """What the counts need of a configuration, from its file."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    heavy: int              # heavy channels r per kv head
    top_k: int              # selected tokens per (slot, kv head)
    weight_bytes: int       # bytes per parameter as served

    @classmethod
    def of(cls, config: dict, max_seq: int) -> "Sizes":
        s = config["serving"]
        hd = config["head_dim"]
        r = max(16, int(s["salca_feature_sparsity"] * hd) // 16 * 16)
        k = max(128, min(int(max_seq * s["salca_retention"]),
                         s["salca_max_k"], max_seq))
        return cls(layers=config["num_hidden_layers"],
                   d_model=config["hidden_size"],
                   heads=config["num_attention_heads"],
                   kv_heads=config["num_key_value_heads"], head_dim=hd,
                   d_ff=config["intermediate_size"],
                   vocab=config["vocab_size"],
                   tied=config["tie_word_embeddings"], heavy=r, top_k=k,
                   weight_bytes=2)


def layer_params(z: Sizes) -> int:
    d, hd = z.d_model, z.head_dim
    attn = d * z.heads * hd * 2 + d * z.kv_heads * hd * 2
    return attn + 3 * d * z.d_ff


def head_params(z: Sizes) -> int:
    return z.vocab * z.d_model


# Per token and kv head, in the pool: 2-bit codes of the heavy channels plus
# an f32 scale and zero point; int8 K and V rows with one f32 scale each.
def feature_bytes(z: Sizes) -> int:
    return z.heavy * 2 // 8 + 8


def kv_row_bytes(z: Sizes) -> int:
    return 2 * z.head_dim + 8


def score_est_bytes(z: Sizes, contexts) -> int:
    """One layer's scoring call: the features of every active slot's real
    context."""
    return sum(contexts) * z.kv_heads * feature_bytes(z)


def flash_decode_bytes(z: Sizes, contexts) -> int:
    """One layer's exact-attention call: k selected K/V rows per active slot
    and kv head (fewer where the context is shorter than k)."""
    return sum(min(z.top_k, n) for n in contexts) * z.kv_heads * kv_row_bytes(z)


def flash_decode_flops(z: Sizes, contexts) -> int:
    return sum(min(z.top_k, n) for n in contexts) * z.heads * z.head_dim * 4


def score_est_need(z: Sizes, contexts) -> tuple[int, int]:
    """(bytes, operations) of one layer's scoring call."""
    return score_est_bytes(z, contexts), 2 * sum(contexts) * z.kv_heads * z.heavy


def flash_decode_need(z: Sizes, contexts) -> tuple[int, int]:
    """(bytes, flops) of one layer's exact-attention call."""
    return flash_decode_bytes(z, contexts), flash_decode_flops(z, contexts)


def step_bytes(z: Sizes, contexts) -> int:
    """A whole decode tick: weights once, then per layer the scoring and
    selected-row reads and the appended token's rows."""
    per_layer = (score_est_bytes(z, contexts) + flash_decode_bytes(z, contexts)
                 + len(contexts) * z.kv_heads * (kv_row_bytes(z) + feature_bytes(z)))
    weights = (z.layers * layer_params(z) + head_params(z)) * z.weight_bytes
    return weights + z.layers * per_layer


def step_flops(z: Sizes, contexts) -> int:
    b = len(contexts)
    matmul = 2 * b * (z.layers * layer_params(z) + head_params(z))
    return matmul + z.layers * (score_est_need(z, contexts)[1]
                                + flash_decode_flops(z, contexts))


def least_time(nbytes: float, flops: float, peak: Peak) -> tuple[float, str]:
    """(seconds, which bound) — the larger of the two rooflines."""
    tb, tf = nbytes / peak.bytes, flops / peak.flops
    return (tb, "bytes") if tb >= tf else (tf, "flops")
