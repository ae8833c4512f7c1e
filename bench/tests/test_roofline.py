"""Byte and operation counts of the decode step, and the peaks table."""

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import roofline  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]


def sizes(max_seq=16384):
    config = json.loads((BENCH / "configs" / "qwen3-0.6b.json").read_text())
    return roofline.Sizes.of(config, max_seq)


def test_pool_row_sizes_match_the_stated_layout():
    z = sizes()
    assert z.heavy == 64 and z.top_k == 819
    assert roofline.feature_bytes(z) == 24        # 64 x 2 bits + f32 scale, zero
    assert roofline.kv_row_bytes(z) == 264        # int8 K, V rows + 2 f32 scales


def test_top_k_follows_the_configured_retention_and_cap():
    assert sizes(32768).top_k == 1638
    assert sizes(128 * 1024).top_k == 4096         # salca_max_k
    assert sizes(1024).top_k == 128                # floor of 128


def test_kernel_needs_scale_with_context_and_k():
    z = sizes()
    ctx = [12288, 15360]
    assert roofline.score_est_bytes(z, ctx) == sum(ctx) * 8 * 24
    assert roofline.flash_decode_bytes(z, ctx) == 2 * 819 * 8 * 264
    assert roofline.flash_decode_bytes(z, [100]) == 100 * 8 * 264


def test_decode_step_is_bytes_bound_on_v5e():
    peak = roofline.peak_for("TPU v5 lite")
    z = sizes()
    ctx = [12288, 13312, 14336, 15360]
    t, bound = roofline.least_time(roofline.step_bytes(z, ctx),
                                   roofline.step_flops(z, ctx), peak)
    assert bound == "bytes"
    # ~1.2 GB of weights dominate: about 1.6 ms at 819 GB/s
    assert 1.3e-3 < t < 3e-3


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.peak_for("TPU v9 imaginary")
