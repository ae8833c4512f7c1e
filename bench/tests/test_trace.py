"""Trace reduction: busy time as a union of op intervals, kernel time by the
name table, the longest idle gaps named by what the host was doing."""

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import trace  # noqa: E402

NAMES = {"kernels": {"score_est": {"match": {"name": "^score"}},
                     "flash_decode": {"match": {"name": "^flash",
                                                "source": "flash_decode"}}}}


def form():
    ms = 1_000_000
    return {
        "devices": {"/device:TPU:0": [
            ["score.1", 0 * ms, 2 * ms, {}],
            ["fusion.3", 1 * ms, 2 * ms, {}],          # overlaps score.1
            ["flash.2", 5 * ms, 1 * ms, {"source": "kernels/flash_decode/kernel.py"}],
            ["flash.9", 6 * ms, 1 * ms, {"source": "elsewhere"}],
            ["score.4", 9 * ms, 1 * ms, {}],
            ["fusion.8", 12 * ms, 5 * ms, {}],         # starts outside the window
        ]},
        "host": [["PjitFunction(tick)", 0, 10 * ms],
                 ["PjitFunction(chunk)", 3 * ms, 1 * ms]],
        "window_ns": [0, 10 * ms],
    }


def test_busy_is_the_union_of_op_intervals_in_the_window():
    r = trace.reduce(form(), NAMES)
    assert r.window_s == pytest.approx(0.010)
    # [0,3) + [5,7) + [9,10) = 6 ms
    assert r.busy_s == pytest.approx(0.006)
    assert r.idle_share == pytest.approx(0.4)


def test_kernels_match_their_name_and_stats():
    r = trace.reduce(form(), NAMES)
    assert r.kernel_s == {"score_est": pytest.approx(0.003),
                          "flash_decode": pytest.approx(0.001)}
    assert r.kernel_calls == {"score_est": 2, "flash_decode": 1}


def test_top_ops_by_instruction_and_gaps_name_the_host():
    f = form()
    f["devices"]["/device:TPU:0"][1][0] = "%fusion.3 = f32[8] fusion(f32[8] %x)"
    r = trace.reduce(f, NAMES)
    assert dict(r.device_ops[:2]) == {"score.1": pytest.approx(0.002),
                                      "fusion.3": pytest.approx(0.002)}
    gaps = dict(r.idle_gaps)
    # [3,5) is half in the chunk call (midpoint 4 ms falls in it)
    assert gaps["PjitFunction(chunk)"] == pytest.approx(0.002)
    assert gaps["PjitFunction(tick)"] == pytest.approx(0.002)


def test_busy_is_averaged_over_devices():
    f = form()
    f["devices"]["/device:TPU:1"] = []
    assert trace.reduce(f, NAMES).busy_s == pytest.approx(0.003)


def test_recorded_chip_trace_of_two_decode_ticks():
    """Two whole decode ticks of qwen3-0.6b.decode-16k traced on a v5e
    (28 layers, 4 slots): each tick calls each paged kernel once per layer,
    and the device is busy nearly all the span."""
    import gzip
    path = Path(__file__).resolve().parent / "data" / "decode-16k.trace.json.gz"
    form = json.loads(gzip.open(path).read())
    r = trace.reduce(form, trace.load_names())
    assert r.kernel_calls == {"score_est": 56, "flash_decode": 56}
    assert r.window_s == pytest.approx(0.802087, rel=1e-5)
    assert 0 < r.idle_share < 0.05
    assert r.kernel_s["flash_decode"] > r.kernel_s["score_est"] > 0
    assert r.device_ops[0][0].startswith("while.")
