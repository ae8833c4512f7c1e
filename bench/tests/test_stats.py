"""The client-side metric arithmetic: percentiles, gaps, deltas."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import stats  # noqa: E402


def test_percentile_matches_linear_interpolation():
    xs = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert stats.percentile(xs, 0) == 1.0
    assert stats.percentile(xs, 50) == 3.0
    assert stats.percentile(xs, 100) == 5.0
    assert stats.percentile(xs, 95) == pytest.approx(4.8)
    assert stats.percentile([], 50) is None


def test_gaps_counted_by_the_window_of_their_later_token():
    times = [0.0, 1.0, 3.0, 3.5, 7.0]
    # gaps ending at 1.0 (before the window), 3.0, 3.5 (inside), 7.0 (after)
    assert stats.gaps_in_window(times, 2.0, 4.0) == [2.0, 0.5]
    assert stats.tokens_in_window(times, 2.0, 4.0) == 2


def test_gap_spanning_a_stall_is_counted_whole():
    # a preempted request's replayed tokens carry no stamp: the next fresh
    # token's gap covers the whole stall
    times = [10.0, 10.5, 19.0]
    assert stats.gaps_in_window(times, 10.0, 20.0) == [0.5, 8.5]


def test_deltas_skip_flags_and_non_numbers():
    before = {"ticks": 3, "decode_s": 1.5, "host_spill": False, "name": "a"}
    after = {"ticks": 10, "decode_s": 4.0, "host_spill": True, "name": "b"}
    assert stats.deltas(before, after) == {"ticks": 7, "decode_s": 2.5}
