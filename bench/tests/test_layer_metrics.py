"""Per-layer readers on a synthetic run: what each reads, and None where the
program under test has nothing for it to read."""

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import spec  # noqa: E402
from harness.record import Run  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]


def reader(name):
    return spec.metric_reader(SimpleNamespace(bench=BENCH), {"name": name},
                              "layer")


def run_with(before, after):
    return Run(cell=None, seconds=30.0, t_start=0.0, window=None, ticks=[],
               before=before, after=after, sizes=None, peak=None, compiles=0,
               peak_bytes=0)


SPANS = {"admit_s": 0.02, "tick_prep_s": 0.01, "tick_dispatch_s": 0.03,
         "tick_wait_s": 4.0, "tick_post_s": 0.04}


def test_tick_overhead_is_host_span_time_per_tick_without_the_wait():
    before = {"ticks": 5, "decode_s": 1.0, **{k: 1.0 for k in SPANS}}
    after = {"ticks": 15, "decode_s": 5.03,
             **{k: 1.0 + v for k, v in SPANS.items()}}
    value = reader("host.tick_overhead_ms")(run_with(before, after))
    assert value == pytest.approx(1e3 * (0.02 + 0.01 + 0.03 + 0.04) / 10)


def test_tick_overhead_is_none_without_spans_or_ticks():
    """A program with no host spans (the parent of the change that added
    them) and a window without ticks read nothing."""
    read = reader("host.tick_overhead_ms")
    old = {"ticks": 3, "decode_s": 1.0, "prefill_s": 0.5}
    assert read(run_with(old, {**old, "ticks": 9})) is None
    idle = {"ticks": 3, **SPANS}
    assert read(run_with(idle, dict(idle))) is None
