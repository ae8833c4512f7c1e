"""Arithmetic of the client-side metrics: gaps, percentiles, deltas.

A copy of the program's sound bookkeeping (the preemption-aware gap
accounting of `benchmarks/serving_throughput.py`), kept here so a
later change to the program cannot change how the benchmark counts. Times
are host wall-clock seconds (``time.time()``), the clock the engine stamps
tokens with.
"""

from __future__ import annotations

import math


def percentile(values, q: float) -> float | None:
    """The ``q``-th percentile (0..100), linear between closest ranks, or
    None for no values."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def gaps_in_window(token_times, t0: float, t1: float) -> list[float]:
    """Gaps between consecutive fresh tokens of one request whose later
    token falls in [t0, t1]. The engine stamps only fresh tokens (a
    preempted request's replayed tokens carry no stamp), so a gap that spans
    a prefill chunk, a preemption or a stall is counted whole."""
    return [b - a for a, b in zip(token_times, token_times[1:])
            if t0 <= b <= t1]


def tokens_in_window(token_times, t0: float, t1: float) -> int:
    return sum(1 for t in token_times if t0 <= t <= t1)


def deltas(before: dict, after: dict) -> dict:
    """Counter increments over the window (numeric fields only)."""
    return {k: after[k] - before[k] for k in after
            if isinstance(after[k], (int, float)) and not isinstance(after[k], bool)}
