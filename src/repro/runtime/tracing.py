"""Host spans of the serving engine, on the profiler's clock.

Every phase of a scheduler iteration runs inside one span: ``serve.admit``
(admission: prefill chunk, zero-prefill adoption or monolithic prefill),
then the decode tick's ``serve.tick.prep`` (expiry, promotion, growth),
``serve.tick.dispatch`` (input uploads and the jitted call),
``serve.tick.wait`` (the blocking read of the tick's tokens) and
``serve.tick.post`` (per-slot bookkeeping, spill policy, audits).

A span does two things. It opens a `jax.profiler.TraceAnnotation`, so a
device trace shows it on the host thread beside the ops it waits for; with
no profiler running that is one cheap object. And it adds its duration, one
`time.perf_counter` pair, to a float counter of `ServeStats` named after it
(``serve.tick.prep`` → ``tick_prep_s``), which a benchmark reads as a delta
over its window.
"""

from __future__ import annotations

import time
from contextlib import contextmanager

import jax


def counter_of(name: str) -> str:
    """The `ServeStats` field a span adds to: ``serve.admit`` → ``admit_s``."""
    return name.removeprefix("serve.").replace(".", "_") + "_s"


class Timing:
    """What a span yields: its duration, set when it closes."""

    __slots__ = ("seconds",)

    def __init__(self):
        self.seconds = 0.0


class HostSpans:
    """The spans of one engine. Besides its `ServeStats` counter, each span
    adds its duration to `phases` (span name → seconds), which the engine
    clears at the start of every scheduler iteration, so a slow tick can be
    blamed on its slowest phase."""

    def __init__(self, stats):
        self.stats = stats
        self.phases: dict[str, float] = {}

    @contextmanager
    def __call__(self, name: str, **ids):
        """``ids`` (a request id, say) ride on the trace event."""
        field = counter_of(name)
        timing = Timing()
        with jax.profiler.TraceAnnotation(name, **ids):
            t0 = time.perf_counter()
            try:
                yield timing
            finally:
                timing.seconds = time.perf_counter() - t0
                setattr(self.stats, field,
                        getattr(self.stats, field) + timing.seconds)
                self.phases[name] = self.phases.get(name, 0.0) + timing.seconds
