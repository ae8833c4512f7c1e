"""What one run observed, as the metric readers see it.

Each reader under ``bench/e2e_metrics`` and ``bench/layer_metrics`` takes a
`Run` and returns a number, or None where the run has nothing to read.
"""

from __future__ import annotations

from dataclasses import dataclass

from harness import roofline, stats


@dataclass
class Run:
    cell: object                # harness.spec.Cell
    seconds: float              # --seconds
    t_start: float              # process start, host clock
    window: object              # harness.driver.Window
    ticks: list                 # [(t, [contexts])] of every decode tick
    before: dict                # engine counters at the window's open
    after: dict                 # ... and at its close
    sizes: roofline.Sizes
    peak: roofline.Peak
    compiles: int               # executables built inside the window
    peak_bytes: int             # peak_bytes_in_use, fullest chip
    trace: object = None        # harness.trace.Reduction (--trace 1)

    @property
    def window_s(self) -> float:
        return self.window.t1 - self.window.t0

    def delta(self, key: str) -> float:
        """A counter's increment over the window."""
        return stats.deltas(self.before, self.after)[key]

    def window_ticks(self) -> list:
        return [c for t, c in self.ticks if self.window.t0 <= t <= self.window.t1]

    def traced_ticks(self) -> list:
        if self.window.trace_span is None:
            return []
        a, b = self.window.trace_span
        return [c for t, c in self.ticks if a <= t <= b]

    def tokens(self) -> int:
        return sum(stats.tokens_in_window(r.token_times, self.window.t0,
                                          self.window.t1)
                   for r in self.window.requests)

    def gaps(self) -> list:
        out = []
        for r in self.window.requests:
            out += stats.gaps_in_window(r.token_times, self.window.t0,
                                        self.window.t1)
        return out

    def least_step_time(self) -> tuple[float, str] | None:
        """Mean over the window's ticks of the least time each tick's work
        could take on this chip, and the bound that sets it."""
        ticks = self.window_ticks()
        if not ticks:
            return None
        times = [roofline.least_time(roofline.step_bytes(self.sizes, c),
                                     roofline.step_flops(self.sizes, c),
                                     self.peak) for c in ticks]
        bound = max({b for _, b in times}, key=[b for _, b in times].count)
        return sum(t for t, _ in times) / len(times), bound

    def kernel_share(self, kernel: str, need) -> tuple[float, str] | None:
        """Share of the roofline one kernel reached in the traced span: the
        least time of its calls' work over its summed device time. ``need``
        maps one tick's contexts to (bytes, flops) of one call; each traced
        tick makes one call per layer."""
        if self.trace is None or not self.trace.kernel_s.get(kernel):
            return None
        ticks = self.traced_ticks()
        if not ticks:
            return None
        total, bounds = 0.0, []
        for c in ticks:
            t, b = roofline.least_time(*need(self.sizes, c), self.peak)
            total += t * self.sizes.layers
            bounds.append(b)
        bound = max(set(bounds), key=bounds.count)
        return total / self.trace.kernel_s[kernel], bound
