"""host.tick_overhead_ms: host time per tick in which the host-synchronous
tick leaves the device nothing queued — the engine's admission, tick prep,
dispatch and post spans (ServeStats admit_s + tick_prep_s + tick_dispatch_s
+ tick_post_s, perf_counter pairs) over the window, over its ticks. The
blocking read of the tick's tokens (tick_wait_s) is the device's share and is
left out. None where the program has no such spans."""

SPANS = ("admit_s", "tick_prep_s", "tick_dispatch_s", "tick_post_s")


def read(run):
    ticks = run.delta("ticks")
    if not ticks or any(k not in run.after for k in SPANS):
        return None
    return 1e3 * sum(run.delta(k) for k in SPANS) / ticks
