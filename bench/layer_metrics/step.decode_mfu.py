"""step.decode_mfu: the whole decode step's share of the chip's peak — the
least time each window tick's work could take (weights once, the features of
every active context, k selected K/V rows per slot and kv head, and their
operations; the larger of bytes over HBM bandwidth and operations over peak
FLOP/s) over the time a tick took (the engine's decode time per tick over
the window, as step.decode_ms reads it), in %."""


def read(run):
    least = run.least_step_time()
    ticks = run.delta("ticks")
    if least is None or not ticks:
        return None
    return 100.0 * least[0] / (run.delta("decode_s") / ticks)
