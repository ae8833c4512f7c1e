"""step.decode_ms: the engine's own decode time per tick over the window
(ServeStats.decode_s, a host clock around the dispatch and the blocking read
of the tick's tokens, over ServeStats.ticks)."""


def read(run):
    ticks = run.delta("ticks")
    return 1e3 * run.delta("decode_s") / ticks if ticks else None
