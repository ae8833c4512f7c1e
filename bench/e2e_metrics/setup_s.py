"""setup_s: process start to the window's open, host clock (loading, weights,
engine, cache fill, warm-up and, on a first run, compilation)."""


def read(run):
    return run.window.t0 - run.t_start
