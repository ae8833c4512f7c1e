"""device.idle_share: share of the traced span in which no operation ran on
the device (1 - union of op intervals / span), in %."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace.idle_share
