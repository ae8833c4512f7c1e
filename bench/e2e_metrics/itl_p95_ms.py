"""itl_p95_ms: 95th percentile of every gap between consecutive tokens of one
request that ends inside the window, in milliseconds."""

from harness.stats import percentile


def read(run):
    p = percentile(run.gaps(), 95)
    return None if p is None else 1e3 * p
