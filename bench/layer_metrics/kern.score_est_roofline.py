"""kern.score_est_roofline: the paged scoring kernel's share of its roofline
in the traced span — least time for the 2-bit features of every active
slot's real context, one call per layer and tick, over the kernel's summed
device time, in %."""

from harness.roofline import score_est_need


def read(run):
    share = run.kernel_share("score_est", score_est_need)
    return None if share is None else 100.0 * share[0]
