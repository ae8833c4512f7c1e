"""Whether what the timed path served is correct.

After the window, a sample of the requests it served (drawn from the seed,
always with the longest) goes through the configuration's plain reference:
for every served token, the reference's logits after the prompt and the
tokens served before it. Each served token's gap is the reference's best
logit minus the served token's. Served tokens are greedy, so a program that
computes what the reference computes serves the reference's best token up
to its precision. The configuration's ``check`` entry names the statistic of
the gaps that is compared (``max``, ``mean``, ``p<q>`` the q-th percentile,
or ``over<x>`` the share of gaps above x) and its limit, set between the
program's readings and a lower-precision control's.
"""

from __future__ import annotations

import numpy as np

from harness.stats import percentile

BAD_STOPS = ("overflow", "error", "rejected", "deadline", "cancelled")


def pick(requests, n: int, seed: int) -> list:
    """``n`` of the requests that served tokens: the longest (prompt plus
    served) and the rest drawn from ``seed``."""
    served = [r for r in requests if r.output]
    if not served:
        return []
    served.sort(key=lambda r: r.rid)
    longest = max(served, key=lambda r: (len(r.prompt) + len(r.output), r.rid))
    rest = [r for r in served if r is not longest]
    rng = np.random.default_rng(int(seed))
    take = rng.permutation(len(rest))[:max(n - 1, 0)]
    return [longest] + [rest[i] for i in sorted(take)]


def gaps(ref_logits, served) -> np.ndarray:
    """Per served token: the reference's best logit minus the served one's."""
    lg = np.asarray(ref_logits, np.float64)
    served = np.asarray(served)
    return lg.max(-1) - lg[np.arange(len(served)), served]


def summary(values, statistic: str) -> float:
    """``max``, ``mean``, ``p<q>`` or ``over<x>`` of ``values``; infinity
    for none, so an empty sample never passes."""
    xs = [float(v) for v in values]
    if not xs:
        return float("inf")
    if statistic == "max":
        return max(xs)
    if statistic == "mean":
        return sum(xs) / len(xs)
    if statistic.startswith("over"):
        over = float(statistic[4:])
        return sum(1 for x in xs if x > over) / len(xs)
    if statistic.startswith("p"):
        return percentile(xs, float(statistic[1:]))
    raise ValueError(f"unknown gap statistic {statistic!r}")


def verdict(gap_values, check: dict, failed: int) -> tuple[bool, dict]:
    """``correct`` and the numbers compared, each with its limit."""
    stat = check["gap_statistic"]
    value = summary(gap_values, stat)
    limit = float(check["gap_limit"])
    checked = {f"logit_gap_{stat}": {"value": value, "limit": limit},
               "failed_requests": {"value": failed, "limit": 0}}
    return bool(value <= limit and failed == 0), checked


def served_gaps(reference, params, config: dict, max_seq: int, sample) -> np.ndarray:
    """Gaps of every served token of every sampled request."""
    out = []
    for r in sample:
        lg = reference.logits(params, config, max_seq, r.prompt, r.output)
        out.append(gaps(lg, r.output))
    return np.concatenate(out) if out else np.zeros(0)


def readings(reference, params, config: dict, max_seq: int, sample,
             precision: str = "fp8") -> tuple[np.ndarray, np.ndarray]:
    """The two readings a limit is set from, at the same positions: the
    served tokens' gaps, and the gaps of the tokens a lower-precision
    reference (the control) puts first."""
    served, control = [], []
    for r in sample:
        ref = np.asarray(reference.logits(params, config, max_seq, r.prompt,
                                          r.output), np.float64)
        low = np.asarray(reference.logits(params, config, max_seq, r.prompt,
                                          r.output, precision=precision))
        served.append(gaps(ref, r.output))
        control.append(gaps(ref, low.argmax(-1)))
    return np.concatenate(served), np.concatenate(control)
