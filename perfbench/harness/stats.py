"""Order statistics the benchmark reports. Kept here, not taken from the
program, so that no later PR can change how a tail is read."""

from __future__ import annotations

import math
import statistics


def percentile(values, q):
    """The q-th percentile (0..100) by linear interpolation between the
    closest ranks (numpy's default), on a copy. Empty input: None."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return None
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * (q / 100.0)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def quartile_spread(values):
    """(Q3 - Q1) / median with ``statistics.quantiles(values, n=4)`` — the
    spread the bounds are set from."""
    q1, q2, q3 = statistics.quantiles(list(values), n=4)
    return (q3 - q1) / q2 if q2 else float("inf")
