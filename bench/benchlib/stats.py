"""Statistics of a measured window, shared by the harness and its tests."""
from __future__ import annotations

import statistics


def percentile(values, q: int) -> float:
    """The q-th percentile (1 <= q <= 99) of every value, linear between
    order statistics (`statistics.quantiles(method="inclusive")`)."""
    vals = list(values)
    if len(vals) < 2:
        if not vals:
            raise ValueError("percentile of no values")
        return float(vals[0])
    return float(statistics.quantiles(vals, n=100, method="inclusive")[q - 1])


def images_per_s(submits) -> float:
    """Frames completed over the whole window: from the first submit's
    start to the last one's end.  `submits` holds (start_s, end_s, n)."""
    if not submits:
        raise ValueError("no submit completed in the window")
    t0 = min(s[0] for s in submits)
    t1 = max(s[1] for s in submits)
    return sum(s[2] for s in submits) / (t1 - t0)


def spread(values) -> float:
    """Distance between the first and third quartile over the median
    (`statistics.quantiles(values, n=4)`)."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
