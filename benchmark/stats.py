"""The benchmark's arithmetic on samples: one definition of a
percentile for every metric and every PR."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) by the nearest-rank rule on the
    sorted sample: the smallest value with at least q% of the sample at
    or below it.  No interpolation, so the result is a value that was
    measured."""
    if not values:
        raise ValueError('percentile of an empty sample')
    xs = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]


def summary(values) -> dict:
    """count, median, upper percentiles and maximum — what every
    latency prints."""
    out = {'n': len(values)}
    for q in (50, 75, 90, 95, 99):
        out['p%d' % q] = percentile(values, q) if values else None
    out['max'] = max(values) if values else None
    return out
