"""Percentiles and spreads, the one way the benchmark takes them."""

import math
import statistics


def percentile(values, q):
    """The ``q``-th percentile (0..100) by the nearest-rank rule on the
    sorted sample: the smallest value with at least ``q`` percent of the
    sample at or below it.  No interpolation, so the answer is always a
    value that was measured."""
    if not values:
        raise ValueError("percentile of an empty sample")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def samples_beyond(values, q):
    """How many samples lie strictly beyond the ``q``-th percentile's
    rank: a percentile with fewer than ten beyond it is close to a
    maximum."""
    n = len(values)
    return n - max(1, math.ceil(q / 100.0 * n)) if n else 0


def median(values):
    return statistics.median(values)


def spread(values):
    """Distance between the first and third quartile, as
    ``statistics.quantiles(values, n=4)`` gives them, as a share of the
    median: the spread the builder's contract sets bounds from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
