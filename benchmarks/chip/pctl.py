"""Percentiles, kept with the benchmark so that every PR computes them
the same way."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of the sample at or below it. No interpolation, so a tail is
    always a value that was measured."""
    if not values:
        raise ValueError("percentile of an empty sample")
    s = sorted(values)
    return float(s[max(0, math.ceil(q / 100.0 * len(s)) - 1)])

