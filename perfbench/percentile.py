"""Exact percentiles of raw samples (nearest rank), misses counted as +inf."""

from __future__ import annotations

import math


def nearest_rank(samples, q: float) -> float:
    """The smallest sample with at least ``q`` percent of samples at or below
    it. ``samples`` may hold ``math.inf`` for requests that never got valid
    work: they sort last, so a tail that reaches them reads inf."""
    xs = sorted(samples)
    if not xs:
        raise ValueError("no samples")
    if not 0 < q <= 100:
        raise ValueError(f"percentile {q} outside (0, 100]")
    rank = math.ceil(q / 100.0 * len(xs))
    return xs[rank - 1]
