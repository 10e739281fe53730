"""Time to valid work of the judged requests of a window, in ms."""

from __future__ import annotations

import math

import percentile


def samples_ms(w) -> list:
    """One sample per judged request intended in the window: reply minus
    intended send for valid work, +inf for anything else."""
    out = []
    for r in w.judged():
        if r["done"] is not None and w.valid(r):
            out.append((r["done"] - r["intended"]) * 1e3)
        else:
            out.append(math.inf)
    return out


def percentile_ms(w, q: float):
    xs = samples_ms(w)
    if not xs:
        return None
    v = percentile.nearest_rank(xs, q)
    if math.isinf(v):
        # The tail reaches a request that never got valid work (such a run
        # is not correct): report the longest a reply may take.
        timeouts = [p.get("timeout", 30) for p in w.traffic.get("parts", [w.traffic])]
        return (max(timeouts) + 60.0) * 1e3
    return v
