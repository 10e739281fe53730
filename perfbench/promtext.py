"""Prometheus text exposition: parse a page, and difference two of them."""

from __future__ import annotations

import re

_LINE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s+(\S+)")


def parse(text: str) -> dict:
    """{series: value}, where series is the name with its label block as
    printed (``dpow_x_sum{engine="jax"}``)."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _LINE.match(line)
        if m:
            out[m.group(1) + (m.group(2) or "")] = float(m.group(3))
    return out


def total(page: dict, name: str) -> float:
    """Sum of every series of ``name`` across labels (0 when absent)."""
    return sum(v for k, v in page.items() if k == name or k.startswith(name + "{"))


def delta(before: dict, after: dict, name: str) -> float:
    return total(after, name) - total(before, name)


def mean_delta(before: dict, after: dict, histogram: str):
    """Δsum/Δcount of a histogram over the interval; None when nothing was
    observed in it."""
    n = delta(before, after, histogram + "_count")
    if n <= 0:
        return None
    return delta(before, after, histogram + "_sum") / n
