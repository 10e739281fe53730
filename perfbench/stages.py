"""Mean request stages over a window (program counter).

``dpow_request_stage_seconds{stage=...}`` holds each stage of a request's
trace, timed from the stage that caused it (``tpu_dpow/obs/trace.py``). A
stage's mean is Δsum/Δcount of its one series over the window. The series is
picked by its label here: ``promtext.total`` sums across labels.
"""

from __future__ import annotations

import re

HISTOGRAM = "dpow_request_stage_seconds"


def _value(page: dict, series: str, label: str, value: str) -> float:
    prefix = series + "{"
    want = re.compile(rf'(^|[{{,]){label}="{re.escape(value)}"')
    return sum(v for k, v in page.items() if k.startswith(prefix) and want.search(k))


def series_mean_ms(pages: tuple, histogram: str, label: str, value: str):
    """Δsum/Δcount over the window of the one series of ``histogram`` whose
    ``label`` is ``value``, in ms; None when it was not observed."""
    before, after = pages
    count, total = histogram + "_count", histogram + "_sum"
    n = _value(after, count, label, value) - _value(before, count, label, value)
    if n <= 0:
        return None
    return 1e3 * (_value(after, total, label, value) - _value(before, total, label, value)) / n


def mean_ms(pages: tuple, stage: str):
    """Mean of one stage over the window, in ms; None when it was not
    observed in the window."""
    return series_mean_ms(pages, HISTOGRAM, "stage", stage)


def sum_ms(pages: tuple, stages: tuple):
    """Sum of the stages' means, in ms; None unless every one was observed."""
    means = [mean_ms(pages, s) for s in stages]
    return None if any(m is None for m in means) else sum(means)
