import math
import types

import latencyof
import percentile
import pytest


def test_nearest_rank_is_exact_on_raw_samples():
    xs = list(range(1, 101))  # 1..100
    assert percentile.nearest_rank(xs, 50) == 50
    assert percentile.nearest_rank(xs, 95) == 95
    assert percentile.nearest_rank(xs, 100) == 100
    assert percentile.nearest_rank([7.25], 95) == 7.25
    # Not a bucket edge: the sample itself.
    assert percentile.nearest_rank([0.1234, 5.6789, 1.5], 50) == 1.5


def test_a_miss_counts_and_sorts_last():
    xs = [10.0] * 94 + [math.inf] * 6
    assert percentile.nearest_rank(xs, 50) == 10.0
    assert math.isinf(percentile.nearest_rank(xs, 95))


def test_percentile_rejects_bad_input():
    with pytest.raises(ValueError):
        percentile.nearest_rank([], 50)
    with pytest.raises(ValueError):
        percentile.nearest_rank([1.0], 0)


def _window(records, t0=100.0, t1=110.0):
    w = types.SimpleNamespace(t0=t0, t1=t1, records=records,
                              traffic={"kind": "open_poisson", "timeout": 30})
    w.valid = lambda r: r["status"] == "work"
    w.judged = lambda: [r for r in records if r["judged"] and t0 <= r["intended"] < t1]
    return w


def test_latency_is_timed_from_the_intended_send_and_failures_are_misses():
    recs = [
        # sent 2 s late by a stalled generator: the stall counts
        {"judged": True, "intended": 101.0, "sent": 103.0, "done": 103.5, "status": "work"},
        {"judged": True, "intended": 102.0, "sent": 102.0, "done": 102.25, "status": "work"},
        {"judged": True, "intended": 103.0, "sent": 103.0, "done": 104.0, "status": "error"},
        # before the window, and a background request: not judged here
        {"judged": True, "intended": 99.0, "sent": 99.0, "done": 99.1, "status": "work"},
        {"judged": False, "intended": 104.0, "sent": 104.0, "done": 109.0, "status": "work"},
    ]
    w = _window(recs)
    xs = latencyof.samples_ms(w)
    assert sorted(xs)[:2] == [250.0, 2500.0]
    assert math.isinf(sorted(xs)[2])
    assert latencyof.percentile_ms(w, 50) == 2500.0
    # The tail reaches the miss: reported as the longest a reply may take.
    assert latencyof.percentile_ms(w, 95) == (30 + 60) * 1e3
