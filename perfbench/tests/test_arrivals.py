"""The open-loop generator's arrivals: a seed reorders the work inside each
stratum and changes nothing else."""

import os
import random
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                                "generators"))
import open_poisson  # noqa: E402


def _gaps(ts, end):
    return [b - a for a, b in zip(ts, ts[1:] + [end])]


@pytest.mark.parametrize("stratum", [None, 1.0])
def test_same_seed_same_arrivals_other_seed_same_work(stratum):
    a = open_poisson.arrivals(96.0, 10.0, 51.0, random.Random("1:arrivals"), stratum)
    again = open_poisson.arrivals(96.0, 10.0, 51.0, random.Random("1:arrivals"), stratum)
    b = open_poisson.arrivals(96.0, 10.0, 51.0, random.Random("2:arrivals"), stratum)
    assert a == again and a != b
    assert len(a) == len(b) == 96 * 51
    assert sorted(_gaps(a, 61.0)) == pytest.approx(sorted(_gaps(b, 61.0)))


def test_every_stratum_holds_the_same_count():
    ts = open_poisson.arrivals(96.0, 0.0, 51.0, random.Random(7), 1.0)
    per_second = [sum(1 for t in ts if k <= t < k + 1) for k in range(51)]
    assert per_second == [96] * 51
    unstratified = open_poisson.arrivals(96.0, 0.0, 51.0, random.Random(7))
    assert len({sum(1 for t in unstratified if k <= t < k + 1) for k in range(51)}) > 1
