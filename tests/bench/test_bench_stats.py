"""Percentile and sample-count arithmetic, and the latency bookkeeping."""

import statistics

import pytest

from benchmarks.lib import stats
from benchmarks.lib.report import serve_latencies
from benchmarks.lib.serve_cell import Tracked


@pytest.mark.parametrize("q,want", [(50, 5), (90, 9), (95, 10), (100, 10),
                                    (10, 1), (1, 1)])
def test_percentile_is_nearest_rank(q, want):
    assert stats.percentile(list(range(10, 0, -1)), q) == want


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


@pytest.mark.parametrize("n,q,beyond", [(200, 90, 20), (200, 95, 10),
                                        (20, 95, 1), (1, 95, 0), (0, 95, 0)])
def test_samples_beyond_a_percentile(n, q, beyond):
    assert stats.samples_beyond(list(range(n)), q) == beyond


def test_spread_is_the_interquartile_share_of_the_median():
    values = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.spread(values) == pytest.approx(
        (q3 - q1) / statistics.median(values))


def test_latency_is_timed_from_the_due_time():
    late = Tracked({"id": "a"}, due=1.0)
    late.sent = 1.5                       # the generator ran half a second late
    late.token_times = [2.0, 2.1, 2.4]
    never = Tracked({"id": "b"}, due=1.0)  # no token: no latency sample
    ttft, itl = serve_latencies([late, never])
    assert ttft == [pytest.approx(1000.0)]  # from 1.0, not from 1.5
    assert itl == [pytest.approx(100.0), pytest.approx(300.0)]
