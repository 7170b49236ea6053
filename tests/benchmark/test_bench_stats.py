"""Percentiles, window accounting and the spread rule, on made-up samples."""

import dataclasses
import statistics

import pytest

from benchmark import stats


@dataclasses.dataclass
class C:
    due: float
    done: float
    ok: bool = True
    completion_tokens: int = 10

    @property
    def ms(self):
        return (self.done - self.due) * 1000.0


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    ([10, 20], 95, 19.5),
    (list(range(1, 101)), 95, 95.05),
    ([7], 99, 7.0),
])
def test_percentile_interpolates_linearly(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_a_call_counts_by_when_it_completes():
    calls = [
        C(due=-5.0, done=1.0),  # started before the window, ends inside
        C(due=2.0, done=4.0),
        C(due=9.0, done=11.0),  # ends after the window: not counted
        C(due=3.0, done=5.0, ok=False, completion_tokens=0),
    ]
    w = stats.window_metrics(calls, 0.0, 10.0)
    assert (w["attempted"], w["failed"]) == (3, 1)
    assert w["out_tok_s"] == pytest.approx(20 / 10.0)
    assert w["call_ms_p50"] == pytest.approx(4000.0)  # of 6000 and 2000
    assert w["call_ms_mean"] == pytest.approx(4000.0)


def test_a_window_with_no_good_call_reports_no_latency():
    w = stats.window_metrics([C(0.0, 1.0, ok=False)], 0.0, 2.0)
    assert "call_ms_p50" not in w and w["failed"] == 1 and w["out_tok_s"] == 0


def test_spread_is_the_quartile_distance_over_the_median():
    values = [100, 101, 99, 102, 98, 100]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.spread(values) == pytest.approx((q3 - q1) / 100)


def test_ratio_of_deltas_reads_a_window_not_a_lifetime():
    before = {"queueMsSum": 1000.0, "queueMsCount": 10}
    after = {"queueMsSum": 1300.0, "queueMsCount": 13}
    assert stats.ratio_of_deltas(after, before, "queueMsSum", "queueMsCount") == 100.0
    assert stats.ratio_of_deltas(before, before, "queueMsSum", "queueMsCount") is None
