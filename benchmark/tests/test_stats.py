import pytest

from harness import stats
from harness.client import Record


def rec(due, events, status=200, done=True, reason="length", sent=None):
    r = Record(0, "window", due, 10, 10)
    r.sent = due if sent is None else sent
    r.status, r.done, r.finish_reason = status, done, reason
    r.events = list(events)
    return r


@pytest.mark.parametrize("pct,want", [(0, 1.0), (50, 3.0), (95, 4.8),
                                      (100, 5.0)])
def test_percentile_interpolates_between_ranks(pct, want):
    assert stats.percentile([5, 1, 4, 2, 3], pct) == pytest.approx(want)


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_quartile_spread_is_the_contracts():
    # statistics.quantiles(n=4) (exclusive): Q1 = 1.75, Q3 = 6.25 here
    assert stats.quartile_spread([1, 2, 3, 4, 5, 6, 7]) == pytest.approx(1.0)


def test_ttft_counts_from_when_the_request_was_due_not_sent():
    late = rec(due=10.0, sent=10.4, events=[(10.5, 1), (10.6, 4)])
    assert stats.ttft_ms(late, censor_at=99.0) == pytest.approx(500.0)


@pytest.mark.parametrize("bad", [
    dict(status=503, events=[]),                    # refused
    dict(status=200, done=False, events=[(11.0, 1)]),  # cut at the drain
    dict(status=200, reason="", events=[(11.0, 1)]),
])
def test_a_failed_request_is_as_late_as_the_run_could_see(bad):
    r = rec(due=10.0, **bad)
    assert not r.ok
    assert stats.ttft_ms(r, censor_at=70.0) == pytest.approx(60000.0)


def test_tpot_is_per_request_over_the_tokens_after_the_first_group():
    # first token alone, then two fused groups of four, 40 ms apart
    r = rec(0.0, [(1.0, 1), (1.04, 1), (1.04, 1), (1.04, 1), (1.04, 1),
                  (1.08, 1), (1.08, 1), (1.08, 1), (1.08, 1)])
    assert stats.tpot_ms(r) == pytest.approx(10.0)
    assert stats.tpot_ms(rec(0.0, [(1.0, 1)])) is None
    failed = rec(0.0, [], status=429)
    slow = rec(0.0, [(1.0, 1), (1.5, 1)])
    assert stats.tpots_with_worst([r, failed, slow]) == pytest.approx(
        [10.0, 500.0, 500.0])


def test_tokens_between_counts_deliveries_inside_the_window_only():
    a = rec(0.0, [(0.9, 1), (1.0, 4), (1.99, 4), (2.0, 4)])
    assert stats.tokens_between([a], 1.0, 2.0) == 8


def test_pooled_gaps():
    a = rec(0.0, [(1.0, 1), (1.0, 1), (1.05, 1)])
    assert stats.pooled_gaps_ms([a]) == pytest.approx([0.0, 50.0])
