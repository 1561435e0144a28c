"""Percentiles, gaps and windows on hand-made records."""

import pytest

import bench_paths  # noqa: F401

import stats


def rec(due, times, sent=None, ended=None, index=0):
    return {"index": index, "due": due, "sent": due if sent is None else sent,
            "token_times": times, "ended": ended or (times[-1] if times
                                                     else due)}


def test_percentile_interpolates_between_order_statistics():
    xs = [10, 20, 30, 40, 50]
    assert stats.percentile(xs, 50) == 30
    assert stats.percentile(xs, 95) == pytest.approx(48.0)
    assert stats.percentile(xs, 0) == 10 and stats.percentile(xs, 100) == 50
    assert stats.percentile([7], 95) == 7
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_percentile_matches_numpy_default():
    import numpy as np

    xs = [0.3, 9.1, 4.4, 2.2, 8.0, 1.5, 6.6]
    for q in (5, 25, 50, 75, 95):
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_time_to_first_token_counts_from_the_due_time_not_the_send():
    late = rec(due=100.0, sent=100.4, times=[100.9, 101.0])
    assert stats.ttfts_due_in_window([late], 99.0, 110.0) == \
        [pytest.approx(0.9)]
    assert stats.lateness([late], 99.0, 110.0)["max_s"] == pytest.approx(0.4)


def test_only_requests_due_in_the_window_are_counted():
    before = rec(due=9.0, times=[10.5])
    inside = rec(due=10.0, times=[10.2])
    after = rec(due=20.0, times=[20.1])
    silent = rec(due=11.0, times=[])
    got = stats.ttfts_due_in_window([before, inside, after, silent],
                                    10.0, 20.0)
    assert got == [pytest.approx(0.2)]


def test_every_gap_that_ends_in_the_window_is_counted_once():
    a = rec(due=0.0, times=[9.8, 10.1, 10.5, 20.2])
    b = rec(due=0.0, times=[10.0, 10.3])
    gaps = sorted(stats.gaps_in_window([a, b], 10.0, 20.0))
    assert gaps == [pytest.approx(x) for x in (0.3, 0.3, 0.4)]


def test_tokens_in_window_counts_arrivals_not_requests():
    a = rec(due=0.0, times=[9.0, 10.0, 15.0, 19.999, 20.0])
    assert stats.tokens_in_window([a], 10.0, 20.0) == 3


def test_step_window_holds_whole_steps_only():
    rows = [{"step": s, "seen": 100.0 + 1.5 * s} for s in range(1, 12)]
    win = stats.step_window(rows, warmup_steps=3, seconds=6.2)
    assert win["w0"] == pytest.approx(104.5)
    assert [r["step"] for r in win["rows"]] == [4, 5, 6, 7]
    assert win["seconds"] == pytest.approx(6.0) and win["steps"] == 4
    with pytest.raises(ValueError):
        stats.step_window(rows[:3], warmup_steps=3, seconds=5)


def test_distribution_of_nothing_says_so():
    assert stats.distribution([]) == {"n": 0}
    d = stats.distribution([1, 2, 3])
    assert d["n"] == 3 and d["sum"] == 6 and d["p50"] == 2
