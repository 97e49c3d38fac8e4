"""The harness's arithmetic: tails over all results, rates over the window."""
import pytest

from bench import stats
from bench.harness import Emitted


def test_percentile_is_nearest_rank_over_every_value():
    vals = list(range(1, 101))
    assert stats.percentile(vals, 95) == 95
    assert stats.percentile(vals, 100) == 100
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile([], 95) is None
    # order of arrival does not matter
    assert stats.percentile(list(reversed(vals)), 50) == 50


def test_tail_covers_every_stage_and_only_the_window():
    window = (10.0, 20.0)
    res = [Emitted("streaming", 11.0, 10.9, 4) for _ in range(18)]
    res += [Emitted("window", 12.0, 11.0, 32), Emitted("window", 13.0, 11.0, 32)]
    res += [Emitted("window", 25.0, 10.0, 32)]            # after the window
    res += [Emitted("streaming", 9.0, 0.0, 4)]            # before it
    # 20 results inside: 18 at 100 ms, 2 at 1000 and 2000 ms; p95 is the
    # 19th, so the window stage's tail counts
    assert stats.tail_ms(res, window, 95) == pytest.approx(1000.0)
    assert stats.tail_ms(res, window, 100) == pytest.approx(2000.0)


def test_rate_is_over_the_whole_window():
    assert stats.rate(500, (100.0, 110.0)) == 50.0
    assert stats.rate(5, (3.0, 3.0)) is None


def test_span_means_and_counter_deltas():
    spans = [("a", 1.0, 1.5), ("a", 2.0, 2.1), ("b", 2.0, 9.0), ("a", 30.0, 40.0)]
    assert stats.mean_span_ms(spans, "a", (0.0, 10.0)) == pytest.approx(300.0)
    assert stats.mean_span_ms(spans, "c", (0.0, 10.0)) is None
    assert stats.delta({"x": 3}, {"x": 10}, "x") == 7


def test_idle_share_from_busy_and_window():
    assert stats.idle_share({"busy_s": 2.5, "window_s": 10.0,
                             "n_devices": 1}) == pytest.approx(75.0)
    assert stats.idle_share({"busy_s": 0.0, "window_s": 10.0,
                             "n_devices": 0}) is None
    assert stats.idle_share(None) is None
