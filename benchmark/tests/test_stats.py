import numpy as np
import pytest

from benchmark.harness import stats


@pytest.mark.parametrize("q", [0, 5, 50, 95, 100])
def test_percentile_is_numpys(q):
    xs = [3.0, 1.0, 4.0, 1.0, 5.0, 9.0, 2.0, 6.0]
    assert stats.percentile(xs, q) == pytest.approx(float(np.percentile(xs, q)))


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_gaps_are_between_successive_tokens_of_one_request():
    # two requests; a gap never spans from one request's last token to another's first
    assert stats.token_gaps([[1.0, 1.5, 2.5], [10.0, 10.25]]) == [0.5, 1.0, 0.25]
    assert stats.token_gaps([[1.0]]) == []


def test_tokens_per_second_counts_the_window_only():
    times = [[0.5, 1.0, 1.5, 2.0], [1.9, 2.0, 2.1]]
    assert stats.tokens_in_window(times, 1.0, 2.0) == 3  # 1.0, 1.5, 1.9; 2.0 is outside
    assert stats.rate(3, 1.0) == 3.0
    with pytest.raises(ValueError):
        stats.rate(3, 0.0)


def test_latency_summary_is_in_milliseconds():
    s = stats.latency_summary([0.010, 0.020, 0.030])
    assert s["n"] == 3 and s["p50_ms"] == pytest.approx(20.0) and s["max_ms"] == pytest.approx(30.0)
