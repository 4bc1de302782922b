"""The arithmetic of the end-to-end metrics."""

from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between closest ranks,
    as numpy's default does. An empty sample has no percentile."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    rank = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(rank), math.ceil(rank)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def latency_summary(values_s: Sequence[float]) -> dict:
    """Median, 95th percentile (ms) and the sample count of a list of seconds."""
    ms = [1e3 * v for v in values_s]
    return {"n": len(ms), "p50_ms": percentile(ms, 50), "p95_ms": percentile(ms, 95), "max_ms": max(ms)}


def rate(count: float, seconds: float) -> float:
    if seconds <= 0:
        raise ValueError(f"a rate over {seconds} s")
    return count / seconds


def token_gaps(times_by_request: Sequence[Sequence[float]]) -> list:
    """All gaps between successive tokens of one request, over all requests."""
    gaps = []
    for times in times_by_request:
        gaps.extend(b - a for a, b in zip(times, times[1:]))
    return gaps


def tokens_in_window(times_by_request: Sequence[Sequence[float]], t_open: float, t_close: float) -> int:
    return sum(1 for times in times_by_request for t in times if t_open <= t < t_close)
