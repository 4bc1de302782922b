"""Median device-idle time between consecutive tick programs: the engine's and the
harness's host code between ticks."""

from benchmark.trace import serving

read = serving.tick_host_gap_ms
