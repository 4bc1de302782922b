"""Median device busy time of one fused tick program (``ragged_tick``) in the trace."""

from benchmark.trace import serving

read = serving.tick_device_ms
