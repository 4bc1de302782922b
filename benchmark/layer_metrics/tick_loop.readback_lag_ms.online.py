"""Median, over ``decode_only`` ticks, of the return of ``serving.sample_sync`` minus the tick
program's end on the device: the time the host took to learn that the device had finished."""

from benchmark.trace import ticks


def read(ctx):
    return ticks.metric(ctx, "tick_loop.readback_lag_ms.online")
