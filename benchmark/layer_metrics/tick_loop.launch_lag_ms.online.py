"""Median, over ``decode_only`` ticks, of the tick program's start on the device minus the
return of ``serving.decode_dispatch``: negative when the device started first."""

from benchmark.trace import ticks


def read(ctx):
    return ticks.metric(ctx, "tick_loop.launch_lag_ms.online")
