"""What a lane adds on the host before the device starts the tick: median of
``serving.decode_dispatch`` (pack, one transfer, jit call) plus the launch lag (the tick
program's start minus the dispatch's return) of ``lane`` ticks, minus that of
``decode_only`` ticks."""

from benchmark.trace import ticks


def read(ctx):
    return ticks.metric(ctx, "tick_loop.lane_extra_host_ms.online")
