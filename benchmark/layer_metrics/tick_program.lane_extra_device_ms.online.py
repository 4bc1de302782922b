"""What a chunk or finish lane adds to the tick program's device time: median device busy
inside the tick program of ``lane`` ticks minus that of ``decode_only`` ticks, each tick
classed by its own record."""

from benchmark.trace import ticks


def read(ctx):
    return ticks.metric(ctx, "tick_program.lane_extra_device_ms.online")
