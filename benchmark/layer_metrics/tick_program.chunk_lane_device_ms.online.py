"""Device time under the scope ``tick.chunk_lanes`` a chunk lane: summed over the ticks whose
record has ``chunk_lanes`` > 0, over their ``chunk_lanes``."""

from benchmark.trace import ticks


def read(ctx):
    return ticks.metric(ctx, "tick_program.chunk_lane_device_ms.online")
