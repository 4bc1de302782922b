"""Device time under the scope ``tick.finish_lanes`` a finish lane: summed over the ticks whose
record has ``finish_lanes`` > 0, over their ``finish_lanes``."""

from benchmark.trace import ticks


def read(ctx):
    return ticks.metric(ctx, "tick_program.finish_lane_device_ms.online")
