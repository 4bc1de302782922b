"""Share of the joined ticks that decode which also carry a chunk or finish lane (class
``lane``: the record's ``chunk_lanes`` / ``finish_lanes`` on ``serving.sample_sync``). The gap's
p95 sits on a lane tick as long as this is over 5."""

from benchmark.trace import ticks


def read(ctx):
    return ticks.metric(ctx, "tick_program.lane_tick_share_pct.online")
