"""Share of the traced span the device idled before a tick whose record says ``after_empty``:
the engine held no request, so the time is the traffic's and not the tick loop's. Part of
``device.idle_pct.online``."""

from benchmark.trace import ticks


def read(ctx):
    return ticks.metric(ctx, "device.idle_no_request_pct.online")
