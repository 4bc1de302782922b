"""95th percentile of the engine's own queue wait (submit to admission, the stamps
``EngineMetrics`` reads) over the finished requests due in the window."""

from benchmark.harness import stats


def read(ctx):
    waits = ctx.get("queue_wait_s")
    return 1e3 * stats.percentile(waits, 95) if waits else None
