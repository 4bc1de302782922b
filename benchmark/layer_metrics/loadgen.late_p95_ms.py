"""How late the load generator submitted: 95th percentile of (submit time - due time)
over the requests due in the window."""

from benchmark.harness import stats


def read(ctx):
    late = ctx.get("late_s")
    return 1e3 * stats.percentile(late, 95) if late else None
