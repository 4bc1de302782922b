"""Per-step reductions shared by the training cells' per-layer readers."""

from __future__ import annotations

from benchmark.trace import reduce


def step_executions(ctx) -> list:
    """(device's operations, one whole execution of the step program) pairs in the trace."""
    trace = ctx.get("trace")
    if not trace or not trace["devices"]:
        return []
    out = []
    for device in trace["devices"].values():
        for _, start, dur in reduce.program_events(device["modules"], ctx["program_name"]):
            out.append(reduce.clip(device["ops"], start, start + dur))
    return out


def step_busy_seconds(ctx):
    """Median device busy time inside one execution of the step program."""
    busy = [reduce.busy_seconds(ops) for ops in step_executions(ctx)]
    return reduce.median(busy) if busy else None


def kernel_seconds_per_step(ctx, prefix: str):
    seconds = [reduce.kernel_seconds(ops, prefix) for ops in step_executions(ctx)]
    seconds = [s for s in seconds if s > 0]
    return reduce.median(seconds) if seconds else None
