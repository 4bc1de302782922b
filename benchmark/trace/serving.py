"""Per-tick reductions shared by the serving cells' per-layer readers."""

from __future__ import annotations

from benchmark.rooflines import decode_attention
from benchmark.trace import reduce

KERNELS = ("fused_paged_decode_attention", "fused_decode_attention")


def _ticks(ctx):
    trace = ctx.get("trace")
    if not trace or not trace["devices"]:
        return None, None
    device = next(iter(trace["devices"].values()))  # a serving cell drives one chip
    programs = sorted(reduce.program_events(device["modules"], ctx["program_name"]), key=lambda e: e[1])
    return (device, programs) if len(programs) >= 2 else (None, None)


def tick_device_ms(ctx):
    """Median device busy time inside one execution of the tick program."""
    device, programs = _ticks(ctx)
    if device is None:
        return None
    return 1e3 * reduce.median(reduce.per_program_busy(device["ops"], programs))


def tick_host_gap_ms(ctx):
    """Median time between consecutive tick programs in which the device ran nothing."""
    device, programs = _ticks(ctx)
    if device is None:
        return None
    idle = []
    for a, b in zip(programs, programs[1:]):
        start, end = a[1] + a[2], b[1]
        if end > start:
            idle.append(end - start - reduce.busy_seconds(reduce.clip(device["ops"], start, end)))
        else:
            idle.append(0.0)
    return 1e3 * reduce.median(idle)


def traced_ticks(ctx) -> list:
    """The harness's per-tick records (time, occupied slots, live entries) inside the trace."""
    span = ctx.get("trace_span")
    if not span:
        return []
    return [t for t in ctx["ticks"] if span[0] <= t[0] <= span[1]]


def decode_attention_roofline_pct(ctx):
    """Bytes of live keys and values the traced ticks had to read (occupied slots only),
    over the chip's memory bandwidth, over the time the two decode kernels took."""
    device, programs = _ticks(ctx)
    ticks = traced_ticks(ctx)
    if device is None or not ticks or not ctx.get("peaks"):
        return None
    seconds = sum(reduce.kernel_seconds(device["ops"], k) for k in KERNELS)
    if seconds <= 0:
        return None
    need = sum(sum(decode_attention.bytes_per_tick(ctx["sizes"], t[1], t[2]).values()) for t in ticks)
    # the trace and the harness's books cover the same ticks up to one at either edge
    need *= len(programs) / len(ticks)
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / seconds
