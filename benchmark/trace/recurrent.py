"""Per-tick reductions for a served model that keeps a recurrent state in every slot: the
time of its two decode kernels in the traced tick programs against the bytes the traced
ticks had to move. Each returns None where the trace holds no such kernel (a program
that lacks it, a run that was not traced)."""

from __future__ import annotations

from benchmark.rooflines import paged_gqa_decode, ssm_decode_update
from benchmark.trace import reduce, serving

SSM_KERNEL = "ssm_decode_update"
GQA_KERNEL = "fused_paged_decode_attention_gqa"


def _traced(ctx, kernel: str):
    """(device, programs, the harness's ticks inside the trace, the kernel's seconds)."""
    device, programs = serving._ticks(ctx)
    ticks = serving.traced_ticks(ctx)
    if device is None or not ticks or not ctx.get("peaks"):
        return None
    seconds = reduce.kernel_seconds(device["ops"], kernel)
    return (device, programs, ticks, seconds) if seconds > 0 else None


def _roofline_pct(ctx, kernel: str, bytes_of_tick) -> float | None:
    traced = _traced(ctx, kernel)
    if traced is None:
        return None
    _, programs, ticks, seconds = traced
    # the trace and the harness's books cover the same ticks up to one at either edge
    need = sum(bytes_of_tick(t) for t in ticks) * len(programs) / len(ticks)
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / seconds


def ssm_update_roofline_pct(ctx):
    """State bytes of the slots each traced tick decoded, read and written, over the
    chip's memory bandwidth, over the state-update kernel's time."""
    return _roofline_pct(ctx, SSM_KERNEL, lambda t: ssm_decode_update.bytes_per_tick(ctx["sizes"], t[1]))


def paged_gqa_roofline_pct(ctx):
    """Live key and value bytes of the slots each traced tick decoded, over the chip's
    memory bandwidth, over the grouped-query paged kernel's time."""
    return _roofline_pct(ctx, GQA_KERNEL, lambda t: paged_gqa_decode.bytes_per_tick(ctx["sizes"], t[2]))


def ssm_update_share_pct(ctx):
    """The state-update kernel's time over the tick programs' busy time."""
    traced = _traced(ctx, SSM_KERNEL)
    if traced is None:
        return None
    device, programs, _, seconds = traced
    busy = sum(reduce.per_program_busy(device["ops"], programs))
    return 100.0 * seconds / busy if busy > 0 else None
