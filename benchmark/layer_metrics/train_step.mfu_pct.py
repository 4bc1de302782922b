"""Model FLOP/s utilisation of the train step on the device: the benchmark's own
analytic FLOPs per step (``rooflines/train_step.py``; recomputation not counted) over
the step's device busy time, the chips used and the chip's bf16 peak."""

from benchmark.rooflines import train_step
from benchmark.trace import training


def read(ctx):
    seconds = training.step_busy_seconds(ctx)
    if seconds is None or not ctx.get("peaks"):
        return None
    sizes = ctx["sizes"]
    flops = train_step.train_flops_per_step(sizes, sizes["max_seq_len"], ctx["rows_per_step"])
    return 100.0 * flops / (seconds * ctx["chips"] * ctx["peaks"]["bf16_flops"])
