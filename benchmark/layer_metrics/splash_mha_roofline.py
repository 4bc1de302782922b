"""The splash kernels' share of their roofline: the least time the chip could take for
one step's attention (``rooflines/splash_mha.py``: FLOP-bound at these shapes) over the
time the ``splash_mha*`` kernels took per step and device."""

from benchmark.rooflines import splash_mha
from benchmark.trace import training


def read(ctx):
    seconds = training.kernel_seconds_per_step(ctx, "splash_mha")
    if seconds is None or not ctx.get("peaks"):
        return None
    sizes = ctx["sizes"]
    least = splash_mha.least_seconds(sizes, sizes["max_seq_len"], ctx["rows_per_step"] / ctx["chips"], ctx["peaks"])
    return 100.0 * least["seconds"] / seconds
