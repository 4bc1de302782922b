"""The busiest expert's assignments over its layer's mean, the worst layer's, over the
run (prefill and decode), from the ``experts`` block of the engine's snapshot."""

from benchmark.trace import experts


def read(ctx):
    return experts.snapshot_experts(ctx, "load_max_over_mean")
