"""Mean number of experts a layer that receive a row in a decode step (of the router's
``num_experts``), from the ``experts`` block of the engine's snapshot: what a decoding
tick has to stream."""

from benchmark.trace import experts


def read(ctx):
    return experts.snapshot_experts(ctx, "touched_per_step", "mean")
