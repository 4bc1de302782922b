"""Share of the tick programs' device busy time under the scope ``short_conv`` (the gated
short convolution's projections, taps and state, in both phases)."""

from benchmark.trace import experts


def read(ctx):
    return experts.scope_share_pct(ctx, "short_conv")
