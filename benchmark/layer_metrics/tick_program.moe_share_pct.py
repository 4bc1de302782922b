"""Share of the tick programs' device busy time under the scope ``moe`` (the router and
the experts' grouped products, in the chunk lanes and the decode step alike)."""

from benchmark.trace import experts


def read(ctx):
    return experts.scope_share_pct(ctx, "moe")
