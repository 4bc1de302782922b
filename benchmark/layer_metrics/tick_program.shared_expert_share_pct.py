"""Share of the tick programs' device busy time under the scope ``moe/shared`` (the shared
expert's two dense products, for every row, in the chunk lanes and the decode step alike)."""

from benchmark.trace import nemotron


def read(ctx):
    return nemotron.scope_share_pct(ctx, "moe/shared")
