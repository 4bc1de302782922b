"""95th percentile of slot claim to first token (the engine's stamps, ``first_token_s``)
over every request of the run: the prefill layer's latency, chunk ticks included."""

from benchmark.trace import books


def read(ctx):
    p95 = books.snapshot_value(ctx, "first_token_s", "p95")
    return None if p95 is None else 1e3 * p95
