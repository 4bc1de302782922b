"""What a tick with a chunk or finish lane costs every decoding slot: median wall time,
dispatch to sync's return, of such ticks minus that of decode-only ticks."""

from benchmark.trace import books


def read(ctx):
    with_prefill = books.phase(ctx, "serving.tick_wall.with_prefill")
    decode_only = books.phase(ctx, "serving.tick_wall.decode_only")
    if not with_prefill or not decode_only or not with_prefill["count"] or not decode_only["count"]:
        return None
    return 1e3 * (with_prefill["p50_s"] - decode_only["p50_s"])
