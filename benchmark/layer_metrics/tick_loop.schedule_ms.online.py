"""The part of the host gap before the dispatch (``serving.host_gap.schedule``, the
``serving.schedule`` span of the gap's ticks): deadline expiry, the chunk lanes' host
work, the admission sweep, replay arrays; mean over the booked gaps."""

from benchmark.trace import books


def read(ctx):
    return books.mean_ms(ctx, "serving.host_gap.schedule")
