"""The part of the host gap after the sync (``serving.host_gap.harvest``, the
``serving.harvest`` span of the gap's ticks): useful-token count, token append and
eviction, watchdog poll, journal flush; mean over the booked gaps."""

from benchmark.trace import books


def read(ctx):
    return books.mean_ms(ctx, "serving.host_gap.harvest")
