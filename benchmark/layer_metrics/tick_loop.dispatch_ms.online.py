"""The part of the host gap inside the dispatch (``serving.host_gap.dispatch``, the
``serving.decode_dispatch`` span of the gap's ticks): descriptor build, host-to-device
transfers and the ``jit`` call; mean over the booked gaps."""

from benchmark.trace import books


def read(ctx):
    return books.mean_ms(ctx, "serving.host_gap.dispatch")
