"""95th percentile of enqueue to slot claim over every admission of the run (the engine's
``queue_wait_s`` window): the wait for a slot alone, without the chunk ticks that
``admission.queue_wait_p95_ms`` also spans."""

from benchmark.trace import books


def read(ctx):
    p95 = books.snapshot_value(ctx, "queue_wait_s", "p95", since=None)
    return None if p95 is None else 1e3 * p95
