"""Host time from one tick's sync returning to the next tick program's dispatch returning
(the engine's ``serving.host_gap``), mean over the steady-state gaps of the recorder's
life: what the device waits for between ticks, measured where it happens."""

from benchmark.trace import books


def read(ctx):
    return books.mean_ms(ctx, "serving.host_gap")
