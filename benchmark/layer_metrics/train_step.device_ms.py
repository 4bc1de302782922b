"""Device busy time of one train step: the union of the operations inside each whole
execution of the step program in the trace, median over executions and devices."""

from benchmark.trace import training


def read(ctx):
    seconds = training.step_busy_seconds(ctx)
    return None if seconds is None else 1e3 * seconds
