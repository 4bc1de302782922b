"""Share of the traced window in which no operation ran on the device (mean over devices)."""

from benchmark.trace import reduce

read = reduce.idle_pct
