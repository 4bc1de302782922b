"""The routed experts' grouped products against their roofline, counted from the work the
tick's counters name (``rooflines/moe_grouped_matmul.py``): the touched experts' weights
and the rows in and out over the memory's bandwidth, or the operations over the matrix
unit's peak, the larger; over the decode step's time under the scope ``moe/experts``."""

from benchmark.trace import experts

read = experts.moe_grouped_matmul_roofline_pct
