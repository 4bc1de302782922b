"""The ungated routed experts' grouped products against their roofline on one chip's share,
counted from the work the tick's counters name (``rooflines/nemotron_moe_grouped_matmul.py``):
the two published matrices of the HELD experts that received a row and the rows of the
assignments that fell to them over the memory's bandwidth, or the operations over the
matrix unit's peak, the larger; over the decode step's time under ``moe/experts``."""

from benchmark.trace import nemotron

read = nemotron.moe_grouped_matmul_roofline_pct
