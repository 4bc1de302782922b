"""The routed experts' grouped products of an UNGATED expert (the scope ``moe/experts``:
``relu(x W_up)^2`` and ``h W_down`` over the assignments sorted by expert) on one chip's
SHARE of the experts, counted from the WORK, whatever implements them. One call in one
expert layer reads the TWO matrices of every HELD expert that received a row, once, and
nothing of an expert that received none or lies elsewhere; beside them each assignment
that fell to a held expert has its row go in and its result come out. The bytes are the
PUBLISHED ones (``moe_intermediate_size`` columns), not those of the lanes' multiple the
stacks are laid out at. At a decode step's handful of rows an expert the weights' bytes
are the bound; the operations overtake them past ``ridge_rows`` rows an expert."""

from __future__ import annotations

WEIGHT_BYTES = 2  # bfloat16, the type the configuration serves its weights in
ROW_BYTES = 2
MATRICES = 2.0


def expert_bytes(sizes: dict) -> float:
    return MATRICES * sizes["hidden_size"] * sizes["moe_intermediate_size"] * WEIGHT_BYTES


def expert_layers(sizes: dict) -> int:
    return sizes["hybrid_override_pattern"].count("E")


def bytes_moved(sizes: dict, touched: float, assignments: float) -> float:
    """``touched``: (layer, held expert) pairs that received a row in the call;
    ``assignments``: (row, held expert) pairs computed, over the layers."""
    return touched * expert_bytes(sizes) + assignments * 2.0 * sizes["hidden_size"] * ROW_BYTES


def flops(sizes: dict, assignments: float) -> float:
    return assignments * 2.0 * MATRICES * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def seconds_at_roofline(sizes: dict, peaks: dict, touched: float, assignments: float) -> float:
    """The longer of moving the bytes at the memory's bandwidth and doing the operations
    at the matrix unit's peak."""
    return max(bytes_moved(sizes, touched, assignments) / peaks["hbm_bytes_per_s"],
               flops(sizes, assignments) / peaks["bf16_flops"])


def ridge_rows(sizes: dict, peaks: dict) -> float:
    """Rows an expert from which the operations, not the weights, bound a call."""
    return expert_bytes(sizes) / peaks["hbm_bytes_per_s"] * peaks["bf16_flops"] / (
        2.0 * MATRICES * sizes["hidden_size"] * sizes["moe_intermediate_size"])
