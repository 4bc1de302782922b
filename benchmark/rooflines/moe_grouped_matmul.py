"""The routed experts' grouped products (the scope ``moe/experts``: ``silu(x W_1) * x W_3``
and ``h W_2`` over the assignments sorted by expert), counted from the WORK, whatever
implements them. One call (a decode step, or one chunk lane) in one expert layer reads
the three matrices of every expert that received a row, once, and nothing of an expert
that received none; beside them each assignment's row goes in and its result comes out.
At a decode step's handful of rows an expert the weights' bytes are the bound; the
operations overtake them past ``ridge_rows`` rows an expert."""

from __future__ import annotations

WEIGHT_BYTES = 2  # bfloat16, the type the configuration serves its weights in
ROW_BYTES = 2


def expert_bytes(sizes: dict) -> float:
    return 3.0 * sizes["hidden_size"] * sizes["moe_intermediate_size"] * WEIGHT_BYTES


def expert_layers(sizes: dict) -> int:
    return sizes["num_hidden_layers"] - sizes["num_dense_layers"]


def bytes_moved(sizes: dict, touched: float, assignments: float) -> float:
    """``touched``: (layer, expert) pairs that received a row in the call; ``assignments``:
    (row, expert) pairs computed, over the layers."""
    return touched * expert_bytes(sizes) + assignments * 2.0 * sizes["hidden_size"] * ROW_BYTES


def flops(sizes: dict, assignments: float) -> float:
    return assignments * 2.0 * 3.0 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def seconds_at_roofline(sizes: dict, peaks: dict, touched: float, assignments: float) -> float:
    """The longer of moving the bytes at the memory's bandwidth and doing the operations
    at the matrix unit's peak."""
    return max(bytes_moved(sizes, touched, assignments) / peaks["hbm_bytes_per_s"],
               flops(sizes, assignments) / peaks["bf16_flops"])


def ridge_rows(sizes: dict, peaks: dict) -> float:
    """Rows an expert from which the operations, not the weights, bound a call."""
    return expert_bytes(sizes) / peaks["hbm_bytes_per_s"] * peaks["bf16_flops"] / (
        2.0 * 3.0 * sizes["hidden_size"] * sizes["moe_intermediate_size"])
