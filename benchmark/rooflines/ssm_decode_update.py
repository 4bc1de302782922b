"""``ssm_decode_update``: one step of the state-space recurrence for every decoding slot,
in every layer, bound by memory bandwidth. For one (slot, layer) the kernel reads the
float32 state ``(heads, d_head, d_state)`` once and writes it once in place; beside it
go the two per-head scale rows it is given (``exp(dt A)`` and ``dt x``), the group's
``B`` and ``C`` and the ``y`` it writes. Nothing is counted
for a slot the kernel skips (free, or in the middle of its prefill), nor the convolution
tails, which the kernel does not move."""

from __future__ import annotations

F32 = 4


def bytes_per_slot_layer(sizes: dict) -> float:
    heads, p, n, groups = sizes["mamba_n_heads"], sizes["mamba_d_head"], sizes["mamba_d_state"], sizes["mamba_n_groups"]
    state = heads * p * n * F32
    vectors = (2 + 1) * heads * p * F32 + 2 * groups * n * F32
    return 2.0 * state + vectors


def bytes_per_tick(sizes: dict, decoding_slots: float) -> float:
    """``decoding_slots``: slots the tick decodes (each has its state moved in every layer)."""
    return sizes["num_hidden_layers"] * decoding_slots * bytes_per_slot_layer(sizes)
