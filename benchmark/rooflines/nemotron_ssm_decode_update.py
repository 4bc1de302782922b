"""``ssm_decode_update`` under a model whose Mamba-2 layers are SOME of its layers
(``hybrid_override_pattern``'s ``M``): one step of the recurrence for every decoding slot
in every ``M`` layer, bound by memory bandwidth. For one (slot, layer) the kernel reads
the float32 state ``(mamba_num_heads, mamba_head_dim, ssm_state_size)`` once and writes it
once in place (2 x 2.1 MB at 64 x 64 x 128); beside it go the two per-head scale rows it
is given, the group's ``B`` and ``C`` and the ``y`` it writes. Nothing is counted for a
slot the kernel skips, nor the convolution tails, which the kernel does not move."""

from __future__ import annotations

F32 = 4


def mamba_layers(sizes: dict) -> int:
    return sizes["hybrid_override_pattern"].count("M")


def bytes_per_slot_layer(sizes: dict) -> float:
    heads, p, n, groups = sizes["mamba_num_heads"], sizes["mamba_head_dim"], sizes["ssm_state_size"], sizes["n_groups"]
    state = heads * p * n * F32
    vectors = (2 + 1) * heads * p * F32 + 2 * groups * n * F32
    return 2.0 * state + vectors


def bytes_per_tick(sizes: dict, decoding_slots: float) -> float:
    """``decoding_slots``: slots the tick decodes (each has its state moved in every ``M`` layer)."""
    return mamba_layers(sizes) * decoding_slots * bytes_per_slot_layer(sizes)
