"""``fused_paged_decode_attention_gqa`` under a model whose ATTENTION layers are the ``*`` of
its ``hybrid_override_pattern``: one call an attention layer, bound by memory bandwidth. A
decoding slot's 32 query heads read each of its live keys and values once, at
``num_key_value_heads`` (2) heads of ``head_dim`` (128) in the pool's type; the ``M`` and
``E`` layers hold no page. What the kernel fetches beyond that (the rest of a slot's newest
page, the trash page) is not needed work and is not counted."""

from __future__ import annotations


def attention_layers(sizes: dict) -> int:
    return sizes["hybrid_override_pattern"].count("*")


def bytes_per_tick(sizes: dict, live_tokens: float, cache_bytes: int = 2) -> float:
    """``live_tokens``: summed live cache entries (a layer) over the slots the tick decodes."""
    width = sizes["num_key_value_heads"] * sizes["head_dim"]
    return 2.0 * attention_layers(sizes) * live_tokens * width * cache_bytes
