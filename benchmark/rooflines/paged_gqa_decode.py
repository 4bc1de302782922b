"""``fused_paged_decode_attention_gqa``: a decode tick's attention over the paged pool,
one call a layer, bound by memory bandwidth. A decoding slot's query heads read each of
its LIVE keys and values once, at ``num_key_value_heads`` heads of ``head_dim`` in the
pool's type: the query heads that share a K/V head share the read. What the kernel
fetches beyond that (the rest of a slot's newest page, the trash page for dead pages) is
not needed work and is not counted."""

from __future__ import annotations


def bytes_per_tick(sizes: dict, live_tokens: float, cache_bytes: int = 2) -> float:
    """``live_tokens``: summed live cache entries over the slots the tick decodes."""
    width = sizes["num_key_value_heads"] * sizes["head_dim"]
    return 2.0 * sizes["num_hidden_layers"] * live_tokens * width * cache_bytes
