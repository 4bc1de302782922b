"""The two decode-attention kernels of a serving tick, both bound by memory bandwidth:

* ``fused_paged_decode_attention``: one call a tick; each occupied slot's query reads the
  keys and values of its live window from the page pool;
* ``fused_decode_attention``: one call per self-attention layer; each occupied slot's
  query reads that layer's dense cache of ``max_latents`` keys and values.

The bytes a tick must read are those of the OCCUPIED slots' LIVE entries, in the cache's
type; what a kernel reads for free slots or dead pages is not needed work."""

from __future__ import annotations


def bytes_per_tick(sizes: dict, occupied_slots: float, live_tokens: float, cache_bytes: int = 2) -> dict:
    """``occupied_slots``: mean occupied slots a tick; ``live_tokens``: mean summed live
    cross-attention entries over the occupied slots a tick."""
    c, layers = sizes["num_channels"], sizes["num_self_attention_layers"]
    paged = 2.0 * live_tokens * c * cache_bytes
    dense = 2.0 * layers * occupied_slots * sizes["max_latents"] * c * cache_bytes
    return {"paged": paged, "dense": dense}
