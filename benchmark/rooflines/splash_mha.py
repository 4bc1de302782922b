"""The splash attention kernels of one training step (``splash_mha_fwd*``, ``*_dq``,
``*_dkv``): the least operations and bytes the attention of a step needs.

Forward: QK^T and PV, 4 FLOPs per unmasked pair and channel. Backward: dP, dV, dQ, dK,
8 per pair and channel. The kernels recompute scores (and, under checkpointing, whole
forward passes); that is not needed work and is not counted, so block skipping and
recomputation cannot push the share over 100%. Bytes: q, k, v, o and their gradients
once each in bf16."""

from __future__ import annotations

from benchmark.rooflines.train_step import attention_pairs


def required(sizes: dict, seq_len: int, rows: int) -> dict:
    c, layers = sizes["num_channels"], sizes["num_self_attention_layers"]
    latents = min(sizes["max_latents"], seq_len)
    pairs = attention_pairs(sizes, seq_len)
    total_pairs = pairs["cross"] + layers * pairs["self"]
    flops = rows * 12.0 * c * total_pairs
    # per attention call: q, o (latents) and k, v (keys) forward; the same again as gradients
    rows_moved = (2 * latents + 2 * seq_len) + layers * (4 * latents)
    bytes_ = rows * 2.0 * rows_moved * c * 2
    return {"flops": flops, "bytes": bytes_}


def least_seconds(sizes: dict, seq_len: int, rows: int, peaks: dict) -> dict:
    need = required(sizes, seq_len, rows)
    by_flops = need["flops"] / peaks["bf16_flops"]
    by_bytes = need["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(by_flops, by_bytes), "bound": "flops" if by_flops >= by_bytes else "bytes", **need}
