"""Operations one Perceiver AR training step needs, from the configuration's sizes.

Forward matrix-multiply FLOPs of one row, times three for forward + backward (Kaplan et
al.'s rule); attention counts only the query-key pairs the causal mask leaves; what
activation checkpointing recomputes is not counted. Embedding look-ups, norms, softmax
and the optimizer are left out (under 1% at these widths)."""

from __future__ import annotations


def attention_pairs(sizes: dict, seq_len: int) -> dict:
    """Unmasked query-key pairs of one row: cross-attention (each latent sees the prefix
    and the latents up to itself) and one self-attention layer (causal over latents)."""
    latents = min(sizes["max_latents"], seq_len)
    prefix = seq_len - latents
    causal = latents * (latents + 1) // 2
    return {"cross": latents * prefix + causal, "self": causal}


def forward_flops_per_row(sizes: dict, seq_len: int) -> dict:
    c, v = sizes["num_channels"], sizes["vocab_size"]
    layers = sizes["num_self_attention_layers"]
    latents = min(sizes["max_latents"], seq_len)
    pairs = attention_pairs(sizes, seq_len)
    cross = (2 * latents * c * c            # q
             + 4 * seq_len * c * c          # k, v over the whole window
             + 2 * latents * c * c          # o
             + 4 * sizes["cross_attention_widening_factor"] * latents * c * c)  # MLP
    self_layer = (8 * latents * c * c       # q, k, v, o
                  + 4 * sizes["self_attention_widening_factor"] * latents * c * c)
    attention = 4 * c * (pairs["cross"] + layers * pairs["self"])  # QK^T and PV, 2 FLOPs a MAC
    return {"dense": cross + layers * self_layer + 2 * latents * c * v, "attention": attention}


def train_flops_per_step(sizes: dict, seq_len: int, rows: int) -> float:
    forward = forward_flops_per_row(sizes, seq_len)
    return 3.0 * rows * (forward["dense"] + forward["attention"])
