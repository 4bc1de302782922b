"""Analytic FLOPs model for Perceiver AR training and the MFU meter.

Mirrors the accounting of the reference's scaling study
(/root/reference/examples/scaling/clm/scaling/flops.py:27-110): a Perceiver AR
step costs a decoder-only-transformer's FLOPs over the latents plus the prefix
cross-attention contribution (scaled by 1 - prefix_dropout), with the 3x
forward->forward+backward rule from Kaplan et al. The reference only used this
model offline for scaling-law fits; here it also powers the live tokens/sec and
MFU telemetry (the BASELINE.json north-star metric the reference never measured).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from perceiver_io_tpu.models.core.config import CausalSequenceModelConfig

# bf16 peak FLOP/s of one chip, keyed by a lower-case substring of the
# ``device_kind`` JAX reports ("TPU v5 lite" is a v5e). Source: Google Cloud
# TPU documentation, the system-architecture page of each generation
# ("TPU v4": 275, "TPU v5e": 197, "TPU v5p": 459, "TPU v6e": 918 TFLOP/s).
TPU_PEAK_FLOPS = {
    "v4": 275e12,
    "v5e": 197e12,
    "v5 lite": 197e12,
    "v5p": 459e12,
    "v6e": 918e12,
}


def detect_peak_flops(device_kind: Optional[str] = None) -> float:
    """Peak bf16 FLOP/s of ``device_kind`` (default: the kind of the device
    this process computes on). A kind the table does not hold is an error,
    never a default: a utilisation against the wrong peak is a wrong number."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    kind = device_kind.lower()
    for name, peak in TPU_PEAK_FLOPS.items():
        if name in kind:
            return peak
    raise ValueError(
        f"no peak FLOP/s on record for device_kind {device_kind!r} "
        f"(known: {sorted(TPU_PEAK_FLOPS)}); add it to TPU_PEAK_FLOPS with its source"
    )


@dataclass
class PerceiverARFlops:
    """Training FLOPs per step for a CausalSequenceModel configuration."""

    config: CausalSequenceModelConfig
    seq_len: int  # actual training sequence length (<= max_seq_len)
    prefix_dropout: float = 0.0

    @property
    def num_latents(self) -> int:
        return min(self.config.max_latents, self.seq_len)

    @property
    def num_prefix(self) -> int:
        return self.seq_len - self.num_latents

    def forward_flops_per_latent(self) -> float:
        c = self.config.num_channels
        n_lat = self.num_latents
        # self-attention stack (decoder-only-equivalent): qkv + scores + out + MLP
        num_layers = self.config.num_self_attention_layers + 1  # incl. hybrid cross layer's q path
        attn = (6 * c**2 + 2 * c * n_lat + 2 * c**2) * num_layers
        mlp = (4 * self.config.self_attention_widening_factor * c**2) * num_layers
        logits = 2 * c * self.config.vocab_size
        embed = 4 * c
        # prefix cross-attention extra: kv projections + scores over kept prefix
        ratio = self.num_prefix / max(1, self.num_latents)
        keep = 1.0 - self.prefix_dropout
        cross = (4 * c**2 + 2 * c * n_lat) * ratio * keep + 4 * c * ratio
        return embed + attn + mlp + logits + cross

    def train_flops_per_step(self, batch_size: int) -> float:
        return 3.0 * self.forward_flops_per_latent() * self.num_latents * batch_size

    def tokens_per_step(self, batch_size: int) -> int:
        """Latent tokens receiving a loss per step (the unit the reference's
        scaling study counts as 'training tokens')."""
        return batch_size * self.num_latents


def mfu(tokens_per_sec: float, flops_model: PerceiverARFlops, batch_size: int, peak_flops: float) -> float:
    steps_per_sec = tokens_per_sec / flops_model.tokens_per_step(batch_size)
    return steps_per_sec * flops_model.train_flops_per_step(batch_size) / peak_flops
