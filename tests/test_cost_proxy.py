"""XLA-cost-proxy invariants (scripts/xla_cost_proxy.py: FLOPs and bytes
counted from compiled programs on the CPU).

The load-bearing discovery: XLA's cost_analysis counts a rolled ``lax.scan``
body ONCE, silently dividing the SA-stack FLOPs by num_layers — every proxy
config therefore unrolls its scan for counting. These tests pin that behavior
(if a jax upgrade starts counting rolled scans correctly, the ratio assertion
below fails and the unroll-for-counting workaround can be dropped) and the new
``EncoderConfig.scan_unroll`` knob's numerics-neutrality."""

import jax
import jax.numpy as jnp
import numpy as np

from perceiver_io_tpu.models.core.config import CausalSequenceModelConfig
from perceiver_io_tpu.models.core.perceiver_ar import CausalSequenceModel


def _fwd_flops(scan_unroll):
    cfg = CausalSequenceModelConfig(
        vocab_size=32, max_seq_len=32, max_latents=16, num_channels=32, num_heads=2,
        num_self_attention_layers=4, cross_attention_dropout=0.0, scan_unroll=scan_unroll,
    )
    model = CausalSequenceModel(config=cfg)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((2, 32), jnp.int32), prefix_len=16)
    )
    x = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    cost = (
        jax.jit(lambda p, xx: model.apply(p, xx, prefix_len=16)).lower(params, x).compile().cost_analysis()
    )
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return float(cost["flops"])


def test_cost_analysis_undercounts_rolled_scan():
    rolled, unrolled = _fwd_flops(1), _fwd_flops(4)
    assert np.isfinite(rolled) and np.isfinite(unrolled)
    # 4 scanned layers: the rolled count misses ~3 of them. If this starts
    # failing because rolled ~= unrolled, cost_analysis learned to multiply
    # loop bodies — drop the unroll-for-counting workaround in the proxy.
    assert unrolled > 1.8 * rolled


def test_encoder_scan_unroll_preserves_outputs():
    """EncoderConfig.scan_unroll is a pure execution knob: same checkpoint,
    same logits (mirrors the CLM-side scan_unroll equivalence)."""
    from perceiver_io_tpu.models.core.config import ClassificationDecoderConfig
    from perceiver_io_tpu.models.vision.image_classifier import (
        ImageClassifier,
        ImageClassifierConfig,
        ImageEncoderConfig,
    )

    def build(unroll):
        enc = ImageEncoderConfig(
            image_shape=(8, 8), num_frequency_bands=4, num_cross_attention_heads=1,
            num_self_attention_heads=2, num_self_attention_layers_per_block=2,
            num_self_attention_blocks=1, scan_unroll=unroll,
        )
        dec = ClassificationDecoderConfig(num_classes=4, num_output_query_channels=16,
                                          num_cross_attention_heads=1)
        cfg = ImageClassifierConfig(encoder=enc, decoder=dec, num_latents=4, num_latent_channels=16)
        return ImageClassifier(config=cfg)

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 8))
    params = build(1).init(jax.random.PRNGKey(1), x)
    out1 = build(1).apply(params, x)
    out2 = build(2).apply(params, x)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)
