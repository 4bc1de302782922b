"""The Falcon-H1 toy size the CPU tests share: every width small, every multiplier away
from 1, so that a dropped multiplier or a swapped segment shows in the logits."""

import jax.numpy as jnp

from benchmark.families import falcon_h1 as family

SIZES = dict(
    vocab_size=512, hidden_size=64, intermediate_size=128, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16, mamba_d_state=16,
    mamba_n_groups=2, mamba_d_conv=4, mamba_chunk_size=8, rms_norm_eps=1e-5, rope_theta=10000.0,
    embedding_multiplier=2.5, lm_head_multiplier=0.3, attention_in_multiplier=0.8, attention_out_multiplier=0.6,
    key_multiplier=0.7, ssm_in_multiplier=0.9, ssm_out_multiplier=0.5, ssm_multipliers=[0.7, 1.3, 0.6, 1.2, 0.8],
    mlp_multipliers=[0.9, 0.4], max_position_embeddings=4096, serving_context_tokens=96, embedding_init_std=0.3)
CONFIG = {"sizes": SIZES, "compute_dtype": "float32", "family": "falcon_h1"}


def build(seed: int = 5):
    """(model, params in the program's tree, the benchmark's weights)."""
    weights = family.make_weights(SIZES, seed, jnp.float32)
    model = family.build_model(CONFIG, deterministic=True)
    params = family.to_program_params(weights)
    family.check_param_tree(model, params)
    return model, params, weights
