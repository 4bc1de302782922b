"""The LFM2 mixture-of-experts toy size the CPU tests share: every width small, the
published pattern in miniature (a leading dense convolution layer, then attention and
convolution layers with routed experts), a router whose bias changes choices."""

import jax.numpy as jnp

from benchmark.families import lfm2_moe as family

SIZES = dict(
    vocab_size=512, hidden_size=64, intermediate_size=128, moe_intermediate_size=32, num_hidden_layers=5,
    num_dense_layers=1, layer_types=["conv", "full_attention", "conv", "conv", "full_attention"],
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, conv_L_cache=3, num_experts=8, num_experts_per_tok=2,
    norm_topk_prob=True, use_expert_bias=True, routed_scaling_factor=1.0, norm_eps=1e-5, rope_theta=10000.0,
    max_position_embeddings=4096, serving_context_tokens=96, embedding_init_std=0.3, router_init_std=1.0,
    expert_bias_std=0.1, expert_out_init_scale=1.0)
CONFIG = {"sizes": SIZES, "compute_dtype": "float32", "family": "lfm2_moe"}


def build(seed: int = 5):
    """(model, params in the program's tree, the benchmark's weights)."""
    weights = family.make_weights(SIZES, seed, jnp.float32)
    model = family.build_model(CONFIG, deterministic=True)
    params = family.to_program_params(weights)
    family.check_param_tree(model, params)
    return model, params, weights
