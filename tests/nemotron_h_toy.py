"""The Nemotron-H toy size the CPU tests share: every width small, the published pattern
in miniature (``M``, ``E`` and ``*`` layers alone in one stack), an expert width that is NO
multiple of 128, a router over 8 experts of which 4 are held, a bias that changes choices."""

import jax.numpy as jnp

from benchmark.families import nemotron_h as family

SIZES = dict(
    vocab_size=512, hidden_size=64, num_hidden_layers=7, hybrid_override_pattern="MEM*EME", num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, mamba_num_heads=8, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
    conv_kernel=4, chunk_size=8, moe_intermediate_size=40, moe_shared_expert_intermediate_size=80,
    n_routed_experts=4, num_experts_per_tok=3, norm_topk_prob=True, routed_scaling_factor=2.5,
    layer_norm_epsilon=1e-5, time_step_min=0.001, time_step_max=0.1, time_step_floor=1e-4,
    max_position_embeddings=4096, router_experts=8, experts_held_first=0, serving_context_tokens=96,
    embedding_init_std=0.3, router_init_std=1.0, expert_bias_std=0.1, expert_out_init_scale=1.0)
CONFIG = {"sizes": SIZES, "compute_dtype": "float32", "family": "nemotron_h"}


def build(seed: int = 5, sizes: dict = SIZES):
    """(model, params in the program's tree, the benchmark's weights)."""
    weights = family.make_weights(sizes, seed, jnp.float32)
    model = family.build_model({**CONFIG, "sizes": sizes}, deterministic=True)
    params = family.to_program_params(weights)
    family.check_param_tree(model, params)
    return model, params, weights
