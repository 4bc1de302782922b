"""LFM2 mixture-of-experts (``Lfm2MoeForCausalLM``): a decoder of gated short-convolution
and grouped-query attention layers whose feed-forward is a routed expert layer after the
leading dense ones. The glue to the program (``program.py``), the weights from the seed
(``weights.py``) and the plain float32 reference (``reference.py``), under the names
``benchmark/families/__init__.py`` lists. Served only: the family gives none of the
training names."""

from __future__ import annotations

from benchmark.families.lfm2_moe.program import (  # noqa: F401
    build_model, check_param_tree, from_program_params, model_config, to_program_params)
from benchmark.families.lfm2_moe.reference import score_served  # noqa: F401
from benchmark.families.lfm2_moe.weights import (  # noqa: F401
    build_weights, count_parameters, make_weights, seed_key)
from benchmark.harness import check

# the keys of a configuration file that size the model: the published config.json's own,
# then this benchmark's (the configuration's ``assumed`` says what they are)
SIZE_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size", "num_hidden_layers",
    "num_dense_layers", "layer_types", "num_attention_heads", "num_key_value_heads", "head_dim", "conv_L_cache",
    "num_experts", "num_experts_per_tok", "norm_topk_prob", "use_expert_bias", "routed_scaling_factor",
    "norm_eps", "rope_theta", "max_position_embeddings", "serving_context_tokens", "embedding_init_std",
    "router_init_std", "expert_bias_std", "expert_out_init_scale",
)

TICK_PROGRAM = "ragged_tick"


def warm_up_prompt_lengths(sizes: dict, shortest: int, longest: int) -> list:
    """One admission path and one tick program whatever the prompt: the shortest prompt
    and the longest run it through both."""
    return sorted({shortest, longest})


def live_cache_entries(sizes: dict, prompt_tokens: int, new_tokens: int) -> int:
    """Only the attention layers cache keys and values, and each is full attention: a
    request holds all its tokens in each of them (the convolution layers hold two
    columns, whatever the length). Counted a layer: the readers multiply by the
    attention layers."""
    return prompt_tokens + new_tokens


def check_served(weights, sizes: dict, served: list, limits: dict, checks: check.Checks, controls=()) -> dict:
    return check.served_token_deficits(score_served, weights, sizes, served, limits, checks, controls)
