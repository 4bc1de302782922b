"""Nemotron-H (``NemotronHForCausalLM``): a decoder whose every layer is one mixer, a
Mamba-2 mixer, routed experts beside a shared expert, or grouped-query attention, by a
pattern; served on one chip's share of its experts and its vocabulary. The glue to the
program (``program.py``), the weights from the seed (``weights.py``) and the plain float32
reference (``reference.py``), under the names ``benchmark/families/__init__.py`` lists.
Served only: the family gives none of the training names."""

from __future__ import annotations

from benchmark.families.nemotron_h.program import (  # noqa: F401
    build_model, check_param_tree, from_program_params, model_config, to_program_params)
from benchmark.families.nemotron_h.reference import score_served  # noqa: F401
from benchmark.families.nemotron_h.weights import (  # noqa: F401
    build_weights, count_parameters, make_weights, seed_key)
from benchmark.harness import check

# the keys of a configuration file that size the model: the published config.json's own
# (``n_routed_experts`` counts the experts held here), then this benchmark's (the
# configuration's ``assumed`` and ``reduced`` say what they are)
SIZE_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "hybrid_override_pattern", "num_attention_heads",
    "num_key_value_heads", "head_dim", "mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size",
    "conv_kernel", "chunk_size", "moe_intermediate_size", "moe_shared_expert_intermediate_size", "n_routed_experts",
    "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor", "layer_norm_epsilon", "time_step_min",
    "time_step_max", "time_step_floor", "max_position_embeddings", "router_experts", "experts_held_first",
    "serving_context_tokens", "embedding_init_std", "router_init_std", "expert_bias_std", "expert_out_init_scale",
)

TICK_PROGRAM = "ragged_tick"


def warm_up_prompt_lengths(sizes: dict, shortest: int, longest: int) -> list:
    """One admission path and one tick program whatever the prompt: the shortest prompt
    and the longest run it through both."""
    return sorted({shortest, longest})


def live_cache_entries(sizes: dict, prompt_tokens: int, new_tokens: int) -> int:
    """Only the ``*`` layers cache keys and values, and each is full attention: a request
    holds all its tokens in each of them (an ``M`` layer holds a state of fixed size).
    Counted a layer: the readers multiply by the attention layers."""
    return prompt_tokens + new_tokens


def check_served(weights, sizes: dict, served: list, limits: dict, checks: check.Checks, controls=()) -> dict:
    return check.served_token_deficits(score_served, weights, sizes, served, limits, checks, controls)
