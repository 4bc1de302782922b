"""Falcon-H1 (``FalconH1ForCausalLM``): a decoder whose every block runs a Mamba-2 mixer
and grouped-query attention in parallel, then a gated MLP. The glue to the program
(``program.py``), the weights from the seed (``weights.py``) and the plain float32
reference (``reference.py``), under the names ``benchmark/families/__init__.py`` lists.
Served only: the family gives none of the training names."""

from __future__ import annotations

from benchmark.families.falcon_h1.program import (  # noqa: F401
    build_model, check_param_tree, from_program_params, model_config, to_program_params)
from benchmark.families.falcon_h1.reference import score_served  # noqa: F401
from benchmark.families.falcon_h1.weights import (  # noqa: F401
    build_weights, count_parameters, make_weights, seed_key)
from benchmark.harness import check

# the keys of a configuration file that size the model: the published config.json's own,
# then two of this benchmark's (the configuration's ``assumed`` says what they are)
SIZE_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "mamba_d_ssm", "mamba_n_heads", "mamba_d_head", "mamba_d_state",
    "mamba_n_groups", "mamba_d_conv", "mamba_chunk_size", "rms_norm_eps", "rope_theta",
    "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier", "attention_out_multiplier",
    "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier", "ssm_multipliers", "mlp_multipliers",
    "max_position_embeddings", "serving_context_tokens", "embedding_init_std",
)

TICK_PROGRAM = "ragged_tick"


def warm_up_prompt_lengths(sizes: dict, shortest: int, longest: int) -> list:
    """One admission path and one tick program whatever the prompt: the shortest prompt
    (one chunk) and the longest (every chunk boundary) run it through both."""
    return sorted({shortest, longest})


def live_cache_entries(sizes: dict, prompt_tokens: int, new_tokens: int) -> int:
    """Every layer is full attention: a request holds all its tokens."""
    return prompt_tokens + new_tokens


def check_served(weights, sizes: dict, served: list, limits: dict, checks: check.Checks, controls=()) -> dict:
    return check.served_token_deficits(score_served, weights, sizes, served, limits, checks, controls)
