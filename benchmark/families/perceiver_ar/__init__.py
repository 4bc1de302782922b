"""Perceiver AR as a causal language model (``CausalSequenceModel``): the glue to the
program (``program.py``), the weights from the seed (``weights.py``) and the plain
float32 reference (``reference.py``), under the names ``benchmark/families/__init__.py``
lists, and what the serving driver asks of this model."""

from __future__ import annotations

from benchmark.families.perceiver_ar.program import (  # noqa: F401
    build_model, check_param_tree, from_program_params, make_program_train_step, model_config, to_program_params)
from benchmark.families.perceiver_ar.reference import (  # noqa: F401
    causal_lm_loss, leaf_norms, make_train_step, score_served)
from benchmark.families.perceiver_ar.weights import (  # noqa: F401
    build_weights, count_parameters, make_weights, seed_key)
from benchmark.harness import check

# the keys of a configuration file that size the model (the rest documents it)
SIZE_KEYS = (
    "vocab_size", "max_seq_len", "max_latents", "num_channels", "num_heads",
    "num_self_attention_layers", "num_self_attention_rotary_layers",
    "self_attention_widening_factor", "cross_attention_widening_factor",
    "cross_attention_dropout", "abs_pos_emb", "output_norm", "output_bias", "init_scale",
)

TICK_PROGRAM = "ragged_tick"


def warm_up_prompt_lengths(sizes: dict, shortest: int, longest: int) -> list:
    """One prompt under the latent count where the mix has such (prefill + install), one
    over it (chunks and finish inside the tick)."""
    latents = sizes["max_latents"]
    return [n for n in (min(shortest, latents - 1) if shortest < latents else None, max(longest, latents)) if n]


def live_cache_entries(sizes: dict, prompt_tokens: int, new_tokens: int) -> int:
    """The window holds the newest ``max_seq_len`` tokens."""
    return min(prompt_tokens + new_tokens, sizes["max_seq_len"])


def row_tokens(sizes: dict) -> tuple:
    """A training row feeds the whole window and trains its latents."""
    return sizes["max_seq_len"], sizes["max_latents"]


def check_served(weights, sizes: dict, served: list, limits: dict, checks: check.Checks, controls=()) -> dict:
    return check.served_token_deficits(score_served, weights, sizes, served, limits, checks, controls)
