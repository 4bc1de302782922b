"""A fixture for ``benchmark/tests/test_families.py``: a second family, added by files.
Its configuration files size a model under other names (``hidden_size``, a list-valued
``layer_kinds``); it maps them onto the program the benchmark already drives, so both
drivers can run it to ``correct`` without any file of the harness knowing it."""

from __future__ import annotations

from benchmark.families import perceiver_ar as base
from benchmark.harness import check

SIZE_KEYS = ("vocab_size", "hidden_size", "head_count", "context", "latents", "layer_kinds", "init_scale")
TICK_PROGRAM = base.TICK_PROGRAM


def _sizes(sizes: dict) -> dict:
    kinds = sizes["layer_kinds"]
    rotary = sum(kind == "rotary" for kind in kinds)
    if kinds[:rotary] != ["rotary"] * rotary:
        raise ValueError(f"rotary layers lead the pattern, got {kinds}")
    return {
        "vocab_size": sizes["vocab_size"], "max_seq_len": sizes["context"], "max_latents": sizes["latents"],
        "num_channels": sizes["hidden_size"], "num_heads": sizes["head_count"],
        "num_self_attention_layers": len(kinds), "num_self_attention_rotary_layers": rotary,
        "self_attention_widening_factor": 4, "cross_attention_widening_factor": 4, "cross_attention_dropout": 0.0,
        "abs_pos_emb": False, "output_norm": True, "output_bias": False, "init_scale": sizes["init_scale"],
    }


def build_model(config: dict, deterministic: bool):
    return base.build_model({**config, "sizes": _sizes(config["sizes"])}, deterministic)


to_program_params, from_program_params = base.to_program_params, base.from_program_params
check_param_tree, seed_key, leaf_norms = base.check_param_tree, base.seed_key, base.leaf_norms


def build_weights(sizes: dict, key, dtype):
    return base.build_weights(_sizes(sizes), key, dtype)


def make_weights(sizes: dict, seed: int, dtype):
    return base.make_weights(_sizes(sizes), seed, dtype)


def warm_up_prompt_lengths(sizes: dict, shortest: int, longest: int) -> list:
    return base.warm_up_prompt_lengths(_sizes(sizes), shortest, longest)


def live_cache_entries(sizes: dict, prompt_tokens: int, new_tokens: int) -> int:
    return min(prompt_tokens + new_tokens, sizes["context"])


def check_served(weights, sizes: dict, served: list, limits: dict, checks: check.Checks, controls=()) -> dict:
    return check.served_token_deficits(base.score_served, weights, _sizes(sizes), served, limits, checks, controls)


def make_program_train_step(model, tx, sizes: dict):
    return base.make_program_train_step(model, tx, _sizes(sizes))


def row_tokens(sizes: dict) -> tuple:
    return sizes["context"], sizes["latents"]


def make_train_step(sizes: dict, optimizer: dict, rows_per_block: int, precision: str = "float32"):
    return base.make_train_step(_sizes(sizes), optimizer, rows_per_block, precision)
