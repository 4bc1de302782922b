"""Glue to the system under test: the family's one module that imports the program's
model. It builds the program's model from a configuration file and lays the benchmark's
own weights (``weights.py``) into the program's parameter tree."""

from __future__ import annotations

import jax
import jax.numpy as jnp

# imported where the family is: a program that has no such model (a parent commit under
# this benchmark) fails when the cell is resolved, at once, before any device is touched
from perceiver_io_tpu.models.core.config import NemotronHConfig

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
TOP_LEVEL = ("embed_tokens", "lm_head", "final_norm")


def model_config(config: dict):
    sizes = config["sizes"]
    # the file's ``n_routed_experts`` counts the experts HELD here (``reduced``); the router's
    # width is stated beside it as published
    return NemotronHConfig.create(**{**sizes, "n_routed_experts": sizes["router_experts"]},
                                  experts_held=(sizes["experts_held_first"], sizes["n_routed_experts"]),
                                  max_seq_len=sizes["serving_context_tokens"], init_scale=sizes["embedding_init_std"])


def build_model(config: dict, deterministic: bool, dtype_name: str | None = None):
    from perceiver_io_tpu.models.core.nemotron_h import NemotronHForCausalLM

    dtype = DTYPES[dtype_name or config["compute_dtype"]]
    return NemotronHForCausalLM(config=model_config(config), deterministic=deterministic, dtype=dtype,
                                param_dtype=dtype)


def to_program_params(weights: dict) -> dict:
    """The benchmark's weight tree in the layout of ``NemotronHForCausalLM``'s parameters:
    one leaf a matrix (an expert layer's two stacks are leaves, as ``weights.py`` laid them
    out), a layer's under ``layers_<i>_<name>``. Pure renaming: no array is made."""
    params = {name: weights[name] for name in TOP_LEVEL}
    for i, layer in enumerate(weights["layers"]):
        params.update({f"layers_{i}_{name}": leaf for name, leaf in layer.items()})
    return {"params": params}


def from_program_params(params: dict) -> dict:
    """Inverse of ``to_program_params``."""
    p = params["params"]
    depth = 1 + max(int(k.split("_")[1]) for k in p if k.startswith("layers_"))
    layers = [{k.split("_", 2)[2]: v for k, v in p.items() if k.startswith(f"layers_{i}_")} for i in range(depth)]
    return {**{name: p[name] for name in TOP_LEVEL}, "layers": layers}


def check_param_tree(model, params: dict) -> None:
    """The laid-out weights must be exactly the tree the program would initialise."""
    want = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    want_shapes = {jax.tree_util.keystr(k): v.shape for k, v in jax.tree_util.tree_flatten_with_path(want)[0]}
    got_shapes = {jax.tree_util.keystr(k): v.shape for k, v in jax.tree_util.tree_flatten_with_path(params)[0]}
    if want_shapes != got_shapes:
        diff = set(want_shapes.items()) ^ set(got_shapes.items())
        raise ValueError(f"the benchmark's weights do not match the program's parameter tree: {sorted(diff)}")
