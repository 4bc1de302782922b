"""Glue to the system under test: the family's one module that imports the program's
model. It builds the program's model and its train step from a configuration file, and
lays the benchmark's own weights (``weights.py``) into the program's parameter tree."""

from __future__ import annotations

import jax
import jax.numpy as jnp

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def model_config(config: dict):
    from perceiver_io_tpu.models.core.config import CausalSequenceModelConfig

    return CausalSequenceModelConfig.create(**config["sizes"], **config.get("execution", {}))


def build_model(config: dict, deterministic: bool, dtype_name: str | None = None):
    from perceiver_io_tpu.models.core.perceiver_ar import CausalSequenceModel

    dtype = DTYPES[dtype_name or config["compute_dtype"]]
    return CausalSequenceModel(config=model_config(config), deterministic=deterministic, dtype=dtype)


def make_program_train_step(model, tx, sizes: dict):
    """The program's own causal-LM step over the latents of each row."""
    from perceiver_io_tpu.training import trainer

    return trainer.make_causal_lm_train_step(model, tx, max_latents=sizes["max_latents"])


def to_program_params(weights: dict) -> dict:
    """The benchmark's weight tree in the layout of ``CausalSequenceModel``'s parameters.
    Pure renaming: every array is used as it is."""

    def attn(w):
        tree = {name + "_proj": {"kernel": w[name]} for name in ("q", "k", "v", "o")}
        if "o_bias" in w:
            tree["o_proj"]["bias"] = w["o_bias"]
        return tree

    def mlp(w):
        return {"norm": {"scale": w["norm_scale"], "bias": w["norm_bias"]},
                "dense_1": {"kernel": w["dense_1"]}, "dense_2": {"kernel": w["dense_2"]}}

    cross, layers = weights["cross"], weights["layers"]
    adapter = {"txt_embedding": {"embedding": weights["embedding"]}}
    if "pos_embedding" in weights:
        adapter["pos_embedding"] = {"embedding": weights["pos_embedding"]}
    params = {
        "ar": {
            "input_adapter": adapter,
            "cross_attention": {
                "cross_attn": {
                    "q_norm": {"scale": cross["q_norm_scale"], "bias": cross["q_norm_bias"]},
                    "kv_norm": {"scale": cross["kv_norm_scale"], "bias": cross["kv_norm_bias"]},
                    "attention": attn(cross["attn"]),
                },
                "mlp": mlp(cross["mlp"]),
            },
            "self_attention": {"layers": {
                "self_attn": {"norm": {"scale": layers["norm_scale"], "bias": layers["norm_bias"]},
                              "attention": attn(layers["attn"])},
                "mlp": mlp(layers["mlp"]),
            }},
        },
    }
    if "out_norm_scale" in weights:
        params["out_norm"] = {"scale": weights["out_norm_scale"], "bias": weights["out_norm_bias"]}
    if "out_bias" in weights:
        params["output_adapter"] = {"bias": weights["out_bias"]}
    return {"params": params}


def from_program_params(params: dict) -> dict:
    """Inverse of ``to_program_params`` (for reading a trained state back)."""
    p = params["params"]

    def attn(t):
        w = {name: t[name + "_proj"]["kernel"] for name in ("q", "k", "v", "o")}
        if "bias" in t["o_proj"]:
            w["o_bias"] = t["o_proj"]["bias"]
        return w

    def mlp(t):
        return {"norm_scale": t["norm"]["scale"], "norm_bias": t["norm"]["bias"],
                "dense_1": t["dense_1"]["kernel"], "dense_2": t["dense_2"]["kernel"]}

    ca = p["ar"]["cross_attention"]
    sa = p["ar"]["self_attention"]["layers"]
    adapter = p["ar"]["input_adapter"]
    weights = {
        "embedding": adapter["txt_embedding"]["embedding"],
        "cross": {
            "q_norm_scale": ca["cross_attn"]["q_norm"]["scale"], "q_norm_bias": ca["cross_attn"]["q_norm"]["bias"],
            "kv_norm_scale": ca["cross_attn"]["kv_norm"]["scale"], "kv_norm_bias": ca["cross_attn"]["kv_norm"]["bias"],
            "attn": attn(ca["cross_attn"]["attention"]), "mlp": mlp(ca["mlp"]),
        },
        "layers": {"norm_scale": sa["self_attn"]["norm"]["scale"], "norm_bias": sa["self_attn"]["norm"]["bias"],
                   "attn": attn(sa["self_attn"]["attention"]), "mlp": mlp(sa["mlp"])},
    }
    if "pos_embedding" in adapter:
        weights["pos_embedding"] = adapter["pos_embedding"]["embedding"]
    if "out_norm" in p:
        weights["out_norm_scale"], weights["out_norm_bias"] = p["out_norm"]["scale"], p["out_norm"]["bias"]
    if "output_adapter" in p and "bias" in p["output_adapter"]:
        weights["out_bias"] = p["output_adapter"]["bias"]
    return weights


def check_param_tree(model, params: dict) -> None:
    """The laid-out weights must be exactly the tree the program would initialise."""
    cfg = model.config
    want = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, cfg.max_seq_len), jnp.int32),
                           prefix_len=cfg.max_seq_len - cfg.max_latents))
    want_shapes = {jax.tree_util.keystr(k): v.shape for k, v in jax.tree_util.tree_flatten_with_path(want)[0]}
    got_shapes = {jax.tree_util.keystr(k): v.shape for k, v in jax.tree_util.tree_flatten_with_path(params)[0]}
    if want_shapes != got_shapes:
        diff = set(want_shapes.items()) ^ set(got_shapes.items())
        raise ValueError(f"the benchmark's weights do not match the program's parameter tree: {sorted(diff)}")
