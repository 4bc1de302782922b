"""Weights of a Perceiver AR configuration, made from the seed on the device.

The benchmark owns the weights: one jitted call makes the whole tree in the type it is
used in, the harness lays the same arrays into the program's parameter tree
(``program.py``), and the reference reads them as they are. Nothing here comes
from the program.

Every leaf is random so that a dropped bias or norm parameter shows: matrices and
embeddings ``N(0, init_scale)``, norm scales ``1 + N(0, init_scale)``, biases
``N(0, init_scale)``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def weight_shapes(sizes: dict) -> dict:
    """Tree of ``(shape, kind)`` with kind in matrix | scale | bias."""
    c, v = sizes["num_channels"], sizes["vocab_size"]
    layers = sizes["num_self_attention_layers"]

    def attn(lead, out_bias):
        tree = {name: ((*lead, c, c), "matrix") for name in ("q", "k", "v", "o")}
        if out_bias:
            tree["o_bias"] = ((*lead, c), "bias")
        return tree

    def mlp(lead, factor):
        return {
            "norm_scale": ((*lead, c), "scale"), "norm_bias": ((*lead, c), "bias"),
            "dense_1": ((*lead, c, factor * c), "matrix"),
            "dense_2": ((*lead, factor * c, c), "matrix"),
        }

    tree = {
        "embedding": ((v, c), "matrix"),
        "cross": {
            "q_norm_scale": ((c,), "scale"), "q_norm_bias": ((c,), "bias"),
            "kv_norm_scale": ((c,), "scale"), "kv_norm_bias": ((c,), "bias"),
            "attn": attn((), True),
            "mlp": mlp((), sizes["cross_attention_widening_factor"]),
        },
        "layers": {
            "norm_scale": ((layers, c), "scale"), "norm_bias": ((layers, c), "bias"),
            "attn": attn((layers,), False),
            "mlp": mlp((layers,), sizes["self_attention_widening_factor"]),
        },
    }
    if sizes["abs_pos_emb"]:
        tree["pos_embedding"] = ((sizes["max_seq_len"], c), "matrix")
    if sizes["output_norm"]:
        tree["out_norm_scale"] = ((c,), "scale")
        tree["out_norm_bias"] = ((c,), "bias")
    if sizes["output_bias"]:
        tree["out_bias"] = ((v,), "bias")
    return tree


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def count_parameters(sizes: dict) -> int:
    total = 0
    for shape, _ in jax.tree.leaves(weight_shapes(sizes), is_leaf=_is_spec):
        n = 1
        for d in shape:
            n *= d
        total += n
    return total


def seed_key(seed: int):
    """A PRNG key from any whole-number seed (the driver's exceed 2**31)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2**31)), seed // (2**31))


def build_weights(sizes: dict, key, dtype=jnp.float32):
    """The whole tree from a key; traceable, so a caller may jit it into its own set-up."""
    leaves, treedef = jax.tree.flatten(weight_shapes(sizes), is_leaf=_is_spec)
    scale = sizes["init_scale"]
    out = []
    for i, (shape, kind) in enumerate(leaves):
        noise = scale * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        out.append((noise + 1.0 if kind == "scale" else noise).astype(dtype))
    return jax.tree.unflatten(treedef, out)


def make_weights(sizes: dict, seed: int, dtype=jnp.float32):
    """The whole tree in one jitted call, in ``dtype``, on the device."""
    return jax.jit(lambda key: build_weights(sizes, key, dtype))(seed_key(seed))
