"""Weights of an LFM2 mixture-of-experts configuration, made from the seed on the device.

The benchmark owns the weights; the harness lays the same arrays into the program's
parameter tree (``program.py``) and the reference reads them as they are. Every leaf
comes from the seed, by the law of its kind:

* ``matrix`` ``(fan_in, fan_out)`` and ``stack`` ``(experts, fan_in, fan_out)``: ``N(0, 1 /
  fan_in)``, so that a projection keeps its input's scale;
* ``stack_out``, the experts' down matrices: ``N(0, expert_out_init_scale^2 / fan_in)``. With
  sigmoid scores the four chosen experts weigh about a quarter each, so ONE routing choice
  that rounding flips swaps a quarter of the layer's output; at scale 1 that moved the
  residual stream by 5-10%, flipped further choices downstream, and the bfloat16 program's
  own tokens lay 14% of the logits' spread under the reference's best, as those of the
  reference with bfloat16 operands did (mean deficit 0.12 and 0.09; PERF.md section 6, PR
  44): rounding, which a check cannot tell from a fault of that size. At 0.3 an expert
  layer adds a third of what a convolution or attention adds, one flip moves the stream
  by 1-2%, and against a sound mean of 0.010 a dropped bias reads 0.057, one expert layer
  of twelve with its down matrices swapped 0.020, float8 or int8 operands 0.40;
* ``embedding``: ``N(0, embedding_init_std)``;
* ``scale`` (norm weights): ``1 + N(0, 0.02)``;
* ``conv`` ``(taps, channels)``: ``N(0, 1 / taps)``;
* ``router`` ``(hidden, experts)``: ``N(0, router_init_std^2 / hidden)``: scores spread by
  ``router_init_std`` around 0 before the sigmoid, so that the top four are decided by
  more than rounding and a wrong expert moves the logits;
* ``expert_bias``: ``N(0, expert_bias_std)``, NON-zero, about a tenth of the scores'
  spread after the sigmoid: it changes choices (a dropped bias routes other experts).

A leaf is made in the type it is used in, in its own jitted call, the largest first and in
row blocks: 4.6B bfloat16 parameters are 9.2 GB of a 16 GB chip, and one call for the
whole tree would hold float32 noise for several matrices at once.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

_BLOCKED_FROM = 2 ** 27  # elements: larger leaves are drawn in blocks of their first axis
_BLOCKS = 16


def layer_shapes(sizes: dict, layer: int) -> dict:
    d, f, w = sizes["hidden_size"], sizes["intermediate_size"], sizes["moe_intermediate_size"]
    hq, hkv, hd = sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"]
    experts = sizes["num_experts"]
    shapes = {"operator_norm": ((d,), "scale"), "ffn_norm": ((d,), "scale")}
    if sizes["layer_types"][layer] == "conv":
        shapes.update({"in_proj": ((d, 3 * d), "matrix"), "conv": ((sizes["conv_L_cache"], d), "conv"),
                       "out_proj": ((d, d), "matrix")})
    else:
        shapes.update({"q_proj": ((d, hq * hd), "matrix"), "k_proj": ((d, hkv * hd), "matrix"),
                       "v_proj": ((d, hkv * hd), "matrix"), "o_proj": ((hq * hd, d), "matrix"),
                       "q_layernorm": ((hd,), "scale"), "k_layernorm": ((hd,), "scale")})
    if layer < sizes["num_dense_layers"]:
        shapes.update({"w1": ((d, f), "matrix"), "w3": ((d, f), "matrix"), "w2": ((f, d), "matrix")})
    else:
        # every expert's gate matrix beside its up matrix, then its down matrix
        shapes.update({"router": ((d, experts), "router"), "expert_bias": ((experts,), "expert_bias"),
                       "experts_w13": ((experts, d, 2 * w), "stack"), "experts_w2": ((experts, w, d), "stack_out")})
    return shapes


def weight_shapes(sizes: dict) -> dict:
    """Tree of ``(shape, kind)``. The head is the embedding (``tie_embedding``)."""
    d, v = sizes["hidden_size"], sizes["vocab_size"]
    return {"embed_tokens": ((v, d), "embedding"), "embedding_norm": ((d,), "scale"),
            "layers": [layer_shapes(sizes, i) for i in range(sizes["num_hidden_layers"])]}


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def count_parameters(sizes: dict) -> int:
    return sum(math.prod(shape) for shape, _ in jax.tree.leaves(weight_shapes(sizes), is_leaf=_is_spec))


def seed_key(seed: int):
    """A PRNG key from any whole-number seed (the driver's exceed 2**31)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2**31)), seed // (2**31))


def _leaf(key, shape: tuple, kind: str, stds: tuple, dtype):
    embedding_std, router_std, bias_std, expert_out_scale = stds
    fan_in = shape[-2] if kind in ("stack", "stack_out") else shape[0]
    std = {"matrix": fan_in ** -0.5, "stack": fan_in ** -0.5, "stack_out": expert_out_scale * fan_in ** -0.5,
           "conv": fan_in ** -0.5, "embedding": embedding_std, "router": router_std * fan_in ** -0.5,
           "expert_bias": bias_std}.get(kind, 0.02)

    def noise(k, part):
        return (std * jax.random.normal(k, part, jnp.float32) + (1.0 if kind == "scale" else 0.0)).astype(dtype)

    if math.prod(shape) < _BLOCKED_FROM or shape[0] % _BLOCKS:
        return noise(key, shape)
    part = (shape[0] // _BLOCKS, *shape[1:])
    blocks = jax.lax.map(lambda i: noise(jax.random.fold_in(key, i), part), jnp.arange(_BLOCKS))
    return blocks.reshape(shape)


def _stds(sizes: dict) -> tuple:
    return (sizes["embedding_init_std"], sizes["router_init_std"], sizes["expert_bias_std"],
            sizes["expert_out_init_scale"])


def build_weights(sizes: dict, key, dtype=jnp.float32):
    """The whole tree from a key; traceable."""
    leaves, treedef = jax.tree.flatten(weight_shapes(sizes), is_leaf=_is_spec)
    return jax.tree.unflatten(treedef, [_leaf(jax.random.fold_in(key, i), shape, kind, _stds(sizes), dtype)
                                        for i, (shape, kind) in enumerate(leaves)])


@functools.partial(jax.jit, static_argnames=("shape", "kind", "stds", "dtype"))
def _make_leaf(key, shape, kind, stds, dtype):
    return _leaf(key, shape, kind, stds, dtype)


def make_weights(sizes: dict, seed: int, dtype=jnp.float32):
    """``build_weights`` leaf by leaf on the device, in ``dtype``, the largest first."""
    leaves, treedef = jax.tree.flatten(weight_shapes(sizes), is_leaf=_is_spec)
    key, out = seed_key(seed), [None] * len(leaves)
    for i in sorted(range(len(leaves)), key=lambda i: -math.prod(leaves[i][0])):
        shape, kind = leaves[i]
        out[i] = _make_leaf(jax.random.fold_in(key, i), tuple(shape), kind, _stds(sizes), jnp.dtype(dtype))
        out[i].block_until_ready()
    return jax.tree.unflatten(treedef, out)
