"""Weights of a Falcon-H1 configuration, made from the seed on the device.

The benchmark owns the weights; the harness lays the same arrays into the program's
parameter tree (``program.py``) and the reference reads them as they are. Every leaf
comes from the seed, by the law of its kind:

* ``matrix`` ``(fan_in, fan_out)``: ``N(0, 1 / fan_in)``, so that a projection keeps its
  input's scale and the published multipliers act on what they were tuned for;
* ``embedding``: ``N(0, embedding_init_std)``;
* ``scale`` (norm weights): ``1 + N(0, 0.02)``; ``bias`` (the convolution's): ``N(0, 0.02)``;
* ``conv`` ``(d_conv, channels)``: ``N(0, 1 / d_conv)``;
* the mixer's own three, by Mamba-2's initialisation: ``A_log = log(A)`` with ``A`` uniform
  in [1, 16]; ``dt_bias`` the inverse softplus of ``dt``, log-uniform in [0.001, 0.1];
  ``D`` ones.

A leaf is made in the type it is used in, in its own jitted call, the two vocabulary
matrices first and in row blocks: 4.4B bfloat16 parameters are 8.8 GB of a 16 GB chip,
and one call for the whole tree would hold float32 noise for several matrices at once.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

_BLOCKED_FROM = 2 ** 28  # elements: larger leaves are drawn in row blocks
_ROW_BLOCKS = 16


def layer_shapes(sizes: dict) -> dict:
    d, f = sizes["hidden_size"], sizes["intermediate_size"]
    hq, hkv, hd = sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"]
    d_ssm, heads = sizes["mamba_d_ssm"], sizes["mamba_n_heads"]
    conv_dim = d_ssm + 2 * sizes["mamba_n_groups"] * sizes["mamba_d_state"]
    return {
        "input_layernorm": ((d,), "scale"), "pre_ff_layernorm": ((d,), "scale"),
        "q_proj": ((d, hq * hd), "matrix"), "k_proj": ((d, hkv * hd), "matrix"),
        "v_proj": ((d, hkv * hd), "matrix"), "o_proj": ((hq * hd, d), "matrix"),
        "in_proj": ((d, d_ssm + conv_dim + heads), "matrix"),
        "conv_weight": ((sizes["mamba_d_conv"], conv_dim), "conv"), "conv_bias": ((conv_dim,), "bias"),
        "dt_bias": ((heads,), "dt_bias"), "A_log": ((heads,), "a_log"), "D": ((heads,), "ones"),
        "mixer_norm": ((d_ssm,), "scale"), "out_proj": ((d_ssm, d), "matrix"),
        "gate_proj": ((d, f), "matrix"), "up_proj": ((d, f), "matrix"), "down_proj": ((f, d), "matrix"),
    }


def weight_shapes(sizes: dict) -> dict:
    """Tree of ``(shape, kind)``."""
    d, v = sizes["hidden_size"], sizes["vocab_size"]
    return {
        "embed_tokens": ((v, d), "embedding"), "lm_head": ((d, v), "matrix"), "final_layernorm": ((d,), "scale"),
        "layers": [layer_shapes(sizes) for _ in range(sizes["num_hidden_layers"])],
    }


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def count_parameters(sizes: dict) -> int:
    return sum(math.prod(shape) for shape, _ in jax.tree.leaves(weight_shapes(sizes), is_leaf=_is_spec))


def seed_key(seed: int):
    """A PRNG key from any whole-number seed (the driver's exceed 2**31)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2**31)), seed // (2**31))


def _leaf(key, shape: tuple, kind: str, embedding_std: float, dtype):
    f32 = jnp.float32
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "a_log":
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0)).astype(dtype)
    if kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f32, math.log(0.001), math.log(0.1)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)  # softplus(dt_bias) = dt
    std = {"matrix": shape[0] ** -0.5, "conv": shape[0] ** -0.5, "embedding": embedding_std}.get(kind, 0.02)

    def noise(k, part):
        return (std * jax.random.normal(k, part, f32) + (1.0 if kind == "scale" else 0.0)).astype(dtype)

    if math.prod(shape) < _BLOCKED_FROM or shape[0] % _ROW_BLOCKS:
        return noise(key, shape)
    part = (shape[0] // _ROW_BLOCKS, *shape[1:])
    blocks = jax.lax.map(lambda i: noise(jax.random.fold_in(key, i), part), jnp.arange(_ROW_BLOCKS))
    return blocks.reshape(shape)


def build_weights(sizes: dict, key, dtype=jnp.float32):
    """The whole tree from a key; traceable."""
    leaves, treedef = jax.tree.flatten(weight_shapes(sizes), is_leaf=_is_spec)
    std = sizes["embedding_init_std"]
    return jax.tree.unflatten(treedef, [_leaf(jax.random.fold_in(key, i), shape, kind, std, dtype)
                                        for i, (shape, kind) in enumerate(leaves)])


@functools.partial(jax.jit, static_argnames=("shape", "kind", "embedding_std", "dtype"))
def _make_leaf(key, shape, kind, embedding_std, dtype):
    return _leaf(key, shape, kind, embedding_std, dtype)


def make_weights(sizes: dict, seed: int, dtype=jnp.float32):
    """``build_weights`` leaf by leaf on the device, in ``dtype``, the largest first."""
    leaves, treedef = jax.tree.flatten(weight_shapes(sizes), is_leaf=_is_spec)
    key, std, out = seed_key(seed), sizes["embedding_init_std"], [None] * len(leaves)
    for i in sorted(range(len(leaves)), key=lambda i: -math.prod(leaves[i][0])):
        shape, kind = leaves[i]
        out[i] = _make_leaf(jax.random.fold_in(key, i), tuple(shape), kind, std, jnp.dtype(dtype))
        out[i].block_until_ready()
    return jax.tree.unflatten(treedef, out)
