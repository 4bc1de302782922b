"""Weights of a Nemotron-H configuration, made from the seed on the device.

The benchmark owns the weights; the harness lays the same arrays into the program's
parameter tree (``program.py``) and the reference reads them as they are. Every leaf
comes from the seed, by the law of its kind:

* ``matrix`` ``(fan_in, fan_out)``: ``N(0, 1 / fan_in)``, so that a projection keeps its
  input's scale;
* ``stack_up`` ``(held, hidden, padded width)`` and ``stack_down`` ``(held, padded width,
  hidden)``: the HELD experts' two matrices, ``N(0, 1 / fan_in)`` and ``N(0,
  expert_out_init_scale^2 / fan_in)`` over the published ``moe_intermediate_size``, ZERO in
  the columns / rows from there to ``pad_to_lanes`` of it (1856 -> 1920). The program's
  grouped kernels want lane-aligned matrices, and this is where that layout is made, once:
  ONE copy of the stacks lives on the device, the reference reads the published columns of
  the same arrays, and ``relu(0)^2 = 0`` through zero rows makes the padding exact.
  ``shared_down`` takes the same ``expert_out_init_scale``: what ``relu(z)^2`` leaves has a
  second moment of 1.5, and with a sigmoid top-6 at ``routed_scaling_factor`` 2.5 a chosen
  expert weighs about 0.4, so at scale 1 one routing choice that rounding flips would move
  the stream by half of what an ``M`` layer adds (the configuration's ``assumed`` has the
  readings that chose the scale);
* ``embedding``: ``N(0, embedding_init_std)``;
* ``scale`` (norm weights): ``1 + N(0, 0.02)``; ``bias`` (the convolution's): ``N(0, 0.02)``;
* ``conv`` ``(conv_kernel, channels)``: ``N(0, 1 / conv_kernel)``;
* ``router`` ``(hidden, router_experts)``: ``N(0, router_init_std^2 / hidden)``;
* ``expert_bias`` (the published ``e_score_correction_bias``): ``N(0, expert_bias_std)``,
  NON-zero: it changes choices (a dropped bias routes other experts);
* the mixer's own three, by Mamba-2's initialisation: ``A_log = log(A)`` with ``A`` uniform
  in [1, 16]; ``dt_bias`` the inverse softplus of ``dt``, log-uniform in [``time_step_min``,
  ``time_step_max``] and at least ``time_step_floor``; ``D`` ones.

A leaf is made in the type it is used in, in its own jitted call, the largest first and in
blocks: 4.7B bfloat16 parameters are 9.4 GB of a 16 GB chip, and one call for the whole
tree would hold float32 noise for several matrices at once.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

LANES = 128
_BLOCKED_FROM = 2 ** 27  # elements: larger leaves are drawn in blocks of their first axis
_BLOCKS = 16


def pad_to_lanes(width: int) -> int:
    return -(-width // LANES) * LANES


def layer_shapes(sizes: dict, kind: str) -> dict:
    d, heads = sizes["hidden_size"], sizes["mamba_num_heads"]
    inner = heads * sizes["mamba_head_dim"]
    conv_dim = inner + 2 * sizes["n_groups"] * sizes["ssm_state_size"]
    hq, hkv, hd = sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"]
    held, routed = sizes["n_routed_experts"], sizes["router_experts"]
    width, shared = pad_to_lanes(sizes["moe_intermediate_size"]), sizes["moe_shared_expert_intermediate_size"]
    return {"norm": ((d,), "scale"), **{
        "M": {"in_proj": ((d, inner + conv_dim + heads), "matrix"),
              "conv_weight": ((sizes["conv_kernel"], conv_dim), "conv"), "conv_bias": ((conv_dim,), "bias"),
              "dt_bias": ((heads,), "dt_bias"), "A_log": ((heads,), "a_log"), "D": ((heads,), "ones"),
              "mixer_norm": ((inner,), "scale"), "out_proj": ((inner, d), "matrix")},
        "*": {"q_proj": ((d, hq * hd), "matrix"), "k_proj": ((d, hkv * hd), "matrix"),
              "v_proj": ((d, hkv * hd), "matrix"), "o_proj": ((hq * hd, d), "matrix")},
        "E": {"router": ((d, routed), "router"), "expert_bias": ((routed,), "expert_bias"),
              "experts_up": ((held, d, width), "stack_up"), "experts_down": ((held, width, d), "stack_down"),
              "shared_up": ((d, shared), "matrix"), "shared_down": ((shared, d), "matrix_out")},
    }[kind]}


def weight_shapes(sizes: dict) -> dict:
    """Tree of ``(shape, kind)``."""
    d, v = sizes["hidden_size"], sizes["vocab_size"]
    return {"embed_tokens": ((v, d), "embedding"), "lm_head": ((d, v), "matrix"), "final_norm": ((d,), "scale"),
            "layers": [layer_shapes(sizes, kind) for kind in sizes["hybrid_override_pattern"]]}


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def count_parameters(sizes: dict) -> int:
    """The PUBLISHED parameters of the share: the stacks at ``moe_intermediate_size``, not
    at the lanes' multiple they are laid out in."""
    width = sizes["moe_intermediate_size"]
    return sum(math.prod(shape) // pad_to_lanes(width) * width if kind in ("stack_up", "stack_down") else math.prod(shape)
               for shape, kind in jax.tree.leaves(weight_shapes(sizes), is_leaf=_is_spec))


def seed_key(seed: int):
    """A PRNG key from any whole-number seed (the driver's exceed 2**31)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2**31)), seed // (2**31))


def _leaf(key, shape: tuple, kind: str, laws: tuple, dtype):
    embedding_std, router_std, bias_std, expert_out_scale, width, dt_min, dt_max, dt_floor = laws
    f32 = jnp.float32
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "a_log":
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0)).astype(dtype)
    if kind == "dt_bias":
        dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, f32, math.log(dt_min), math.log(dt_max))), dt_floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)  # softplus(dt_bias) = dt
    # an expert's fan-in is the published width, whatever the stack is laid out at
    fan_in = shape[-2] if kind == "stack_up" else width if kind == "stack_down" else shape[0]
    std = {"matrix": fan_in ** -0.5, "matrix_out": expert_out_scale * fan_in ** -0.5, "stack_up": fan_in ** -0.5,
           "stack_down": expert_out_scale * fan_in ** -0.5, "conv": fan_in ** -0.5, "embedding": embedding_std,
           "router": router_std * fan_in ** -0.5, "expert_bias": bias_std}.get(kind, 0.02)

    def noise(k, part):
        out = std * jax.random.normal(k, part, f32) + (1.0 if kind == "scale" else 0.0)
        if kind == "stack_up":
            out = jnp.where(jnp.arange(part[-1]) < width, out, 0.0)
        elif kind == "stack_down":
            out = jnp.where(jnp.arange(part[-2])[:, None] < width, out, 0.0)
        return out.astype(dtype)

    if math.prod(shape) < _BLOCKED_FROM or shape[0] % _BLOCKS:
        return noise(key, shape)
    part = (shape[0] // _BLOCKS, *shape[1:])
    blocks = jax.lax.map(lambda i: noise(jax.random.fold_in(key, i), part), jnp.arange(_BLOCKS))
    return blocks.reshape(shape)


def _laws(sizes: dict) -> tuple:
    return (sizes["embedding_init_std"], sizes["router_init_std"], sizes["expert_bias_std"],
            sizes["expert_out_init_scale"], sizes["moe_intermediate_size"], sizes["time_step_min"],
            sizes["time_step_max"], sizes["time_step_floor"])


def build_weights(sizes: dict, key, dtype=jnp.float32):
    """The whole tree from a key; traceable."""
    leaves, treedef = jax.tree.flatten(weight_shapes(sizes), is_leaf=_is_spec)
    return jax.tree.unflatten(treedef, [_leaf(jax.random.fold_in(key, i), shape, kind, _laws(sizes), dtype)
                                        for i, (shape, kind) in enumerate(leaves)])


@functools.partial(jax.jit, static_argnames=("shape", "kind", "laws", "dtype"))
def _make_leaf(key, shape, kind, laws, dtype):
    return _leaf(key, shape, kind, laws, dtype)


def make_weights(sizes: dict, seed: int, dtype=jnp.float32):
    """``build_weights`` leaf by leaf on the device, in ``dtype``, the largest first."""
    leaves, treedef = jax.tree.flatten(weight_shapes(sizes), is_leaf=_is_spec)
    key, out = seed_key(seed), [None] * len(leaves)
    for i in sorted(range(len(leaves)), key=lambda i: -math.prod(leaves[i][0])):
        shape, kind = leaves[i]
        out[i] = _make_leaf(jax.random.fold_in(key, i), tuple(shape), kind, _laws(sizes), jnp.dtype(dtype))
        out[i].block_until_ready()
    return jax.tree.unflatten(treedef, out)
