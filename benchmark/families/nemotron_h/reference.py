"""Plain reference for Nemotron-H (``model_type`` ``nemotron_h``) as a causal language
model, on one chip's share of a stated deployment.

Written from the published description (the row's ``config`` and ``described_as``; the
hub's ``modeling_nemotron_h.py`` is not on this machine, but ``transformers`` has the
published code of the parts it took over: ``models/bamba/modeling_bamba.py``, the Mamba-2
mixer's ``torch_forward``, and ``models/deepseek_v3/modeling_deepseek_v3.py``, the sigmoid
router with a correction bias, normalisation and scaling): straightforward ``jax.numpy``,
float32, every matrix product at ``highest`` precision, no kernels, no cache, no batching
of requests, the mixer as the plain recurrence under ``lax.scan`` (never the chunked
form), EVERY held expert computed for every token and weighted by the router's sparse
weights (never a sorted or grouped product). It imports nothing of ``perceiver_io_tpu`` and
takes only what the benchmark itself made (weights from ``weights.py`` beside it, tokens
from the traffic generator).

The model, ``h`` the residual stream, every norm ``rms(x) = w * x / sqrt(mean(x^2) + eps)``
with ``eps = layer_norm_epsilon``, no bias anywhere but the convolution's:

* ``h = E[token]``; layer ``i``, by ``hybrid_override_pattern[i]``: ``h = h + mixer_i(rms(h))``;
  then ``logits = rms(h) @ lm_head`` (the head is its own matrix).
* ``M``, Mamba-2 (``mamba_num_heads`` H heads of ``mamba_head_dim`` P, so the inner width is
  H x P and NOT ``expand`` x hidden; ``n_groups`` G; ``ssm_state_size`` N; ``conv_kernel``
  taps): ``[z | xBC | dt] = u @ in_proj``; ``xBC = silu(causal depthwise conv(xBC) + bias)``;
  ``[x (H, P) | B (G, N) | C (G, N)] = xBC``; ``dt = softplus(dt + dt_bias)`` (no upper clamp:
  ``time_step_limit`` is (0, inf)); ``A = -exp(A_log)`` a head; ``S_t = exp(dt_t A) S_{t-1} +
  dt_t x_t (x) B_t``; ``y_t = S_t C_t + D x_t``, head ``j`` reading group ``j // (H / G)``;
  ``y`` gated by ``silu(z)`` FIRST, then RMS-normalised in G equal parts; out ``y @ out_proj``.
* ``*``, attention: ``num_attention_heads`` query heads over ``num_key_value_heads`` key /
  value heads of ``head_dim`` (query head ``j`` reads head ``j // n_rep``); NO rotary
  embedding and no other position signal (the published code reads neither ``rope_theta``
  nor ``partial_rotary_factor``: the ``M`` layers carry order); causal softmax at ``1 /
  sqrt(head_dim)``; output projection.
* ``E``, experts: ``s = sigmoid(u @ router)`` over ALL ``router_experts``; the chosen are the
  ``num_experts_per_tok`` largest of ``s + expert_bias`` (the bias moves the CHOICE only;
  ``n_group`` = ``topk_group`` = 1: the group limit selects the one group, a no-op, and is
  not built); weights ``s_i / (sum of the chosen s_i + 1e-20)`` (``norm_topk_prob``) times
  ``routed_scaling_factor``; an expert is ``relu(u @ up)^2 @ down`` (ungated: two matrices);
  out ``sum_i w_i E_i(u) + E_shared(u)``.

The share (``held`` = (first, count); the configuration's is ``(experts_held_first,
n_routed_experts)``): the routed sum runs over the chosen experts ``first .. first + count -
1`` ONLY, whose stacks are the ones given; what the absent experts would have added is left
out and that partial result goes on to the next layer. The shared expert is added in full:
every chip of the deployment computes it alike. The vocabulary is the slice the weights
hold: a sliced vocabulary is a smaller vocabulary.

Departures from the published code: none in the arithmetic. The weights arrive in the
type they are served in (bfloat16, 9.4 GB at the published widths) and stay on the
device: each matrix is raised to float32 where it is used, an expert at a time and the
head in blocks of vocabulary columns and over the answer's positions only, so that the
pass fits beside them. The expert stacks arrive laid out for the program at
``pad_to_lanes(moe_intermediate_size)`` columns (``weights.py``); the reference reads the
published ``moe_intermediate_size`` columns of them.

``precision`` (the controls ``benchmark/control.py`` reads): ``float8`` / ``int8`` /
``bfloat16`` round both operands of every weight matrix product, the router's among them
(each expert's matrix a tensor of its own).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float32", "bfloat16", "int8", "float8")
HEAD_BLOCKS = 16
ROUTER_NORM_EPS = 1e-20
_HI = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------------ arithmetic
def _fake_int8(x, peak=None):
    """Symmetric per-tensor int8: what an int8 matmul path would feed the MXU. ``peak``:
    the tensor's largest magnitude where ``x`` is only a block of it."""
    scale = (jnp.max(jnp.abs(x)) if peak is None else peak) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(x / scale).clip(-127, 127) * scale


def _fake_float8(x, peak=None):
    """Per-tensor scaled float8 (e4m3): the largest magnitude sits at 448."""
    scale = (jnp.max(jnp.abs(x)) if peak is None else peak) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def matmul(x, w, precision: str, w_peak=None):
    """``x @ w`` with both operands first rounded to ``precision``; ``w`` is raised to
    float32 here, where it is used. Products accumulate in float32 at ``highest``."""
    w = w.astype(jnp.float32)
    if precision == "bfloat16":
        x, w = (t.astype(jnp.bfloat16).astype(jnp.float32) for t in (x, w))
    elif precision == "int8":
        x, w = _fake_int8(x), _fake_int8(w, w_peak)
    elif precision == "float8":
        x, w = _fake_float8(x), _fake_float8(w, w_peak)
    elif precision != "float32":
        raise ValueError(f"precision must be one of {PRECISIONS}, got {precision!r}")
    return jnp.matmul(x, w, precision=_HI)


def rms(x, weight, eps):
    return weight.astype(jnp.float32) * x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


# ------------------------------------------------------------------- the layers
def mixer(w, sizes: dict, x, precision: str = "float32"):
    n = x.shape[0]
    heads, p = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    groups, state, taps = sizes["n_groups"], sizes["ssm_state_size"], sizes["conv_kernel"]
    inner = heads * p
    conv_dim = inner + 2 * groups * state
    u = matmul(x, w["in_proj"], precision)
    z, xbc, dt = u[:, :inner], u[:, inner: inner + conv_dim], u[:, inner + conv_dim:]
    padded = jnp.concatenate([jnp.zeros((taps - 1, conv_dim)), xbc])
    kernel = w["conv_weight"].astype(jnp.float32)
    xbc = jax.nn.silu(sum(kernel[k] * padded[k: k + n] for k in range(taps)) + w["conv_bias"].astype(jnp.float32))
    xs = xbc[:, :inner].reshape(n, heads, p)
    b = jnp.repeat(xbc[:, inner: inner + groups * state].reshape(n, groups, state), heads // groups, axis=1)
    c = jnp.repeat(xbc[:, inner + groups * state:].reshape(n, groups, state), heads // groups, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(w["A_log"].astype(jnp.float32))

    def step(s, row):
        xt, bt, ct, dtt = row
        s = jnp.exp(dtt * a)[:, None, None] * s + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, ct, precision=_HI)

    _, y = jax.lax.scan(step, jnp.zeros((heads, p, state)), (xs, b, c, dt))
    y = (y + w["D"].astype(jnp.float32)[:, None] * xs).reshape(n, inner) * jax.nn.silu(z)
    parts = y.reshape(n, groups, inner // groups)
    parts = parts * jax.lax.rsqrt(jnp.mean(jnp.square(parts), axis=-1, keepdims=True) + sizes["layer_norm_epsilon"])
    return matmul(parts.reshape(n, inner) * w["mixer_norm"].astype(jnp.float32), w["out_proj"], precision)


def attention(w, sizes: dict, x, precision: str = "float32"):
    n = x.shape[0]
    hq, hkv, hd = sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"]
    q = matmul(x, w["q_proj"], precision).reshape(n, hq, hd)
    k = matmul(x, w["k_proj"], precision).reshape(n, hkv, hd)
    v = matmul(x, w["v_proj"], precision).reshape(n, hkv, hd)
    k, v = (jnp.repeat(t, hq // hkv, axis=1) for t in (k, v))  # query head j reads head j // n_rep
    scores = jnp.einsum("ihd,jhd->hij", q, k, precision=_HI) * hd ** -0.5
    causal = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
    prob = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hij,jhd->ihd", prob, v, precision=_HI).reshape(n, hq * hd)
    return matmul(out, w["o_proj"], precision)


def relu2_mlp(x, up, down, precision: str = "float32"):
    return matmul(jnp.square(jax.nn.relu(matmul(x, up, precision))), down, precision)


def route(w, sizes: dict, x, precision: str = "float32"):
    """x (n, hidden) -> (n, router_experts): each token's weight on every expert the router
    knows, zero for the experts it did not choose."""
    s = jax.nn.sigmoid(matmul(x, w["router"], precision))
    _, chosen = jax.lax.top_k(s + w["expert_bias"].astype(jnp.float32), sizes["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if sizes["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + ROUTER_NORM_EPS)
    picked = picked * sizes["routed_scaling_factor"]
    return jnp.zeros_like(s).at[jnp.arange(x.shape[0])[:, None], chosen].set(picked)


def held_experts(sizes: dict) -> tuple:
    """(first, count) of the router's experts whose matrices the weights hold."""
    return sizes["experts_held_first"], sizes["n_routed_experts"]


def expert_layer(w, sizes: dict, x, precision: str = "float32", held=None, shared: bool = True):
    """``sum_i w_i E_i(x)`` over the chosen experts among ``held`` = (first, count), whose
    stacks ``w["experts_up"]`` / ``w["experts_down"]`` are (the configuration's share if not
    given), every one in turn over every token, weighted by ``route``'s sparse weights;
    plus the shared expert, in full, where ``shared``."""
    weights = route(w, sizes, x, precision)
    width = sizes["moe_intermediate_size"]
    first, count = held or held_experts(sizes)

    def add(total, e):
        out = relu2_mlp(x, w["experts_up"][e][:, :width], w["experts_down"][e][:width], precision)
        return total + weights[:, first + e, None] * out, None

    total, _ = jax.lax.scan(add, jnp.zeros_like(x), jnp.arange(count))
    return total + relu2_mlp(x, w["shared_up"], w["shared_down"], precision) if shared else total


MIXERS = {"M": mixer, "*": attention, "E": expert_layer}


def hidden_states(weights, sizes: dict, tokens, precision: str = "float32"):
    """tokens (n,) -> the residual stream after the last layer, (n, hidden)."""
    h = weights["embed_tokens"][tokens].astype(jnp.float32)
    for kind, w in zip(sizes["hybrid_override_pattern"], weights["layers"]):
        h = h + MIXERS[kind](w, sizes, rms(h, w["norm"], sizes["layer_norm_epsilon"]), precision)
    return h


def head(weights, sizes: dict, h, precision: str = "float32"):
    """h (rows, hidden) -> logits (rows, vocab), the head raised to float32 a block of
    vocabulary columns at a time."""
    x = rms(h, weights["final_norm"], sizes["layer_norm_epsilon"])
    w = weights["lm_head"]
    v = w.shape[1]
    blocks = HEAD_BLOCKS if v % HEAD_BLOCKS == 0 else 1
    cols = v // blocks
    # per-tensor rounding goes by the whole matrix's largest magnitude, not a block's
    peak = jnp.max(jnp.abs(w)).astype(jnp.float32)

    def block(i):
        return matmul(x, jax.lax.dynamic_slice_in_dim(w, i * cols, cols, axis=1), precision, peak)

    logits = jax.lax.map(block, jnp.arange(blocks))  # (blocks, rows, cols)
    return jnp.moveaxis(logits, 0, 1).reshape(x.shape[0], v)


def forward(weights, sizes: dict, tokens, precision: str = "float32"):
    """tokens (n,) -> logits (n, vocab): the whole forward pass (tests)."""
    with jax.default_matmul_precision("highest"):
        return head(weights, sizes, hidden_states(weights, sizes, tokens, precision), precision)


def _freeze(sizes: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v) for k, v in sizes.items()))


@functools.partial(jax.jit, static_argnames=("sizes_key", "rows", "precision"))
def _score(weights, sizes_key, tokens, first_row, rows: int, precision: str):
    sizes = dict(sizes_key)
    with jax.default_matmul_precision("highest"):
        h = hidden_states(weights, sizes, tokens, precision)
        return head(weights, sizes, jax.lax.dynamic_slice_in_dim(h, first_row, rows, axis=0), precision)


def score_served(weights, sizes: dict, prompt, served, precision: str = "float32", pad_to: int = 256):
    """Logits (len(served), vocab) that predict each served token, from ONE forward pass
    over prompt + served tokens, right-padded to a multiple of ``pad_to`` rows so that
    few shapes compile (a causal model's real rows never see the padding)."""
    prompt, served = np.asarray(prompt, np.int32), np.asarray(served, np.int32)
    n_total = len(prompt) + len(served)
    n_rows = -(-n_total // pad_to) * pad_to
    tokens = np.zeros((n_rows,), np.int32)
    tokens[:n_total] = np.concatenate([prompt, served])
    # the row of position i predicts the token at i + 1; the answer's rows padded likewise
    rows = -(-len(served) // pad_to) * pad_to
    first = min(len(prompt) - 1, n_rows - rows)
    logits = _score(weights, _freeze(sizes), jnp.asarray(tokens), first, rows, precision)
    lo = len(prompt) - 1 - first
    return logits[lo: lo + len(served)]
