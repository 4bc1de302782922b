"""Plain reference for LFM2 mixture-of-experts (``model_type`` ``lfm2_moe``) as a causal
language model.

Written from the published implementation's equations (``transformers``
``models/lfm2/modeling_lfm2.py``: ``Lfm2ShortConv.slow_forward``, ``Lfm2Attention``,
``Lfm2MLP`` with ``block_auto_adjust_ff_dim`` false, ``Lfm2DecoderLayer``, ``Lfm2Model``; the
release's ``Lfm2MoeSparseMoeBlock`` for the router): straightforward ``jax.numpy``,
float32, every matrix product at ``highest`` precision, no kernels, no cache, no batching
of requests, EVERY expert computed for every token and weighted by the router's sparse
weights (never a sorted or grouped product). It imports nothing of ``perceiver_io_tpu`` and
takes only what the benchmark itself made (weights from ``weights.py`` beside it, tokens
from the traffic generator).

The model, ``h`` the residual stream, every norm ``rms(x) = w * x / sqrt(mean(x^2) + eps)``:

* ``h = E[token]``; then ``num_hidden_layers`` layers; then ``logits = rms(h) @ E^T``: the
  published ``embedding_norm`` is applied LAST and the head is tied to the embedding.
* Layer ``l``: ``h = h + op_l(rms(h))``; ``h = h + ffn_l(rms(h))``. ``op_l`` is the short
  convolution where ``layer_types[l] == "conv"``, attention where ``"full_attention"``;
  ``ffn_l`` the dense gated MLP for ``l < num_dense_layers``, the expert layer after.
* Short convolution (``conv_L_cache`` taps, no bias, no activation): ``[B, C, x] =
  split3(u @ in_proj)``; ``z = B * x``; ``c_t = sum_j conv[j] * z_{t - (L-1) + j}`` per
  channel, zeros before the first token; out ``(C * c) @ out_proj``.
* Attention: ``num_attention_heads`` query heads over ``num_key_value_heads`` key / value
  heads of ``head_dim`` (query head ``j`` reads key / value head ``j // n_rep``), no bias;
  queries and keys each through an RMSNorm over the head (``q_layernorm``,
  ``k_layernorm``) BEFORE rotate-half rotary over the whole head at ``rope_theta``; causal
  softmax at ``1 / sqrt(head_dim)``; output projection.
* Dense MLP and each expert: ``(silu(u @ w1) * (u @ w3)) @ w2``.
* Expert layer: ``s = sigmoid(u @ router)``; the chosen are the ``num_experts_per_tok``
  largest of ``s + expert_bias`` (the bias moves the CHOICE only); their weights are ``s_i /
  (sum of the chosen s_i + 1e-6)`` (``norm_topk_prob``) times ``routed_scaling_factor``; out
  ``sum_i w_i E_i(u)``. No shared expert, no capacity, no dropped token. ``held`` = (first,
  count) gives the part of that sum that experts ``first .. first + count - 1`` give (the
  share test), the router unchanged.

Departures from the published code: none in the arithmetic. The weights arrive in the
type they are served in (bfloat16, 9.2 GB at the published widths) and stay on the
device: each matrix is raised to float32 where it is used, an expert at a time and the
head in blocks of vocabulary rows and over the answer's positions only, so that the pass
fits beside them. An expert's gate and up matrices lie side by side in one stack
(``experts_w13``); they are read apart.

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


def rotary(t, positions, theta):
    """t (n, heads, d): channel ``i`` pairs with ``i + d/2``."""
    d = t.shape[-1]
    angles = positions.astype(jnp.float32)[:, None] / (float(theta) ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    cos, sin = (jnp.concatenate([f(angles)] * 2, axis=-1)[:, None, :] for f in (jnp.cos, jnp.sin))
    return t * cos + jnp.concatenate([-t[..., d // 2:], t[..., : d // 2]], axis=-1) * sin


# ------------------------------------------------------------------- the layers
def short_conv(w, sizes: dict, x, precision: str = "float32"):
    n, taps = x.shape[0], sizes["conv_L_cache"]
    b, c, xs = jnp.split(matmul(x, w["in_proj"], precision), 3, axis=-1)
    z = b * xs
    padded = jnp.concatenate([jnp.zeros((taps - 1, z.shape[1])), z])
    kernel = w["conv"].astype(jnp.float32)
    conv = sum(kernel[j] * padded[j: j + n] for j in range(taps))
    return matmul(c * conv, w["out_proj"], precision)


def attention(w, sizes: dict, x, positions, precision: str = "float32"):
    n = x.shape[0]
    hq, hkv, hd = sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"]
    eps, theta = sizes["norm_eps"], sizes["rope_theta"]
    q = rotary(rms(matmul(x, w["q_proj"], precision).reshape(n, hq, hd), w["q_layernorm"], eps), positions, theta)
    k = rotary(rms(matmul(x, w["k_proj"], precision).reshape(n, hkv, hd), w["k_layernorm"], eps), positions, theta)
    v = matmul(x, w["v_proj"], precision).reshape(n, hkv, hd)
    k, v = (jnp.repeat(t, hq // hkv, axis=1) for t in (k, v))  # query head j reads head j // n_rep
    scores = jnp.einsum("ihd,jhd->hij", q, k, precision=_HI) * hd ** -0.5
    causal = positions[:, None] >= positions[None, :]
    prob = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hij,jhd->ihd", prob, v, precision=_HI).reshape(n, hq * hd)
    return matmul(out, w["o_proj"], precision)


def gated_mlp(x, w1, w3, w2, precision: str = "float32"):
    return matmul(jax.nn.silu(matmul(x, w1, precision)) * matmul(x, w3, precision), w2, precision)


def route(w, sizes: dict, x, precision: str = "float32"):
    """x (n, hidden) -> (n, experts): each token's weight on every expert, zero for the
    experts it did not choose."""
    s = jax.nn.sigmoid(matmul(x, w["router"], precision))
    choice = s + w["expert_bias"].astype(jnp.float32) if sizes["use_expert_bias"] else s
    _, chosen = jax.lax.top_k(choice, sizes["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if sizes["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-6)
    picked = picked * sizes["routed_scaling_factor"]
    return jnp.zeros_like(s).at[jnp.arange(x.shape[0])[:, None], chosen].set(picked)


def expert_layer(w, sizes: dict, x, precision: str = "float32", held=None):
    """``sum_i w_i E_i(x)`` over the chosen experts (those in ``held`` = (first, count), if
    given): every expert in turn over every token, weighted by ``route``'s sparse weights."""
    weights = route(w, sizes, x, precision)
    width = sizes["moe_intermediate_size"]
    first, count = held or (0, sizes["num_experts"])

    def add(total, e):
        w13, w2 = w["experts_w13"][e], w["experts_w2"][e]
        out = gated_mlp(x, w13[:, :width], w13[:, width:], w2, precision)
        return total + weights[:, e, None] * out, None

    total, _ = jax.lax.scan(add, jnp.zeros_like(x), first + jnp.arange(count))
    return total


def feed_forward(w, sizes: dict, x, precision: str = "float32"):
    if "w1" in w:
        return gated_mlp(x, w["w1"], w["w3"], w["w2"], precision)
    return expert_layer(w, sizes, x, precision)


def hidden_states(weights, sizes: dict, tokens, precision: str = "float32"):
    """tokens (n,) -> the residual stream after the last layer, (n, hidden)."""
    eps = sizes["norm_eps"]
    positions = jnp.arange(tokens.shape[0])
    h = weights["embed_tokens"][tokens].astype(jnp.float32)
    for kind, w in zip(sizes["layer_types"], weights["layers"]):
        x = rms(h, w["operator_norm"], eps)
        h = h + (short_conv(w, sizes, x, precision) if kind == "conv" else attention(w, sizes, x, positions, precision))
        h = h + feed_forward(w, sizes, rms(h, w["ffn_norm"], eps), precision)
    return h


def head(weights, sizes: dict, h, precision: str = "float32"):
    """h (rows, hidden) -> logits (rows, vocab): the embedding, transposed, raised to
    float32 a block of vocabulary rows at a time."""
    x = rms(h, weights["embedding_norm"], sizes["norm_eps"])
    e = weights["embed_tokens"]
    v = e.shape[0]
    blocks = HEAD_BLOCKS if v % HEAD_BLOCKS == 0 else 1
    rows = v // blocks
    # per-tensor rounding goes by the whole matrix's largest magnitude, not a block's
    peak = jnp.max(jnp.abs(e)).astype(jnp.float32)

    def block(i):
        return matmul(x, jax.lax.dynamic_slice_in_dim(e, i * rows, rows, axis=0).T, precision, peak)

    logits = jax.lax.map(block, jnp.arange(blocks))  # (blocks, rows of h, vocabulary rows)
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
