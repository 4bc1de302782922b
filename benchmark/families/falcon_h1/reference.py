"""Plain reference for Falcon-H1 as a causal language model.

Written from the published implementation's equations (``transformers``
``models/falcon_h1/modeling_falcon_h1.py``; the Falcon-H1 release and Dao & Gu 2024,
"Transformers are SSMs", for the mixer): straightforward ``jax.numpy``, float32, every
matrix product at ``highest`` precision, no kernels, no cache, no batching of requests,
the mixer as the plain recurrence under ``lax.scan`` (never the chunked form). It imports
nothing of ``perceiver_io_tpu`` and takes only what the benchmark itself made (weights
from ``weights.py`` beside it, tokens from the traffic generator).

The model, ``h`` the residual stream, every norm ``rms(x) = w * x / sqrt(mean(x^2) + eps)``:

* ``h = E[token] * embedding_multiplier``; then ``num_hidden_layers`` blocks; then
  ``logits = (rms(h) @ lm_head) * lm_head_multiplier``.
* Block: ``x = rms(h)``; ``h = h + mixer(x) * ssm_out_multiplier + attention(x *
  attention_in_multiplier) * attention_out_multiplier``; ``h = h + mlp(rms(h))``.
* MLP: ``(up(x) * silu(gate(x) * mlp_multipliers[0])) @ down * mlp_multipliers[1]``.
* Attention: ``num_attention_heads`` query heads over ``num_key_value_heads`` key / value
  heads of ``head_dim`` (query head ``j`` reads key / value head ``j // n_rep``); keys times
  ``key_multiplier``; rotate-half rotary on queries and keys over the whole head,
  ``rope_theta``, no scaling; causal softmax at ``1 / sqrt(head_dim)``; output projection.
* Mixer: ``u = ((x * ssm_in_multiplier) @ in_proj) * mup_vector``, where ``mup_vector``
  scales the segments ``[z | x | B | C | dt]`` by ``ssm_multipliers[0..4]``; ``xBC =
  silu(causal depthwise conv1d(xBC) + bias)``; per head ``S_t = exp(dt_t A) S_{t-1} + dt_t
  x_t (x) B_t`` and ``y_t = S_t C_t + D x_t`` with ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``, head ``j`` reading group ``j // (heads / groups)``; ``y`` gated by
  ``silu(z)`` FIRST, then RMS-normalised in ``mamba_n_groups`` equal parts
  (``mamba_norm_before_gate`` false, ``mamba_rms_norm`` true); output projection.

Departures from the published code: none in the arithmetic. The weights arrive in the
type they are served in (bfloat16, 8.8 GB at the published widths) and stay on the
device: each matrix is raised to float32 where it is used, the output head in blocks of
vocabulary columns and over the answer's positions only, so that the pass fits beside
them.

``precision`` (the controls ``benchmark/control.py`` reads): ``float8`` / ``int8`` /
``bfloat16`` round both operands of every weight matrix product; ``bf16state`` keeps the
products exact and rounds the recurrent state to bfloat16 after every step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float32", "bfloat16", "int8", "float8", "bf16state")
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
    elif precision not in ("float32", "bf16state"):
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
def attention(w, sizes: dict, x, positions, precision: str):
    n = x.shape[0]
    hq, hkv, hd = sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"]
    x = x * sizes["attention_in_multiplier"]
    q = rotary(matmul(x, w["q_proj"], precision).reshape(n, hq, hd), positions, sizes["rope_theta"])
    k = rotary(matmul(x, w["k_proj"], precision).reshape(n, hkv, hd) * sizes["key_multiplier"], positions,
               sizes["rope_theta"])
    v = matmul(x, w["v_proj"], precision).reshape(n, hkv, hd)
    k, v = (jnp.repeat(t, hq // hkv, axis=1) for t in (k, v))  # query head j reads head j // n_rep
    scores = jnp.einsum("ihd,jhd->hij", q, k, precision=_HI) * hd ** -0.5
    causal = positions[:, None] >= positions[None, :]
    prob = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hij,jhd->ihd", prob, v, precision=_HI).reshape(n, hq * hd)
    return matmul(out, w["o_proj"], precision) * sizes["attention_out_multiplier"]


def mixer(w, sizes: dict, x, precision: str):
    n = x.shape[0]
    d_ssm, heads, p = sizes["mamba_d_ssm"], sizes["mamba_n_heads"], sizes["mamba_d_head"]
    groups, state, width = sizes["mamba_n_groups"], sizes["mamba_d_state"], sizes["mamba_d_conv"]
    conv_dim = d_ssm + 2 * groups * state
    m = sizes["ssm_multipliers"]
    mup = jnp.concatenate([jnp.full((d_ssm,), m[0]), jnp.full((d_ssm,), m[1]), jnp.full((groups * state,), m[2]),
                           jnp.full((groups * state,), m[3]), jnp.full((heads,), m[4])])
    u = matmul(x * sizes["ssm_in_multiplier"], w["in_proj"], precision) * mup
    z, xbc, dt = u[:, :d_ssm], u[:, d_ssm: d_ssm + conv_dim], u[:, d_ssm + conv_dim:]
    padded = jnp.concatenate([jnp.zeros((width - 1, conv_dim)), xbc])
    kernel = w["conv_weight"].astype(jnp.float32)
    xbc = jax.nn.silu(sum(kernel[k] * padded[k: k + n] for k in range(width)) + w["conv_bias"].astype(jnp.float32))
    xs = xbc[:, :d_ssm].reshape(n, heads, p)
    b = jnp.repeat(xbc[:, d_ssm: d_ssm + groups * state].reshape(n, groups, state), heads // groups, axis=1)
    c = jnp.repeat(xbc[:, d_ssm + groups * state:].reshape(n, groups, state), heads // groups, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(w["A_log"].astype(jnp.float32))

    def step(s, row):
        xt, bt, ct, dtt = row
        s = jnp.exp(dtt * a)[:, None, None] * s + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
        if precision == "bf16state":
            s = s.astype(jnp.bfloat16).astype(jnp.float32)
        return s, jnp.einsum("hpn,hn->hp", s, ct, precision=_HI)

    _, y = jax.lax.scan(step, jnp.zeros((heads, p, state)), (xs, b, c, dt))
    y = (y + w["D"].astype(jnp.float32)[:, None] * xs).reshape(n, d_ssm) * jax.nn.silu(z)
    parts = y.reshape(n, groups, d_ssm // groups)
    parts = parts * jax.lax.rsqrt(jnp.mean(jnp.square(parts), axis=-1, keepdims=True) + sizes["rms_norm_eps"])
    y = parts.reshape(n, d_ssm) * w["mixer_norm"].astype(jnp.float32)
    return matmul(y, w["out_proj"], precision) * sizes["ssm_out_multiplier"]


def mlp(w, sizes: dict, x, precision: str):
    gate_m, down_m = sizes["mlp_multipliers"]
    y = matmul(x, w["up_proj"], precision) * jax.nn.silu(matmul(x, w["gate_proj"], precision) * gate_m)
    return matmul(y, w["down_proj"], precision) * down_m


def hidden_states(weights, sizes: dict, tokens, precision: str = "float32"):
    """tokens (n,) -> the residual stream after the last block, (n, hidden)."""
    eps = sizes["rms_norm_eps"]
    positions = jnp.arange(tokens.shape[0])
    h = weights["embed_tokens"][tokens].astype(jnp.float32) * sizes["embedding_multiplier"]
    for w in weights["layers"]:
        x = rms(h, w["input_layernorm"], eps)
        h = h + mixer(w, sizes, x, precision) + attention(w, sizes, x, positions, precision)
        h = h + mlp(w, sizes, rms(h, w["pre_ff_layernorm"], eps), precision)
    return h


def head(weights, sizes: dict, h, precision: str = "float32"):
    """h (rows, hidden) -> logits (rows, vocab), the head raised to float32 a block of
    vocabulary columns at a time."""
    x = rms(h, weights["final_layernorm"], sizes["rms_norm_eps"])
    w = weights["lm_head"]
    v = w.shape[1]
    blocks = HEAD_BLOCKS if v % HEAD_BLOCKS == 0 else 1
    cols = v // blocks
    # per-tensor rounding goes by the whole matrix's largest magnitude, not a block's
    peak = jnp.max(jnp.abs(w)).astype(jnp.float32)

    def block(i):
        return matmul(x, jax.lax.dynamic_slice_in_dim(w, i * cols, cols, axis=1), precision, peak)

    logits = jax.lax.map(block, jnp.arange(blocks))  # (blocks, rows, cols)
    return jnp.moveaxis(logits, 0, 1).reshape(x.shape[0], v) * sizes["lm_head_multiplier"]


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


def score_served(weights, sizes: dict, prompt, served, precision: str = "float32", pad_to: int = 512):
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
