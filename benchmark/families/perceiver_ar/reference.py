"""Plain reference for Perceiver AR as a causal language model.

Written from the published description (Hawthorne et al. 2022, "General-purpose,
long-context autoregressive modeling with Perceiver AR", and the krasserm/perceiver-io
``CausalSequenceModel``): straightforward ``jax.numpy``, float32, every matrix
multiplication at ``highest`` precision, no kernels, no cache, no batching of requests.
It imports nothing of ``perceiver_io_tpu`` and takes only what the benchmark itself made
(weights from ``weights.py`` beside it, tokens from the traffic generator).

The model (sizes from the configuration file):

* tokens are embedded (``E[token]``, plus a learned ``P[position]`` where the
  configuration has ``abs_pos_emb``); rotary angles ``position * 10000**(-2k/r)`` rotate
  the first ``r`` channels of each head in adjacent pairs (``r`` = head size, halved with
  ``abs_pos_emb``);
* the last ``max_latents`` positions are the latents. One cross-attention layer lets each
  latent attend causally to the whole window: queries ``LN_q(latent)``, keys and values
  from ``concat(LN_kv(prefix), LN_q(latents))``; then ``x = attn + latent`` and
  ``x = x + MLP(x)`` with ``MLP = dense(gelu(dense(LN(x))))``;
* ``num_self_attention_layers`` pre-LN causal transformer layers over the latents, rotary
  in the first ``num_self_attention_rotary_layers`` only;
* optional final LN, logits by the transposed embedding (plus a bias where configured).

Departures from the description, each needed to follow what a *served* model computes:

1. Decoding with a cache computes every position's hidden state once, when the position
   joins the latents, and keeps the newest ``max_latents`` of them. That equals this
   forward pass with self-attention restricted to a sliding window of ``max_latents``
   latents and cross-attention to the newest ``max_seq_len`` tokens, which is what
   ``score_served`` computes in one pass over prompt + served tokens.
2. A prompt shorter than ``max_latents`` is left-padded to it (pad token 0, positions
   shifted and clamped at 0, padded keys masked in cross-attention only, as in the
   published code). A padded query has every key masked; masking by a finite minimum
   makes its attention uniform over the padded prompt's slots, and this reference says so
   explicitly (``fallback``).
3. The served system holds a request's left-padding count constant while it decodes
   (the published wrapper recomputes it as padding leaves the window). So a decoded
   token is embedded at the position of the prompt's last token, ``n - 1``, and as
   query ``i`` sees it, key ``j`` sits at ``max(n - 1 - (i - j), 0)``: rotary distances
   saturate at ``n - 1``. The reference follows the served system (``off``); PERF.md
   lists this under Open questions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-5
PRECISIONS = ("float32", "bfloat16", "int8", "float8")


# ------------------------------------------------------------------ arithmetic
def _fake_int8(x):
    """Symmetric per-tensor int8: what an int8 matmul path would feed the MXU."""
    scale = jnp.max(jnp.abs(x)) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    rounded = jnp.round(x / scale).clip(-127, 127) * scale
    return x + jax.lax.stop_gradient(rounded - x)  # straight through: gradients pass


def _fake_float8(x):
    """Per-tensor scaled float8 (e4m3): the tensor's largest magnitude sits at the
    format's largest finite value, 448."""
    scale = jnp.max(jnp.abs(x)) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    rounded = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(rounded - x)


def matmul(x, w, precision: str):
    """``x @ w`` with both operands first rounded to ``precision`` (float32 = exact
    inputs); products accumulate in float32 at ``highest`` in every case."""
    if precision == "bfloat16":
        x, w = (t.astype(jnp.bfloat16).astype(jnp.float32) for t in (x, w))
    elif precision == "int8":
        x, w = _fake_int8(x), _fake_int8(w)
    elif precision == "float8":
        x, w = _fake_float8(x), _fake_float8(w)
    elif precision != "float32":
        raise ValueError(f"precision must be one of {PRECISIONS}, got {precision!r}")
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def layer_norm(x, scale, bias):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * scale + bias


def rotary_angles(positions, rotated: int):
    inv_freq = 1.0 / (10000.0 ** (np.arange(0, rotated, 2, dtype=np.float32) / rotated))
    return jnp.repeat(positions.astype(jnp.float32)[..., None] * inv_freq, 2, axis=-1)


def rotate(t, angles):
    """``t`` (heads, n, head size); ``angles`` (n, r) rotate the first r channels."""
    r = angles.shape[-1]
    head, rest = t[..., :r], t[..., r:]
    pairs = head.reshape(*head.shape[:-1], r // 2, 2)
    turned = jnp.stack((-pairs[..., 1], pairs[..., 0]), axis=-1).reshape(head.shape)
    head = head * jnp.cos(angles)[None] + turned * jnp.sin(angles)[None]
    return jnp.concatenate((head, rest), axis=-1)


def attention(q_in, kv_in, w, heads: int, visible, rope, precision: str, fallback=None):
    """Multi-head attention of ``q_in`` (n_q, c) over ``kv_in`` (n_k, c). ``visible``
    (n_q, n_k) is the whole mask; a query that sees no key attends uniformly to
    ``fallback`` (n_k,) where given. ``rope`` = (q_pos (n_q,), k_pos (n_k,), off (n_q,),
    rotated, gate): query i is turned by ``max(q_pos - off, 0)`` and key j, as query i
    sees it, by ``max(k_pos - off, 0)`` (departure 3; ``off`` = 0 gives plain rotary)."""
    split = lambda t: t.reshape(t.shape[0], heads, -1).transpose(1, 0, 2)
    q = split(matmul(q_in, w["q"], precision))
    k = split(matmul(kv_in, w["k"], precision))
    v = split(matmul(kv_in, w["v"], precision))
    q = q * (q.shape[-1] ** -0.5)
    dot = lambda a, b: jnp.einsum("hic,hjc->hij", a, b, precision=jax.lax.Precision.HIGHEST)
    q_pos, k_pos, off, rotated, gate = rope
    q = rotate(q, rotary_angles(jnp.maximum(q_pos - off, 0), rotated) * gate)
    # keys whose turned position stays above 0 keep their true distance to the query
    # (any common offset cancels); the others sit at position 0, unturned
    q_true = rotate(q, rotary_angles(off, rotated) * gate)
    k_true = rotate(k, rotary_angles(jnp.maximum(k_pos, 0), rotated) * gate)
    scores = jnp.where((k_pos[None, :] - off[:, None] > 0)[None], dot(q_true, k_true), dot(q, k))
    if fallback is not None:
        empty = ~visible.any(axis=1, keepdims=True)
        visible = jnp.where(empty, fallback[None, :], visible)
        scores = jnp.where(empty[None], 0.0, scores)
    scores = jnp.where(visible[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hij,hjc->hic", probs, v, precision=jax.lax.Precision.HIGHEST)
    out = out.transpose(1, 0, 2).reshape(q_in.shape[0], -1)
    out = matmul(out, w["o"], precision)
    return out + w["o_bias"] if "o_bias" in w else out


def mlp(x, w, precision: str):
    h = matmul(layer_norm(x, w["norm_scale"], w["norm_bias"]), w["dense_1"], precision)
    return matmul(jax.nn.gelu(h, approximate=False), w["dense_2"], precision)


# --------------------------------------------------------------------- forward
def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def forward(weights, sizes: dict, tokens, positions, embed_positions, rows_from: int,
            is_latent, ca_visible, sa_visible, precision: str = "float32", ca_fallback=None,
            off=None):
    """Logits (n - rows_from, vocab) of one sequence ``tokens`` (n,). Rows are computed
    for positions ``rows_from..n`` (static); ``is_latent`` (n,) says which positions are
    latents (keys normed as queries), and the caller gives the whole masks:
    ``ca_visible`` (rows, n) and ``sa_visible`` (rows, rows). Rows that are not latents
    are computed and never read. ``positions`` (n,) may be negative (left padding) and
    ``off`` (rows,) is departure 3's offset, 0 where not given."""
    heads = sizes["num_heads"]
    rotated = sizes["num_channels"] // heads // (2 if sizes["abs_pos_emb"] else 1)
    emb = _f32(weights["embedding"])
    x = emb[tokens]
    if sizes["abs_pos_emb"]:
        x = x + _f32(weights["pos_embedding"])[embed_positions]
    latent = x[rows_from:]
    pos_lat = positions[rows_from:]
    off = jnp.zeros_like(pos_lat) if off is None else off

    ca = _f32(weights["cross"])
    q_in = layer_norm(latent, ca["q_norm_scale"], ca["q_norm_bias"])
    kv_in = jnp.where(is_latent[:, None], layer_norm(x, ca["q_norm_scale"], ca["q_norm_bias"]),
                      layer_norm(x, ca["kv_norm_scale"], ca["kv_norm_bias"]))
    att = attention(q_in, kv_in, ca["attn"], heads, ca_visible,
                    (pos_lat, positions, off, rotated, 1.0), precision, fallback=ca_fallback)
    x = att + latent
    x = x + mlp(x, ca["mlp"], precision)

    rotary_layers = sizes["num_self_attention_rotary_layers"]

    def layer(x, inputs):
        w, index = inputs
        w = _f32(w)
        gate = ((index < rotary_layers) | (rotary_layers == -1)).astype(jnp.float32)
        h = layer_norm(x, w["norm_scale"], w["norm_bias"])
        x = x + attention(h, h, w["attn"], heads, sa_visible,
                          (pos_lat, pos_lat, off, rotated, gate), precision)
        return x + mlp(x, w["mlp"], precision), None

    n_layers = sizes["num_self_attention_layers"]
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, (weights["layers"], jnp.arange(n_layers)))
    if sizes["output_norm"]:
        x = layer_norm(x, _f32(weights["out_norm_scale"]), _f32(weights["out_norm_bias"]))
    logits = matmul(x, emb.T, precision)
    if sizes["output_bias"]:
        logits = logits + _f32(weights["out_bias"])
    return logits


# -------------------------------------------------------------------- training
def train_logits(weights, sizes: dict, tokens, precision: str = "float32"):
    """The training pass over one full row ``tokens`` (seq_len,): prefix = everything
    before the last ``max_latents`` positions, plain causal masks, no padding."""
    n = tokens.shape[0]
    first = n - sizes["max_latents"]
    idx = jnp.arange(n)
    ca_visible = idx[None, :] <= idx[first:, None]
    sa_visible = idx[None, first:] <= idx[first:, None]
    return forward(weights, sizes, tokens, idx, idx, first, idx >= first, ca_visible, sa_visible,
                   precision)


def causal_lm_loss(weights, sizes: dict, input_ids, labels, precision: str = "float32"):
    """Mean cross-entropy over the latent positions of a block of rows."""
    first = input_ids.shape[1] - sizes["max_latents"]

    def row(tokens, targets):
        logits = train_logits(weights, sizes, tokens, precision)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, targets[first:, None], axis=1)[:, 0].sum()

    total = jax.lax.map(lambda args: row(*args), (input_ids, labels)).sum()
    return total / (input_ids.shape[0] * sizes["max_latents"])


def leaf_norms(tree) -> dict:
    """L2 norm of every leaf, keyed by its path; a leaf of the stacked ``layers`` gives
    one norm per layer."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = [k.key for k in path]
        axes = tuple(range(1, leaf.ndim)) if keys[0] == "layers" else None
        out["/".join(keys)] = jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32)), axis=axes))
    return out


def make_train_step(sizes: dict, optimizer: dict, rows_per_block: int, precision: str = "float32"):
    """``step(weights, mu, nu, batch, t) -> (weights, mu, nu, loss, clipped-gradient leaf
    norms)``: one AdamW step with global-norm clipping, as the cell's trainer settings
    state them, in float32. The batch ({"input_ids","labels"}: (rows, seq)) is taken in
    blocks of ``rows_per_block`` rows, and the update is a program of its own, so that
    the pass fits on the chip beside nothing else."""
    lr, b1, b2 = optimizer["learning_rate"], optimizer["b1"], optimizer["b2"]
    eps, wd, clip = optimizer["eps"], optimizer["weight_decay"], optimizer["max_grad_norm"]

    @jax.jit
    def gradient(weights, batch):
        rows = batch["input_ids"].shape[0]
        n_blocks = rows // rows_per_block
        blocks = jax.tree.map(lambda x: x.reshape(n_blocks, rows_per_block, *x.shape[1:]), batch)

        def body(carry, block):
            loss, grad = jax.value_and_grad(causal_lm_loss)(
                weights, sizes, block["input_ids"], block["labels"], precision)
            return (carry[0] + loss, jax.tree.map(jnp.add, carry[1], grad)), None

        zero = jax.tree.map(jnp.zeros_like, weights)
        (loss, grad), _ = jax.lax.scan(body, (jnp.float32(0.0), zero), blocks)
        return loss / n_blocks, jax.tree.map(lambda g: g / n_blocks, grad)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def update(weights, mu, nu, grad, t):
        if clip is not None:
            norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grad)))
            factor = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-30))
            grad = jax.tree.map(lambda g: g * factor, grad)
        norms = leaf_norms(grad)
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grad)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grad)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        weights = jax.tree.map(
            lambda w, m, v: w - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * w),
            weights, mu, nu)
        return weights, mu, nu, norms

    def step(weights, mu, nu, batch, t):
        loss, grad = gradient(weights, batch)
        weights, mu, nu, norms = update(weights, mu, nu, grad, jnp.float32(t))
        return weights, mu, nu, loss, norms

    step.gradient = gradient
    return step


# --------------------------------------------------------------------- serving
def served_layout(sizes: dict, n_prompt: int, n_total: int, n_rows: int) -> dict:
    """Index arithmetic of one served request (host side, numpy), for a sequence laid
    into ``n_rows`` slots: the left padding, the positions, which slots are latents, and
    the masks of departures 1-3 in the module docstring. Every slot gets a row; slots
    that are no latent (the prefix, the right padding) see themselves only."""
    latents, window = sizes["max_latents"], sizes["max_seq_len"]
    pad = max(latents - n_prompt, 0)
    n = pad + n_total
    idx = np.arange(n_rows)
    first = max(n_prompt, latents) - latents
    is_latent = (idx >= first) & (idx < n)
    real = (idx >= pad) & (idx < n)
    q, k = idx[:, None], idx[None, :]
    own = q == k
    ca_visible = (k <= q) & real[None, :] & (q - k < window)
    sa_visible = (k <= q) & is_latent[None, :] & (q - k < latents)
    keep = is_latent[:, None]
    return {
        "pad": pad, "first_latent": first, "length": n, "is_latent": is_latent,
        "positions": np.where(idx < n, idx - pad, 0),
        "embed_positions": np.where(idx < n, np.clip(idx - pad, 0, n_prompt - 1), 0),
        "off": np.where(idx < n, np.maximum(idx - pad - (n_prompt - 1), 0), 0),
        "ca_visible": np.where(keep, ca_visible, own), "sa_visible": np.where(keep, sa_visible, own),
        "ca_fallback": idx < pad + n_prompt,
    }


@functools.partial(jax.jit, static_argnames=("sizes_key", "precision"))
def _score(weights, sizes_key, tokens, positions, embed_positions, off, is_latent,
           ca_visible, sa_visible, ca_fallback, precision):
    return forward(weights, dict(sizes_key), tokens, positions, embed_positions, 0, is_latent,
                   ca_visible, sa_visible, precision, ca_fallback=ca_fallback, off=off)


def score_served(weights, sizes: dict, prompt, served, precision: str = "float32",
                 pad_to: int = 256):
    """Logits (len(served), vocab) that predict each served token, from ONE forward pass
    over prompt + served tokens, laid into a multiple of ``pad_to`` slots so that few
    shapes compile."""
    prompt, served = np.asarray(prompt, np.int32), np.asarray(served, np.int32)
    n_prompt, n_total = len(prompt), len(prompt) + len(served)
    pad = max(sizes["max_latents"] - n_prompt, 0)
    n_rows = -(-(pad + n_total) // pad_to) * pad_to
    lay = served_layout(sizes, n_prompt, n_total, n_rows)
    tokens = np.zeros((n_rows,), np.int32)
    tokens[pad: pad + n_total] = np.concatenate([prompt, served])
    sizes_key = tuple(sorted((k, v) for k, v in sizes.items() if isinstance(v, (int, bool))))
    logits = _score(weights, sizes_key, jnp.asarray(tokens), jnp.asarray(lay["positions"]),
                    jnp.asarray(lay["embed_positions"]), jnp.asarray(lay["off"]),
                    jnp.asarray(lay["is_latent"]),
                    jnp.asarray(lay["ca_visible"]), jnp.asarray(lay["sa_visible"]),
                    jnp.asarray(lay["ca_fallback"]), precision)
    # the row of slot i predicts the token in slot i + 1
    lo = pad + n_prompt - 1
    return logits[lo: lo + len(served)]
