"""Falcon-H1: a hybrid decoder whose every block runs a Mamba-2 mixer and
grouped-query attention IN PARALLEL on the same normed input, adds both to the
residual stream, then a gated MLP (``transformers``' ``modeling_falcon_h1.py``
is the published implementation; the equations are restated in
``benchmark/families/falcon_h1/reference.py``).

    h = embed(ids) * embedding_multiplier
    block:  x = rms(h)
            h = h + mixer(x) * ssm_out_multiplier
                  + attention(x * attention_in_multiplier) * attention_out_multiplier
            h = h + mlp(rms(h))
    logits = lm_head(rms(h)) * lm_head_multiplier

So every layer of a served slot holds TWO kinds of state: keys and values that
grow a row a token (paged, ``ops/paged_decode_kernel.py``'s grouped-query form)
and a recurrent state of fixed size that every token rewrites whole, plus the
mixer's short convolution tail (``ops/ssm.py``). ``FalconH1Cache`` is the one
pytree that holds both for a pool of slots; ``prefill_chunk_paged`` and
``decode_rows_paged`` are the two steps the serving engine's tick program is
built from, with one pass of ``_head`` over the slots' rows a tick
(``models/core/serving_api.py`` lists what the engine asks).

Arithmetic: matrix products in ``dtype`` (bfloat16 when served) accumulated in
float32; norms, the convolution, the state-space recurrence and the softmax in
float32. The recurrent state is float32 always: it is summed over thousands of
steps.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import flax.linen as nn
import flax.struct
import jax
import jax.numpy as jnp

from perceiver_io_tpu.models.core.config import FalconH1Config
from perceiver_io_tpu.models.core.serving_api import ServingTraits
from perceiver_io_tpu.ops import paged_decode_kernel as paged
from perceiver_io_tpu.ops import ssm

_HIGHEST = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------ building blocks
def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    """``weight * x / rms(x)``, the statistics in float32, in ``x``'s dtype."""
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return weight.astype(x.dtype) * xf.astype(x.dtype)


def gated_group_rms_norm(y: jax.Array, gate: jax.Array, weight: jax.Array, groups: int, eps: float) -> jax.Array:
    """The mixer's output norm (``mamba_norm_before_gate`` false): gate first,
    ``y * silu(gate)``, then RMS-normalise each of ``groups`` equal parts of the
    last axis on its own, then scale. float32 in and out."""
    y = y.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
    parts = y.reshape(*y.shape[:-1], groups, y.shape[-1] // groups)
    parts = parts * jax.lax.rsqrt(jnp.mean(jnp.square(parts), axis=-1, keepdims=True) + eps)
    return parts.reshape(y.shape) * weight.astype(jnp.float32)


def rope_half(t: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotate-half rotary over the whole head: t (..., n, heads, d), positions
    (..., n); channel ``i`` pairs with ``i + d/2``. float32 angles."""
    d = t.shape[-1]
    inv_freq = 1.0 / (float(theta) ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # (..., n, d/2)
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[..., None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[..., None, :]
    tf = t.astype(jnp.float32)
    rotated = jnp.concatenate([-tf[..., d // 2:], tf[..., : d // 2]], axis=-1)
    return (tf * cos + rotated * sin).astype(t.dtype)


# ------------------------------------------------------------------- the cache
class FalconH1Cache(flax.struct.PyTreeNode):
    """Everything a pool of serving slots keeps between ticks.

    ``kp`` / ``vp``: (layers, num_pages, page_size, kv_heads*head_dim) page
        pools of ROTATED keys and values under ONE ``page_table`` (B, P); page
        0 is the trash page. Token ``t`` of a slot sits at physical position
        ``t`` of its row for the slot's whole life (no ring: every layer is
        full attention).
    ``length``: (B,) tokens written; ``active``: (B,) the slot decodes. A slot
        in the middle of its prefill is neither: its chunks write through the
        reservation the engine holds, its row here stays trash, and the decode
        step leaves its recurrent state alone.
    ``ssm_state``: (layers, B, heads, d_head, d_state) float32;
    ``conv_state``: (layers, B, (d_conv - 1) * conv_dim), the mixer's last
        ``d_conv - 1`` inputs, oldest first, laid flat (a slot's tail is one
        lane-dense row, not three rows of a tile);
    ``last_hidden``: (B, hidden) the residual stream at a slot's newest prompt
        token: the row the finish lane installs, whose head gives the first
        token's logits.
    """

    kp: jax.Array
    vp: jax.Array
    page_table: jax.Array
    length: jax.Array
    active: jax.Array
    ssm_state: jax.Array
    conv_state: jax.Array
    last_hidden: jax.Array

    @property
    def page_size(self) -> int:
        return self.kp.shape[2]

    @property
    def pages_per_slot(self) -> int:
        return self.page_table.shape[1]

    def install_slot(self, slot: jax.Array, table_row: jax.Array, tokens: jax.Array) -> "FalconH1Cache":
        """The end of a prompt: the slot's pages and recurrent state are already
        written; point its row at them and let it decode from ``tokens`` on."""
        return self.replace(
            page_table=self.page_table.at[slot].set(table_row),
            length=self.length.at[slot].set(jnp.asarray(tokens, jnp.int32)),
            active=self.active.at[slot].set(True),
        )

    def release_slot(self, slot: jax.Array) -> "FalconH1Cache":
        """Free form: trash row, no tokens, not decoding. The recurrent state
        is left as it is — nothing reads it before the next claim's first chunk
        lane zeroes it — and the pages go back to the pool untouched."""
        return self.replace(
            page_table=self.page_table.at[slot].set(jnp.zeros((self.pages_per_slot,), jnp.int32)),
            length=self.length.at[slot].set(0),
            active=self.active.at[slot].set(False),
        )

    def quarantine_slot(self, slot: jax.Array, table_row: jax.Array) -> "FalconH1Cache":
        """Containment: zero the pages ``table_row`` names and the slot's
        recurrent state, so nothing non-finite survives in the pool."""
        return self.replace(
            kp=self.kp.at[:, table_row].set(0), vp=self.vp.at[:, table_row].set(0),
            ssm_state=self.ssm_state.at[:, slot].set(0), conv_state=self.conv_state.at[:, slot].set(0),
            last_hidden=self.last_hidden.at[slot].set(0),
        )


# ------------------------------------------------------------------- the model
class FalconH1ForCausalLM(nn.Module):
    config: FalconH1Config
    deterministic: bool = True
    dtype: Optional[jnp.dtype] = None
    param_dtype: jnp.dtype = jnp.float32

    def setup(self):
        cfg = self.config
        d, f = cfg.hidden_size, cfg.intermediate_size
        hq, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        normal = nn.initializers.normal(cfg.init_scale)
        ones = nn.initializers.ones

        def leaf(name, init, shape):
            return self.param(name, init, shape, self.param_dtype)

        self.embed_tokens = leaf("embed_tokens", normal, (cfg.vocab_size, d))
        self.lm_head = leaf("lm_head", normal, (d, cfg.vocab_size))
        self.final_layernorm = leaf("final_layernorm", ones, (d,))
        shapes = {
            "input_layernorm": (ones, (d,)), "pre_ff_layernorm": (ones, (d,)),
            "q_proj": (normal, (d, hq * hd)), "k_proj": (normal, (d, hkv * hd)),
            "v_proj": (normal, (d, hkv * hd)), "o_proj": (normal, (hq * hd, d)),
            "in_proj": (normal, (d, cfg.mamba_d_ssm + cfg.conv_dim + cfg.mamba_n_heads)),
            "conv_weight": (normal, (cfg.mamba_d_conv, cfg.conv_dim)), "conv_bias": (normal, (cfg.conv_dim,)),
            "dt_bias": (ones, (cfg.mamba_n_heads,)), "A_log": (ones, (cfg.mamba_n_heads,)),
            "D": (ones, (cfg.mamba_n_heads,)), "mixer_norm": (ones, (cfg.mamba_d_ssm,)),
            "out_proj": (normal, (cfg.mamba_d_ssm, d)),
            "gate_proj": (normal, (d, f)), "up_proj": (normal, (d, f)), "down_proj": (normal, (f, d)),
        }
        # one buffer a leaf: a layer's matrices are read where they lie, never sliced out of a stack
        self.layers = [{name: leaf(f"layers_{i}_{name}", init, shape) for name, (init, shape) in shapes.items()}
                       for i in range(cfg.num_hidden_layers)]
        m = cfg.ssm_multipliers
        g_n = cfg.mamba_n_groups * cfg.mamba_d_state
        self.mup_vector = jnp.concatenate([
            jnp.full((cfg.mamba_d_ssm,), m[0]), jnp.full((cfg.mamba_d_ssm,), m[1]), jnp.full((g_n,), m[2]),
            jnp.full((g_n,), m[3]), jnp.full((cfg.mamba_n_heads,), m[4])]).astype(jnp.float32)

    # ----------------------------------------------------------- arithmetic
    @property
    def _dt(self):
        return self.dtype if self.dtype is not None else self.param_dtype

    def _mm(self, x: jax.Array, w: jax.Array) -> jax.Array:
        dt = self._dt
        precision = _HIGHEST if dt == jnp.float32 else None
        return jnp.dot(x.astype(dt), w.astype(dt), precision=precision, preferred_element_type=jnp.float32).astype(dt)

    def _norm(self, x, weight):
        return rms_norm(x, weight, self.config.rms_norm_eps)

    def _embed(self, ids: jax.Array) -> jax.Array:
        return (jnp.take(self.embed_tokens, ids, axis=0).astype(self._dt)
                * jnp.asarray(self.config.embedding_multiplier, self._dt))

    def _head(self, h: jax.Array) -> jax.Array:
        # "head": the scope name the trace tools read the output head's time by
        with jax.named_scope("head"):
            logits = self._mm(self._norm(h, self.final_layernorm), self.lm_head)
            return logits * jnp.asarray(self.config.lm_head_multiplier, logits.dtype)

    def _mlp(self, p, h: jax.Array) -> jax.Array:
        gate_m, down_m = self.config.mlp_multipliers
        x = self._norm(h, p["pre_ff_layernorm"])
        gate = self._mm(x, p["gate_proj"]) * jnp.asarray(gate_m, self._dt)
        y = self._mm(x, p["up_proj"]) * jax.nn.silu(gate.astype(jnp.float32)).astype(self._dt)
        return self._mm(y, p["down_proj"]) * jnp.asarray(down_m, self._dt)

    def _mixer_in(self, p, x: jax.Array):
        """x (..., hidden) normed -> (gate z, conv input xBC, raw dt)."""
        cfg = self.config
        u = self._mm(x * jnp.asarray(cfg.ssm_in_multiplier, x.dtype), p["in_proj"])
        u = u * self.mup_vector.astype(u.dtype)
        return jnp.split(u, (cfg.mamba_d_ssm, cfg.mamba_d_ssm + cfg.conv_dim), axis=-1)

    def _mixer_split(self, p, conv_out: jax.Array, dt_raw: jax.Array):
        """Convolved (..., conv_dim) float32 -> (x (..., H, P), B, C (..., G, N), dt (..., H), A (H,))."""
        cfg = self.config
        g, n = cfg.mamba_n_groups, cfg.mamba_d_state
        xs, b, c = jnp.split(conv_out, (cfg.mamba_d_ssm, cfg.mamba_d_ssm + g * n), axis=-1)
        lead = conv_out.shape[:-1]
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
        a = -jnp.exp(p["A_log"].astype(jnp.float32))
        return (xs.reshape(*lead, cfg.mamba_n_heads, cfg.mamba_d_head), b.reshape(*lead, g, n),
                c.reshape(*lead, g, n), dt, a)

    def _mixer_out(self, p, y: jax.Array, xs: jax.Array, z: jax.Array) -> jax.Array:
        """y, xs (..., H, P) float32 -> the mixer's contribution to the residual stream."""
        cfg = self.config
        y = y + p["D"].astype(jnp.float32)[:, None] * xs
        y = gated_group_rms_norm(y.reshape(*y.shape[:-2], cfg.mamba_d_ssm), z, p["mixer_norm"],
                                 cfg.mamba_n_groups, cfg.rms_norm_eps)
        return self._mm(y, p["out_proj"]) * jnp.asarray(cfg.ssm_out_multiplier, self._dt)

    def _conv(self, p, window: jax.Array, rows: int) -> jax.Array:
        """Causal depthwise convolution + SiLU over ``window`` (rows + d_conv - 1,
        conv_dim): row ``j`` of the result sees window rows ``j .. j + d_conv - 1``."""
        w = p["conv_weight"].astype(jnp.float32)
        wf = window.astype(jnp.float32)
        out = sum(w[k] * jax.lax.dynamic_slice_in_dim(wf, k, rows, axis=0) for k in range(w.shape[0]))
        return jax.nn.silu(out + p["conv_bias"].astype(jnp.float32))

    def _qkv(self, p, x: jax.Array, positions: jax.Array):
        """x (n, hidden) normed, positions (n,) -> (q (n, h_q, d) scaled and
        rotated, k (n, h_kv*d) rotated, v (n, h_kv*d))."""
        cfg = self.config
        n = x.shape[0]
        hq, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        xa = x * jnp.asarray(cfg.attention_in_multiplier, x.dtype)
        q = self._mm(xa, p["q_proj"]).reshape(n, hq, hd)
        k = (self._mm(xa, p["k_proj"]) * jnp.asarray(cfg.key_multiplier, self._dt)).reshape(n, hkv, hd)
        q = rope_half(q, positions, cfg.rope_theta) * jnp.asarray(hd ** -0.5, self._dt)
        k = rope_half(k, positions, cfg.rope_theta)
        return q, k.reshape(n, hkv * hd), self._mm(xa, p["v_proj"])

    def _attend(self, p, q: jax.Array, k: jax.Array, v: jax.Array, visible: jax.Array) -> jax.Array:
        """q (n, h_q, d) against k / v (m, h_kv*d) under ``visible`` (n, m): one
        softmax per query head, each K/V head shared by its ``n_rep`` query heads."""
        cfg = self.config
        hq, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        n, m = q.shape[0], k.shape[0]
        dt = self._dt
        precision = _HIGHEST if dt == jnp.float32 else None
        qg = q.reshape(n, hkv, hq // hkv, hd)
        s = jnp.einsum("nkgd,mkd->kgnm", qg, k.reshape(m, hkv, hd).astype(dt), precision=precision,
                       preferred_element_type=jnp.float32)
        s = jnp.where(visible[None, None], s, -jnp.inf)
        prob = jax.nn.softmax(s, axis=-1).astype(dt)
        o = jnp.einsum("kgnm,mkd->nkgd", prob, v.reshape(m, hkv, hd).astype(dt), precision=precision,
                       preferred_element_type=jnp.float32).astype(dt)
        return self._mm(o.reshape(n, hq * hd), p["o_proj"]) * jnp.asarray(cfg.attention_out_multiplier, dt)

    # --------------------------------------------------------- full forward
    def _forward_one(self, ids: jax.Array) -> jax.Array:
        cfg = self.config
        n = ids.shape[0]
        pos = jnp.arange(n)
        causal = pos[:, None] >= pos[None, :]
        h = self._embed(ids)
        for p in self.layers:
            x = self._norm(h, p["input_layernorm"])
            z, xbc, dt_raw = self._mixer_in(p, x)
            window = jnp.concatenate([jnp.zeros((cfg.mamba_d_conv - 1, cfg.conv_dim), xbc.dtype), xbc])
            xs, b, c, dt, a = self._mixer_split(p, self._conv(p, window, n), dt_raw)
            zero = jnp.zeros((cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state), jnp.float32)
            y, _ = ssm.ssd_chunk_scan(xs, dt, a, b, c, zero, cfg.mamba_chunk_size)
            q, k, v = self._qkv(p, x, pos)
            h = h + self._mixer_out(p, y, xs, z) + self._attend(p, q, k, v, causal)
            h = h + self._mlp(p, h)
        return self._head(h)

    def __call__(self, ids: jax.Array) -> jax.Array:
        """ids (B, n) -> logits (B, n, vocab): the plain forward pass, no cache."""
        return jax.vmap(self._forward_one)(ids)

    # ------------------------------------------------------- what is served
    @property
    def max_seq_len(self) -> int:
        return self.config.max_seq_len

    def init_paged_cache(self, batch_size: int, num_pages: int, page_size: int, dtype=jnp.float32,
                         kv_quant: Optional[str] = None) -> FalconH1Cache:
        """(a) the cache for ``batch_size`` slots over ``num_pages`` pages. Built
        from the config alone, so it works on an unbound module."""
        cfg = self.config
        if kv_quant is not None:
            raise ValueError("this model's pages are served in full precision only")
        layers, c = cfg.num_hidden_layers, cfg.num_key_value_heads * cfg.head_dim
        return FalconH1Cache(
            kp=jnp.zeros((layers, num_pages, page_size, c), dtype),
            vp=jnp.zeros((layers, num_pages, page_size, c), dtype),
            page_table=jnp.zeros((batch_size, -(-cfg.max_seq_len // page_size)), jnp.int32),
            length=jnp.zeros((batch_size,), jnp.int32),
            active=jnp.zeros((batch_size,), bool),
            ssm_state=jnp.zeros((layers, batch_size, cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state),
                                jnp.float32),
            conv_state=jnp.zeros((layers, batch_size, (cfg.mamba_d_conv - 1) * cfg.conv_dim), dtype),
            last_hidden=jnp.zeros((batch_size, cfg.hidden_size), dtype),
        )

    def serving_traits(self) -> ServingTraits:
        cfg = self.config
        state = 4 * cfg.mamba_n_heads * cfg.mamba_d_head * cfg.mamba_d_state
        missing = "a slot's recurrent state is not snapshotted"
        return ServingTraits(
            vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size, window=cfg.max_seq_len, finish_ids=0,
            recurrent_bytes_per_slot=cfg.num_hidden_layers * state,
            # (g) ``in_proj`` is the one matrix whose column count (z | xBC | dt: 9,248 at the published
            # widths) is no multiple of 128, so the one the compiler takes transposed, the layout that pads
            # nothing. The decode step reads that; the chunk lanes' product does not, and copied each layer's
            # 94.7 MB three times in front of the lanes' loop. Row-major is what every other matrix arrives in
            # and both branches read without a copy (the compiled tick: tests/test_aot_tpu_compile.py)
            row_major_leaves=tuple(f"params/layers_{i}_in_proj" for i in range(cfg.num_hidden_layers)),
            unsupported={
                "prefix_cache": f"{missing} at page boundaries, so a shared prefix's pages would come "
                                "without the state that goes with them",
                "kv_quant": "the grouped-query paged kernel reads full-precision pages only",
                "handle_preemption": f"{missing}, so a drained slot cannot be resumed elsewhere from its pages alone",
                "journal": f"{missing}: a journal replay re-admits a session through forced decode steps, "
                           "which this model's one admission path has not been proven on",
            })

    def serving_pages(self, prompt_tokens: int, max_new_tokens: int, page_size: int, bucket: int) -> int:
        """(b) every layer is full attention: a request holds all its tokens."""
        return -(-min(prompt_tokens + max_new_tokens, self.config.max_seq_len) // page_size)

    def serving_chunk_phase(self, params, cache: FalconH1Cache, lanes) -> FalconH1Cache:
        """(c) the tick's chunk lanes, packed from lane 0: each writes its rows'
        keys and values into the slot's pages in every layer and carries the
        slot's recurrent state and convolution tail on."""
        def lane(i, cache):
            return self.apply(params, lanes.ch_ids[i], lanes.ch_offset[i], lanes.ch_count[i], lanes.ch_reset[i],
                              lanes.ch_slot[i], lanes.ch_tables[i], cache, method=type(self).prefill_chunk_paged)

        return jax.lax.fori_loop(0, jnp.sum((lanes.ch_count > 0).astype(jnp.int32)), lane, cache)

    def serving_finish_phase(self, params, cache: FalconH1Cache, state, lanes, install_state: Callable):
        """(c, the end of a prompt) the last chunk left the slot's newest hidden
        row behind: it becomes the row the slot carries, from which the tick's
        one head pass reads its first token's logits, and the slot's table
        row, length and sampling state go live. No head runs here."""
        def lane(i, carry):
            cache, state = carry
            slot = lanes.fin_slot[i]
            cache = cache.install_slot(slot, lanes.fin_tables[i], lanes.fin_n[i])
            state = install_state(state, slot, cache.last_hidden[slot], lanes.fin_rng[i], lanes.fin_temp[i],
                                  lanes.fin_tk[i], lanes.fin_tp[i], lanes.fin_ds[i], lanes.fin_pad[i])
            return cache, state

        return jax.lax.fori_loop(0, jnp.sum(lanes.fin_active.astype(jnp.int32)), lane, (cache, state))

    def prefill_chunk_paged(self, ids: jax.Array, offset: jax.Array, count: jax.Array, reset: jax.Array,
                            slot: jax.Array, table_row: jax.Array, cache: FalconH1Cache) -> FalconH1Cache:
        """Prompt tokens ``[offset, offset + count)`` of the request in ``slot``;
        ids (cap,) with the rows past ``count`` padding. ``reset`` starts the
        recurrent state and the convolution tail from zero (a slot's first
        chunk); otherwise they are carried from the chunk before."""
        cfg = self.config
        cap, ps = ids.shape[0], cache.page_size
        j = jnp.arange(cap)
        real = j < count
        pos = offset + j
        # rows to pages: padding rows land on the trash page with a zero payload
        pidx = jnp.clip(pos // ps, 0, cache.pages_per_slot - 1)
        page_ids = jnp.where(real, table_row[pidx], 0)
        offs = jnp.where(real, pos % ps, 0)
        kpos = jnp.arange(cache.pages_per_slot * ps)
        visible = (kpos[None, :] <= pos[:, None]) & (kpos[None, :] < offset + count)
        kp, vp, ssm_state, conv_state = cache.kp, cache.vp, cache.ssm_state, cache.conv_state
        h = self._embed(ids)
        for l, p in enumerate(self.layers):
            x = self._norm(h, p["input_layernorm"])
            with jax.named_scope("ssd_scan"):
                z, xbc, dt_raw = self._mixer_in(p, x)
                tail = jnp.where(reset, 0, conv_state[l, slot]).reshape(cfg.mamba_d_conv - 1, cfg.conv_dim)
                window = jnp.concatenate([tail.astype(xbc.dtype), xbc])
                xs, b, c, dt, a = self._mixer_split(p, self._conv(p, window, cap), dt_raw)
                before = jnp.where(reset, 0.0, ssm_state[l, slot])
                y, after = ssm.ssd_chunk_scan(xs, jnp.where(real[:, None], dt, 0.0), a, b, c, before,
                                              cfg.mamba_chunk_size)
                ssm_state = ssm_state.at[l, slot].set(after)
                conv_state = conv_state.at[l, slot].set(
                    jax.lax.dynamic_slice_in_dim(window, count, cfg.mamba_d_conv - 1, axis=0)
                    .astype(conv_state.dtype).reshape(-1))
                mixed = self._mixer_out(p, y, xs, z)
            with jax.named_scope("attention"):
                q, k, v = self._qkv(p, x, pos)
                kp = kp.at[l, page_ids, offs].set(jnp.where(real[:, None], k, 0).astype(kp.dtype))
                vp = vp.at[l, page_ids, offs].set(jnp.where(real[:, None], v, 0).astype(vp.dtype))
                # the slot's pages, the chunk's own rows among them, in position order
                attended = self._attend(p, q, kp[l, table_row].reshape(-1, k.shape[-1]),
                                        vp[l, table_row].reshape(-1, v.shape[-1]), visible)
            h = h + mixed + attended
            with jax.named_scope("mlp"):
                h = h + self._mlp(p, h)
        last = jax.lax.dynamic_index_in_dim(h, jnp.maximum(count - 1, 0), axis=0, keepdims=False)
        return cache.replace(kp=kp, vp=vp, ssm_state=ssm_state, conv_state=conv_state,
                             last_hidden=cache.last_hidden.at[slot].set(last.astype(cache.last_hidden.dtype)))

    def decode_rows_paged(self, ids: jax.Array, cache: FalconH1Cache) -> Tuple[jax.Array, FalconH1Cache]:
        """(d) one token for every decoding slot: ids (B, 1) -> the residual
        stream's new last rows (B, hidden), the head's input. A slot that is not
        ``active`` (free, or in the middle of its prefill) computes a discarded
        row: its key and value go to the trash page, its length, recurrent state
        and convolution tail stay as they are."""
        cfg = self.config
        b, ps = ids.shape[0], cache.page_size
        active = cache.active
        pos = jnp.where(active, cache.length, 0)
        rows = jnp.arange(b)
        page_ids = jnp.where(active, cache.page_table[rows, jnp.clip(pos // ps, 0, cache.pages_per_slot - 1)], 0)
        offs = jnp.where(active, pos % ps, 0)
        visible = jnp.where(active, pos + 1, 0)
        use_ssm_kernel = ssm.ssm_kernel_supported(cfg.mamba_n_heads, cfg.mamba_n_groups, cfg.mamba_d_head,
                                                  cfg.mamba_d_state)
        use_gqa_kernel = paged.paged_gqa_decode_supported(ps, cfg.head_dim, cfg.num_key_value_heads)
        kp, vp, ssm_state, conv_state = cache.kp, cache.vp, cache.ssm_state, cache.conv_state
        h = self._embed(ids[:, 0])
        for l, p in enumerate(self.layers):
            x = self._norm(h, p["input_layernorm"])
            with jax.named_scope("ssm_update"):
                z, xbc, dt_raw = self._mixer_in(p, x)
                tails = conv_state[l].reshape(b, cfg.mamba_d_conv - 1, cfg.conv_dim)
                window = jnp.concatenate([tails.astype(xbc.dtype), xbc[:, None]], axis=1)  # (B, d_conv, C)
                conv_state = conv_state.at[l].set(jnp.where(
                    active[:, None], window[:, 1:].astype(conv_state.dtype).reshape(b, -1), conv_state[l]))
                conv = jnp.sum(window.astype(jnp.float32) * p["conv_weight"].astype(jnp.float32), axis=1)
                conv = jax.nn.silu(conv + p["conv_bias"].astype(jnp.float32))
                xs, bm, cm, dt, a = self._mixer_split(p, conv, dt_raw)
                step = ssm.ssm_decode_update if use_ssm_kernel else ssm.ssm_decode_update_xla
                ssm_state, y = step(ssm_state, l, xs, dt, a, bm, cm, active)
                mixed = self._mixer_out(p, y, xs, z)
            with jax.named_scope("attention"):
                # each slot is its own sequence of one row: positions (B, 1)
                xa = x[:, None]
                q, k, v = jax.vmap(lambda xr, pr: self._qkv(p, xr, pr))(xa, pos[:, None])
                kp = kp.at[l, page_ids, offs].set(k[:, 0].astype(kp.dtype))
                vp = vp.at[l, page_ids, offs].set(v[:, 0].astype(vp.dtype))
                attend = (paged.fused_paged_decode_attention_gqa if use_gqa_kernel
                          else paged.paged_gqa_reference_attention)
                o = attend(q[:, 0], kp, vp, cache.page_table, visible, l)
                attended = (self._mm(o.reshape(b, -1), p["o_proj"])
                            * jnp.asarray(cfg.attention_out_multiplier, self._dt))
            h = h + mixed + attended
            with jax.named_scope("mlp"):
                h = h + self._mlp(p, h)
        cache = cache.replace(kp=kp, vp=vp, ssm_state=ssm_state, conv_state=conv_state,
                              length=cache.length + active.astype(jnp.int32))
        return h, cache

    def decode_step_paged(self, ids: jax.Array, cache: FalconH1Cache) -> Tuple[jax.Array, FalconH1Cache]:
        """ids (B, 1) -> logits (B, 1, vocab): the head of ``decode_rows_paged``'s rows."""
        rows, cache = self.decode_rows_paged(ids, cache)
        return self._head(rows)[:, None], cache
