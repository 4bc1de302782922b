"""LFM2 mixture-of-experts (``model_type`` ``lfm2_moe``): a decoder whose layers
mix tokens by a gated short convolution or by grouped-query attention, and
whose feed-forward is a routed expert layer after the first dense ones
(``transformers``' ``models/lfm2`` is the published implementation of three of
its four parts; the router is the release's ``Lfm2MoeSparseMoeBlock``; the
equations are restated in ``benchmark/families/lfm2_moe/reference.py``).

    h = embed(ids)
    layer l:  h = h + op_l(rms(h))        op_l: short convolution | attention
              h = h + ffn_l(rms(h))       ffn_l: dense gated MLP | routed experts
    logits = rms(h) embed^T               (the head is tied to the embedding)

    short convolution:  [B, C, x] = split3(u W_in); z = B * x;
                        c_t = sum_j w[j] * z_{t-2+j}; out (C * c) W_out
    attention:          32 query heads over 8 K/V heads of 64; q and k through an
                        RMSNorm over the head BEFORE rotate-half RoPE
    experts:            ``ops/moe.py``: sigmoid scores, top-4 of score + bias,
                        weights score / (sum + 1e-6)

A served slot holds two kinds of state: keys and values of its ATTENTION layers
that grow a row a token (paged, ``ops/paged_decode_kernel.py``'s grouped-query
form) and, for every CONVOLUTION layer, the last ``conv_L_cache - 1`` columns of
``z``. ``Lfm2MoeCache`` is the one pytree that holds both for a pool of slots,
with the expert layers' assignment counters beside them; ``prefill_chunk_paged``
and ``decode_rows_paged`` are the two steps the serving engine's tick program is
built from (``models/core/serving_api.py`` lists what the engine asks). Layer
kinds are static Python over ``layer_types``.

Arithmetic: matrix products in ``dtype`` (bfloat16 when served) accumulated in
float32; norms, the convolution's sum, the router's sigmoid, top-k and
normalisation, and the softmax in float32.
"""

from __future__ import annotations

import contextlib
from typing import Callable, Optional, Tuple

import flax.linen as nn
import flax.struct
import jax
import jax.numpy as jnp

from perceiver_io_tpu.models.core.config import Lfm2MoeConfig
from perceiver_io_tpu.models.core.falcon_h1 import rms_norm, rope_half
from perceiver_io_tpu.models.core.serving_api import TICK_CHUNK_SCOPE, TICK_DECODE_SCOPE, ServingTraits
from perceiver_io_tpu.ops import moe
from perceiver_io_tpu.ops import paged_decode_kernel as paged

_HIGHEST = jax.lax.Precision.HIGHEST
# rows of ``Lfm2MoeCache.expert_counts``: the decode step's assignments, the chunk lanes'
DECODE_COUNTS, CHUNK_COUNTS = 0, 1


def _lane(lanes, i):
    """Chunk lane ``i`` of the tick's descriptor, as ``prefill_chunk_paged`` takes it."""
    return (lanes.ch_ids[i], lanes.ch_offset[i], lanes.ch_count[i], lanes.ch_reset[i], lanes.ch_slot[i],
            lanes.ch_tables[i])


# ------------------------------------------------------------------- the cache
class Lfm2MoeCache(flax.struct.PyTreeNode):
    """Everything a pool of serving slots keeps between ticks.

    ``kp`` / ``vp``: (attention layers, num_pages, page_size, kv_heads*head_dim)
        page pools of ROTATED keys and values under ONE ``page_table`` (B, P);
        page 0 is the trash page. Token ``t`` of a slot sits at physical
        position ``t`` of its row for the slot's whole life. The convolution
        layers hold no page.
    ``length``: (B,) tokens written; ``active``: (B,) the slot decodes. A slot
        in the middle of its prefill is neither.
    ``conv_state``: (convolution layers, B, (conv_L_cache - 1) * hidden): a
        layer's last ``conv_L_cache - 1`` columns of ``z = B * x``, oldest
        first, laid flat;
    ``last_hidden``: (B, hidden) the residual stream at a slot's newest prompt
        token: the row the finish lane installs.
    ``expert_counts``: (2, expert layers, experts) int32: assignments each
        expert received since the counters were last taken, the decode steps'
        in row 0 and the chunk lanes' in row 1 (an expert's matrices are read
        once a CALL, and the two phases are two calls).
    """

    kp: jax.Array
    vp: jax.Array
    page_table: jax.Array
    length: jax.Array
    active: jax.Array
    conv_state: jax.Array
    last_hidden: jax.Array
    expert_counts: jax.Array

    @property
    def page_size(self) -> int:
        return self.kp.shape[2]

    @property
    def pages_per_slot(self) -> int:
        return self.page_table.shape[1]

    def install_slot(self, slot: jax.Array, table_row: jax.Array, tokens: jax.Array) -> "Lfm2MoeCache":
        """The end of a prompt: the slot's pages and convolution columns are
        already written; point its row at them and let it decode from
        ``tokens`` on."""
        return self.replace(
            page_table=self.page_table.at[slot].set(table_row),
            length=self.length.at[slot].set(jnp.asarray(tokens, jnp.int32)),
            active=self.active.at[slot].set(True),
        )

    def release_slot(self, slot: jax.Array) -> "Lfm2MoeCache":
        """Free form: trash row, no tokens, not decoding. The convolution
        columns are left as they are (the next claim's first chunk lane zeroes
        them) and the pages go back to the pool untouched."""
        return self.replace(
            page_table=self.page_table.at[slot].set(jnp.zeros((self.pages_per_slot,), jnp.int32)),
            length=self.length.at[slot].set(0),
            active=self.active.at[slot].set(False),
        )

    def quarantine_slot(self, slot: jax.Array, table_row: jax.Array) -> "Lfm2MoeCache":
        """Containment: zero the pages ``table_row`` names and the slot's
        convolution columns, so nothing non-finite survives in the pool."""
        return self.replace(
            kp=self.kp.at[:, table_row].set(0), vp=self.vp.at[:, table_row].set(0),
            conv_state=self.conv_state.at[:, slot].set(0), last_hidden=self.last_hidden.at[slot].set(0),
        )

    def take_expert_counts(self, taken: jax.Array) -> Tuple[jax.Array, "Lfm2MoeCache"]:
        """(the counters, the cache): where ``taken`` (a traced flag: the tick's
        outputs will be read) the counters start again from zero, else they go
        on adding (``ServingTraits.expert_counters``)."""
        counts = self.expert_counts
        return counts, self.replace(expert_counts=jnp.where(taken, 0, counts))


# ------------------------------------------------------------------- the model
class Lfm2MoeForCausalLM(nn.Module):
    config: Lfm2MoeConfig
    deterministic: bool = True
    dtype: Optional[jnp.dtype] = None
    param_dtype: jnp.dtype = jnp.float32

    def setup(self):
        cfg = self.config
        d, f, w = cfg.hidden_size, cfg.intermediate_size, cfg.moe_intermediate_size
        hq, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        normal = nn.initializers.normal(cfg.init_scale)
        ones, zeros = nn.initializers.ones, nn.initializers.zeros

        def leaf(name, init, shape):
            return self.param(name, init, shape, self.param_dtype)

        self.embed_tokens = leaf("embed_tokens", normal, (cfg.vocab_size, d))
        self.embedding_norm = leaf("embedding_norm", ones, (d,))
        operators = {
            "conv": {"in_proj": (normal, (d, 3 * d)), "conv": (normal, (cfg.conv_L_cache, d)),
                     "out_proj": (normal, (d, d))},
            "full_attention": {"q_proj": (normal, (d, hq * hd)), "k_proj": (normal, (d, hkv * hd)),
                               "v_proj": (normal, (d, hkv * hd)), "o_proj": (normal, (hq * hd, d)),
                               "q_layernorm": (ones, (hd,)), "k_layernorm": (ones, (hd,))},
        }
        dense = {"w1": (normal, (d, f)), "w3": (normal, (d, f)), "w2": (normal, (f, d))}
        experts = {"router": (normal, (d, cfg.num_experts)), "expert_bias": (zeros, (cfg.num_experts,)),
                   "experts_w13": (normal, (cfg.num_experts, d, 2 * w)),
                   "experts_w2": (normal, (cfg.num_experts, w, d))}
        # one buffer a leaf: a layer's matrices are read where they lie, never sliced out of a stack
        layers = []
        for i, kind in enumerate(cfg.layer_types):
            shapes = {"operator_norm": (ones, (d,)), "ffn_norm": (ones, (d,)), **operators[kind],
                      **(dense if i < cfg.num_dense_layers else experts)}
            layers.append({name: leaf(f"layers_{i}_{name}", init, shape) for name, (init, shape) in shapes.items()})
        self.layers = layers

    # ----------------------------------------------------------- arithmetic
    @property
    def _dt(self):
        return self.dtype if self.dtype is not None else self.param_dtype

    def _mm(self, x: jax.Array, w: jax.Array) -> jax.Array:
        dt = self._dt
        precision = _HIGHEST if dt == jnp.float32 else None
        return jnp.dot(x.astype(dt), w.astype(dt), precision=precision, preferred_element_type=jnp.float32).astype(dt)

    def _norm(self, x, weight):
        return rms_norm(x, weight, self.config.norm_eps)

    def _embed(self, ids: jax.Array) -> jax.Array:
        return jnp.take(self.embed_tokens, ids, axis=0).astype(self._dt)

    def _head(self, h: jax.Array) -> jax.Array:
        # "head": the scope name the trace tools read the output head's time by
        with jax.named_scope("head"):
            dt = self._dt
            x = self._norm(h, self.embedding_norm).astype(dt)
            # tied to the embedding: contracted over its hidden axis where it lies
            return jax.lax.dot_general(x, self.embed_tokens.astype(dt), (((x.ndim - 1,), (1,)), ((), ())),
                                       precision=_HIGHEST if dt == jnp.float32 else None,
                                       preferred_element_type=jnp.float32).astype(dt)

    def _conv_in(self, p, x: jax.Array):
        """x (..., hidden) normed -> (z = B * x, the gate C), each (..., hidden)."""
        b, c, xs = jnp.split(self._mm(x, p["in_proj"]), 3, axis=-1)
        return b * xs, c

    def _conv_out(self, p, c: jax.Array, conv: jax.Array) -> jax.Array:
        return self._mm(c.astype(jnp.float32) * conv, p["out_proj"])

    def _conv_rows(self, p, window: jax.Array, rows: int) -> jax.Array:
        """Causal depthwise convolution over ``window`` (rows + L - 1, hidden):
        row ``t`` of the result is ``sum_j w[j] * window[t + j]``. float32."""
        w = p["conv"].astype(jnp.float32)
        wf = window.astype(jnp.float32)
        return sum(w[j] * jax.lax.dynamic_slice_in_dim(wf, j, rows, axis=0) for j in range(w.shape[0]))

    def _qkv(self, p, x: jax.Array, positions: jax.Array):
        """x (n, hidden) normed, positions (n,) -> (q (n, h_q, d) normed, rotated
        and scaled, k (n, h_kv*d) normed and rotated, v (n, h_kv*d))."""
        cfg = self.config
        n = x.shape[0]
        hq, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        q = self._norm(self._mm(x, p["q_proj"]).reshape(n, hq, hd), p["q_layernorm"])
        k = self._norm(self._mm(x, p["k_proj"]).reshape(n, hkv, hd), p["k_layernorm"])
        q = rope_half(q, positions, cfg.rope_theta) * jnp.asarray(hd ** -0.5, self._dt)
        k = rope_half(k, positions, cfg.rope_theta)
        return q, k.reshape(n, hkv * hd), self._mm(x, p["v_proj"])

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
        return self._mm(o.reshape(n, hq * hd), p["o_proj"])

    def _ffn(self, p, h: jax.Array, valid: Optional[jax.Array] = None, load_groups: Optional[jax.Array] = None):
        """h (T, hidden) -> (the feed-forward's contribution (T, hidden), the
        experts' load (experts,) int32, by group of rows (G, experts) where
        ``load_groups`` (G, T) names them, or None for a dense layer)."""
        cfg = self.config
        x = self._norm(h, p["ffn_norm"])
        if "w1" in p:
            with jax.named_scope("mlp"):
                gate = jax.nn.silu(self._mm(x, p["w1"]).astype(jnp.float32)).astype(self._dt)
                return self._mm(gate * self._mm(x, p["w3"]), p["w2"]), None
        with jax.named_scope("moe"):
            weights = moe.ExpertWeights(p["router"], p["expert_bias"] if cfg.use_expert_bias
                                        else jnp.zeros_like(p["expert_bias"]), p["experts_w13"], p["experts_w2"])
            return moe.expert_layer(
                x.astype(self._dt), weights, (0, cfg.num_experts), cfg.num_experts_per_tok,
                cfg.routed_scaling_factor, cfg.norm_topk_prob, valid,
                use_kernel=moe.grouped_kernel_supported(cfg.hidden_size, cfg.moe_intermediate_size),
                load_groups=load_groups)

    # --------------------------------------------------------- full forward
    def _forward_one(self, ids: jax.Array) -> jax.Array:
        cfg = self.config
        n = ids.shape[0]
        pos = jnp.arange(n)
        causal = pos[:, None] >= pos[None, :]
        h = self._embed(ids)
        for kind, p in zip(cfg.layer_types, self.layers):
            x = self._norm(h, p["operator_norm"])
            if kind == "conv":
                z, c = self._conv_in(p, x)
                window = jnp.concatenate([jnp.zeros((cfg.conv_L_cache - 1, cfg.hidden_size), z.dtype), z])
                h = h + self._conv_out(p, c, self._conv_rows(p, window, n))
            else:
                q, k, v = self._qkv(p, x, pos)
                h = h + self._attend(p, q, k, v, causal)
            h = h + self._ffn(p, h)[0]
        return self._head(h)

    def __call__(self, ids: jax.Array) -> jax.Array:
        """ids (B, n) -> logits (B, n, vocab): the plain forward pass, no cache,
        a row at a time (the grouped expert product has no batched form)."""
        return jax.lax.map(self._forward_one, ids)

    # ------------------------------------------------------- what is served
    @property
    def max_seq_len(self) -> int:
        return self.config.max_seq_len

    def init_paged_cache(self, batch_size: int, num_pages: int, page_size: int, dtype=jnp.float32,
                         kv_quant: Optional[str] = None) -> Lfm2MoeCache:
        """(a) the cache for ``batch_size`` slots over ``num_pages`` pages. Built
        from the config alone, so it works on an unbound module."""
        cfg = self.config
        if kv_quant is not None:
            raise ValueError("this model's pages are served in full precision only")
        c = cfg.num_key_value_heads * cfg.head_dim
        attention, conv = len(cfg.attention_layers), len(cfg.conv_layers)
        return Lfm2MoeCache(
            kp=jnp.zeros((attention, num_pages, page_size, c), dtype),
            vp=jnp.zeros((attention, num_pages, page_size, c), dtype),
            page_table=jnp.zeros((batch_size, -(-cfg.max_seq_len // page_size)), jnp.int32),
            length=jnp.zeros((batch_size,), jnp.int32),
            active=jnp.zeros((batch_size,), bool),
            conv_state=jnp.zeros((conv, batch_size, (cfg.conv_L_cache - 1) * cfg.hidden_size), dtype),
            last_hidden=jnp.zeros((batch_size, cfg.hidden_size), dtype),
            expert_counts=jnp.zeros((2, len(cfg.expert_layers), cfg.num_experts), jnp.int32),
        )

    def serving_traits(self) -> ServingTraits:
        cfg = self.config
        # the columns are kept in the served dtype; counted at two bytes a value
        state = len(cfg.conv_layers) * (cfg.conv_L_cache - 1) * cfg.hidden_size * 2
        missing = "a slot's convolution columns are not snapshotted"
        return ServingTraits(
            vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size, window=cfg.max_seq_len, finish_ids=0,
            recurrent_bytes_per_slot=state,
            expert_counters=(len(cfg.expert_layers), cfg.num_experts) if cfg.expert_layers else None,
            # an expert's matrices are read once a call, and a decode step and a chunk lane are
            # both bound by that read: the lane's rows ride the decode step's one call a layer
            chunk_rides_decode=bool(cfg.expert_layers),
            unsupported={
                "prefix_cache": f"{missing} at page boundaries, so a shared prefix's pages would come "
                                "without the columns that go with them",
                "kv_quant": "the grouped-query paged kernel reads full-precision pages only",
                "handle_preemption": f"{missing}, so a drained slot cannot be resumed elsewhere from its pages alone",
                "journal": f"{missing}: a journal replay re-admits a session through forced decode steps, "
                           "which this model's one admission path has not been proven on",
            })

    def serving_pages(self, prompt_tokens: int, max_new_tokens: int, page_size: int, bucket: int) -> int:
        """(b) every attention layer is full attention: a request holds all its tokens."""
        return -(-min(prompt_tokens + max_new_tokens, self.config.max_seq_len) // page_size)

    def serving_ride_phase(self, params, cache: Lfm2MoeCache, lanes, ids: jax.Array, decodes: jax.Array):
        """(h, in place of (c)'s chunk phase) the tick's chunk lanes, packed from
        lane 0, with the decode step riding the first: one pass of
        ``decode_rows_with_chunk_paged`` a carried lane. ``ids`` (B, 1) the
        sampled tokens, ``decodes`` (traced) whether the tick decodes: the decode
        rows are live in lane 0's pass where it does, and computed for nobody in
        every other (a lane past the first, a tick that only carries lanes: both
        rare, and no second traced body is kept for them). Returns ``(rows (B,
        hidden), cache)``."""
        def lane(i, carry):
            rows, cache = carry
            live = decodes & (i == 0)
            new, cache = self.apply(params, ids, cache, *_lane(lanes, i), live,
                                    method=type(self).decode_rows_with_chunk_paged)
            return jnp.where(live, new, rows), cache

        rows = jnp.zeros_like(cache.last_hidden)
        return jax.lax.fori_loop(0, jnp.sum((lanes.ch_count > 0).astype(jnp.int32)), lane, (rows, cache))

    def serving_finish_phase(self, params, cache: Lfm2MoeCache, state, lanes, install_state: Callable):
        """(c, the end of a prompt) the last chunk left the slot's newest hidden
        row behind: it becomes the row the slot carries, and the slot's table
        row, length and sampling state go live. No head runs here."""
        def lane(i, carry):
            cache, state = carry
            slot = lanes.fin_slot[i]
            cache = cache.install_slot(slot, lanes.fin_tables[i], lanes.fin_n[i])
            state = install_state(state, slot, cache.last_hidden[slot], lanes.fin_rng[i], lanes.fin_temp[i],
                                  lanes.fin_tk[i], lanes.fin_tp[i], lanes.fin_ds[i], lanes.fin_pad[i])
            return cache, state

        return jax.lax.fori_loop(0, jnp.sum(lanes.fin_active.astype(jnp.int32)), lane, (cache, state))

    def _decode_operator(self, p, kind: str, l: int, h: jax.Array, cache: Lfm2MoeCache, pools, at):
        """Layer ``l`` of its kind's operator for the decode rows ``h`` (B,
        hidden), one row a slot: ``(the operator's contribution, pools)``;
        ``pools`` = (kp, vp, conv_state) as the layers so far left them, ``at``
        = (the rows that decode, positions, page ids, offsets, visible)."""
        cfg = self.config
        b, tail_rows = h.shape[0], cfg.conv_L_cache - 1
        kp, vp, conv_state = pools
        active, pos, page_ids, offs, visible = at
        x = self._norm(h, p["operator_norm"])
        if kind == "conv":
            with jax.named_scope("short_conv"):
                z, c = self._conv_in(p, x)
                tails = conv_state[l].reshape(b, tail_rows, cfg.hidden_size)
                window = jnp.concatenate([tails.astype(z.dtype), z[:, None]], axis=1)  # (B, L, hidden)
                conv_state = conv_state.at[l].set(jnp.where(
                    active[:, None], window[:, 1:].astype(conv_state.dtype).reshape(b, -1), conv_state[l]))
                conv = jnp.sum(window.astype(jnp.float32) * p["conv"].astype(jnp.float32), axis=1)
                return self._conv_out(p, c, conv), (kp, vp, conv_state)
        with jax.named_scope("attention"):
            # each slot is its own sequence of one row: positions (B, 1)
            q, k, v = jax.vmap(lambda xr, pr: self._qkv(p, xr, pr))(x[:, None], pos[:, None])
            kp = kp.at[l, page_ids, offs].set(k[:, 0].astype(kp.dtype))
            vp = vp.at[l, page_ids, offs].set(v[:, 0].astype(vp.dtype))
            use_kernel = paged.paged_gqa_decode_supported(cache.page_size, cfg.head_dim, cfg.num_key_value_heads)
            attend = paged.fused_paged_decode_attention_gqa if use_kernel else paged.paged_gqa_reference_attention
            o = attend(q[:, 0], kp, vp, cache.page_table, visible, l)
            return self._mm(o.reshape(b, -1), p["o_proj"]), (kp, vp, conv_state)

    def _chunk_operator(self, p, kind: str, l: int, h: jax.Array, pools, lane, at):
        """Layer ``l`` of its kind's operator for a chunk's rows ``h`` (cap,
        hidden) of ONE slot: ``(the operator's contribution, pools)``; ``lane`` =
        (ids, offset, count, reset, slot, table_row), ``at`` = (positions, page
        ids, offsets, visible, real) of the rows."""
        cfg = self.config
        cap, tail_rows = h.shape[0], cfg.conv_L_cache - 1
        kp, vp, conv_state = pools
        _, _, count, reset, slot, table_row = lane
        pos, page_ids, offs, visible, real = at
        x = self._norm(h, p["operator_norm"])
        if kind == "conv":
            with jax.named_scope("short_conv"):
                z, c = self._conv_in(p, x)
                tail = jnp.where(reset, 0, conv_state[l, slot]).reshape(tail_rows, cfg.hidden_size)
                window = jnp.concatenate([tail.astype(z.dtype), z])
                # the columns of the last real rows: window row count + i is z row count - 2 + i
                conv_state = conv_state.at[l, slot].set(
                    jax.lax.dynamic_slice_in_dim(window, count, tail_rows, axis=0)
                    .astype(conv_state.dtype).reshape(-1))
                return self._conv_out(p, c, self._conv_rows(p, window, cap)), (kp, vp, conv_state)
        with jax.named_scope("attention"):
            q, k, v = self._qkv(p, x, pos)
            kp = kp.at[l, page_ids, offs].set(jnp.where(real[:, None], k, 0).astype(kp.dtype))
            vp = vp.at[l, page_ids, offs].set(jnp.where(real[:, None], v, 0).astype(vp.dtype))
            # the slot's pages, the chunk's own rows among them, in position order
            out = self._attend(p, q, kp[l, table_row].reshape(-1, k.shape[-1]),
                               vp[l, table_row].reshape(-1, v.shape[-1]), visible)
            return out, (kp, vp, conv_state)

    def _rows_paged(self, cache: Lfm2MoeCache, ids: Optional[jax.Array] = None, lane=None, live=True):
        """THE loop over the layers, over an optional group of decode rows
        (``ids`` (B, 1): one token for every decoding slot) and an optional chunk
        (``lane`` = (ids (cap,), offset, count, reset, slot, table_row): prompt
        tokens ``[offset, offset + count)`` of the request in ``slot``). Each
        layer's operator runs for the two groups apart (their slots are
        disjoint: a prefilling slot does not decode), its feed-forward ONCE over
        their rows together, so an expert's matrices are read once for both.
        ``live`` (may be traced): False makes every decode row a discarded one.
        Returns ``(the decode rows' new last rows (B, hidden) or None, cache)``.
        With both groups the operations carry the tick's phase names
        (``serving_api.py`` (h)); with one, the caller's."""
        cfg = self.config
        ps, pages = cache.page_size, cache.pages_per_slot
        decodes, chunks = ids is not None, lane is not None
        both = decodes and chunks
        phase = jax.named_scope if both else (lambda name: contextlib.nullcontext())
        pools, counts = (cache.kp, cache.vp, cache.conv_state), cache.expert_counts
        h_d = h_c = None
        if decodes:
            with phase(TICK_DECODE_SCOPE):
                active = cache.active if live is True else cache.active & live
                pos = jnp.where(active, cache.length, 0)
                page_ids = jnp.where(
                    active, cache.page_table[jnp.arange(ids.shape[0]), jnp.clip(pos // ps, 0, pages - 1)], 0)
                decode_at = (active, pos, page_ids, jnp.where(active, pos % ps, 0), jnp.where(active, pos + 1, 0))
                h_d = self._embed(ids[:, 0])
        if chunks:
            with phase(TICK_CHUNK_SCOPE):
                chunk_ids, offset, count, _, slot, table_row = lane
                real = jnp.arange(chunk_ids.shape[0]) < count
                pos = offset + jnp.arange(chunk_ids.shape[0])
                # rows to pages: padding rows land on the trash page with a zero payload
                page_ids = jnp.where(real, table_row[jnp.clip(pos // ps, 0, pages - 1)], 0)
                kpos = jnp.arange(pages * ps)
                visible = (kpos[None, :] <= pos[:, None]) & (kpos[None, :] < offset + count)
                chunk_at = (pos, page_ids, jnp.where(real, pos % ps, 0), visible, real)
                h_c = self._embed(chunk_ids)
        # the rows a feed-forward sees, the decode rows first; each group's assignments
        # are counted in its own row of the counters
        book = [DECODE_COUNTS] * decodes + [CHUNK_COUNTS] * chunks
        valid, groups = (active, None) if decodes else (real, None)
        if both:
            b = ids.shape[0]
            valid = jnp.concatenate([active, real])
            groups = jnp.stack([jnp.arange(valid.shape[0]) < b, jnp.arange(valid.shape[0]) >= b])
        at = {"conv": 0, "full_attention": 0, "experts": 0}
        for kind, p in zip(cfg.layer_types, self.layers):
            l = at[kind]
            at[kind] += 1
            if decodes:
                with phase(TICK_DECODE_SCOPE):
                    out, pools = self._decode_operator(p, kind, l, h_d, cache, pools, decode_at)
                    if chunks:
                        # the chunk's rows land in the pools AFTER the decode rows' operator has read
                        # them: an order the compiler is told, or it keeps the pools apart by copying them
                        out, pools = jax.lax.optimization_barrier((out, pools))
                    h_d = h_d + out
            if chunks:
                with phase(TICK_CHUNK_SCOPE):
                    out, pools = self._chunk_operator(p, kind, l, h_c, pools, lane, chunk_at)
                    h_c = h_c + out
            # what the two groups share is booked where the decode step's is
            with phase(TICK_DECODE_SCOPE):
                h = jnp.concatenate([h_d, h_c]) if both else h_d if decodes else h_c
                out, load = self._ffn(p, h, valid, groups)
                if decodes:
                    h_d = h_d + (out[:b] if both else out)
                if chunks:
                    h_c = h_c + (out[b:] if both else out)
                if load is not None:
                    counts = counts.at[jnp.asarray(book), at["experts"]].add(load.reshape(len(book), -1))
                    at["experts"] += 1
        kp, vp, conv_state = pools
        cache = cache.replace(kp=kp, vp=vp, conv_state=conv_state, expert_counts=counts)
        if decodes:
            with phase(TICK_DECODE_SCOPE):
                cache = cache.replace(length=cache.length + active.astype(jnp.int32))
        if chunks:
            with phase(TICK_CHUNK_SCOPE):
                last = jax.lax.dynamic_index_in_dim(h_c, jnp.maximum(count - 1, 0), axis=0, keepdims=False)
                cache = cache.replace(last_hidden=cache.last_hidden.at[slot].set(last.astype(cache.last_hidden.dtype)))
        return h_d, cache

    def prefill_chunk_paged(self, ids: jax.Array, offset: jax.Array, count: jax.Array, reset: jax.Array,
                            slot: jax.Array, table_row: jax.Array, cache: Lfm2MoeCache) -> Lfm2MoeCache:
        """Prompt tokens ``[offset, offset + count)`` of the request in ``slot``;
        ids (cap,) with the rows past ``count`` padding. ``reset`` starts the
        convolution columns from zero (a slot's first chunk); otherwise they
        are carried from the chunk before."""
        return self._rows_paged(cache, lane=(ids, offset, count, reset, slot, table_row))[1]

    def decode_rows_paged(self, ids: jax.Array, cache: Lfm2MoeCache) -> Tuple[jax.Array, Lfm2MoeCache]:
        """(d) one token for every decoding slot: ids (B, 1) -> the residual
        stream's new last rows (B, hidden), the head's input. A slot that is not
        ``active`` (free, or in the middle of its prefill) computes a discarded
        row: its key and value go to the trash page, it is routed to no expert,
        and its length and convolution columns stay as they are."""
        return self._rows_paged(cache, ids=ids)

    def decode_rows_with_chunk_paged(self, ids: jax.Array, cache: Lfm2MoeCache, chunk_ids: jax.Array, offset: jax.Array,
                                     count: jax.Array, reset: jax.Array, slot: jax.Array, table_row: jax.Array,
                                     live=True) -> Tuple[jax.Array, Lfm2MoeCache]:
        """(h) the decode step with a chunk riding it: what ``prefill_chunk_paged``
        of the chunk then ``decode_rows_paged`` return, every layer's feed-forward
        run once over the rows of both. Where ``live`` (traced) is False no slot
        decodes in this pass: the chunk alone is served."""
        return self._rows_paged(cache, ids=ids, lane=(chunk_ids, offset, count, reset, slot, table_row), live=live)

    def decode_step_paged(self, ids: jax.Array, cache: Lfm2MoeCache) -> Tuple[jax.Array, Lfm2MoeCache]:
        """ids (B, 1) -> logits (B, 1, vocab): the head of ``decode_rows_paged``'s rows."""
        rows, cache = self.decode_rows_paged(ids, cache)
        return self._head(rows)[:, None], cache
