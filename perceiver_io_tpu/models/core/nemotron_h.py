"""Nemotron-H (``model_type`` ``nemotron_h``): a decoder whose every layer is ONE
mixer under one norm and one residual, its kind read from
``hybrid_override_pattern``: ``M`` a Mamba-2 mixer, ``E`` routed experts beside a
shared expert, ``*`` grouped-query attention (the hub's ``modeling_nemotron_h.py``
is the published implementation; ``transformers``' ``models/bamba`` holds the
mixer it took over and ``models/deepseek_v3`` the router; the equations are
restated in ``benchmark/families/nemotron_h/reference.py``).

    h = embed(ids)
    layer i:  h = h + mixer_i(rms(h))     mixer_i: Mamba-2 | experts | attention
    logits = rms(h) W_head                (the head is its own matrix)

    Mamba-2:    [z | xBC | dt] = u W_in; xBC = silu(conv4(xBC) + b); the
                recurrence of ``ops/ssm.py`` at 64 heads x 64 x state 128 in 8
                groups; y silu(z), normalised in 8 parts; out y W_out
    attention:  32 query heads over 2 K/V heads of 128, NO rotary embedding and
                no other position signal (the ``M`` layers carry order): keys
                are stored as projected
    experts:    ``ops/moe.py``: sigmoid scores over ALL the router's experts,
                top-6 of score + bias, weights score / (sum + 1e-20) x 2.5;
                ``relu(u W_up)^2 W_down`` for the experts HELD here
                (``config.experts_held``), plus the shared expert, in full

A served slot holds three kinds of state: a float32 recurrent state and the
convolution's last three inputs for every ``M`` layer, pages of keys and values
for the ``*`` layers only, and nothing for an ``E`` layer but its share of the
pool's assignment counters. ``NemotronHCache`` is the one pytree that holds all
three; ``prefill_chunk_paged`` and ``decode_rows_paged`` are the two steps the
serving engine's tick program is built from (``models/core/serving_api.py``),
and ``decode_rows_with_chunk_paged`` is both in one loop over the layers: the
model states ``serving_api.py`` (h), so a tick that carries a chunk lane and
decodes reads each ``E`` layer's held experts once, for the rows of both.

The expert stacks lie at ``ops/moe.pad_width(moe_intermediate_size)`` columns
(1856 -> 1920, the padding zero: exact), laid out where the weights are made.

Arithmetic: matrix products in ``dtype`` (bfloat16 when served) accumulated in
float32; norms, the convolution, the recurrence, the router's sigmoid, top-k
and normalisation, and the softmax in float32. The recurrent state is float32
always.
"""

from __future__ import annotations

import contextlib
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from perceiver_io_tpu.models.core.config import NemotronHConfig
from perceiver_io_tpu.models.core.falcon_h1 import FalconH1Cache, FalconH1ForCausalLM, gated_group_rms_norm, rms_norm
from perceiver_io_tpu.models.core.lfm2_moe import Lfm2MoeForCausalLM
from perceiver_io_tpu.models.core.serving_api import TICK_CHUNK_SCOPE, TICK_DECODE_SCOPE, ServingTraits
from perceiver_io_tpu.ops import moe
from perceiver_io_tpu.ops import paged_decode_kernel as paged
from perceiver_io_tpu.ops import ssm

_HIGHEST = jax.lax.Precision.HIGHEST
# rows of ``NemotronHCache.expert_counts``: the decode step's assignments, the chunk lanes'
DECODE_COUNTS, CHUNK_COUNTS = 0, 1


# ------------------------------------------------------------------- the cache
class NemotronHCache(FalconH1Cache):
    """``FalconH1Cache`` with another count of layers a kind: ``kp`` / ``vp``
    hold the ``*`` layers' pages (keys as projected: nothing rotates them),
    ``ssm_state`` / ``conv_state`` the ``M`` layers' state, and beside them

    ``expert_counts``: (2, ``E`` layers, the router's experts) int32:
        assignments each expert received, HELD here or not, since the counters
        were last taken; the decode steps' in row 0, the chunk lanes' in row 1.
    """

    expert_counts: jax.Array

    def take_expert_counts(self, taken: jax.Array) -> Tuple[jax.Array, "NemotronHCache"]:
        """(the counters, the cache): where ``taken`` (a traced flag: the tick's
        outputs will be read) the counters start again from zero, else they go
        on adding (``ServingTraits.expert_counters``)."""
        counts = self.expert_counts
        return counts, self.replace(expert_counts=jnp.where(taken, 0, counts))


# ------------------------------------------------------------------- the model
class NemotronHForCausalLM(nn.Module):
    config: NemotronHConfig
    deterministic: bool = True
    dtype: Optional[jnp.dtype] = None
    param_dtype: jnp.dtype = jnp.float32

    def setup(self):
        cfg = self.config
        d, inner, conv_dim, heads = cfg.hidden_size, cfg.mamba_inner, cfg.conv_dim, cfg.mamba_num_heads
        hq, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        width, shared = moe.pad_width(cfg.moe_intermediate_size), cfg.moe_shared_expert_intermediate_size
        held = cfg.experts_held[1]
        normal = nn.initializers.normal(cfg.init_scale)
        ones, zeros = nn.initializers.ones, nn.initializers.zeros

        def leaf(name, init, shape):
            return self.param(name, init, shape, self.param_dtype)

        self.embed_tokens = leaf("embed_tokens", normal, (cfg.vocab_size, d))
        self.lm_head = leaf("lm_head", normal, (d, cfg.vocab_size))
        self.final_norm = leaf("final_norm", ones, (d,))
        kinds = {
            "M": {"in_proj": (normal, (d, inner + conv_dim + heads)), "conv_weight": (normal, (cfg.conv_kernel, conv_dim)),
                  "conv_bias": (normal, (conv_dim,)), "dt_bias": (ones, (heads,)), "A_log": (ones, (heads,)),
                  "D": (ones, (heads,)), "mixer_norm": (ones, (inner,)), "out_proj": (normal, (inner, d))},
            "*": {"q_proj": (normal, (d, hq * hd)), "k_proj": (normal, (d, hkv * hd)),
                  "v_proj": (normal, (d, hkv * hd)), "o_proj": (normal, (hq * hd, d))},
            "E": {"router": (normal, (d, cfg.n_routed_experts)), "expert_bias": (zeros, (cfg.n_routed_experts,)),
                  "experts_up": (normal, (held, d, width)), "experts_down": (normal, (held, width, d)),
                  "shared_up": (normal, (d, shared)), "shared_down": (normal, (shared, d))},
        }
        # one buffer a leaf: a layer's matrices are read where they lie, never sliced out of a stack
        self.layers = [{name: leaf(f"layers_{i}_{name}", init, shape)
                        for name, (init, shape) in {"norm": (ones, (d,)), **kinds[kind]}.items()}
                       for i, kind in enumerate(cfg.hybrid_override_pattern)]

    # ----------------------------------------------------------- arithmetic
    @property
    def _dt(self):
        return self.dtype if self.dtype is not None else self.param_dtype

    def _mm(self, x: jax.Array, w: jax.Array) -> jax.Array:
        dt = self._dt
        precision = _HIGHEST if dt == jnp.float32 else None
        return jnp.dot(x.astype(dt), w.astype(dt), precision=precision, preferred_element_type=jnp.float32).astype(dt)

    def _norm(self, x, weight):
        return rms_norm(x, weight, self.config.layer_norm_epsilon)

    def _embed(self, ids: jax.Array) -> jax.Array:
        return jnp.take(self.embed_tokens, ids, axis=0).astype(self._dt)

    def _head(self, h: jax.Array) -> jax.Array:
        # "head": the scope name the trace tools read the output head's time by
        with jax.named_scope("head"):
            return self._mm(self._norm(h, self.final_norm), self.lm_head)

    def _mixer_in(self, p, x: jax.Array):
        """x (..., hidden) normed -> (gate z, conv input xBC, raw dt)."""
        cfg = self.config
        return jnp.split(self._mm(x, p["in_proj"]), (cfg.mamba_inner, cfg.mamba_inner + cfg.conv_dim), axis=-1)

    def _mixer_split(self, p, conv_out: jax.Array, dt_raw: jax.Array):
        """Convolved (..., conv_dim) float32 -> (x (..., H, P), B, C (..., G, N), dt (..., H), A (H,))."""
        cfg = self.config
        g, n, inner = cfg.n_groups, cfg.ssm_state_size, cfg.mamba_inner
        xs, b, c = jnp.split(conv_out, (inner, inner + g * n), axis=-1)
        lead = conv_out.shape[:-1]
        # no upper clamp: the published ``time_step_limit`` is (0, inf)
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
        a = -jnp.exp(p["A_log"].astype(jnp.float32))
        return (xs.reshape(*lead, cfg.mamba_num_heads, cfg.mamba_head_dim), b.reshape(*lead, g, n),
                c.reshape(*lead, g, n), dt, a)

    def _mixer_out(self, p, y: jax.Array, xs: jax.Array, z: jax.Array) -> jax.Array:
        """y, xs (..., H, P) float32 -> the mixer's contribution to the residual stream."""
        cfg = self.config
        y = y + p["D"].astype(jnp.float32)[:, None] * xs
        y = gated_group_rms_norm(y.reshape(*y.shape[:-2], cfg.mamba_inner), z, p["mixer_norm"], cfg.n_groups,
                                 cfg.layer_norm_epsilon)
        return self._mm(y, p["out_proj"])

    def _conv(self, p, window: jax.Array, rows: int) -> jax.Array:
        """Causal depthwise convolution + SiLU over ``window`` (rows + conv_kernel
        - 1, conv_dim): row ``j`` of the result sees window rows ``j .. j + conv_kernel - 1``."""
        w = p["conv_weight"].astype(jnp.float32)
        wf = window.astype(jnp.float32)
        out = sum(w[k] * jax.lax.dynamic_slice_in_dim(wf, k, rows, axis=0) for k in range(w.shape[0]))
        return jax.nn.silu(out + p["conv_bias"].astype(jnp.float32))

    def _qkv(self, p, x: jax.Array):
        """x (n, hidden) normed -> (q (n, h_q, d) scaled, k (n, h_kv*d), v (n, h_kv*d)): no position signal."""
        cfg = self.config
        q = self._mm(x, p["q_proj"]).reshape(x.shape[0], cfg.num_attention_heads, cfg.head_dim)
        return q * jnp.asarray(cfg.head_dim ** -0.5, self._dt), self._mm(x, p["k_proj"]), self._mm(x, p["v_proj"])

    def _attend(self, p, q: jax.Array, k: jax.Array, v: jax.Array, visible: jax.Array) -> jax.Array:
        """q (n, h_q, d) against k / v (m, h_kv*d) under ``visible`` (n, m): one
        softmax per query head, each K/V head shared by its ``n_rep`` query heads."""
        cfg = self.config
        hq, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        n, m = q.shape[0], k.shape[0]
        dt = self._dt
        precision = _HIGHEST if dt == jnp.float32 else None
        s = jnp.einsum("nkgd,mkd->kgnm", q.reshape(n, hkv, hq // hkv, hd), k.reshape(m, hkv, hd).astype(dt),
                       precision=precision, preferred_element_type=jnp.float32)
        prob = jax.nn.softmax(jnp.where(visible[None, None], s, -jnp.inf), axis=-1).astype(dt)
        o = jnp.einsum("kgnm,mkd->nkgd", prob, v.reshape(m, hkv, hd).astype(dt), precision=precision,
                       preferred_element_type=jnp.float32).astype(dt)
        return self._mm(o.reshape(n, hq * hd), p["o_proj"])

    def _experts(self, p, x: jax.Array, valid: Optional[jax.Array] = None, load_groups: Optional[jax.Array] = None):
        """x (T, hidden) normed -> (the held experts' part of the routed sum plus
        the shared expert (T, hidden), the load (the router's experts,) int32, by
        group of rows (G, experts) where ``load_groups`` (G, T) names them)."""
        cfg = self.config
        with jax.named_scope("moe"):
            weights = moe.ExpertWeights(p["router"], p["expert_bias"], p["experts_up"], p["experts_down"],
                                        p["shared_up"], p["shared_down"])
            return moe.expert_layer(
                x.astype(self._dt), weights, cfg.experts_held, cfg.num_experts_per_tok, cfg.routed_scaling_factor,
                cfg.norm_topk_prob, valid, form="relu2", norm_eps=1e-20,
                use_kernel=moe.grouped_kernel_supported(cfg.hidden_size, moe.pad_width(cfg.moe_intermediate_size)),
                load_groups=load_groups)

    # --------------------------------------------------------- full forward
    def _forward_one(self, ids: jax.Array) -> jax.Array:
        cfg = self.config
        n = ids.shape[0]
        pos = jnp.arange(n)
        causal = pos[:, None] >= pos[None, :]
        h = self._embed(ids)
        for kind, p in zip(cfg.hybrid_override_pattern, self.layers):
            x = self._norm(h, p["norm"])
            if kind == "M":
                z, xbc, dt_raw = self._mixer_in(p, x)
                window = jnp.concatenate([jnp.zeros((cfg.conv_kernel - 1, cfg.conv_dim), xbc.dtype), xbc])
                xs, b, c, dt, a = self._mixer_split(p, self._conv(p, window, n), dt_raw)
                zero = jnp.zeros((cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size), jnp.float32)
                y, _ = ssm.ssd_chunk_scan(xs, dt, a, b, c, zero, cfg.chunk_size)
                h = h + self._mixer_out(p, y, xs, z)
            elif kind == "E":
                h = h + self._experts(p, x)[0]
            else:
                h = h + self._attend(p, *self._qkv(p, x), causal)
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
                         kv_quant: Optional[str] = None) -> NemotronHCache:
        """(a) the cache for ``batch_size`` slots over ``num_pages`` pages. Built
        from the config alone, so it works on an unbound module."""
        cfg = self.config
        if kv_quant is not None:
            raise ValueError("this model's pages are served in full precision only")
        mamba, attention, c = len(cfg.layers_of("M")), len(cfg.layers_of("*")), cfg.num_key_value_heads * cfg.head_dim
        return NemotronHCache(
            kp=jnp.zeros((attention, num_pages, page_size, c), dtype),
            vp=jnp.zeros((attention, num_pages, page_size, c), dtype),
            page_table=jnp.zeros((batch_size, -(-cfg.max_seq_len // page_size)), jnp.int32),
            length=jnp.zeros((batch_size,), jnp.int32),
            active=jnp.zeros((batch_size,), bool),
            ssm_state=jnp.zeros((mamba, batch_size, cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size),
                                jnp.float32),
            conv_state=jnp.zeros((mamba, batch_size, (cfg.conv_kernel - 1) * cfg.conv_dim), dtype),
            last_hidden=jnp.zeros((batch_size, cfg.hidden_size), dtype),
            expert_counts=jnp.zeros((2, len(cfg.layers_of("E")), cfg.n_routed_experts), jnp.int32),
        )

    def serving_traits(self) -> ServingTraits:
        cfg = self.config
        state = 4 * cfg.mamba_num_heads * cfg.mamba_head_dim * cfg.ssm_state_size
        expert_layers = len(cfg.layers_of("E"))
        missing = "a slot's recurrent state is not snapshotted"
        return ServingTraits(
            vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size, window=cfg.max_seq_len, finish_ids=0,
            recurrent_bytes_per_slot=len(cfg.layers_of("M")) * state,
            expert_counters=(expert_layers, cfg.n_routed_experts) if expert_layers else None,
            experts_held=cfg.experts_held if expert_layers else None,
            # (h) a held expert's matrices are read once a call, and a decode step and a chunk lane are
            # both bound by that read: the lane's rows ride the decode step's one call an ``E`` layer
            chunk_rides_decode=bool(expert_layers),
            # (g) ``in_proj`` (z | xBC | dt: 10,304 columns at the published widths, no multiple of 128) is
            # the one matrix the compiler takes transposed; the chunk lanes' product cannot read that and
            # copied each ``M`` layer's 55.4 MB in front of the lanes' loop (the compiled tick, read before
            # this was stated: six copies; tests/test_aot_tpu_compile.py), as Falcon-H1's did
            row_major_leaves=tuple(f"params/layers_{i}_in_proj" for i in cfg.layers_of("M")),
            unsupported={
                "prefix_cache": f"{missing} at page boundaries, so a shared prefix's pages would come "
                                "without the state that goes with them",
                "kv_quant": "the grouped-query paged kernel reads full-precision pages only",
                "handle_preemption": f"{missing}, so a drained slot cannot be resumed elsewhere from its pages alone",
                "journal": f"{missing}: a journal replay re-admits a session through forced decode steps, "
                           "which this model's one admission path has not been proven on",
            })

    def serving_pages(self, prompt_tokens: int, max_new_tokens: int, page_size: int, bucket: int) -> int:
        """(b) every attention layer is full attention: a request holds all its tokens."""
        return -(-min(prompt_tokens + max_new_tokens, self.config.max_seq_len) // page_size)

    # (h) and (c): the lanes' loops ask nothing of the layers they loop over: the expert model's ride, a hybrid's finish
    serving_ride_phase = Lfm2MoeForCausalLM.serving_ride_phase
    serving_finish_phase = FalconH1ForCausalLM.serving_finish_phase

    def _decode_operator(self, p, kind: str, l: int, h: jax.Array, cache: NemotronHCache, pools, at):
        """Layer ``l`` of its kind (``M`` or ``*``) for the decode rows ``h`` (B,
        hidden), one row a slot: ``(the mixer's contribution, pools)``; ``pools``
        = (kp, vp, ssm_state, conv_state) as the layers so far left them, ``at`` =
        (the rows that decode, page ids, offsets, visible)."""
        cfg = self.config
        b, tail_rows = h.shape[0], cfg.conv_kernel - 1
        kp, vp, ssm_state, conv_state = pools
        active, page_ids, offs, visible = at
        x = self._norm(h, p["norm"])
        if kind == "M":
            with jax.named_scope("ssm"):
                step = (ssm.ssm_decode_update if ssm.ssm_kernel_supported(
                    cfg.mamba_num_heads, cfg.n_groups, cfg.mamba_head_dim, cfg.ssm_state_size)
                    else ssm.ssm_decode_update_xla)
                z, xbc, dt_raw = self._mixer_in(p, x)
                tails = conv_state[l].reshape(b, tail_rows, cfg.conv_dim)
                window = jnp.concatenate([tails.astype(xbc.dtype), xbc[:, None]], axis=1)  # (B, conv_kernel, C)
                conv_state = conv_state.at[l].set(jnp.where(
                    active[:, None], window[:, 1:].astype(conv_state.dtype).reshape(b, -1), conv_state[l]))
                conv = jnp.sum(window.astype(jnp.float32) * p["conv_weight"].astype(jnp.float32), axis=1)
                conv = jax.nn.silu(conv + p["conv_bias"].astype(jnp.float32))
                xs, bm, cm, dt, a = self._mixer_split(p, conv, dt_raw)
                ssm_state, y = step(ssm_state, l, xs, dt, a, bm, cm, active)
                return self._mixer_out(p, y, xs, z), (kp, vp, ssm_state, conv_state)
        with jax.named_scope("attention"):
            attend = (paged.fused_paged_decode_attention_gqa
                      if paged.paged_gqa_decode_supported(cache.page_size, cfg.head_dim, cfg.num_key_value_heads)
                      else paged.paged_gqa_reference_attention)
            q, k, v = self._qkv(p, x)
            kp = kp.at[l, page_ids, offs].set(k.astype(kp.dtype))
            vp = vp.at[l, page_ids, offs].set(v.astype(vp.dtype))
            o = attend(q, kp, vp, cache.page_table, visible, l)
            return self._mm(o.reshape(b, -1), p["o_proj"]), (kp, vp, ssm_state, conv_state)

    def _chunk_operator(self, p, kind: str, l: int, h: jax.Array, pools, lane, at):
        """Layer ``l`` of its kind (``M`` or ``*``) for a chunk's rows ``h`` (cap,
        hidden) of ONE slot: ``(the mixer's contribution, pools)``; ``lane`` =
        (ids, offset, count, reset, slot, table_row), ``at`` = (page ids, offsets,
        visible, real) of the rows."""
        cfg = self.config
        cap, tail_rows = h.shape[0], cfg.conv_kernel - 1
        kp, vp, ssm_state, conv_state = pools
        _, _, count, reset, slot, table_row = lane
        page_ids, offs, visible, real = at
        x = self._norm(h, p["norm"])
        if kind == "M":
            with jax.named_scope("ssm"):
                z, xbc, dt_raw = self._mixer_in(p, x)
                tail = jnp.where(reset, 0, conv_state[l, slot]).reshape(tail_rows, cfg.conv_dim)
                window = jnp.concatenate([tail.astype(xbc.dtype), xbc])
                xs, b, c, dt, a = self._mixer_split(p, self._conv(p, window, cap), dt_raw)
                before = jnp.where(reset, 0.0, ssm_state[l, slot])
                y, after = ssm.ssd_chunk_scan(xs, jnp.where(real[:, None], dt, 0.0), a, b, c, before, cfg.chunk_size)
                ssm_state = ssm_state.at[l, slot].set(after)
                conv_state = conv_state.at[l, slot].set(
                    jax.lax.dynamic_slice_in_dim(window, count, tail_rows, axis=0)
                    .astype(conv_state.dtype).reshape(-1))
                return self._mixer_out(p, y, xs, z), (kp, vp, ssm_state, conv_state)
        with jax.named_scope("attention"):
            q, k, v = self._qkv(p, x)
            kp = kp.at[l, page_ids, offs].set(jnp.where(real[:, None], k, 0).astype(kp.dtype))
            vp = vp.at[l, page_ids, offs].set(jnp.where(real[:, None], v, 0).astype(vp.dtype))
            # the slot's pages, the chunk's own rows among them, in position order
            out = self._attend(p, q, kp[l, table_row].reshape(-1, k.shape[-1]),
                               vp[l, table_row].reshape(-1, v.shape[-1]), visible)
            return out, (kp, vp, ssm_state, conv_state)

    def _rows_paged(self, cache: NemotronHCache, ids: Optional[jax.Array] = None, lane=None, live=True):
        """THE loop over the layers, over an optional group of decode rows
        (``ids`` (B, 1): one token for every decoding slot) and an optional chunk
        (``lane`` = (ids (cap,), offset, count, reset, slot, table_row): prompt
        tokens ``[offset, offset + count)`` of the request in ``slot``). An ``M``
        or ``*`` layer runs for the two groups apart (their slots are disjoint: a
        prefilling slot does not decode; a recurrence's decode update and its
        chunk scan share nothing, nor do the two attentions), an ``E`` layer ONCE
        over their rows together, so a held expert's matrices are read once for
        both. ``live`` (may be traced): False makes every decode row a discarded
        one. Returns ``(the decode rows' new last rows (B, hidden) or None,
        cache)``. With both groups the operations carry the tick's phase names
        (``serving_api.py`` (h)); with one, the caller's."""
        cfg = self.config
        ps, pages = cache.page_size, cache.pages_per_slot
        decodes, chunks = ids is not None, lane is not None
        both = decodes and chunks
        phase = jax.named_scope if both else (lambda name: contextlib.nullcontext())
        pools, counts = (cache.kp, cache.vp, cache.ssm_state, cache.conv_state), cache.expert_counts
        h_d = h_c = None
        if decodes:
            with phase(TICK_DECODE_SCOPE):
                b = ids.shape[0]
                active = cache.active if live is True else cache.active & live
                pos = jnp.where(active, cache.length, 0)
                page_ids = jnp.where(active, cache.page_table[jnp.arange(b), jnp.clip(pos // ps, 0, pages - 1)], 0)
                decode_at = (active, page_ids, jnp.where(active, pos % ps, 0), jnp.where(active, pos + 1, 0))
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
                chunk_at = (page_ids, jnp.where(real, pos % ps, 0), visible, real)
                h_c = self._embed(chunk_ids)
        # the rows an expert layer sees, the decode rows first; each group's assignments
        # are counted in its own row of the counters
        book = [DECODE_COUNTS] * decodes + [CHUNK_COUNTS] * chunks
        valid, groups = (active, None) if decodes else (real, None)
        if both:
            valid = jnp.concatenate([active, real])
            groups = jnp.stack([jnp.arange(valid.shape[0]) < b, jnp.arange(valid.shape[0]) >= b])
        at = {"M": 0, "E": 0, "*": 0}
        for kind, p in zip(cfg.hybrid_override_pattern, self.layers):
            l = at[kind]
            at[kind] += 1
            if kind == "E":
                # what the two groups share is booked where the decode step's is
                with phase(TICK_DECODE_SCOPE):
                    h = jnp.concatenate([h_d, h_c]) if both else h_d if decodes else h_c
                    out, load = self._experts(p, self._norm(h, p["norm"]), valid, groups)
                    if decodes:
                        h_d = h_d + (out[:b] if both else out)
                    if chunks:
                        h_c = h_c + (out[b:] if both else out)
                    counts = counts.at[jnp.asarray(book), l].add(load.reshape(len(book), -1))
                continue
            if decodes:
                with phase(TICK_DECODE_SCOPE):
                    out, pools = self._decode_operator(p, kind, l, h_d, cache, pools, decode_at)
                    if chunks and kind == "*":
                        # the chunk's rows land in the page pools AFTER the decode kernel has READ them: an order
                        # the compiler is told, or it keeps the pools apart by copying them. (An ``M`` layer's chunk
                        # reads the state its decode update WROTE: the data orders them, and a barrier there made the
                        # compiled loop move the convolution columns' pool out of VMEM and back, every layer.)
                        out, pools = jax.lax.optimization_barrier((out, pools))
                    h_d = h_d + out
            if chunks:
                with phase(TICK_CHUNK_SCOPE):
                    out, pools = self._chunk_operator(p, kind, l, h_c, pools, lane, chunk_at)
                    h_c = h_c + out
        kp, vp, ssm_state, conv_state = pools
        cache = cache.replace(kp=kp, vp=vp, ssm_state=ssm_state, conv_state=conv_state, expert_counts=counts)
        if decodes:
            with phase(TICK_DECODE_SCOPE):
                cache = cache.replace(length=cache.length + active.astype(jnp.int32))
        if chunks:
            with phase(TICK_CHUNK_SCOPE):
                last = jax.lax.dynamic_index_in_dim(h_c, jnp.maximum(count - 1, 0), axis=0, keepdims=False)
                cache = cache.replace(last_hidden=cache.last_hidden.at[slot].set(last.astype(cache.last_hidden.dtype)))
        return h_d, cache

    def prefill_chunk_paged(self, ids: jax.Array, offset: jax.Array, count: jax.Array, reset: jax.Array,
                            slot: jax.Array, table_row: jax.Array, cache: NemotronHCache) -> NemotronHCache:
        """Prompt tokens ``[offset, offset + count)`` of the request in ``slot``;
        ids (cap,) with the rows past ``count`` padding. ``reset`` starts the
        recurrent state and the convolution tail from zero (a slot's first
        chunk); otherwise they are carried from the chunk before."""
        return self._rows_paged(cache, lane=(ids, offset, count, reset, slot, table_row))[1]

    def decode_rows_paged(self, ids: jax.Array, cache: NemotronHCache) -> Tuple[jax.Array, NemotronHCache]:
        """(d) one token for every decoding slot: ids (B, 1) -> the residual
        stream's new last rows (B, hidden), the head's input. A slot that is not
        ``active`` (free, or in the middle of its prefill) computes a discarded
        row: its key and value go to the trash page, it is routed to no expert,
        and its length, recurrent state and convolution tail stay as they are."""
        return self._rows_paged(cache, ids=ids)

    def decode_rows_with_chunk_paged(self, ids: jax.Array, cache: NemotronHCache, chunk_ids: jax.Array,
                                     offset: jax.Array, count: jax.Array, reset: jax.Array, slot: jax.Array,
                                     table_row: jax.Array, live=True) -> Tuple[jax.Array, NemotronHCache]:
        """(h) the decode step with a chunk riding it: what ``prefill_chunk_paged``
        of the chunk then ``decode_rows_paged`` return, every ``E`` layer run once
        over the rows of both. Where ``live`` (traced) is False no slot decodes in
        this pass: the chunk alone is served."""
        return self._rows_paged(cache, ids=ids, lane=(chunk_ids, offset, count, reset, slot, table_row), live=live)

    def decode_step_paged(self, ids: jax.Array, cache: NemotronHCache) -> Tuple[jax.Array, NemotronHCache]:
        """ids (B, 1) -> logits (B, 1, vocab): the head of ``decode_rows_paged``'s rows."""
        rows, cache = self.decode_rows_paged(ids, cache)
        return self._head(rows)[:, None], cache
