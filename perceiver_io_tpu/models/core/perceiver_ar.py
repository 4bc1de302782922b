"""Perceiver AR: long-context causal modeling via latent compression.

Parity targets (reference: /root/reference/perceiver/model/core/modules.py):
  - ``PerceiverAR``          -> modules.py:691-871. Split the input at ``prefix_len``
    into prefix + latents; latents attend causally to concat(prefix, latents) via one
    cross-attention (``x_kv_prefix`` mode, right-aligned causal mask), then a causal
    self-attention stack runs over the latents only. RoPE angles come from a
    frequency encoding of pad-shifted absolute positions. Training-time
    cross-attention (prefix) dropout randomly keeps a fixed-size subset of prefix
    positions (modules.py:809-830).
  - ``CausalSequenceModel``  -> modules.py:874-930 (token adapter + optional final
    LN + tied token head; RoPE over half the head channels when abs-pos-emb on).

TPU-first design notes:
  * torch overloads one ``forward`` across training, prefill, and cached decode with
    dynamic shapes. Here the three paths are explicit methods with static shapes:
    ``__call__`` (uncached), ``prefill`` (fills fixed-capacity caches), and
    ``decode_step`` (one token; caches roll when full, which reproduces the
    reference HF wrapper's latent->prefix->slide window policy,
    core/huggingface.py:89-156).
  * Prefix dropout keeps a *static* count ``prefix_len - int(prefix_len * p)`` of
    positions (the reference computes the same count at modules.py:817), realised as
    a sorted top-k gather — a static-shape operation XLA can fuse, in place of
    torch's boolean-mask reshape.
  * Decode positions are derived from cache slot indices: slot ``j`` of the
    cross-attention cache is sequence position ``j`` (minus the per-example left-pad
    shift, clamped at 0 — reference position.py:9-17), so RoPE tables are computed
    from ``arange(capacity)`` with no dynamic shapes anywhere.
"""

from __future__ import annotations

from typing import Optional, Tuple

import flax.linen as nn
import flax.struct
import jax
import jax.numpy as jnp

from perceiver_io_tpu.models.core.adapter import (
    TiedTokenOutputAdapter,
    TokenInputAdapterWithRotarySupport,
)
from perceiver_io_tpu.models.core.config import CausalSequenceModelConfig
from perceiver_io_tpu.models.core.modules import LN_EPS, CrossAttentionLayer, SelfAttentionBlock
from perceiver_io_tpu.models.core.serving_api import ServingTraits
from perceiver_io_tpu.ops.attention import KVCache, RingKVCache
from perceiver_io_tpu.ops.paged_decode_kernel import PagedKVCache
from perceiver_io_tpu.ops.position import frequency_position_encoding, positions


class PerceiverARCache(flax.struct.PyTreeNode):
    """Decode state for Perceiver AR.

    ``ca``: cross-attention KV cache, capacity ``max_seq_len`` (keys/values of the
        whole sliding window: prefix + latents).
    ``sa``: stacked per-layer self-attention KV caches, capacity ``max_latents``.
    ``pad_slots``: (B, max_seq_len) boolean, True where a cross-attention cache slot
        holds a padding token; rolled in lockstep with ``ca``.
    ``shift``: (B, 1) int32 left-pad count (constant per sequence), subtracted from
        positions before clamping at 0.
    ``live``: (B,) int32 count of live (non-pad) entries per row. The live region
        is always the TAIL ``[ca.length - live, ca.length)`` of the valid slots
        (left-pads sit at the head and roll out first), so masking a key slot
        ``j`` iff ``j < ca.length - live`` is exactly equivalent to the pad-slot
        mask — a redundancy the ragged decode kernel exploits to SKIP whole KV
        blocks below each row's live region (ops/decode_kernel.py) while the
        masked-softmax fallback applies the same bound for bitwise parity.
    """

    ca: KVCache
    sa: KVCache
    pad_slots: jax.Array
    shift: jax.Array
    live: jax.Array

    @property
    def seq_len(self) -> jax.Array:
        return self.ca.length

    def rewind(self, k: jax.Array) -> "PerceiverARCache":
        """Drop the ``k`` most recently appended tokens by rewinding the cache
        lengths (``k`` may be traced). Valid ONLY when none of those appends
        rolled the buffers (the no-roll contract of ``decode_block``): the
        rejected rows then sit beyond the rewound length, invisible behind the
        causal/validity bounds, and the next append overwrites them. This is
        what makes speculative/chunked decode verification O(1): committing m
        of n drafted tokens is a scalar length update, not a buffer edit."""
        k = jnp.asarray(k, jnp.int32)
        return self.replace(
            ca=self.ca.replace(length=jnp.maximum(self.ca.length - k, 0)),
            sa=self.sa.replace(length=jnp.maximum(self.sa.length - k, 0)),
            live=jnp.maximum(self.live - k, 0),
        )


class PagedPerceiverARCache(flax.struct.PyTreeNode):
    """Paged decode state for a Perceiver AR serving pool (docs/serving.md).

    A ``PerceiverARCache`` at full window capacity per slot would reserve
    ``window`` cross-attention KV rows per slot whether or not they hold live
    tokens. Here the cross-attention KV lives in a shared PAGE POOL
    (``ca``: ops/paged_decode_kernel.PagedKVCache) addressed through per-slot
    page tables, so HBM cost scales with live tokens and admission/eviction
    are page-table edits — ``install_slot``, the paged form of ``rewind``,
    and the ``live`` bookkeeping. The self-attention cache
    (capacity ``max_latents``, one per layer) stays dense, as a ring
    (``sa``: ops/attention.RingKVCache): per slot an offset ``sa.start``, an
    append of one row a slot a layer, nothing shifted.

    Engine-only invariants (serving/engine.py): every row sits at FULL window
    occupancy at all times, so validity is fully encoded by ``live`` and the ring
    offset ``ca.start`` — there is no pad-slot buffer and no shared length.
    The self-attention ring rests on the same invariant: every slot holds
    ``max_latents`` latents from its install on (a prefill always yields that
    many) and appends one per decode step, so all its rows are always visible;
    a slot that holds no installed request is not read at all (``sa.active``,
    equal to the engine's ``SlotState.active``).
    """

    ca: PagedKVCache
    sa: RingKVCache
    shift: jax.Array  # (B, 1) left-pad position shift, as in PerceiverARCache
    live: jax.Array  # (B,) live (non-pad) entries per row

    def rewind(self, k: jax.Array) -> "PagedPerceiverARCache":
        """Paged form of ``PerceiverARCache.rewind``: un-append the ``k`` most
        recently written tokens by stepping the ring offset back (their pages
        stay allocated — pages are only returned at eviction — so the slots
        still hold the rewound values and the next append overwrites them
        exactly, the speculative-verification contract). The self-attention
        ring steps back the same way (``RingKVCache.rewind``)."""
        k = jnp.asarray(k, jnp.int32)
        return self.replace(
            ca=self.ca.replace(start=jnp.mod(self.ca.start - k, self.ca.window)),
            sa=self.sa.rewind(k),
            live=jnp.maximum(self.live - k, 0),
        )

    def install_slot(
        self, slot: jax.Array, table_row: jax.Array, src: PerceiverARCache
    ) -> "PagedPerceiverARCache":
        """The one-shot admission primitive: install a bucket-prefilled request
        (``src``: batch-1 DENSE cache at bucket capacity, straight from the
        shared prefill program) into pool slot ``slot`` whose page table row
        becomes ``table_row`` (P,) — the first ceil(bucket/page) entries are
        the freshly allocated pages that receive the prompt's KV rows
        page-by-page, the remainder are the request's decode-growth
        reservation (content written later by ``append_token``) padded with
        the trash page.

        The layout is PAGE-ALIGNED on the prompt (docs/serving.md "Prefix
        cache"): the bucket's left-pad head is rolled out so prompt token i
        lands at physical ring position i — page ``i // page_size``, offset
        ``i % page_size`` — and the ring offset starts at ``n mod window``
        (n = live prompt length). Page k's contents are therefore a pure
        function of prompt tokens ``[k*ps, (k+1)*ps)`` alone, independent of
        the covering bucket and the tail beyond the page — the property the
        cross-request prefix cache keys on. Positionally this is a scatter
        of the bucket rows into the window's tail, in a rotated frame (ring
        slot i holds logical window position ``window - n + i``), with the
        head left-pad represented by ``live``/``shift`` alone instead of a
        zero-filled buffer. The rolled-out pad rows land past position n as inert
        garbage: never visible (``live`` bounds the window) and overwritten
        by decode appends before they ever could be.

        QUANTIZED pools (docs/serving.md "Quantized KV pages & weight
        serving") zero those rolled-out garbage rows first — they would
        otherwise inflate their page's amax scale and cost the real rows
        precision — then write the prompt pages WHOLE through
        ``PagedKVCache.write_pages`` (fresh per-page-per-head scales, bytes a
        pure function of the page's tokens: chunk/install byte-interchange
        survives quantization) after resetting the whole reservation's scale
        sidecars (a later decode append into an untouched reservation page
        must start from scale 0, zeroing any stale tenant bytes)."""
        ps = self.ca.page_size
        window = self.ca.window
        bucket = src.ca.capacity
        nb = -(-bucket // ps)  # pages holding prompt (+ inert tail) content
        pad_rows = nb * ps - bucket
        shift = src.shift[0, 0]  # left-pad count: bucket - n
        n = bucket - shift  # live prompt length
        kc = jnp.roll(src.ca.k[0], -shift, axis=0)
        vc = jnp.roll(src.ca.v[0], -shift, axis=0)
        kc = jnp.pad(kc, ((0, pad_rows), (0, 0)))
        vc = jnp.pad(vc, ((0, pad_rows), (0, 0)))
        ids = table_row[:nb]
        ca = self.ca
        if ca.quantized:
            prompt_row = (jnp.arange(nb * ps) < n)[:, None]
            kc = jnp.where(prompt_row, kc, 0)
            vc = jnp.where(prompt_row, vc, 0)
            ca = ca.reset_page_scales(table_row)
        ca = ca.write_pages(ids, kc.reshape(nb, ps, -1), vc.reshape(nb, ps, -1))
        ca = ca.replace(
            page_table=ca.page_table.at[slot].set(table_row),
            start=ca.start.at[slot].set(jnp.mod(n, window)),
        )
        return self.replace(
            ca=ca,
            sa=self.sa.write_batch_row(slot, src.sa),
            shift=jax.lax.dynamic_update_slice_in_dim(
                self.shift, src.shift + (window - bucket), slot, axis=0
            ),
            live=jax.lax.dynamic_update_slice_in_dim(self.live, src.live, slot, axis=0),
        )

    def install_finish(
        self, slot: jax.Array, table_row: jax.Array, sa_src: KVCache, live: jax.Array
    ) -> "PagedPerceiverARCache":
        """Device half of the chunked-prefill FINISH (docs/serving.md
        "Chunked prefill"): the slot's CA pages were already written by
        ``PagedKVCache.write_rows`` chunks (through ``table_row`` directly —
        the in-cache table stayed trash so interleaved decode ticks could
        not corrupt the half-built slot), so installing the slot is pure
        bookkeeping: point the table at the reservation, set the ring offset
        to ``live mod window`` (the page-aligned layout's post-prompt
        append point), write the finish step's self-attention cache (the
        slot's ring restarts at 0), and pin shift/live exactly as
        ``install_slot`` would for a prompt of ``live`` tokens."""
        window = self.ca.window
        live = jnp.asarray(live, jnp.int32)
        return self.replace(
            ca=self.ca.replace(
                page_table=self.ca.page_table.at[slot].set(table_row),
                start=self.ca.start.at[slot].set(jnp.mod(live, window)),
            ),
            sa=self.sa.write_batch_row(slot, sa_src),
            shift=self.shift.at[slot].set(window - live),
            live=self.live.at[slot].set(live),
        )

    def quarantine_slot(self, slot: jax.Array, table_row: jax.Array) -> "PagedPerceiverARCache":
        """Containment: zero the slot's self-attention rows and every page
        ``table_row`` names (trash-padding entries re-zero the trash page —
        duplicate scatter indices with identical zero payloads, deterministic),
        and on a quantized pool their scale sidecars too (a NaN that reached
        the quantizer lands in the scale, and dequant multiplies every byte of
        the page by it)."""
        ca = self.ca
        ca = ca.replace(
            kp=ca.kp.at[table_row].set(0), vp=ca.vp.at[table_row].set(0)
        ).reset_page_scales(table_row)
        return self.replace(
            ca=ca,
            sa=self.sa.replace(
                k=self.sa.k.at[:, slot].set(0), v=self.sa.v.at[:, slot].set(0)
            ),
        )

    def release_slot(self, slot: jax.Array) -> "PagedPerceiverARCache":
        """Reset slot ``slot`` to the free canonical form: page table entries
        all trash (page 0), ring offset 0, live pinned at the full window,
        the self-attention ring no longer read (``RingKVCache.active``; it
        keeps turning, its rows and offset replaced whole by the next
        install). CRITICAL for correctness, not just hygiene: a freed slot
        stays a row of every decode step — it embeds its pad token, appends
        to the trash page and to its ring, and its output is discarded — and
        a stale table entry would route its writes into a page since
        reallocated to a live request."""
        p = self.ca.pages_per_slot
        return self.replace(
            ca=self.ca.replace(
                page_table=self.ca.page_table.at[slot].set(jnp.zeros((p,), jnp.int32)),
                start=self.ca.start.at[slot].set(0),
            ),
            sa=self.sa.replace(active=self.sa.active.at[slot].set(False)),
            shift=self.shift.at[slot].set(0),
            live=self.live.at[slot].set(self.ca.window),
        )


def _make_ar_cache(
    batch_size: int, max_seq_len: int, max_latents: int, num_layers: int, num_channels: int, dtype=jnp.float32
) -> PerceiverARCache:
    """Single construction point for the Perceiver AR decode state (the capacities
    encode the reference's sliding-window policy — see module docstring)."""
    return PerceiverARCache(
        ca=KVCache.create(batch_size, max_seq_len, num_channels, num_channels, dtype),
        sa=KVCache.create_stacked(num_layers, batch_size, max_latents, num_channels, num_channels, dtype),
        pad_slots=jnp.zeros((batch_size, max_seq_len), dtype=bool),
        shift=jnp.zeros((batch_size, 1), dtype=jnp.int32),
        live=jnp.zeros((batch_size,), dtype=jnp.int32),
    )


def _make_paged_ar_cache(
    batch_size: int,
    max_seq_len: int,
    max_latents: int,
    num_layers: int,
    num_channels: int,
    num_pages: int,
    page_size: int,
    dtype=jnp.float32,
    num_heads: int = 1,
    kv_quant: Optional[str] = None,
) -> PagedPerceiverARCache:
    """Paged decode-pool state: a shared (num_pages, page_size, C) KV page
    pool (page 0 reserved as the trash page) + per-slot page tables over
    ceil(max_seq_len / page_size) logical pages, and the dense self-attention
    caches as one stacked ring (``RingKVCache``, every row full from the
    start: free slots hold zeros). ``page_size`` need not divide the window —
    the last logical page's tail is simply never visible. ``kv_quant="int8"`` stores the page
    pool as int8 with per-page-per-head float32 scale sidecars (the KV bytes
    per token drop ~4x vs f32; ops/paged_decode_kernel.py module docstring) —
    the self-attention caches and everything dense stay in ``dtype``.
    ``kv_quant="int4"`` nibble-packs two 4-bit codes per byte, so the pool's
    physical last dim is ``num_channels // 2`` uint8 (num_channels must be
    even) — KV bytes per token halve again vs int8, same scale layout."""
    from perceiver_io_tpu.ops.paged_decode_kernel import (
        KV_QUANT_MODES, quant_mode_qbits,
    )

    if page_size < 1:
        raise ValueError(f"page_size must be >= 1, got {page_size}")
    if page_size > max_seq_len:
        raise ValueError(f"page_size ({page_size}) exceeds the window ({max_seq_len})")
    pages_per_slot = -(-max_seq_len // page_size)
    if num_pages < 2:
        raise ValueError(f"num_pages must be >= 2 (page 0 is the reserved trash page), got {num_pages}")
    if kv_quant is not None and kv_quant not in KV_QUANT_MODES:
        raise ValueError(f"kv_quant must be one of {KV_QUANT_MODES} or None, got {kv_quant!r}")
    if kv_quant is not None and num_channels % max(num_heads, 1) != 0:
        raise ValueError("num_channels must divide evenly over num_heads for per-head scales")
    qbits = quant_mode_qbits(kv_quant)
    if kv_quant is not None and qbits == 4 and num_channels % 2 != 0:
        raise ValueError(
            f"kv_quant='int4' nibble-packs channel pairs: num_channels must be even, got {num_channels}"
        )
    pool_dtype = (jnp.uint8 if qbits == 4 else jnp.int8) if kv_quant else dtype
    pool_channels = num_channels // 2 if (kv_quant and qbits == 4) else num_channels
    quant_fields = {}
    if kv_quant:
        quant_fields = dict(
            k_scale=jnp.zeros((num_pages, num_heads), jnp.float32),
            v_scale=jnp.zeros((num_pages, num_heads), jnp.float32),
            num_heads=num_heads,
            qbits=qbits,
        )
    return PagedPerceiverARCache(
        ca=PagedKVCache(
            kp=jnp.zeros((num_pages, page_size, pool_channels), pool_dtype),
            vp=jnp.zeros((num_pages, page_size, pool_channels), pool_dtype),
            page_table=jnp.zeros((batch_size, pages_per_slot), jnp.int32),
            start=jnp.zeros((batch_size,), jnp.int32),
            window=max_seq_len,
            **quant_fields,
        ),
        sa=RingKVCache.create(num_layers, batch_size, max_latents, num_channels, num_channels, dtype),
        shift=jnp.zeros((batch_size, 1), jnp.int32),
        live=jnp.full((batch_size,), max_seq_len, jnp.int32),
    )


class PerceiverAR(nn.Module):
    """Generic Perceiver AR over an input adapter with rotary support."""

    input_adapter: nn.Module
    num_heads: int = 8
    max_heads_parallel: Optional[int] = None
    num_self_attention_layers: int = 6
    num_self_attention_rotary_layers: int = 1
    self_attention_widening_factor: int = 4
    cross_attention_widening_factor: int = 4
    cross_attention_dropout: float = 0.5
    cross_attention_dropout_mode: str = "gather"  # "gather" (reference-exact, fastest) | "mask"
    post_attention_dropout: float = 0.0
    residual_dropout: float = 0.0
    activation_checkpointing: bool = False
    remat_policy: Optional[str] = None
    activation_offloading: bool = False  # stage checkpointed dots to pinned host (modules._remat_policy)
    scan_unroll: int = 1
    fused_qkv: bool = False  # single-GEMM q/k/v projections (execution knob; NOTES.md)
    init_scale: float = 0.02
    sequence_parallel_axis: Optional[str] = None  # mesh axis for ring attention (long context)
    pipeline_axis: Optional[str] = None  # mesh axis for GPipe over the SA stack (parallel/pipeline.py)
    pipeline_microbatches: Optional[int] = None
    deterministic: bool = True
    dtype: Optional[jnp.dtype] = None
    param_dtype: jnp.dtype = jnp.float32

    def setup(self):
        num_channels = self.input_adapter.num_input_channels
        self.cross_attention = CrossAttentionLayer(
            num_heads=self.num_heads,
            num_q_input_channels=num_channels,
            num_kv_input_channels=num_channels,
            max_heads_parallel=self.max_heads_parallel,
            causal_attention=True,
            widening_factor=self.cross_attention_widening_factor,
            dropout=self.post_attention_dropout,
            residual_dropout=self.residual_dropout,
            qkv_bias=False,
            fused_qkv=self.fused_qkv,
            out_bias=True,
            mlp_bias=False,
            init_scale=self.init_scale,
            seq_axis=self.sequence_parallel_axis,
            deterministic=self.deterministic,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="cross_attention",
        )
        self.self_attention = SelfAttentionBlock(
            num_layers=self.num_self_attention_layers,
            num_heads=self.num_heads,
            num_channels=num_channels,
            causal_attention=True,
            widening_factor=self.self_attention_widening_factor,
            dropout=self.post_attention_dropout,
            residual_dropout=self.residual_dropout,
            num_rotary_layers=self.num_self_attention_rotary_layers,
            activation_checkpointing=self.activation_checkpointing,
            remat_policy=self.remat_policy,
            activation_offloading=self.activation_offloading,
            scan_unroll=self.scan_unroll,
            qkv_bias=False,
            fused_qkv=self.fused_qkv,
            out_bias=False,
            mlp_bias=False,
            init_scale=self.init_scale,
            seq_axis=self.sequence_parallel_axis,
            pipeline_axis=self.pipeline_axis,
            pipeline_microbatches=self.pipeline_microbatches,
            deterministic=self.deterministic,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="self_attention",
        )

    def attend(self, x: jax.Array) -> jax.Array:
        """Tied-embedding readout, delegated to the input adapter."""
        return self.input_adapter.attend(x)

    # ------------------------------------------------------------------ uncached
    def __call__(
        self,
        x: jax.Array,
        prefix_len: int,
        pad_mask: Optional[jax.Array] = None,
    ) -> jax.Array:
        """Uncached forward over tokens ``x`` (B, N) with a static ``prefix_len``.
        Returns latent hidden states (B, N - prefix_len, C)."""
        b, n = x.shape
        if not 0 <= prefix_len < n:
            raise ValueError(f"prefix_len ({prefix_len}) out of valid range [0..{n})")

        shift = None if pad_mask is None else jnp.sum(pad_mask, axis=1, keepdims=True)
        x_emb, frq_pos_enc = self.input_adapter(x, abs_pos=positions(b, n, shift=shift))

        x_latent = x_emb[:, prefix_len:]
        x_prefix = x_emb[:, :prefix_len]
        frq_latent = frq_pos_enc[:, prefix_len:]
        frq_prefix = frq_pos_enc[:, :prefix_len]
        pad_latent = None if pad_mask is None else pad_mask[:, prefix_len:]
        pad_prefix = None if pad_mask is None else pad_mask[:, :prefix_len]

        if (not self.deterministic) and prefix_len > 0 and self.cross_attention_dropout > 0.0:
            if self.cross_attention_dropout_mode == "mask":
                # Bernoulli drop of prefix positions expressed through the attention
                # pad mask: no sort/gather, shapes stay static and flash-compatible.
                # Subset-size variance vs the reference's fixed-count subset is
                # negligible (std ~ sqrt(p(1-p)n), <2% of the keep count at n=3584).
                dropped = jax.random.bernoulli(
                    self.make_rng("dropout"), self.cross_attention_dropout, (b, prefix_len)
                )
                pad_prefix = dropped if pad_prefix is None else (pad_prefix | dropped)
            elif self.cross_attention_dropout_mode == "gather":
                # Reference-exact: keep a static-count random subset of prefix
                # positions, order-preserving (reference modules.py:809-830).
                keep = prefix_len - int(prefix_len * self.cross_attention_dropout)
                rand = jax.random.uniform(self.make_rng("dropout"), (b, prefix_len))
                _, keep_idx = jax.lax.top_k(rand, keep)
                keep_idx = jnp.sort(keep_idx, axis=1)
                x_prefix = jnp.take_along_axis(x_prefix, keep_idx[..., None], axis=1)
                frq_prefix = jnp.take_along_axis(frq_prefix, keep_idx[..., None], axis=1)
                if pad_prefix is not None:
                    pad_prefix = jnp.take_along_axis(pad_prefix, keep_idx, axis=1)
            else:
                raise ValueError(
                    f"unknown cross_attention_dropout_mode '{self.cross_attention_dropout_mode}'"
                )

        rope_q = frq_latent
        rope_k = jnp.concatenate([frq_prefix, frq_latent], axis=1)
        if pad_prefix is None and pad_latent is None:
            pad_full = None
        else:
            pp = pad_prefix if pad_prefix is not None else jnp.zeros((b, x_prefix.shape[1]), bool)
            pl = pad_latent if pad_latent is not None else jnp.zeros((b, n - prefix_len), bool)
            pad_full = jnp.concatenate([pp, pl], axis=1)

        x_latent, _ = self.cross_attention(
            x_latent, x_kv_prefix=x_prefix, pad_mask=pad_full, rope_q=rope_q, rope_k=rope_k
        )
        x_latent, _ = self.self_attention(x_latent, rope_q=frq_latent, rope_k=frq_latent)
        return x_latent

    # ------------------------------------------------------------------- cached
    def init_cache(
        self, batch_size: int, max_seq_len: int, max_latents: int, dtype=jnp.float32
    ) -> PerceiverARCache:
        # Built from constructor fields only, so it works on an unbound module
        # (no params or setup state involved).
        num_channels = self.input_adapter.num_input_channels
        return _make_ar_cache(
            batch_size, max_seq_len, max_latents, self.num_self_attention_layers, num_channels, dtype
        )

    def _rotated_dim(self) -> int:
        return self.input_adapter.rotated_channels_per_head

    def prefill(
        self,
        x: jax.Array,
        prefix_len: int,
        cache: PerceiverARCache,
        pad_mask: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, PerceiverARCache]:
        """Process a full prompt (B, N) into caches; N - prefix_len latents.
        The given cache is structurally RESET first (prefill defines the window
        from scratch), so passing a used cache cannot corrupt state. Prefix
        dropout must be off (deterministic instance) — reference raises the same
        way for cache + dropout (modules.py:810-812)."""
        if not self.deterministic:
            raise ValueError("cross-attention dropout not supported with caching")
        b, n = x.shape
        ca_cap = cache.ca.capacity
        sa_cap = cache.sa.k.shape[2]
        if not 0 <= prefix_len < n:
            raise ValueError(f"prefix_len ({prefix_len}) out of valid range [0..{n})")
        if n > ca_cap or (n - prefix_len) > sa_cap:
            raise ValueError("prompt does not fit cache capacities")
        cache = cache.replace(ca=cache.ca.reset(), sa=cache.sa.reset())

        shift = (
            jnp.zeros((b, 1), jnp.int32) if pad_mask is None else jnp.sum(pad_mask, axis=1, keepdims=True).astype(jnp.int32)
        )
        x_emb, frq = self.input_adapter(x, abs_pos=positions(b, n, shift=shift))

        x_latent = x_emb[:, prefix_len:]
        x_prefix = x_emb[:, :prefix_len]
        frq_latent = frq[:, prefix_len:]

        # RoPE table over cross-attention cache slots: slot j is position j - shift.
        slot_pos = jnp.maximum(jnp.arange(ca_cap)[None, :] - shift, 0)
        rope_k_ca = frequency_position_encoding(slot_pos, self._rotated_dim())

        pad_slots = jnp.zeros((b, ca_cap), dtype=bool)
        if pad_mask is not None:
            pad_slots = pad_slots.at[:, :n].set(pad_mask)
        live = jnp.full((b,), n, jnp.int32) - shift[:, 0]

        x_latent, ca_cache = self.cross_attention(
            x_latent,
            x_kv_prefix=x_prefix,
            pad_mask=pad_slots,
            rope_q=frq_latent,
            rope_k=rope_k_ca,
            kv_cache=cache.ca,
            kv_live=live,
        )
        # Self-attention cache slot j will hold latent j, i.e. sequence position
        # prefix_len + j; the RoPE table must span the full cache capacity.
        sa_slot_pos = jnp.maximum(prefix_len + jnp.arange(sa_cap)[None, :] - shift, 0)
        rope_k_sa = frequency_position_encoding(sa_slot_pos, self._rotated_dim())
        x_latent, sa_cache = self.self_attention(
            x_latent, rope_q=frq_latent, rope_k=rope_k_sa, kv_cache=cache.sa
        )
        new_cache = PerceiverARCache(ca=ca_cache, sa=sa_cache, pad_slots=pad_slots, shift=shift, live=live)
        return x_latent, new_cache

    def decode_block(self, x: jax.Array, cache: PerceiverARCache) -> Tuple[jax.Array, PerceiverARCache]:
        """Decode ``n`` tokens ``x`` (B, n) in one forward: every token joins the
        latents and each attends causally to the cache plus its block
        predecessors (the cached-attention per-query bounds,
        ops/attention.py:310-314 — on TPU the fused multi-query decode kernel,
        ops/decode_kernel.py, for n <= 8).

        ``n == 1`` is the general sliding-window step: full caches roll their
        oldest entry out (= the reference's window policy where the oldest
        latent is absorbed into the prefix, core/huggingface.py:89-156).

        ``n > 1`` is the speculative/chunked-verification step and carries a
        NO-ROLL CONTRACT: the caller must guarantee ``length + n <= capacity``
        for both caches (generation/generate.py sizes its chunked phase
        statically so this holds). Under that contract the block append never
        evicts, so (a) every block token's attention set is exactly what n
        sequential steps would see, and (b) ``cache.rewind`` can un-append
        rejected draft tokens exactly."""
        b, n = x.shape
        ca_cap = cache.ca.capacity
        sa_cap = cache.sa.k.shape[2]
        rot = self._rotated_dim()

        n_after = jnp.minimum(cache.ca.length + n, ca_cap)  # window length after append
        # token i's absolute position; saturation only ever engages for n == 1
        # (the no-roll contract keeps n > 1 strictly below capacity)
        q_pos = jnp.maximum(n_after - n + jnp.arange(n)[None, :] - cache.shift, 0)  # (b, n)

        x_emb, frq_q = self.input_adapter(x, abs_pos=q_pos)

        if n == 1:
            # Roll the pad-slot mask in lockstep with the cross-attention cache append.
            full = cache.ca.length >= ca_cap
            pad_slots = jnp.where(full, jnp.roll(cache.pad_slots, -1, axis=1), cache.pad_slots)
            write_pos = jnp.minimum(cache.ca.length, ca_cap - 1)
        else:
            pad_slots = cache.pad_slots
            write_pos = cache.ca.length  # fits by the no-roll contract
        pad_slots = jax.lax.dynamic_update_slice_in_dim(pad_slots, jnp.zeros((b, n), bool), write_pos, axis=1)

        slot_pos = jnp.maximum(jnp.arange(ca_cap)[None, :] - cache.shift, 0)
        rope_k_ca = frequency_position_encoding(slot_pos, rot)

        # n real tokens join; while the buffer is full each append rolls a
        # left-pad (or, once none remain, a live token) out of the head —
        # either way the live count saturates at capacity (see PerceiverARCache)
        live = jnp.minimum(cache.live + n, ca_cap)

        x_latent, ca_cache = self.cross_attention(
            x_emb, x_kv_prefix=x_emb[:, :0], pad_mask=pad_slots, rope_q=frq_q, rope_k=rope_k_ca,
            kv_cache=cache.ca, kv_live=live,
        )

        # Self-attention cache slot j holds the (j+1)-th oldest latent; its sequence
        # position is n_after - sa_len_after + j.
        sa_len_after = jnp.minimum(cache.sa.length[0] + n, sa_cap)
        sa_slot_pos = n_after - sa_len_after + jnp.arange(sa_cap)[None, :]
        sa_slot_pos = jnp.maximum(sa_slot_pos - cache.shift, 0)
        rope_k_sa = frequency_position_encoding(sa_slot_pos, rot)

        x_latent, sa_cache = self.self_attention(
            x_latent, rope_q=frq_q, rope_k=rope_k_sa, kv_cache=cache.sa
        )
        new_cache = PerceiverARCache(ca=ca_cache, sa=sa_cache, pad_slots=pad_slots, shift=cache.shift, live=live)
        return x_latent, new_cache

    def decode_step(self, x: jax.Array, cache: PerceiverARCache) -> Tuple[jax.Array, PerceiverARCache]:
        """One decode step with token(s) ``x`` (B, 1); see ``decode_block``."""
        assert x.shape[1] == 1, "decode_step processes one token at a time; use decode_block for chunks"
        return self.decode_block(x, cache)

    def decode_step_paged(
        self, x: jax.Array, cache: PagedPerceiverARCache
    ) -> Tuple[jax.Array, PagedPerceiverARCache]:
        """``decode_block`` with n = 1 against the PAGED pool. Every row sits
        at full window occupancy (the serving-pool invariant), so the append
        is the ring write ``PagedKVCache.append_token`` — O(1) per token where
        the dense full-cache append ROLLS the whole KV buffer — and the
        sliding-window re-positioning is pure arithmetic: ring slot r holds
        logical window position ``(r - start) mod window``, so the RoPE table
        and the visibility bound are computed per PHYSICAL slot from the
        post-append ring offset. Token-for-token this assigns exactly the
        angles and masks of the dense path in a rotated frame (f64
        token-parity pinned by tests/test_paging.py)."""
        b, n = x.shape
        assert n == 1, "paged decode processes one token at a time"
        window = cache.ca.window
        rot = self._rotated_dim()

        q_pos = jnp.maximum(window - 1 - cache.shift, 0)  # (B, 1)
        x_emb, frq_q = self.input_adapter(x, abs_pos=q_pos)

        # post-append ring state: append_token (inside cross_attention's paged
        # branch) advances start by one; the new token's logical position is
        # window - 1 and one more entry is live (saturating)
        start_after = jnp.mod(cache.ca.start + 1, window)
        live = jnp.minimum(cache.live + 1, window)
        n_phys = cache.ca.pages_per_slot * cache.ca.page_size
        logical = jnp.mod(jnp.arange(n_phys)[None, :] - start_after[:, None], window)
        slot_pos = jnp.maximum(logical - cache.shift, 0)
        rope_k_ca = frequency_position_encoding(slot_pos, rot)

        x_latent, ca_cache = self.cross_attention(
            x_emb, x_kv_prefix=x_emb[:, :0], rope_q=frq_q, rope_k=rope_k_ca,
            kv_cache=cache.ca, kv_live=live,
        )

        # self-attention over the latents' ring, full like the window: the
        # append (inside self_attention) writes physical row sa.start, after
        # which row r holds latent (r - start - 1) mod cap, whose window
        # position is window - cap + that: decode_block's angles (n = 1,
        # n_after == window), per PHYSICAL row as for rope_k_ca above
        sa_cap = cache.sa.capacity
        sa_logical = jnp.mod(jnp.arange(sa_cap)[None, :] - (cache.sa.start[:, None] + 1), sa_cap)
        sa_slot_pos = jnp.maximum(window - sa_cap + sa_logical - cache.shift, 0)
        rope_k_sa = frequency_position_encoding(sa_slot_pos, rot)
        x_latent, sa_cache = self.self_attention(
            x_latent, rope_q=frq_q, rope_k=rope_k_sa, kv_cache=cache.sa
        )
        return x_latent, cache.replace(ca=ca_cache, sa=sa_cache, live=live)

    # ------------------------------------------------------------ chunked prefill
    def prefill_chunk_kv(
        self, x: jax.Array, abs_pos: jax.Array, latent_mask: jax.Array
    ) -> Tuple[jax.Array, jax.Array]:
        """One chunk of the split prefill (docs/serving.md "Chunked
        prefill"): the cross-attention KV rows for prompt tokens ``x``
        (1, C) at absolute positions ``abs_pos`` — position-wise math only
        (embed + norm + k/v projection), NO attention, so a chunk's cost is
        O(chunk) with a tiny constant. ``latent_mask`` marks rows inside the
        prompt's latent region (position >= n - max_latents), which the
        one-shot prefill's KV concat normalizes with ``q_norm`` rather than
        ``kv_norm`` — reproduced row-for-row so a chunk-built page is
        byte-interchangeable with an install-built one."""
        x_emb, _frq = self.input_adapter(x, abs_pos=abs_pos)
        return self.cross_attention.prefill_chunk_kv(x_emb, latent_mask)

    def prefill_latents_paged(
        self, x: jax.Array, n_live: jax.Array, ca: PagedKVCache, table_row: jax.Array
    ) -> Tuple[jax.Array, KVCache]:
        """The split prefill's FINISH step: compute the latents for a slot
        whose prompt KV already sits page-aligned in the pool (written by
        ``prefill_chunk_kv`` chunks and/or shared prefix-cache pages). ``x``
        (1, L = max_latents) are the prompt's LAST L tokens, ``n_live`` the
        traced prompt length (n >= L — shorter prompts take the one-shot
        path), ``table_row`` the slot's page reservation. Queries attend to
        the gathered pages under the page-aligned visibility bound — key
        ring position r holds prompt position r, visible to query j iff
        r < n and r <= n - L + j (exactly the one-shot prefill's pad +
        causal masking in the rotated frame) — then run the standard
        self-attention stack into a fresh bucket-shaped SA cache. ONE
        compiled program ever: every shape here is static (L, the window,
        the page count), n/slot/table ride as traced data."""
        b, latents = x.shape
        window = ca.window
        rot = self._rotated_dim()
        n = jnp.asarray(n_live, jnp.int32)
        q_pos = jnp.maximum(n - latents + jnp.arange(latents)[None, :], 0)
        x_emb, frq_q = self.input_adapter(x, abs_pos=q_pos)

        # gather_slot dequantizes on quantized pools: the finish's latents see
        # exactly the bytes decode will gather — uniform quantization error
        k_rows, v_rows = ca.gather_slot(table_row)
        n_phys = k_rows.shape[1]
        start = jnp.mod(n, window)
        logical = jnp.mod(jnp.arange(n_phys)[None, :] - start, window)
        slot_pos = jnp.maximum(logical - (window - n), 0)
        rope_k = frequency_position_encoding(slot_pos, rot)
        r = jnp.arange(n_phys)[None, :]
        live_ok = (logical >= window - n) & (r < window)  # (1, n_phys)
        causal = logical[:, None, :] <= (
            window - latents + jnp.arange(latents)
        )[None, :, None]  # (1, L, n_phys)
        visible = live_ok[:, None, :] & causal

        x_latent = self.cross_attention.prefill_latents_paged(
            x_emb, k_rows, v_rows, visible, rope_q=frq_q, rope_k=rope_k
        )
        num_channels = self.input_adapter.num_input_channels
        # k_rows.dtype, not ca.kp.dtype: a quantized pool is int8, but the SA
        # cache stays in the dequantized compute dtype
        sa_fresh = KVCache.create_stacked(
            self.num_self_attention_layers, b, latents, num_channels,
            num_channels, k_rows.dtype,
        )
        sa_slot_pos = jnp.maximum(n - latents + jnp.arange(latents)[None, :], 0)
        rope_k_sa = frequency_position_encoding(sa_slot_pos, rot)
        x_latent, sa_cache = self.self_attention(
            x_latent, rope_q=frq_q, rope_k=rope_k_sa, kv_cache=sa_fresh
        )
        return x_latent, sa_cache


class CausalSequenceModel(nn.Module):
    """Perceiver AR + token input adapter + optional final LN + tied token head."""

    config: CausalSequenceModelConfig
    deterministic: bool = True
    dtype: Optional[jnp.dtype] = None
    param_dtype: jnp.dtype = jnp.float32

    def setup(self):
        cfg = self.config
        num_rotated_channels = cfg.num_channels // cfg.num_heads
        if cfg.abs_pos_emb:
            # rotary embedding only for the first 50% of head channels
            num_rotated_channels = num_rotated_channels // 2

        input_adapter = TokenInputAdapterWithRotarySupport(
            rotated_channels_per_head=num_rotated_channels,
            vocab_size=cfg.vocab_size,
            max_seq_len=cfg.max_seq_len,
            num_input_channels_=cfg.num_channels,
            abs_pos_emb=cfg.abs_pos_emb,
            init_scale=cfg.init_scale,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
        )
        self.ar = PerceiverAR(
            input_adapter=input_adapter,
            num_heads=cfg.num_heads,
            max_heads_parallel=cfg.max_heads_parallel,
            num_self_attention_layers=cfg.num_self_attention_layers,
            num_self_attention_rotary_layers=cfg.num_self_attention_rotary_layers,
            self_attention_widening_factor=cfg.self_attention_widening_factor,
            cross_attention_widening_factor=cfg.cross_attention_widening_factor,
            cross_attention_dropout=cfg.cross_attention_dropout,
            cross_attention_dropout_mode=cfg.cross_attention_dropout_mode,
            post_attention_dropout=cfg.post_attention_dropout,
            sequence_parallel_axis=cfg.sequence_parallel_axis,
            pipeline_axis=cfg.pipeline_axis,
            pipeline_microbatches=cfg.pipeline_microbatches,
            residual_dropout=cfg.residual_dropout,
            activation_checkpointing=cfg.activation_checkpointing,
            remat_policy=cfg.remat_policy,
            activation_offloading=cfg.activation_offloading,
            scan_unroll=cfg.scan_unroll,
            fused_qkv=cfg.fused_qkv,
            init_scale=cfg.init_scale,
            deterministic=self.deterministic,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="ar",
        )
        if cfg.output_norm:
            self.out_norm = nn.LayerNorm(epsilon=LN_EPS, dtype=self.dtype, param_dtype=self.param_dtype, name="out_norm")
        self.output_adapter = TiedTokenOutputAdapter(
            vocab_size=cfg.vocab_size, emb_bias=cfg.output_bias, param_dtype=self.param_dtype, name="output_adapter"
        )

    @property
    def max_seq_len(self) -> int:
        return self.config.max_seq_len

    @property
    def max_latents(self) -> int:
        return self.config.max_latents

    @property
    def max_prefix_len(self) -> int:
        return self.config.max_seq_len - self.config.max_latents

    def _head(self, hidden: jax.Array) -> jax.Array:
        # "head": a stable scope name profiler-trace tools read device time by
        # (serving/engine.py TICK_SCOPES); metadata only
        with jax.named_scope("head"):
            if self.config.output_norm:
                hidden = self.out_norm(hidden)
            return self.output_adapter(self.ar.attend(hidden))

    def __call__(self, x: jax.Array, prefix_len: int, pad_mask: Optional[jax.Array] = None) -> jax.Array:
        """Logits (B, N - prefix_len, vocab) over the latent positions."""
        if prefix_len > self.max_prefix_len:
            raise ValueError(f"prefix_len ({prefix_len}) exceeds max_prefix_len ({self.max_prefix_len})")
        hidden = self.ar(x, prefix_len=prefix_len, pad_mask=pad_mask)
        return self._head(hidden)

    def init_cache(
        self, batch_size: int, dtype=jnp.float32, max_seq_len: Optional[int] = None
    ) -> PerceiverARCache:
        # Built from config only, so it works on an unbound module.
        # ``max_seq_len`` overrides the cross-attention capacity for BUCKETED
        # prefill (serving/engine.py): a prompt prefilled at a smaller bucket
        # window produces a cache whose rows scatter into the slot's pages
        # (PagedPerceiverARCache.install_slot).
        cfg = self.config
        return _make_ar_cache(
            batch_size, max_seq_len or cfg.max_seq_len, cfg.max_latents,
            cfg.num_self_attention_layers, cfg.num_channels, dtype,
        )

    def prefill_with_hidden(
        self, x: jax.Array, prefix_len: int, cache: PerceiverARCache, pad_mask: Optional[jax.Array] = None
    ) -> Tuple[jax.Array, jax.Array, PerceiverARCache]:
        """prefill returning (logits, pre-head hidden, cache) — the single
        implementation; the hidden states feed contrastive search's penalty."""
        if prefix_len > self.max_prefix_len:
            raise ValueError(f"prefix_len ({prefix_len}) exceeds max_prefix_len ({self.max_prefix_len})")
        hidden, cache = self.ar.prefill(x, prefix_len=prefix_len, cache=cache, pad_mask=pad_mask)
        return self._head(hidden), hidden, cache

    def prefill(
        self, x: jax.Array, prefix_len: int, cache: PerceiverARCache, pad_mask: Optional[jax.Array] = None
    ) -> Tuple[jax.Array, PerceiverARCache]:
        logits, _, cache = self.prefill_with_hidden(x, prefix_len, cache, pad_mask)
        return logits, cache

    def decode_step_with_hidden(
        self, x: jax.Array, cache: PerceiverARCache
    ) -> Tuple[jax.Array, jax.Array, PerceiverARCache]:
        """decode_step returning (logits, pre-head hidden, cache) — the single
        implementation."""
        hidden, cache = self.ar.decode_step(x, cache)
        return self._head(hidden), hidden, cache

    def decode_step(self, x: jax.Array, cache: PerceiverARCache) -> Tuple[jax.Array, PerceiverARCache]:
        logits, _, cache = self.decode_step_with_hidden(x, cache)
        return logits, cache

    def prefill_rows(
        self, x: jax.Array, prefix_len: int, cache: PerceiverARCache, pad_mask: Optional[jax.Array] = None
    ) -> Tuple[jax.Array, PerceiverARCache]:
        """prefill without the head: (the last position's hidden row (B, C),
        cache) — what the serving engine installs as a slot's row."""
        hidden, cache = self.ar.prefill(x, prefix_len=prefix_len, cache=cache, pad_mask=pad_mask)
        return hidden[:, -1], cache

    def decode_block(self, x: jax.Array, cache: PerceiverARCache) -> Tuple[jax.Array, PerceiverARCache]:
        """Decode ``n`` tokens at once (chunked/speculative verification); see
        ``PerceiverAR.decode_block`` for the n > 1 no-roll contract. Returns
        logits (B, n, vocab) — one next-token distribution per block position."""
        hidden, cache = self.ar.decode_block(x, cache)
        return self._head(hidden), cache

    def init_paged_cache(
        self, batch_size: int, num_pages: int, page_size: int, dtype=jnp.float32,
        kv_quant: Optional[str] = None,
    ) -> PagedPerceiverARCache:
        """Paged decode-pool state for the serving engine (serving/paging.py):
        a shared KV page pool + per-slot page tables in place of a per-slot
        full-window cross-attention cache. Built from config only,
        so it works on an unbound module. ``kv_quant="int8"`` makes the pool
        int8 with per-page-per-head scale sidecars (docs/serving.md
        "Quantized KV pages & weight serving")."""
        cfg = self.config
        return _make_paged_ar_cache(
            batch_size, cfg.max_seq_len, cfg.max_latents, cfg.num_self_attention_layers,
            cfg.num_channels, num_pages, page_size, dtype,
            num_heads=cfg.num_heads, kv_quant=kv_quant,
        )

    def prefill_chunk_kv(
        self, x: jax.Array, abs_pos: jax.Array, latent_mask: jax.Array
    ) -> Tuple[jax.Array, jax.Array]:
        """Chunked prefill's per-chunk KV rows; see
        ``PerceiverAR.prefill_chunk_kv`` (the head plays no part — chunks
        produce keys/values, never logits)."""
        return self.ar.prefill_chunk_kv(x, abs_pos, latent_mask)

    def prefill_finish_paged(
        self, x: jax.Array, n_live: jax.Array, ca: PagedKVCache, table_row: jax.Array
    ) -> Tuple[jax.Array, KVCache]:
        """Chunked prefill's finish: latents over the slot's pages. Returns
        (the last position's hidden row (1, C) — the row the slot carries,
        the one-shot ``prefill_rows``' exactly — and the batch-1 SA cache to
        install); see ``PerceiverAR.prefill_latents_paged``. No head runs."""
        hidden, sa_cache = self.ar.prefill_latents_paged(x, n_live, ca, table_row)
        return hidden[:, -1], sa_cache

    # ---- what the serving engine asks of a model (models/core/serving_api.py)
    def serving_traits(self) -> ServingTraits:
        cfg = self.config
        return ServingTraits(vocab_size=cfg.vocab_size, hidden_size=cfg.num_channels, window=cfg.max_seq_len,
                             finish_ids=cfg.max_latents)

    def serving_pages(self, prompt_tokens: int, max_new_tokens: int, page_size: int, bucket: int) -> int:
        """The covering prefill bucket plus the whole generation budget, capped
        at the window (``serving/paging.pages_for_request``; imported here, at
        the engine's call, because ``serving`` imports this module)."""
        from perceiver_io_tpu.serving.paging import pages_for_request

        return pages_for_request(bucket, max_new_tokens, self.config.max_seq_len, page_size)

    def serving_chunk_phase(self, params, cache: PagedPerceiverARCache, lanes) -> PagedPerceiverARCache:
        """One SPLIT-prefill chunk a CARRIED lane (packed from lane 0, so the
        live ones are the first ``sum(ch_count > 0)``; docs/serving.md "Chunked
        prefill"): position-wise KV for prompt tokens [offset, offset + count)
        scattered page-wise through the lane's table row — the slot's IN-CACHE
        table stays trash until the finish, so the decode phase cannot write
        into the half-built reservation."""
        cap = lanes.ch_ids.shape[1]
        j = jnp.arange(cap)

        def lane(i, cache):
            offset = lanes.ch_offset[i]
            pos = jnp.clip(offset + j, 0, self.max_seq_len - 1)[None, :]
            latent_mask = ((offset + j) >= lanes.ch_latent_start[i])[None, :]
            k, v = self.apply(params, lanes.ch_ids[i][None, :], pos, latent_mask,
                              method=type(self).prefill_chunk_kv)
            # the lane's own padding rows (past count) deposit zero payloads on
            # the trash page — write_rows' padding discipline, deterministic
            return cache.replace(
                ca=cache.ca.write_rows(lanes.ch_tables[i], offset, lanes.ch_count[i], k[0], v[0])
            )

        return jax.lax.fori_loop(0, jnp.sum((lanes.ch_count > 0).astype(jnp.int32)), lane, cache)

    def serving_finish_phase(self, params, cache: PagedPerceiverARCache, state, lanes, install_state):
        """The SPLIT prefill's finish, a lane a slot: latents for the last
        ``max_latents`` prompt tokens against the slot's already-written
        pages, then the install bookkeeping (table, ring offset, SA cache,
        slot state activation)."""
        def body(carry, lane):
            (active, slot, trow, ids, n, rng, temp, tk, tp, ds, pad) = lane

            def fin(args):
                cache, state = args
                rows, sa_src = self.apply(
                    params, ids[None, :], n, cache.ca, trow,
                    method=type(self).prefill_finish_paged,
                )
                cache = cache.install_finish(slot, trow, sa_src, n)
                state = install_state(state, slot, rows[0], rng, temp, tk, tp, ds, pad)
                return cache, state

            return jax.lax.cond(active, fin, lambda a: a, carry), None

        carry, _ = jax.lax.scan(
            body, (cache, state),
            (lanes.fin_active, lanes.fin_slot, lanes.fin_tables, lanes.fin_ids, lanes.fin_n,
             lanes.fin_rng, lanes.fin_temp, lanes.fin_tk, lanes.fin_tp, lanes.fin_ds, lanes.fin_pad),
        )
        return carry

    def decode_rows_paged(
        self, x: jax.Array, cache: PagedPerceiverARCache
    ) -> Tuple[jax.Array, PagedPerceiverARCache]:
        """One decode token against the paged pool, without the head: (hidden
        rows (B, C), cache); see ``PerceiverAR.decode_step_paged``."""
        hidden, cache = self.ar.decode_step_paged(x, cache)
        return hidden[:, -1], cache

    def decode_step_paged(
        self, x: jax.Array, cache: PagedPerceiverARCache
    ) -> Tuple[jax.Array, PagedPerceiverARCache]:
        """x (B, 1) -> logits (B, 1, vocab): the head of ``decode_rows_paged``'s rows."""
        rows, cache = self.decode_rows_paged(x, cache)
        return self._head(rows)[:, None], cache
