"""Multi-head attention with pad/causal masking, RoPE, and a static-shape KV cache.

Behavioral parity targets (reference: /root/reference/perceiver/model/core/modules.py):
  - ``MultiHeadAttention``  -> modules.py:23-170 (separate qk/v widths, right-aligned
    causal masking for queries/keys of different length, pad-mask over keys, RoPE
    applied after cache concatenation so caches hold *unrotated* keys)
  - ``KVCache``             -> modules.py:20,117-121 (torch grows tensors; XLA cannot,
    so here the cache is a fixed-capacity, left-aligned buffer + a scalar length)

TPU-first design notes:
  * The torch reference appends to caches by concatenation and the HF wrapper later
    truncates them to implement a sliding window (reference core/huggingface.py:89-156).
    Under XLA both collapse into one mechanism: a fixed-capacity buffer whose append
    rolls the oldest entry out when full. Capacity = max_latents for self-attention
    caches and max_seq_len for the Perceiver AR cross-attention cache reproduces the
    reference's grow-latents -> grow-prefix -> slide policy exactly, with fully
    static shapes.
  * Attention logits are computed with an fp32 softmax accumulator regardless of the
    compute dtype (bf16 on TPU), the standard numerically-safe formulation the MXU
    supports natively.
  * The reference's ``max_heads_parallel`` head-chunking loop (modules.py:146-166)
    is a CUDA peak-memory workaround; under XLA attention is fused (and later
    replaced by a Pallas flash kernel), so the field is accepted for config parity
    but does not alter the computation.
"""

from __future__ import annotations

from typing import Optional, Tuple

import flax.linen as nn
import flax.struct
import jax
import jax.numpy as jnp

from perceiver_io_tpu.ops.position import apply_rope


class KVCache(flax.struct.PyTreeNode):
    """Fixed-capacity, left-aligned key/value cache.

    ``k``: (B, capacity, num_qk_channels) unrotated projected keys
    ``v``: (B, capacity, num_v_channels)
    ``length``: scalar int32, number of valid (oldest-first) entries.

    Append semantics: entries are written at ``length``; a single-token append to a
    full cache first rolls the buffer left by one (dropping the oldest entry), which
    is exactly the reference's cache-truncation sliding window.
    """

    k: jax.Array
    v: jax.Array
    length: jax.Array

    @property
    def capacity(self) -> int:
        return self.k.shape[1]

    @staticmethod
    def create(batch_size: int, capacity: int, num_qk_channels: int, num_v_channels: int, dtype=jnp.float32) -> "KVCache":
        return KVCache(
            k=jnp.zeros((batch_size, capacity, num_qk_channels), dtype=dtype),
            v=jnp.zeros((batch_size, capacity, num_v_channels), dtype=dtype),
            length=jnp.zeros((), dtype=jnp.int32),
        )

    @staticmethod
    def create_stacked(
        num_layers: int, batch_size: int, capacity: int, num_qk_channels: int, num_v_channels: int, dtype=jnp.float32
    ) -> "KVCache":
        """Per-layer caches stacked on a leading layer axis, consumed/produced one
        slice per ``nn.scan`` iteration (see SelfAttentionBlock)."""
        return KVCache(
            k=jnp.zeros((num_layers, batch_size, capacity, num_qk_channels), dtype=dtype),
            v=jnp.zeros((num_layers, batch_size, capacity, num_v_channels), dtype=dtype),
            length=jnp.zeros((num_layers,), dtype=jnp.int32),
        )

    def reset(self) -> "KVCache":
        """Empty the cache (length -> 0) without reallocating buffers; stale slot
        contents are unreachable behind the causal/validity masks."""
        return self.replace(length=jnp.zeros_like(self.length))

    def append(self, k_new: jax.Array, v_new: jax.Array) -> "KVCache":
        n_new = k_new.shape[1]
        cap = self.capacity
        if n_new == 1:
            full = self.length >= cap
            k = jnp.where(full, jnp.roll(self.k, -1, axis=1), self.k)
            v = jnp.where(full, jnp.roll(self.v, -1, axis=1), self.v)
            pos = jnp.minimum(self.length, cap - 1)
            k = jax.lax.dynamic_update_slice_in_dim(k, k_new.astype(k.dtype), pos, axis=1)
            v = jax.lax.dynamic_update_slice_in_dim(v, v_new.astype(v.dtype), pos, axis=1)
            length = jnp.minimum(self.length + 1, cap)
        else:
            # Multi-token (prefill) append: caller guarantees it fits.
            k = jax.lax.dynamic_update_slice_in_dim(self.k, k_new.astype(self.k.dtype), self.length, axis=1)
            v = jax.lax.dynamic_update_slice_in_dim(self.v, v_new.astype(self.v.dtype), self.length, axis=1)
            length = self.length + n_new
        return KVCache(k=k, v=v, length=length)


class RingKVCache(flax.struct.PyTreeNode):
    """Stacked per-layer self-attention cache of the serving engine's PAGED
    pool (``PagedPerceiverARCache.sa``): a ring that is written in place and
    read where it lies.

    ``k`` / ``v``: (num_layers, B, capacity, C) unrotated projected keys / values.
    ``start``: (B,) int32 ring offset per slot, as ``PagedKVCache.start``: the
        NEXT append writes physical row ``start``; physical row ``r`` holds
        logical latent ``(r - start) mod capacity`` (0 = oldest).
    ``active``: (B,) bool, the slot holds an installed request: set by
        ``write_batch_row``, cleared by the pool's ``release_slot``, so it
        equals the engine's ``SlotState.active`` after every program that
        changes either. Only an active slot's ring is READ.
    ``layer``: the layer a view addresses; set by ``SelfAttentionBlock``'s
        layer loop (which carries the stacked buffers), None outside it.

    The pool's invariant is what makes the ring exact: every row is FULL at
    all times and every slot appends once per decode step, free slots too
    (their rings turn; nothing reads them). An active slot's one query
    therefore sees all ``capacity`` rows (no validity bound, no pad mask) and
    an append is one row a slot a layer; nothing is shifted.
    ``KVCache`` (left-aligned, shared scalar length, rolled when full) stays
    the cache of ``generate()`` and of the one-shot prefill.
    """

    k: jax.Array
    v: jax.Array
    start: jax.Array
    active: jax.Array
    layer: Optional[jax.Array] = None

    @property
    def capacity(self) -> int:
        return self.k.shape[2]

    @staticmethod
    def create(
        num_layers: int, batch_size: int, capacity: int, num_qk_channels: int, num_v_channels: int, dtype=jnp.float32
    ) -> "RingKVCache":
        return RingKVCache(
            k=jnp.zeros((num_layers, batch_size, capacity, num_qk_channels), dtype=dtype),
            v=jnp.zeros((num_layers, batch_size, capacity, num_v_channels), dtype=dtype),
            start=jnp.zeros((batch_size,), dtype=jnp.int32),
            active=jnp.zeros((batch_size,), dtype=bool),
        )

    def append_row(self, k_new: jax.Array, v_new: jax.Array) -> "RingKVCache":
        """Write one token's (B, 1, C) keys / values into layer ``layer`` at
        each slot's ring position. ``start`` is NOT advanced: every layer of
        one decode step writes the same position, and the layer loop's owner
        advances once after the last (``advance``)."""
        rows = jnp.arange(self.start.shape[0])
        at = lambda buf, new: buf.at[self.layer, rows, self.start].set(
            new[:, 0].astype(buf.dtype), indices_are_sorted=True, unique_indices=True
        )
        return self.replace(k=at(self.k, k_new), v=at(self.v, v_new))

    def advance(self) -> "RingKVCache":
        return self.replace(start=jnp.mod(self.start + 1, self.capacity))

    def rewind(self, k: jax.Array) -> "RingKVCache":
        """Step every slot's offset back by ``k`` appends. A full ring has no
        spare row: the rewound rows keep what was appended over the ``k``
        oldest latents, and are read in their place until appended over again.
        So ``k = 1`` is exact (the one rewound row is rewritten before it is
        read), and appending the same ``k`` tokens again restores the bytes."""
        return self.replace(start=jnp.mod(self.start - k, self.capacity))

    def write_batch_row(self, idx: jax.Array, src: KVCache) -> "RingKVCache":
        """Install slot ``idx`` (traced OK) from a FULL stacked ``KVCache`` of
        batch 1 in age order (what a prefill leaves): a plain row write, with
        the slot's ring restarting at 0 and read from now on (``active``)."""
        return self.replace(
            k=jax.lax.dynamic_update_slice_in_dim(self.k, src.k.astype(self.k.dtype), idx, axis=1),
            v=jax.lax.dynamic_update_slice_in_dim(self.v, src.v.astype(self.v.dtype), idx, axis=1),
            start=self.start.at[idx].set(0),
            active=self.active.at[idx].set(True),
        )


class MultiHeadAttention(nn.Module):
    """Scaled dot-product multi-head attention (Perceiver IO appendix-E style).

    Causal attention requires queries and keys to be right-aligned when their
    lengths differ (reference modules.py:139-140).
    """

    num_heads: int
    num_q_input_channels: int
    num_kv_input_channels: int
    num_qk_channels: Optional[int] = None
    num_v_channels: Optional[int] = None
    num_output_channels: Optional[int] = None
    max_heads_parallel: Optional[int] = None  # accepted for config parity; see module docstring
    causal_attention: bool = False
    dropout: float = 0.0
    qkv_bias: bool = True
    out_bias: bool = True
    kernel_init_scale: float = 0.02
    fused_qkv: bool = False  # one GEMM for q/k/v (self-attn) or k/v (cross-attn):
    # kernels are CONCATENATED AT APPLY TIME, so the param tree and checkpoints
    # are identical to the unfused layout — a pure execution knob (NOTES.md §1)
    use_flash: Optional[bool] = None  # None = auto (TPU + supported shapes)
    seq_axis: Optional[str] = None  # sequence-parallel ring attention over this mesh axis
    deterministic: bool = True
    dtype: Optional[jnp.dtype] = None
    param_dtype: jnp.dtype = jnp.float32

    def _dims(self) -> Tuple[int, int, int]:
        num_qk = self.num_qk_channels if self.num_qk_channels is not None else self.num_q_input_channels
        num_v = self.num_v_channels if self.num_v_channels is not None else num_qk
        num_out = self.num_output_channels if self.num_output_channels is not None else self.num_q_input_channels
        if num_qk % self.num_heads != 0:
            raise ValueError("num_qk_channels must be divisible by num_heads")
        if num_v % self.num_heads != 0:
            raise ValueError("num_v_channels must be divisible by num_heads")
        return num_qk, num_v, num_out

    def setup(self):
        num_qk, num_v, num_out = self._dims()
        dense = lambda feat, bias, name: nn.Dense(
            feat,
            use_bias=bias,
            kernel_init=nn.initializers.normal(stddev=self.kernel_init_scale),
            name=name,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
        )
        self.q_proj = dense(num_qk, self.qkv_bias, "q_proj")
        self.k_proj = dense(num_qk, self.qkv_bias, "k_proj")
        self.v_proj = dense(num_v, self.qkv_bias, "v_proj")
        self.o_proj = dense(num_out, self.out_bias, "o_proj")
        self.attn_dropout = nn.Dropout(self.dropout)

    def _fused_projections(self, x_q, x_kv, num_qk: int, num_v: int):
        """q/k/v (or k/v when queries differ) in ONE GEMM: the separate kernels
        are concatenated column-wise at apply time, so each output column's
        contraction is identical to the unfused layout (bit-equal results) and
        the parameter tree / checkpoints are unchanged. Kernel-launch and
        weight-fetch overheads collapse 3x -> 1x (self-attn) or 2x -> 1x."""
        from flax.linen.dtypes import promote_dtype

        p = self.variables["params"]
        if x_q is x_kv:
            kernel = jnp.concatenate(
                [p["q_proj"]["kernel"], p["k_proj"]["kernel"], p["v_proj"]["kernel"]], axis=1
            )
            bias = (
                jnp.concatenate([p["q_proj"]["bias"], p["k_proj"]["bias"], p["v_proj"]["bias"]])
                if self.qkv_bias
                else None
            )
            x, kernel, bias = promote_dtype(x_kv, kernel, bias, dtype=self.dtype)
            qkv = x @ kernel if bias is None else x @ kernel + bias
            return qkv[..., :num_qk], qkv[..., num_qk : 2 * num_qk], qkv[..., 2 * num_qk :]
        kernel = jnp.concatenate([p["k_proj"]["kernel"], p["v_proj"]["kernel"]], axis=1)
        bias = (
            jnp.concatenate([p["k_proj"]["bias"], p["v_proj"]["bias"]]) if self.qkv_bias else None
        )
        x, kernel, bias = promote_dtype(x_kv, kernel, bias, dtype=self.dtype)
        kv = x @ kernel if bias is None else x @ kernel + bias
        return self.q_proj(x_q), kv[..., :num_qk], kv[..., num_qk:]

    def _paged_cached_attention(self, q, k, v, kv_cache, rope_q, rope_k, kv_live, scale):
        """Single-token causal decode against a paged KV pool (the serving
        engine's hot path under paging — docs/serving.md). ``q``/``k``/``v``
        are the UNSPLIT (B, 1, C) projections of the new token. The append is
        an O(1) per-row scatter through the page table (vs the dense ring's
        full-buffer roll); attention runs the fused paged kernel where
        supported, else an XLA gather + masked softmax applying the identical
        ``(start, live)`` visibility bound (``paged_visibility``) — the parity
        contract tests/test_paging.py pins."""
        from perceiver_io_tpu.ops import paged_decode_kernel as pdk
        from perceiver_io_tpu.ops import ragged_paged_kernel as rpk
        from perceiver_io_tpu.ops.decode_kernel import ragged_decode_enabled

        b, n_q = q.shape[0], q.shape[1]
        if n_q != 1 or not self.causal_attention:
            raise ValueError("paged KV caches support single-token causal decode only")
        if kv_live is None:
            raise ValueError("paged attention requires kv_live (visibility is "
                             "encoded by the ring offset + live count alone)")
        if self.dropout > 0.0 and not self.deterministic:
            raise ValueError("paged decode is inference-only (no attention dropout)")
        num_qk, num_v, _ = self._dims()
        with jax.named_scope("cache_append"):
            kv_cache = kv_cache.append_token(k, v)
        live = jnp.broadcast_to(jnp.asarray(kv_live, jnp.int32).reshape(-1), (b,))

        split = lambda t: t.reshape(t.shape[0], t.shape[1], self.num_heads, -1).transpose(0, 2, 1, 3)
        q = split(q) * scale
        if rope_q is not None:
            q = apply_rope(q, rope_q)

        n_phys = kv_cache.pages_per_slot * kv_cache.page_size
        with jax.named_scope("decode_attention"):
            if self.use_flash is not False and pdk.paged_decode_supported(
                kv_cache.page_size, num_qk, num_v, self.num_heads,
                quantized=kv_cache.quantized, qbits=kv_cache.qbits,
            ):
                ang = rope_k if rope_k is not None else jnp.zeros((b, n_phys, 2), jnp.float32)
                if ang.shape[0] != b:
                    ang = jnp.broadcast_to(ang, (b, *ang.shape[1:]))
                o = pdk.fused_paged_decode_attention(
                    q, kv_cache.kp, kv_cache.vp, kv_cache.page_table, kv_cache.start,
                    live, ang, kv_cache.window,
                    # the ragged kill-switch disables the dead-page skip (every
                    # page fetched + masked) but never the visibility bound
                    skip_dead_pages=ragged_decode_enabled(),
                    # int8 pools: scales ride the scalar-prefetch path and the
                    # dequant fuses into the page stream (None on fp pools)
                    k_scale=kv_cache.k_scale, v_scale=kv_cache.v_scale,
                )
            elif self.use_flash is not False and rpk.ragged_paged_supported(
                kv_cache.page_size, num_qk, num_v, self.num_heads,
                quantized=kv_cache.quantized, qbits=kv_cache.qbits,
            ):
                # int4 pools (and anything else the legacy single-query kernel
                # gates out but the ragged program serves): dispatch the decode
                # batch as a ragged descriptor of full-bound items — the nibble
                # unpack fuses into the page stream (ops/ragged_paged_kernel.py)
                ang = rope_k if rope_k is not None else jnp.zeros((b, n_phys, 2), jnp.float32)
                if ang.shape[0] != b:
                    ang = jnp.broadcast_to(ang, (b, *ang.shape[1:]))
                o = rpk.fused_ragged_paged_attention(
                    q, kv_cache.kp, kv_cache.vp, kv_cache.page_table, kv_cache.start,
                    live, jnp.full((b,), kv_cache.window - 1, jnp.int32), ang,
                    kv_cache.window, skip_dead_pages=ragged_decode_enabled(),
                    k_scale=kv_cache.k_scale, v_scale=kv_cache.v_scale,
                    qbits=kv_cache.qbits,
                )
            else:
                k_full, v_full = kv_cache.gather_dense()
                kf, vf = split(k_full), split(v_full)
                if rope_k is not None:
                    kf = apply_rope(kf, rope_k)
                attn = jnp.einsum("bhic,bhjc->bhij", q, kf, preferred_element_type=jnp.float32)
                neg = jnp.finfo(attn.dtype).min
                visible = pdk.paged_visibility(kv_cache.start, live, kv_cache.window, n_phys)
                attn = jnp.where(visible[:, None, None, :], attn, neg)
                attn = jax.nn.softmax(attn, axis=-1).astype(vf.dtype)
                o = jnp.einsum("bhij,bhjc->bhic", attn, vf)
        o = o.transpose(0, 2, 1, 3).reshape(o.shape[0], n_q, -1)
        return self.o_proj(o), kv_cache

    def _ring_cached_attention(self, q, k, v, kv_cache, rope_q, rope_k, scale):
        """Single-token causal decode against one layer of the paged pool's
        self-attention ring (``RingKVCache``). ``q``/``k``/``v`` are the
        UNSPLIT (B, 1, C) projections of the new token. The append writes one
        row a slot into the stacked buffer; the fused kernel then reads the
        layer where it lies (its stacked form), else the XLA formulation
        reads it through one slice. In an ACTIVE slot every row is visible:
        the ring is full and the query is its newest entry, so there is no
        mask on this path and ``rope_k`` carries the order (angles per
        PHYSICAL row). A slot that is not active (free, or in the middle of
        its prefill) has its row appended like the others and its ring left
        unread: the kernel is told ``live = 0`` for it, moves no bytes and
        returns zeros (ops/decode_kernel.py ``_step_block``). The XLA
        formulation computes every slot; nothing harvests the others' rows."""
        from perceiver_io_tpu.ops.decode_kernel import decode_kernel_supported, fused_decode_attention_auto

        b, n_q = q.shape[0], q.shape[1]
        if n_q != 1 or not self.causal_attention:
            raise ValueError("ring KV caches support single-token causal decode only")
        if self.dropout > 0.0 and not self.deterministic:
            raise ValueError("ring decode is inference-only (no attention dropout)")
        num_qk, num_v, _ = self._dims()
        with jax.named_scope("cache_append"):
            kv_cache = kv_cache.append_row(k, v)
        cap = kv_cache.capacity

        split = lambda t: t.reshape(t.shape[0], t.shape[1], self.num_heads, -1).transpose(0, 2, 1, 3)
        q = split(q) * scale
        if rope_q is not None:
            q = apply_rope(q, rope_q)

        with jax.named_scope("decode_attention"):
            if self.use_flash is not False and decode_kernel_supported(
                n_q, cap, num_qk, num_v, self.num_heads, batch_size=b,
                itemsize=kv_cache.k.dtype.itemsize,
            ):
                ang = rope_k if rope_k is not None else jnp.zeros((b, cap, 2), jnp.float32)
                if ang.shape[0] != b:
                    ang = jnp.broadcast_to(ang, (b, *ang.shape[1:]))
                o = fused_decode_attention_auto(
                    q, kv_cache.k, kv_cache.v, ang, cap - 1, jnp.zeros((b, cap), bool),
                    live=jnp.where(kv_cache.active, cap, 0), layer=kv_cache.layer,
                )
            else:
                take = lambda buf: jax.lax.dynamic_index_in_dim(buf, kv_cache.layer, axis=0, keepdims=False)
                kf, vf = split(take(kv_cache.k)), split(take(kv_cache.v))
                if rope_k is not None:
                    kf = apply_rope(kf, rope_k)
                attn = jnp.einsum("bhic,bhjc->bhij", q, kf, preferred_element_type=jnp.float32)
                attn = jax.nn.softmax(attn, axis=-1).astype(vf.dtype)
                o = jnp.einsum("bhij,bhjc->bhic", attn, vf)
        o = o.transpose(0, 2, 1, 3).reshape(o.shape[0], n_q, -1)
        return self.o_proj(o), kv_cache

    def paged_prefill_attention(
        self,
        x_q: jax.Array,
        k_rows: jax.Array,
        v_rows: jax.Array,
        visible: jax.Array,
        rope_q: Optional[jax.Array] = None,
        rope_k: Optional[jax.Array] = None,
    ) -> jax.Array:
        """Multi-query attention of the prefill-finish latents against ONE
        slot's gathered KV pages (docs/serving.md "Chunked prefill"): ``x_q``
        (1, L, D) are the already-normed latent inputs, ``k_rows``/``v_rows``
        (1, n_phys, C) the slot's page rows in PHYSICAL ring order (unsplit,
        unrotated — exactly as chunk writes left them), and ``visible``
        (1, L, n_phys) the caller-computed per-query bound combining the
        (start, live) paged visibility with the latents' causal order. The
        arithmetic mirrors the module's XLA masked-softmax formulation (fp32
        scores, finfo-min mask, softmax, value sum in the cache dtype) so the
        finish step's latents track the one-shot prefill's token-for-token."""
        if self.dropout > 0.0 and not self.deterministic:
            raise ValueError("paged prefill is inference-only (no attention dropout)")
        num_qk, _num_v, _ = self._dims()
        scale = (num_qk // self.num_heads) ** -0.5
        n_q = x_q.shape[1]
        split = lambda t: t.reshape(t.shape[0], t.shape[1], self.num_heads, -1).transpose(0, 2, 1, 3)
        q = split(self.q_proj(x_q)) * scale
        if rope_q is not None:
            q = apply_rope(q, rope_q)
        kf, vf = split(k_rows), split(v_rows)
        if rope_k is not None:
            kf = apply_rope(kf, rope_k)
        attn = jnp.einsum("bhic,bhjc->bhij", q, kf, preferred_element_type=jnp.float32)
        neg = jnp.finfo(attn.dtype).min
        attn = jnp.where(visible[:, None, :, :], attn, neg)
        attn = jax.nn.softmax(attn, axis=-1).astype(vf.dtype)
        o = jnp.einsum("bhij,bhjc->bhic", attn, vf)
        o = o.transpose(0, 2, 1, 3).reshape(o.shape[0], n_q, -1)
        return self.o_proj(o)

    def project_kv(self, x_kv: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """Key/value projections of already-normed inputs — the chunked
        prefill's per-token write path (position-wise: no attention, no
        queries). Matches what cached prefill appends row-for-row."""
        return self.k_proj(x_kv), self.v_proj(x_kv)

    def __call__(
        self,
        x_q: jax.Array,
        x_kv: jax.Array,
        pad_mask: Optional[jax.Array] = None,
        rope_q: Optional[jax.Array] = None,
        rope_k: Optional[jax.Array] = None,
        kv_cache: Optional[KVCache] = None,
        kv_live: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, Optional[KVCache]]:
        """Attend ``x_q`` (B, N, D) to ``x_kv`` (B, L, C).

        ``pad_mask``: boolean over keys, True = padding. In cached mode its second
        dim must equal the cache capacity (a slot-mask maintained by the caller).
        ``rope_q`` / ``rope_k``: rotary phase angles, one row per query / key row
        ((B, N, r) / (B, n_k, r)); callers do any right-alignment slicing.
        ``kv_live``: optional (B,) per-row live-entry count for cached mode; key
        slots below ``length - kv_live`` (the left-pad head) are masked — a
        bound redundant with ``pad_mask`` that lets the fused decode kernel
        SKIP those KV blocks entirely (ragged length-aware decode).
        Returns (output (B, N, F), updated cache or None).
        """
        num_qk, num_v, _ = self._dims()
        num_qk_per_head = num_qk // self.num_heads
        scale = num_qk_per_head**-0.5

        paged = False
        if kv_cache is not None:
            from perceiver_io_tpu.ops.paged_decode_kernel import PagedKVCache

            paged = isinstance(kv_cache, PagedKVCache)
        if kv_live is not None and not paged:
            from perceiver_io_tpu.ops.decode_kernel import ragged_decode_enabled

            if kv_cache is None or not ragged_decode_enabled():
                kv_live = None  # kill-switch / uncached: fall back to full-length masking

        if self.fused_qkv and not self.is_initializing():
            q, k, v = self._fused_projections(x_q, x_kv, num_qk, num_v)
        else:
            q = self.q_proj(x_q)
            k = self.k_proj(x_kv)
            v = self.v_proj(x_kv)

        if isinstance(kv_cache, RingKVCache):
            return self._ring_cached_attention(q, k, v, kv_cache, rope_q, rope_k, scale)

        if paged:
            # Paged ring-cache decode (serving/paging.py; ops/paged_decode_kernel.py):
            # the cache is a page-table-indirected pool, visibility is fully
            # encoded by (start, live) — the ragged kill-switch governs only the
            # kernel's dead-page skipping, never the masking bound (correctness
            # needs it: there is no pad-slot buffer in the paged layout).
            return self._paged_cached_attention(q, k, v, kv_cache, rope_q, rope_k, kv_live, scale)

        if kv_cache is not None:
            # "cache_append" / "decode_attention": stable scope names a
            # profiler trace's device time is read by (serving/engine.py
            # TICK_SCOPES); metadata only
            with jax.named_scope("cache_append"):
                kv_cache = kv_cache.append(k, v)
            k, v = kv_cache.k, kv_cache.v  # full capacity buffers

        b, n_q = q.shape[0], q.shape[1]
        n_k = k.shape[1]

        split = lambda t: t.reshape(t.shape[0], t.shape[1], self.num_heads, -1).transpose(0, 2, 1, 3)
        q = split(q) * scale
        if rope_q is not None:
            q = apply_rope(q, rope_q)

        has_dropout = self.dropout > 0.0 and not self.deterministic

        # Fused single-token decode path: a Pallas kernel streams the unrotated
        # cache buffers once (RoPE-on-keys + masked flash softmax + weighted sum
        # in VMEM) instead of materializing a rotated copy of the whole cache
        # per token (ops/decode_kernel.py; ~1.8x over the XLA formulation).
        if kv_cache is not None and self.causal_attention and not has_dropout and self.use_flash is not False:
            from perceiver_io_tpu.ops.decode_kernel import decode_kernel_supported, fused_decode_attention_auto

            if kv_cache.k.shape[0] == b and decode_kernel_supported(
                n_q, n_k, num_qk, num_v, self.num_heads, batch_size=b,
                itemsize=kv_cache.k.dtype.itemsize,
            ):
                ang = rope_k if rope_k is not None else jnp.zeros((b, n_k, 2), jnp.float32)
                if ang.shape[0] != b:
                    ang = jnp.broadcast_to(ang, (b, *ang.shape[1:]))
                pad = pad_mask if pad_mask is not None else jnp.zeros((b, n_k), bool)
                if pad.shape[0] != b:
                    pad = jnp.broadcast_to(pad, (b, n_k))
                with jax.named_scope("decode_attention"):
                    o = fused_decode_attention_auto(
                        q, kv_cache.k, kv_cache.v, ang, kv_cache.length - 1, pad, live=kv_live
                    )
                o = o.transpose(0, 2, 1, 3).reshape(o.shape[0], n_q, -1)
                return self.o_proj(o), kv_cache

        k, v = split(k), split(v)
        if rope_k is not None:
            k = apply_rope(k, rope_k)

        # Sequence-parallel path: ring attention over the configured mesh axis
        # (long-context training; queries and keys sharded over `seq`). With
        # attention dropout the differentiable einsum ring runs with a
        # position-keyed mask; without it the custom-VJP ring (splash blocks on
        # TPU, O(n/S) backward memory) is used.
        if self.seq_axis is not None and kv_cache is None:
            from perceiver_io_tpu.parallel.ring_attention import ring_attention_ambient

            if q.shape[0] != k.shape[0]:
                q = jnp.broadcast_to(q, (k.shape[0], *q.shape[1:]))
            o = ring_attention_ambient(
                q, k, v, pad_mask=pad_mask, causal=self.causal_attention, seq_axis=self.seq_axis,
                dropout_rate=self.dropout if has_dropout else 0.0,
                dropout_rng=self.make_rng("dropout") if has_dropout else None,
            )
            o = o.transpose(0, 2, 1, 3).reshape(o.shape[0], n_q, -1)
            return self.o_proj(o), kv_cache

        # TPU fast path: fused splash (flash) attention — no materialized
        # (Nq, Nk) matrix. Falls through to the XLA formulation when unsupported
        # (caches, attention dropout, mismatched qk/v head widths, odd shapes).
        from perceiver_io_tpu.ops.flash import flash_supported, splash_mha
        flash_ok = flash_supported(
            num_qk // self.num_heads,
            num_v // self.num_heads,
            n_q,
            n_k,
            has_dropout,
            kv_cache is not None,
            batch_size=k.shape[0],
            num_heads=self.num_heads,
        )
        if self.use_flash is True and not flash_ok:
            raise ValueError(
                "use_flash=True but this attention call cannot use the splash kernel "
                f"(backend={jax.default_backend()}, devices={jax.device_count()}, n_q={n_q}, n_k={n_k}, "
                f"dropout={has_dropout}, cached={kv_cache is not None}); use use_flash=None for auto fallback"
            )
        if self.use_flash is not False and flash_ok:
            if q.shape[0] != k.shape[0]:  # broadcast (1, ...) queries for vmap
                q = jnp.broadcast_to(q, (k.shape[0], *q.shape[1:]))
            o = splash_mha(q, k, v, pad_mask=pad_mask, causal=self.causal_attention)
            o = o.transpose(0, 2, 1, 3).reshape(o.shape[0], n_q, -1)
            return self.o_proj(o), kv_cache

        # fp32 logits + softmax for numerical stability in bf16 compute
        attn = jnp.einsum("bhic,bhjc->bhij", q, k, preferred_element_type=jnp.float32)
        neg = jnp.finfo(attn.dtype).min

        if pad_mask is not None:
            attn = jnp.where(pad_mask[:, None, None, :], neg, attn)

        if self.causal_attention:
            if kv_cache is None:
                # Right-aligned causal mask: query row i may see key cols 0..(n_k - n_q + i).
                causal = jnp.triu(jnp.ones((n_q, n_k), dtype=bool), k=n_k - n_q + 1)
                attn = jnp.where(causal[None, None, :, :], neg, attn)
            else:
                # Cached mode: key slot j holds sequence position j (left-aligned
                # buffer); query row i has absolute position length - n_q + i.
                q_pos = kv_cache.length - n_q + jnp.arange(n_q)
                visible = jnp.arange(n_k)[None, :] <= q_pos[:, None]
                if kv_live is not None:
                    # ragged lower bound: slots below each row's live tail are
                    # dead left-pads — the same bound the fused kernel skips
                    # whole KV blocks by, applied here for bitwise parity
                    lo = (kv_cache.length - kv_live)[:, None, None]  # (B, 1, 1)
                    visible = visible[None] & (jnp.arange(n_k)[None, None, :] >= lo)
                    attn = jnp.where(visible[:, None, :, :], attn, neg)
                else:
                    attn = jnp.where(visible[None, None, :, :], attn, neg)
        elif kv_cache is not None:
            valid = jnp.arange(n_k) < kv_cache.length
            if kv_live is not None:
                valid = valid[None, :] & (
                    jnp.arange(n_k)[None, :] >= (kv_cache.length - kv_live)[:, None]
                )
                attn = jnp.where(valid[:, None, None, :], attn, neg)
            else:
                attn = jnp.where(valid[None, None, None, :], attn, neg)

        attn = jax.nn.softmax(attn, axis=-1)
        attn = self.attn_dropout(attn, deterministic=self.deterministic)
        attn = attn.astype(v.dtype)

        o = jnp.einsum("bhij,bhjc->bhic", attn, v)
        # o's batch may exceed x_q's when a (1, N, D) query broadcast against a
        # batched key/value input, so recover the batch size from o itself.
        o = o.transpose(0, 2, 1, 3).reshape(o.shape[0], n_q, -1)
        o = self.o_proj(o)
        return o, kv_cache
