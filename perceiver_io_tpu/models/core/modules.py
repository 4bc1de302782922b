"""Perceiver IO building blocks: attention layers, blocks, encoder, decoder.

Parity targets (reference: /root/reference/perceiver/model/core/modules.py):
  - ``CrossAttention``      -> modules.py:173-230 (pre-LN; ``x_kv_prefix`` mode where
    key/value input = concat(prefix, query) — the Perceiver AR trick)
  - ``SelfAttention``       -> modules.py:233-278
  - ``CrossAttentionLayer`` -> modules.py:293-330 (attention residual optional)
  - ``SelfAttentionLayer``  -> modules.py:333-367
  - ``SelfAttentionBlock``  -> modules.py:370-441 (``num_rotary_layers`` leading
    layers get RoPE; -1 = all; per-layer KV-cache threading)
  - ``MLP``                 -> modules.py:444-454 (LN -> Dense x widening -> GELU -> Dense)
  - ``PerceiverEncoder``    -> modules.py:457-607 (repeated cross-attention with
    weight-sharing flags; validation rules at modules.py:519-526)
  - ``PerceiverDecoder``    -> modules.py:610-675
  - ``PerceiverIO``         -> modules.py:678-688

TPU-first design notes:
  * ``SelfAttentionBlock`` runs its layers under ``nn.scan`` (stacked params with a
    leading layer axis): one traced layer body regardless of depth — O(1) compile
    time — and pairs with per-layer ``nn.remat`` when activation checkpointing is
    enabled (replacing the reference's fairscale checkpoint_wrapper,
    modules.py:933-956). Per-layer rotary gating is branch-free: rotary angles are
    multiplied by a 0/1 per-layer flag (rotation by zero angle is the identity).
  * Weight sharing across repeated cross-attention layers / self-attention blocks
    (modules.py:564-571) is plain module reuse — calling the same flax submodule
    twice shares its parameters.
  * Dropout determinism is a module field, not a call argument: training code
    instantiates the model with ``deterministic=False`` and binds the same params —
    modules are pure functions of (params, inputs, rngs).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from perceiver_io_tpu.models.core.adapter import InputAdapter, TrainableQueryProvider
from perceiver_io_tpu.ops.attention import KVCache, MultiHeadAttention, RingKVCache

LN_EPS = 1e-5  # matches torch.nn.LayerNorm default for checkpoint-conversion parity


# the argument-free jax.checkpoint_policies; the factory attributes there
# (save_only_these_names, offload variants, ...) take arguments and would be
# silently misapplied if resolved by name
_REMAT_POLICIES = (
    "everything_saveable",
    "nothing_saveable",
    "dots_saveable",
    "checkpoint_dots",
    "dots_with_no_batch_dims_saveable",
    "checkpoint_dots_with_no_batch_dims",
)


def _remat_policy(name: Optional[str], activation_checkpointing: bool = True,
                  activation_offloading: bool = False):
    """Resolve a jax.checkpoint_policies attribute by name (None = full remat).
    Policies like ``dots_with_no_batch_dims_saveable`` keep matmul outputs and
    recompute only the cheap elementwise ops in the backward pass — on the 455M
    flagship this is the difference between paying a full extra forward and
    nearly none (see NOTES.md MFU table).

    ``activation_offloading`` is the TPU-native equivalent of the reference's
    ``offload_to_cpu`` checkpoint wrapper (reference core/modules.py:933-956,
    torch CheckpointImpl + offload): instead of saving matmul outputs in HBM,
    the ``offload_dot_with_no_batch_dims`` policy stages them to pinned host
    memory during the forward pass and fetches them back for the backward —
    trading HBM residency for PCIe/DMA traffic, which pays off when HBM is the
    binding constraint (long-context configs; see NOTES.md)."""
    if activation_offloading:
        if not activation_checkpointing:
            raise ValueError(
                "activation_offloading requires activation_checkpointing=True "
                "(offloading is a property of what the checkpoint saves)"
            )
        if name not in (None, "dots_with_no_batch_dims_saveable"):
            raise ValueError(
                f"activation_offloading composes with remat_policy=None or "
                f"'dots_with_no_batch_dims_saveable' (it offloads exactly that "
                f"policy's saveable set to host memory), got {name!r}"
            )
        return jax.checkpoint_policies.offload_dot_with_no_batch_dims("device", "pinned_host")
    if name is None:
        return None
    if name not in _REMAT_POLICIES:
        raise ValueError(f"unknown remat_policy {name!r}; expected one of {_REMAT_POLICIES}")
    if not activation_checkpointing:
        raise ValueError("remat_policy is set but activation_checkpointing is False; enable it (or clear the policy)")
    return getattr(jax.checkpoint_policies, name)


class MLP(nn.Module):
    num_channels: int
    widening_factor: int
    bias: bool = True
    init_scale: float = 0.02
    dtype: Optional[jnp.dtype] = None
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        dense = lambda feat, name: nn.Dense(
            feat,
            use_bias=self.bias,
            kernel_init=nn.initializers.normal(stddev=self.init_scale),
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name=name,
        )
        x = nn.LayerNorm(epsilon=LN_EPS, dtype=self.dtype, param_dtype=self.param_dtype, name="norm")(x)
        x = dense(self.widening_factor * self.num_channels, "dense_1")(x)
        x = jax.nn.gelu(x, approximate=False)
        x = dense(self.num_channels, "dense_2")(x)
        return x


class CrossAttention(nn.Module):
    """Pre-layer-norm cross-attention. If ``x_kv_prefix`` is given, the key/value
    input is concat(norm(x_kv_prefix), norm(x_q)) so the query attends to itself at
    the end of the key/value sequence (Perceiver AR)."""

    num_heads: int
    num_q_input_channels: int
    num_kv_input_channels: int
    num_qk_channels: Optional[int] = None
    num_v_channels: Optional[int] = None
    max_heads_parallel: Optional[int] = None
    causal_attention: bool = False
    dropout: float = 0.0
    qkv_bias: bool = True
    fused_qkv: bool = False  # single-GEMM q/k/v (see MultiHeadAttention.fused_qkv)
    out_bias: bool = True
    init_scale: float = 0.02
    seq_axis: Optional[str] = None
    deterministic: bool = True
    dtype: Optional[jnp.dtype] = None
    param_dtype: jnp.dtype = jnp.float32

    def setup(self):
        ln = lambda name: nn.LayerNorm(epsilon=LN_EPS, dtype=self.dtype, param_dtype=self.param_dtype, name=name)
        self.q_norm = ln("q_norm")
        self.kv_norm = ln("kv_norm")
        self.attention = MultiHeadAttention(
            num_heads=self.num_heads,
            num_q_input_channels=self.num_q_input_channels,
            num_kv_input_channels=self.num_kv_input_channels,
            num_qk_channels=self.num_qk_channels,
            num_v_channels=self.num_v_channels,
            max_heads_parallel=self.max_heads_parallel,
            causal_attention=self.causal_attention,
            dropout=self.dropout,
            qkv_bias=self.qkv_bias,
            fused_qkv=self.fused_qkv,
            out_bias=self.out_bias,
            kernel_init_scale=self.init_scale,
            seq_axis=self.seq_axis,
            deterministic=self.deterministic,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="attention",
        )

    def __call__(
        self,
        x_q: jax.Array,
        x_kv: Optional[jax.Array] = None,
        x_kv_prefix: Optional[jax.Array] = None,
        pad_mask: Optional[jax.Array] = None,
        rope_q: Optional[jax.Array] = None,
        rope_k: Optional[jax.Array] = None,
        kv_cache: Optional[KVCache] = None,
        kv_live: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, Optional[KVCache]]:
        from perceiver_io_tpu.parallel.mesh import constrain_batch_sharded

        x_q = constrain_batch_sharded(self.q_norm(x_q))
        if x_kv is None:
            x_kv_prefix = self.kv_norm(x_kv_prefix)
            # batch-pin the concat: XLA's propagation otherwise channel-shards
            # this intermediate and pays a replicate-then-reshard before the
            # fsdp kv projection (see constrain_batch_sharded)
            x_kv = constrain_batch_sharded(jnp.concatenate([x_kv_prefix, x_q], axis=1))
        else:
            x_kv = self.kv_norm(x_kv)
        return self.attention(
            x_q, x_kv, pad_mask=pad_mask, rope_q=rope_q, rope_k=rope_k, kv_cache=kv_cache, kv_live=kv_live
        )


    def prefill_chunk_kv(
        self, x_emb: jax.Array, latent_mask: jax.Array
    ) -> Tuple[jax.Array, jax.Array]:
        """Chunked prefill's position-wise half (docs/serving.md "Chunked
        prefill"): the cross-attention KV rows for a chunk of prompt-token
        embeddings, with NO attention — each row is a pure function of its own
        token and position. The norm choice per row reproduces the one-shot
        prefill's concat exactly: prefix positions contribute
        ``kv_norm(x_emb)``, latent-region positions (``latent_mask`` True)
        contribute ``q_norm(x_emb)`` — the query rows re-used as keys in the
        Perceiver AR concat (see ``__call__``'s x_kv construction)."""
        x_kv = jnp.where(latent_mask[..., None], self.q_norm(x_emb), self.kv_norm(x_emb))
        return self.attention.project_kv(x_kv)

    def prefill_latents_paged(
        self,
        x_q: jax.Array,
        k_rows: jax.Array,
        v_rows: jax.Array,
        visible: jax.Array,
        rope_q: Optional[jax.Array] = None,
        rope_k: Optional[jax.Array] = None,
    ) -> jax.Array:
        """Chunked prefill's finish half: the latent queries (raw embeddings —
        q_norm applies here, as in ``__call__``) attend against the slot's
        already-written KV pages under the caller's visibility/causality
        bound. No cache append — the chunk writes already hold every key."""
        x_q = self.q_norm(x_q)
        return self.attention.paged_prefill_attention(
            x_q, k_rows, v_rows, visible, rope_q=rope_q, rope_k=rope_k
        )


class SelfAttention(nn.Module):
    """Pre-layer-norm self-attention (q = k = v = norm(x))."""

    num_heads: int
    num_channels: int
    num_qk_channels: Optional[int] = None
    num_v_channels: Optional[int] = None
    max_heads_parallel: Optional[int] = None
    causal_attention: bool = False
    dropout: float = 0.0
    qkv_bias: bool = True
    fused_qkv: bool = False  # single-GEMM q/k/v (see MultiHeadAttention.fused_qkv)
    out_bias: bool = True
    init_scale: float = 0.02
    seq_axis: Optional[str] = None
    deterministic: bool = True
    dtype: Optional[jnp.dtype] = None
    param_dtype: jnp.dtype = jnp.float32

    def setup(self):
        self.norm = nn.LayerNorm(epsilon=LN_EPS, dtype=self.dtype, param_dtype=self.param_dtype, name="norm")
        self.attention = MultiHeadAttention(
            num_heads=self.num_heads,
            num_q_input_channels=self.num_channels,
            num_kv_input_channels=self.num_channels,
            num_qk_channels=self.num_qk_channels,
            num_v_channels=self.num_v_channels,
            max_heads_parallel=self.max_heads_parallel,
            causal_attention=self.causal_attention,
            dropout=self.dropout,
            qkv_bias=self.qkv_bias,
            fused_qkv=self.fused_qkv,
            out_bias=self.out_bias,
            kernel_init_scale=self.init_scale,
            seq_axis=self.seq_axis,
            deterministic=self.deterministic,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="attention",
        )

    def __call__(
        self,
        x: jax.Array,
        pad_mask: Optional[jax.Array] = None,
        rope_q: Optional[jax.Array] = None,
        rope_k: Optional[jax.Array] = None,
        kv_cache: Optional[KVCache] = None,
    ) -> Tuple[jax.Array, Optional[KVCache]]:
        x = self.norm(x)
        return self.attention(x, x, pad_mask=pad_mask, rope_q=rope_q, rope_k=rope_k, kv_cache=kv_cache)


class CrossAttentionLayer(nn.Module):
    num_heads: int
    num_q_input_channels: int
    num_kv_input_channels: int
    num_qk_channels: Optional[int] = None
    num_v_channels: Optional[int] = None
    max_heads_parallel: Optional[int] = None
    causal_attention: bool = False
    widening_factor: int = 1
    dropout: float = 0.0
    residual_dropout: float = 0.0
    attention_residual: bool = True
    qkv_bias: bool = True
    fused_qkv: bool = False  # single-GEMM q/k/v (see MultiHeadAttention.fused_qkv)
    out_bias: bool = True
    mlp_bias: bool = True
    init_scale: float = 0.02
    seq_axis: Optional[str] = None
    deterministic: bool = True
    dtype: Optional[jnp.dtype] = None
    param_dtype: jnp.dtype = jnp.float32

    def setup(self):
        self.cross_attn = CrossAttention(
            num_heads=self.num_heads,
            num_q_input_channels=self.num_q_input_channels,
            num_kv_input_channels=self.num_kv_input_channels,
            num_qk_channels=self.num_qk_channels,
            num_v_channels=self.num_v_channels,
            max_heads_parallel=self.max_heads_parallel,
            causal_attention=self.causal_attention,
            dropout=self.dropout,
            qkv_bias=self.qkv_bias,
            fused_qkv=self.fused_qkv,
            out_bias=self.out_bias,
            init_scale=self.init_scale,
            seq_axis=self.seq_axis,
            deterministic=self.deterministic,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="cross_attn",
        )
        self.mlp = MLP(
            num_channels=self.num_q_input_channels,
            widening_factor=self.widening_factor,
            bias=self.mlp_bias,
            init_scale=self.init_scale,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="mlp",
        )
        self.res_dropout = nn.Dropout(self.residual_dropout)

    def __call__(
        self,
        x_q: jax.Array,
        x_kv: Optional[jax.Array] = None,
        x_kv_prefix: Optional[jax.Array] = None,
        pad_mask: Optional[jax.Array] = None,
        rope_q: Optional[jax.Array] = None,
        rope_k: Optional[jax.Array] = None,
        kv_cache: Optional[KVCache] = None,
        kv_live: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, Optional[KVCache]]:
        att, kv_cache = self.cross_attn(
            x_q, x_kv=x_kv, x_kv_prefix=x_kv_prefix, pad_mask=pad_mask, rope_q=rope_q, rope_k=rope_k,
            kv_cache=kv_cache, kv_live=kv_live,
        )
        att = self.res_dropout(att, deterministic=self.deterministic)
        x = att + x_q if self.attention_residual else att
        x = x + self.res_dropout(self.mlp(x), deterministic=self.deterministic)
        return x, kv_cache

    def prefill_chunk_kv(self, x_emb: jax.Array, latent_mask: jax.Array):
        """Chunked-prefill KV rows (see ``CrossAttention.prefill_chunk_kv``);
        the layer adds nothing position-wise — residual/MLP act on queries."""
        return self.cross_attn.prefill_chunk_kv(x_emb, latent_mask)

    def prefill_latents_paged(
        self,
        x_q: jax.Array,
        k_rows: jax.Array,
        v_rows: jax.Array,
        visible: jax.Array,
        rope_q=None,
        rope_k=None,
    ) -> jax.Array:
        """Chunked-prefill finish through the full layer: paged attention +
        the same residual/MLP the one-shot prefill applies to its latents."""
        att = self.cross_attn.prefill_latents_paged(
            x_q, k_rows, v_rows, visible, rope_q=rope_q, rope_k=rope_k
        )
        att = self.res_dropout(att, deterministic=self.deterministic)
        x = att + x_q if self.attention_residual else att
        x = x + self.res_dropout(self.mlp(x), deterministic=self.deterministic)
        return x


class SelfAttentionLayer(nn.Module):
    num_heads: int
    num_channels: int
    num_qk_channels: Optional[int] = None
    num_v_channels: Optional[int] = None
    max_heads_parallel: Optional[int] = None
    causal_attention: bool = False
    widening_factor: int = 1
    dropout: float = 0.0
    residual_dropout: float = 0.0
    qkv_bias: bool = True
    fused_qkv: bool = False  # single-GEMM q/k/v (see MultiHeadAttention.fused_qkv)
    out_bias: bool = True
    mlp_bias: bool = True
    init_scale: float = 0.02
    seq_axis: Optional[str] = None
    deterministic: bool = True
    dtype: Optional[jnp.dtype] = None
    param_dtype: jnp.dtype = jnp.float32

    def setup(self):
        self.self_attn = SelfAttention(
            num_heads=self.num_heads,
            num_channels=self.num_channels,
            num_qk_channels=self.num_qk_channels,
            num_v_channels=self.num_v_channels,
            max_heads_parallel=self.max_heads_parallel,
            causal_attention=self.causal_attention,
            dropout=self.dropout,
            qkv_bias=self.qkv_bias,
            fused_qkv=self.fused_qkv,
            out_bias=self.out_bias,
            init_scale=self.init_scale,
            seq_axis=self.seq_axis,
            deterministic=self.deterministic,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="self_attn",
        )
        self.mlp = MLP(
            num_channels=self.num_channels,
            widening_factor=self.widening_factor,
            bias=self.mlp_bias,
            init_scale=self.init_scale,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="mlp",
        )
        self.res_dropout = nn.Dropout(self.residual_dropout)

    def __call__(
        self,
        x: jax.Array,
        rope_gate: Optional[jax.Array] = None,
        kv_cache: Optional[KVCache] = None,
        rope_q: Optional[jax.Array] = None,
        rope_k: Optional[jax.Array] = None,
        pad_mask: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, Optional[KVCache]]:
        # Per-layer rotary gating: multiply angles by a scalar 0/1 flag (zero angle
        # rotation is the identity) — branch-free under nn.scan.
        rq, rk = rope_q, rope_k
        if rope_gate is not None:
            rq = None if rq is None else rq * rope_gate
            rk = None if rk is None else rk * rope_gate
        att, kv_cache = self.self_attn(x, pad_mask=pad_mask, rope_q=rq, rope_k=rk, kv_cache=kv_cache)
        x = x + self.res_dropout(att, deterministic=self.deterministic)
        x = x + self.res_dropout(self.mlp(x), deterministic=self.deterministic)
        return x, kv_cache


class _RingDecodeLayer(SelfAttentionLayer):
    """``SelfAttentionLayer`` as the body of the serving pool's decode loop:
    the stacked ``RingKVCache`` travels in the CARRY beside the activations
    and the layer index is the scanned input, so no layer's cache is sliced
    out of the stacked buffer or written back into it. Same submodules, same
    parameter tree."""

    def __call__(self, carry, rope_gate, layer, rope_q, rope_k):
        x, kv_cache = carry
        x, kv_cache = super().__call__(x, rope_gate, kv_cache.replace(layer=layer), rope_q, rope_k)
        return (x, kv_cache.replace(layer=None)), None


class SelfAttentionBlock(nn.Module):
    """Stack of ``num_layers`` self-attention layers, scanned over a stacked
    parameter axis. ``num_rotary_layers`` leading layers apply RoPE (-1 = all)."""

    num_layers: int
    num_heads: int
    num_channels: int
    num_qk_channels: Optional[int] = None
    num_v_channels: Optional[int] = None
    num_rotary_layers: int = 1
    max_heads_parallel: Optional[int] = None
    causal_attention: bool = False
    widening_factor: int = 1
    dropout: float = 0.0
    residual_dropout: float = 0.0
    activation_checkpointing: bool = False
    remat_policy: Optional[str] = None  # jax.checkpoint_policies name, e.g. "dots_with_no_batch_dims_saveable"
    activation_offloading: bool = False  # stage checkpointed dots to pinned host (see _remat_policy)
    qkv_bias: bool = True
    fused_qkv: bool = False  # single-GEMM q/k/v (see MultiHeadAttention.fused_qkv)
    out_bias: bool = True
    mlp_bias: bool = True
    init_scale: float = 0.02
    seq_axis: Optional[str] = None
    scan_unroll: int = 1  # lax.scan unroll factor for the layer loop; config-
    # dependent: -10% on the 30M config (scan 176.6k vs unroll=8 159.4k tok/s)
    # but +2.9 MFU points on the 455M flagship at full unroll (NOTES.md)
    # GPipe pipeline parallelism over this mesh axis: the stacked layer params
    # shard over it and microbatches flow stage-to-stage (parallel/pipeline.py).
    # Pure execution knob — params/checkpoints unchanged; decode (kv_cache)
    # paths fall back to the scanned loop.
    pipeline_axis: Optional[str] = None
    pipeline_microbatches: Optional[int] = None  # default = stage count
    deterministic: bool = True
    dtype: Optional[jnp.dtype] = None
    param_dtype: jnp.dtype = jnp.float32

    @property
    def resolved_num_qk_channels(self) -> int:
        return self.num_qk_channels if self.num_qk_channels is not None else self.num_channels

    @property
    def resolved_num_v_channels(self) -> int:
        return self.num_v_channels if self.num_v_channels is not None else self.resolved_num_qk_channels

    def empty_kv_cache(self, batch_size: int, capacity: int, dtype=jnp.float32) -> KVCache:
        """Stacked per-layer cache (reference per-layer empty_kv_cache factory,
        modules.py:282-285). Built from constructor fields only — usable unbound."""
        return KVCache.create_stacked(
            self.num_layers, batch_size, capacity, self.resolved_num_qk_channels, self.resolved_num_v_channels, dtype
        )

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        pad_mask: Optional[jax.Array] = None,
        rope_q: Optional[jax.Array] = None,
        rope_k: Optional[jax.Array] = None,
        kv_cache: Optional[KVCache] = None,
    ) -> Tuple[jax.Array, Optional[KVCache]]:
        idx = np.arange(self.num_layers)
        use_rope = (idx < self.num_rotary_layers) | (self.num_rotary_layers == -1)
        rope_gates = jnp.asarray(use_rope, dtype=jnp.float32)

        policy = _remat_policy(self.remat_policy, self.activation_checkpointing, self.activation_offloading)

        if self.pipeline_axis is not None and kv_cache is None and not self.is_initializing():
            from perceiver_io_tpu.parallel.pipeline import pipeline_mesh_plan

            plan = pipeline_mesh_plan(self.pipeline_axis)
            if plan is not None:
                return self._pipelined(plan, x, rope_gates, rope_q, rope_k, pad_mask, policy)

        scan = functools.partial(
            nn.scan,
            variable_axes={"params": 0},
            split_rngs={"params": True, "dropout": True},
            out_axes=0,
            length=self.num_layers,
            unroll=max(1, min(self.scan_unroll, self.num_layers)),
            metadata_params={nn.PARTITION_NAME: "layers"},
        )
        if isinstance(kv_cache, RingKVCache):
            # the paged pool's decode step: the stacked ring is a carry, written
            # in place one row a slot a layer (see _RingDecodeLayer)
            scanned = scan(_RingDecodeLayer, in_axes=(0, 0, nn.broadcast, nn.broadcast))(
                **self._layer_kwargs(), name="layers"
            )
            (x, kv_cache), _ = scanned((x, kv_cache), rope_gates, jnp.asarray(idx, jnp.int32), rope_q, rope_k)
            return x, kv_cache.advance()

        layer_cls = SelfAttentionLayer
        if self.activation_checkpointing:
            layer_cls = nn.remat(layer_cls, policy=policy)

        scanned = scan(layer_cls, in_axes=(0, 0, nn.broadcast, nn.broadcast, nn.broadcast))(
            **self._layer_kwargs(), name="layers"
        )
        return scanned(x, rope_gates, kv_cache, rope_q, rope_k, pad_mask)

    def _layer_kwargs(self, **overrides):
        """The single source of SelfAttentionLayer construction kwargs — shared
        by the scanned path and the pipeline path so the two cannot drift (a
        field present in one but not the other would silently change numerics
        between the execution modes)."""
        kwargs = dict(
            num_heads=self.num_heads,
            num_channels=self.num_channels,
            num_qk_channels=self.num_qk_channels,
            num_v_channels=self.num_v_channels,
            max_heads_parallel=self.max_heads_parallel,
            causal_attention=self.causal_attention,
            widening_factor=self.widening_factor,
            dropout=self.dropout,
            residual_dropout=self.residual_dropout,
            qkv_bias=self.qkv_bias,
            fused_qkv=self.fused_qkv,
            out_bias=self.out_bias,
            mlp_bias=self.mlp_bias,
            init_scale=self.init_scale,
            seq_axis=self.seq_axis,
            deterministic=self.deterministic,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
        )
        kwargs.update(overrides)
        return kwargs

    def _pipelined(self, plan, x, rope_gates, rope_q, rope_k, pad_mask, policy):
        """GPipe path: apply the already-initialized stacked layer params as a
        pure function inside the pipeline shard_map (parallel/pipeline.py). The
        scanned module above creates/owns the params (init and every
        non-pipelined apply); this path only READS them, so checkpoints and the
        param tree are identical with the knob on or off."""
        from perceiver_io_tpu.parallel.pipeline import pipeline_layer_stack

        num_stages, batch_axes = plan
        stacked = self.get_variable("params", "layers")

        needs_rng = (self.dropout > 0.0 or self.residual_dropout > 0.0) and not self.deterministic
        keys = jax.random.split(self.make_rng("dropout"), self.num_layers) if needs_rng else None

        b = x.shape[0]
        present = tuple(a is not None for a in (rope_q, rope_k, pad_mask))
        extra = tuple(
            a if a.shape[0] == b else jnp.broadcast_to(a, (b, *a.shape[1:]))
            for a in (rope_q, rope_k, pad_mask)
            if a is not None
        )

        # seq_axis off inside the pipeline shard (pipeline_mesh_plan rejects
        # meshes with a >1 seq axis; a leftover config value must not trigger
        # ring-attention mesh validation inside the stage computation)
        layer = SelfAttentionLayer(**self._layer_kwargs(seq_axis=None))

        def layer_apply(p, rng, h, gate, *ex):
            it = iter(ex)
            rq, rk, pm = (next(it) if have else None for have in present)
            rngs = None if rng is None else {"dropout": rng}
            out, _ = layer.apply({"params": p}, h, gate, None, rq, rk, pm, rngs=rngs)
            return out

        y = pipeline_layer_stack(
            layer_apply,
            stacked,
            x,
            rope_gates,
            keys,
            num_stages=num_stages,
            batch_axes=batch_axes,
            pipe_axis=self.pipeline_axis,
            num_microbatches=self.pipeline_microbatches,
            remat=self.activation_checkpointing,
            remat_policy=policy,
            extra=extra,
        )
        return y, None


class PerceiverEncoder(nn.Module):
    """Generic Perceiver IO encoder: a trainable latent array cross-attends to the
    adapted input, followed by self-attention blocks; optionally repeated
    cross-attention with weight sharing (Perceiver-classic mode)."""

    input_adapter: InputAdapter
    num_latents: int
    num_latent_channels: int
    num_cross_attention_heads: int = 4
    num_cross_attention_qk_channels: Optional[int] = None
    num_cross_attention_v_channels: Optional[int] = None
    num_cross_attention_layers: int = 1
    first_cross_attention_layer_shared: bool = False
    cross_attention_widening_factor: int = 1
    num_self_attention_heads: int = 4
    num_self_attention_qk_channels: Optional[int] = None
    num_self_attention_v_channels: Optional[int] = None
    num_self_attention_layers_per_block: int = 6
    num_self_attention_blocks: int = 1
    first_self_attention_block_shared: bool = True
    self_attention_widening_factor: int = 1
    dropout: float = 0.0
    residual_dropout: float = 0.0
    init_scale: float = 0.02
    activation_checkpointing: bool = False
    remat_policy: Optional[str] = None  # jax.checkpoint_policies name (None = full remat)
    activation_offloading: bool = False  # stage checkpointed dots to pinned host (see _remat_policy)
    scan_unroll: int = 1  # SA-block layer-loop unroll (see EncoderConfig.scan_unroll)
    deterministic: bool = True
    dtype: Optional[jnp.dtype] = None
    param_dtype: jnp.dtype = jnp.float32

    @property
    def extra_cross_attention_layer(self) -> bool:
        return self.num_cross_attention_layers > 1 and not self.first_cross_attention_layer_shared

    @property
    def extra_self_attention_block(self) -> bool:
        return self.num_self_attention_blocks > 1 and not self.first_self_attention_block_shared

    def setup(self):
        if self.num_cross_attention_layers <= 0:
            raise ValueError("num_cross_attention_layers must be > 0")
        if self.num_self_attention_blocks <= 0:
            raise ValueError("num_self_attention_blocks must be > 0")
        if self.num_cross_attention_layers > self.num_self_attention_blocks:
            raise ValueError("num_cross_attention_layers must be <= num_self_attention_blocks")

        self.latent_provider = TrainableQueryProvider(
            num_queries=self.num_latents,
            num_query_channels_=self.num_latent_channels,
            init_scale=self.init_scale,
            param_dtype=self.param_dtype,
            name="latent_provider",
        )

        def cross_attn(name):
            layer_cls = CrossAttentionLayer
            if self.activation_checkpointing:
                layer_cls = nn.remat(
                    layer_cls, policy=_remat_policy(self.remat_policy, True, self.activation_offloading)
                )
            return layer_cls(
                num_heads=self.num_cross_attention_heads,
                num_q_input_channels=self.num_latent_channels,
                num_kv_input_channels=self.input_adapter.num_input_channels,
                num_qk_channels=self.num_cross_attention_qk_channels,
                num_v_channels=self.num_cross_attention_v_channels,
                widening_factor=self.cross_attention_widening_factor,
                dropout=self.dropout,
                residual_dropout=self.residual_dropout,
                init_scale=self.init_scale,
                deterministic=self.deterministic,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                name=name,
            )

        def self_attn(name):
            return SelfAttentionBlock(
                num_layers=self.num_self_attention_layers_per_block,
                num_heads=self.num_self_attention_heads,
                num_channels=self.num_latent_channels,
                num_qk_channels=self.num_self_attention_qk_channels,
                num_v_channels=self.num_self_attention_v_channels,
                num_rotary_layers=0,
                widening_factor=self.self_attention_widening_factor,
                dropout=self.dropout,
                residual_dropout=self.residual_dropout,
                activation_checkpointing=self.activation_checkpointing,
                remat_policy=self.remat_policy,
                activation_offloading=self.activation_offloading,
                scan_unroll=self.scan_unroll,
                init_scale=self.init_scale,
                deterministic=self.deterministic,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                name=name,
            )

        self.cross_attn_1 = cross_attn("cross_attn_1")
        self.self_attn_1 = self_attn("self_attn_1")
        if self.extra_cross_attention_layer:
            self.cross_attn_n = cross_attn("cross_attn_n")
        if self.extra_self_attention_block:
            self.self_attn_n = self_attn("self_attn_n")

    def attend(self, x: jax.Array) -> jax.Array:
        """Tied-embedding readout via the input adapter (token adapters only)."""
        return self.input_adapter.attend(x)

    def __call__(self, x: jax.Array, pad_mask: Optional[jax.Array] = None, return_adapted_input: bool = False):
        b = x.shape[0]
        x_adapted = self.input_adapter(x)
        x_latent = jnp.broadcast_to(
            self.latent_provider(), (b, self.num_latents, self.num_latent_channels)
        ).astype(x_adapted.dtype)

        x_latent, _ = self.cross_attn_1(x_latent, x_kv=x_adapted, pad_mask=pad_mask)
        x_latent, _ = self.self_attn_1(x_latent)

        cross_attn_n = self.cross_attn_n if self.extra_cross_attention_layer else self.cross_attn_1
        self_attn_n = self.self_attn_n if self.extra_self_attention_block else self.self_attn_1

        for i in range(1, self.num_self_attention_blocks):
            if i < self.num_cross_attention_layers:
                x_latent, _ = cross_attn_n(x_latent, x_kv=x_adapted, pad_mask=pad_mask)
            x_latent, _ = self_attn_n(x_latent)

        if return_adapted_input:
            return x_latent, x_adapted
        return x_latent


class PerceiverDecoder(nn.Module):
    """Generic Perceiver IO decoder: an output query cross-attends to the latents;
    the output adapter maps the result to task-specific output."""

    output_adapter: nn.Module
    output_query_provider: nn.Module
    num_latent_channels: int
    num_cross_attention_heads: int = 4
    num_cross_attention_qk_channels: Optional[int] = None
    num_cross_attention_v_channels: Optional[int] = None
    cross_attention_widening_factor: int = 1
    cross_attention_residual: bool = True
    dropout: float = 0.0
    init_scale: float = 0.02
    activation_checkpointing: bool = False
    remat_policy: Optional[str] = None  # jax.checkpoint_policies name (None = full remat)
    activation_offloading: bool = False  # stage checkpointed dots to pinned host (see _remat_policy)
    deterministic: bool = True
    dtype: Optional[jnp.dtype] = None
    param_dtype: jnp.dtype = jnp.float32

    def setup(self):
        policy = _remat_policy(self.remat_policy, self.activation_checkpointing, self.activation_offloading)
        layer_cls = CrossAttentionLayer
        if self.activation_checkpointing:
            layer_cls = nn.remat(layer_cls, policy=policy)
        self.cross_attn = layer_cls(
            num_heads=self.num_cross_attention_heads,
            num_q_input_channels=self.output_query_provider.num_query_channels,
            num_kv_input_channels=self.num_latent_channels,
            num_qk_channels=self.num_cross_attention_qk_channels,
            num_v_channels=self.num_cross_attention_v_channels,
            widening_factor=self.cross_attention_widening_factor,
            attention_residual=self.cross_attention_residual,
            dropout=self.dropout,
            init_scale=self.init_scale,
            deterministic=self.deterministic,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="cross_attn",
        )

    def __call__(self, x_latent: jax.Array, x_adapted: Optional[jax.Array] = None, **kwargs):
        output_query = self.output_query_provider(x_adapted)
        if output_query.shape[0] == 1 and x_latent.shape[0] != 1:
            output_query = jnp.broadcast_to(output_query, (x_latent.shape[0], *output_query.shape[1:]))
        output_query = output_query.astype(x_latent.dtype)
        output, _ = self.cross_attn(output_query, x_kv=x_latent)
        return self.output_adapter(output, **kwargs)


class PerceiverIO(nn.Module):
    encoder: PerceiverEncoder
    decoder: PerceiverDecoder

    def __call__(self, x: jax.Array, pad_mask: Optional[jax.Array] = None, **kwargs):
        x_latent = self.encoder(x, pad_mask=pad_mask)
        return self.decoder(x_latent, **kwargs)
