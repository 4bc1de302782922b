"""Flash (splash) attention fast path for TPU.

The hot attention shapes in this framework are skewed: Perceiver AR's prefix
cross-attention attends 512 latent queries to up to ~8k keys under a
right-aligned causal mask (SURVEY.md §7 'hard parts') — neither standard
flash-causal nor full-bidirectional. Pallas splash attention expresses exactly
this as ``CausalMask((Nq, Nk), offset=Nk-Nq)`` and provides fused forward and
backward kernels, replacing the O(Nq*Nk) materialized attention matrix (the
reference's torch einsum, modules.py:151-163) with O(block) VMEM traffic.

Padding is expressed through segment ids (pad kv tokens get segment 0, real
tokens 1; all queries are real in the paths that use this — Perceiver AR latents
are the sequence suffix).

Multi-chip: the pallas call is not auto-partitioned by XLA SPMD, so under an
active mesh (``jax.sharding.set_mesh``) the kernel runs inside ``shard_map``
over the batch (``data``/``fsdp``) and head (``tensor``) axes — each device runs
splash on its local shard with no extra communication. Meshes with other
sharded axes (e.g. ``seq``) fall back to the XLA formulation (the model's
ring-attention path owns sequence parallelism). CPU test runs fall back via
``flash_supported`` (or use interpret mode explicitly).
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp

# candidate tile sizes, largest first. 512-wide blocks measured +0.9 MFU
# points on the 455M flagship (head dim 128) and +16% optical-flow fps (head
# dim 64 but 2048-long self-attention), yet -8% on the 30M config (head dim
# 64, 512-long sequences, where one 512 tile covers the whole axis), so they
# are offered when the head is wide OR the sequences are long; smaller sizes
# keep shapes like the optical-flow decoder's 182,528 queries (divisible by
# 256, not 512) on the fused path (NOTES.md)
_BLOCKS_WIDE = (512, 256, 128)  # head_dim >= 128 or min seq >= 1024
_BLOCKS_NARROW = (256, 128)
_DISABLE_ENV = "PERCEIVER_IO_TPU_DISABLE_FLASH"
_BATCH_AXES = ("data", "fsdp")
_HEAD_AXIS = "tensor"


def single_device_trace() -> bool:
    """Is the program being traced a one-device program? With no ambient mesh
    (or one whose axes all have size 1) a jitted function runs on the single
    device its arrays live on, however many devices the process can see — a
    one-chip engine or train step on a four-chip host included. The repo's
    sharded paths all trace under ``jax.sharding.set_mesh`` (parallel/api.py)."""
    return all(size == 1 for size in jax.sharding.get_abstract_mesh().shape.values())


def _mesh_plan():
    """(batch_axes, head_axis_or_None, b_shards, h_shards): the one-device
    plan ``((), None, 1, 1)`` for a one-device trace, the mapping when the
    ambient mesh's sharded axes are all batch/head-mappable, None otherwise
    (axes like 'seq' that this wrapper cannot map)."""
    import numpy as np

    if single_device_trace():
        return ((), None, 1, 1)
    sizes = dict(jax.sharding.get_abstract_mesh().shape)
    for name, size in sizes.items():
        if size > 1 and name not in (*_BATCH_AXES, _HEAD_AXIS):
            return None
    baxes = tuple(a for a in _BATCH_AXES if sizes.get(a, 1) > 1)
    head = _HEAD_AXIS if sizes.get(_HEAD_AXIS, 1) > 1 else None
    b_shards = int(np.prod([sizes[a] for a in baxes])) if baxes else 1
    h_shards = sizes.get(head, 1) if head else 1
    return (baxes, head, b_shards, h_shards)


def flash_supported(
    num_qk_channels_per_head: int,
    num_v_channels_per_head: int,
    n_q: int,
    n_k: int,
    has_dropout: bool,
    has_cache: bool,
    batch_size: Optional[int] = None,
    num_heads: Optional[int] = None,
) -> bool:
    """Static predicate: can the splash kernel serve this attention call?"""
    if os.environ.get(_DISABLE_ENV, "").lower() not in ("", "0", "false"):
        return False
    if has_dropout or has_cache:
        return False
    if jax.default_backend() != "tpu":
        return False
    plan = _mesh_plan()
    if plan is None:
        # a sharded trace needs the shard_map wrapper, which needs an ambient
        # mesh whose axes we know how to map (batch/head); else fall back
        return False
    _, _, b_shards, h_shards = plan
    if b_shards > 1 or h_shards > 1:
        if batch_size is None or num_heads is None:
            return False  # without shapes we cannot certify divisibility on a mesh
        if batch_size % b_shards != 0 or num_heads % h_shards != 0:
            return False
    if num_qk_channels_per_head != num_v_channels_per_head:
        return False  # splash assumes one head_dim for q/k/v
    if num_qk_channels_per_head % 64 != 0:
        return False
    return _pick_block(n_q, n_k, num_qk_channels_per_head) is not None and n_q >= 128 and n_k >= 128


def _pick_block(n_q: int, n_k: int, head_dim: int):
    """Largest candidate tile dividing both sequence lengths (None = no fit).

    Deliberately restricted to power-of-two candidates: the previous
    ``min(256, n_q, n_k)`` rule would hand shapes like 192 (or any n in
    [128, 256)) to Mosaic as the tile size itself, which is neither
    lane-aligned nor ever validated — such shapes now take the XLA path."""
    wide = head_dim >= 128 or min(n_q, n_k) >= 1024
    for block in _BLOCKS_WIDE if wide else _BLOCKS_NARROW:
        if n_q % block == 0 and n_k % block == 0:
            return block
    return None


@functools.lru_cache(maxsize=64)
def _kernel(num_heads: int, n_q: int, n_k: int, block: int, causal: bool, interpret: bool,
            save_residuals: bool = False):
    import jax.experimental.pallas.ops.tpu.splash_attention as sa

    # This is usually reached inside a jit trace; mask-info preprocessing must
    # produce concrete arrays (they get cached), not tracers.
    with jax.ensure_compile_time_eval():
        return _build_kernel(sa, num_heads, n_q, n_k, block, causal, interpret, save_residuals)


def _resolve_block(n_q: int, n_k: int, head_dim: int) -> int:
    block = _pick_block(n_q, n_k, head_dim)
    if block is None:
        raise ValueError(
            f"no splash tile size fits (n_q={n_q}, n_k={n_k}); "
            "sequence lengths must be divisible by 128 — gate calls with flash_supported()"
        )
    return block


def _build_kernel(sa, num_heads: int, n_q: int, n_k: int, block: int, causal: bool, interpret: bool,
                  save_residuals: bool = False):
    if causal:
        # right-aligned causal: query row i sees keys 0..(n_k - n_q + i)
        head_mask = sa.CausalMask((n_q, n_k), offset=n_k - n_q)
    else:
        head_mask = sa.FullMask((n_q, n_k))
    mask = sa.MultiHeadMask([head_mask for _ in range(num_heads)])
    bs = sa.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        block_q_dq=block, block_kv_dq=block,
    )
    # save_residuals returns (out, (logsumexp,)) — the ring-attention merge
    # needs the block logsumexp; that path wraps the call in its own custom-VJP
    # (splash's residuals output is forward-only).
    return sa.make_splash_mha(
        mask, head_shards=1, q_seq_shards=1, block_sizes=bs,
        save_residuals=save_residuals, interpret=interpret,
    )


def splash_mha(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    pad_mask: Optional[jax.Array] = None,
    causal: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """q (B, H, Nq, D) [pre-scaled, pre-rotated], k/v (B, H, Nk, D),
    pad_mask (B, Nk) True=padding. Returns (B, H, Nq, D)."""
    import jax.experimental.pallas.ops.tpu.splash_attention as sa

    b, h, n_q, _ = q.shape
    n_k = k.shape[2]

    plan = _mesh_plan()
    if plan is not None and (plan[0] or plan[1]):
        return _splash_mha_sharded(q, k, v, pad_mask, causal, interpret, plan)

    kernel = _kernel(h, n_q, n_k, _resolve_block(n_q, n_k, q.shape[-1]), causal, interpret)
    if pad_mask is None:
        return jax.vmap(kernel)(q, k, v)

    seg_q = jnp.ones((b, n_q), jnp.int32)
    seg_kv = jnp.where(pad_mask, 0, 1).astype(jnp.int32)
    return jax.vmap(lambda q, k, v, sq, skv: kernel(q, k, v, segment_ids=sa.SegmentIds(sq, skv)))(
        q, k, v, seg_q, seg_kv
    )


def _splash_mha_sharded(q, k, v, pad_mask, causal, interpret, plan):
    """Run splash per-device inside shard_map: batch sharded over data/fsdp,
    heads over tensor — embarrassingly parallel, no collectives."""
    import jax.experimental.pallas.ops.tpu.splash_attention as sa
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    baxes, head_axis, b_shards, h_shards = plan
    b, h, n_q, _ = q.shape
    n_k = k.shape[2]
    if b % b_shards or h % h_shards:
        raise ValueError(  # flash_supported should have routed this away
            f"splash shard_map needs batch {b} % {b_shards} == 0 and heads {h} % {h_shards} == 0"
        )
    kernel = _kernel(h // h_shards, n_q, n_k, _resolve_block(n_q, n_k, q.shape[-1]), causal, interpret)

    bspec = baxes if baxes else None
    qkv_spec = P(bspec, head_axis, None, None)
    pad_spec = P(bspec, None)

    if pad_mask is None:
        fn = shard_map(
            lambda q, k, v: jax.vmap(kernel)(q, k, v),
            in_specs=(qkv_spec, qkv_spec, qkv_spec),
            out_specs=qkv_spec,
            check_vma=False,
        )
        return fn(q, k, v)

    def local(q, k, v, pad):
        seg_q = jnp.ones((q.shape[0], n_q), jnp.int32)
        seg_kv = jnp.where(pad, 0, 1).astype(jnp.int32)
        return jax.vmap(lambda q, k, v, sq, skv: kernel(q, k, v, segment_ids=sa.SegmentIds(sq, skv)))(
            q, k, v, seg_q, seg_kv
        )

    fn = shard_map(
        local,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, pad_spec),
        out_specs=qkv_spec,
        check_vma=False,
    )
    return fn(q, k, v, pad_mask)
