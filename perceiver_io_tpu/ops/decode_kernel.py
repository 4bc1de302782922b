"""Pallas fused cached-decode attention (single query over a KV ring cache).

The XLA formulation of the scan-decode hot loop's per-layer attention
materializes a rotated copy of the ENTIRE cached key buffer every token (the
reference's torch design re-rotates the cache each forward, core
modules.py:126-130), then runs masked softmax-attention over it — several full
HBM round trips per token per layer. This kernel streams the caches once: per
KV block it computes masked scores of the rotated keys against the query and
merges into flash-style running (max, sum, accumulator) scratch — no rotated-K
materialization, no (1, cap) score tensor in HBM.

Where the rotation is applied: on the QUERY side. The cached keys are stored
unrotated and stay so inside the kernel; for k_rot = k*cos + rotate_half(k)*sin
the score is k_rot . q = (k*cos) . q + (k*sin) . q_hat with q_hat the
rotate-half of the query ([q1, q2] -> [q2, -q1] per rotary pair), because the
angles are equal within a pair. q_hat is built once per query outside the
kernel (``_blockdiag_queries``); a block costs two elementwise products and two
thin score matmuls (``_rotary_scores``). Rotating the keys in the kernel needs
either a pair swizzle along the lanes, which Mosaic cannot lower, or a matmul
against an (h*d, h*d) rotate-half constant, which at width 1280 was four fifths
of the kernel's matrix-unit work and 6.5 MB of its VMEM (PERF.md, PR 29). The
paged and ragged kernels (ops/paged_decode_kernel.py,
ops/ragged_paged_kernel.py) share both helpers.

Forward-only (decode is inference); the training paths use the splash kernel.
Masking: slot j is visible iff j <= q_pos (the ring cache's left-aligned
validity+causality in one bound, ops/attention.py cached branch) and not a pad
slot.

SURVEY.md §7 construction item 9 ("fused cached-decode attention").
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp

# KV block candidates, largest first, and the VMEM the kernel may plan for.
# The kernel's scoped VMEM at block ``blk`` and packed width ``hd = h*d`` was
# measured by compiling for a v5e without one (binary search on
# ``vmem_limit_bytes``; PERF.md, PR 29): per block element the double-buffered
# K and V blocks (4 x itemsize) and, over n_q 1-8, widths 512-1280 and batches
# 1-64, at most 7.6 bytes of f32 temporaries — 6.9 MiB at (512, 1280) with a
# bf16 cache and one query, 10.2 MiB with eight; 3.9 MiB at (512, 512). Since
# the rotation moved to the query side no (hd, hd) constant is held. The budget
# leaves the rest of the 16 MiB scoped default to what XLA itself parks in
# VMEM around the call (batch and capacity variants moved the measured need
# by up to 5 MiB).
_BLOCKS = (512, 256, 128)
_VMEM_BUDGET = 12 * 2**20


def _vmem_estimate(blk: int, hd: int, itemsize: int) -> int:
    return blk * hd * (4 * itemsize + 8) + 2**19


def _kv_block(capacity: int, hd: int, itemsize: int) -> Optional[int]:
    """Largest KV block that tiles ``capacity`` and fits the VMEM budget;
    None when even the smallest does not (the XLA formulation serves)."""
    for blk in _BLOCKS:
        blk = min(blk, capacity)
        if capacity % blk == 0 and _vmem_estimate(blk, hd, itemsize) <= _VMEM_BUDGET:
            return blk
    return None


def ragged_decode_enabled() -> bool:
    """Kill-switch for ragged (live-length-aware) decode masking/skipping:
    PERCEIVER_IO_TPU_DISABLE_RAGGED_DECODE=1 makes per-row live lengths fall
    back to the full valid length (pad masking alone — the pre-ragged
    behavior). Checked at trace time, like the kernel kill-switch."""
    return os.environ.get("PERCEIVER_IO_TPU_DISABLE_RAGGED_DECODE", "0").lower() in ("0", "false", "")


def decode_kernel_supported(
    n_q: int, capacity: int, num_qk: int, num_v: int, num_heads: int = 1,
    batch_size: Optional[int] = None, itemsize: int = 2,
) -> bool:
    """Short-query cached decode on TPU with symmetric qk/v widths and a
    block-tileable cache whose KV block fits the VMEM budget (``_kv_block``;
    ``itemsize`` is the cache dtype's). ``n_q > 1`` covers multi-query decode
    (speculative / chunked verification); each query keeps its flash stats in
    its own scratch row, so n_q is bounded by the 8-sublane scratch tile.
    Sharded traces: supported when the ambient mesh shards only batch axes and
    the batch divides evenly (the kernel then runs per-device inside shard_map
    — no collectives). Kill-switch: PERCEIVER_IO_TPU_DISABLE_DECODE_KERNEL."""
    if os.environ.get("PERCEIVER_IO_TPU_DISABLE_DECODE_KERNEL", "0").lower() not in ("0", "false", ""):
        return False
    if jax.default_backend() != "tpu":
        return False
    from perceiver_io_tpu.ops.flash import _mesh_plan

    plan = _mesh_plan()
    if plan is None:
        return False
    _, head_axis, b_shards, _ = plan
    if head_axis is not None:
        # heads live packed inside the (cap, h*d) cache layout; a sharded
        # head axis cannot be mapped onto this kernel
        return False
    if b_shards > 1 and (batch_size is None or batch_size % b_shards != 0):
        return False
    return (
        1 <= n_q <= 8  # one (8, 128) scratch sublane of running stats per query
        and num_qk == num_v
        and num_heads <= 128  # per-head stats live in one (8, 128) scratch row
        and capacity >= 128
        and capacity % 8 == 0  # sublane-aligned KV blocks
        and _kv_block(capacity, num_qk, itemsize) is not None
    )


def _blockdiag_queries(q: jax.Array, r: int) -> jax.Array:
    """(B, H, n_q, D) scaled+rotated queries -> (B, 2, H*D, n_q*H), both planes
    block-diagonal (column qi*H+head holds query qi's head slice in rows
    [head*D, (head+1)*D), zeros elsewhere): plane 0 carries q, plane 1 carries
    q_hat, the rotate-half of q on each head's ``r`` rotary dims (per pair
    [q1, q2] -> [q2, -q1]) and zero on the rest. ``_rotary_scores`` says why."""
    from perceiver_io_tpu.ops.position import rotate_half

    b, h, n_q, d = q.shape
    # rotate_half is [q1, q2] -> [-q2, q1], the map applied to the KEYS; its
    # transpose, which lands on the query, is the negative
    q_hat = jnp.pad(-rotate_half(q[..., :r]), ((0, 0), (0, 0), (0, 0), (0, d - r)))
    planes = jnp.stack([q, q_hat], axis=1).transpose(0, 1, 2, 4, 3)  # (B, 2, H, D, n_q)
    eye = jnp.eye(h, dtype=q.dtype)
    return (planes[..., None] * eye[:, None, None, :]).reshape(b, 2, h * d, n_q * h)


def _rotary_scores(k: jax.Array, ang: jax.Array, qq_ref, h: int) -> jax.Array:
    """Scores of the ROTATED keys against every query, (blk, n_q*h), from the
    unrotated f32 keys ``k`` (blk, h*d), their angles ``ang`` (blk, r;
    pairwise-repeated) and the two query planes of ``_blockdiag_queries``.

    The rotation is moved off the keys. With M the rotate-half map,
    k_rot = k*cos + (k M)*sin, and sin is equal within a rotary pair, so it
    commutes with M:  k_rot . q = (k*cos) . q + (k*sin) . (M q).  M q = q_hat
    is built once per query outside the kernel; a block then needs two
    elementwise products and two thin score matmuls. No pair swizzle along the
    lanes (which Mosaic cannot lower) and no (h*d, h*d) rotate-half matmul
    (which was four fifths of the kernel's matrix-unit work at width 1280)."""
    blk, hd = k.shape
    d = hd // h
    r = ang.shape[1]
    contract = (((1,), (0,)), ((), ()))
    # tile the angles' cos / sin across heads -> per-channel (blk, h*d); off the
    # rotary dims the keys pass unrotated (cos 1) and q_hat is zero (sin 0)
    cos_fill = [jnp.ones((blk, d - r), jnp.float32)] if d > r else []
    sin_fill = [jnp.zeros((blk, d - r), jnp.float32)] if d > r else []
    cos = jnp.concatenate(([jnp.cos(ang)] + cos_fill) * h, -1)
    sin = jnp.concatenate(([jnp.sin(ang)] + sin_fill) * h, -1)
    return (
        jax.lax.dot_general(k * cos, qq_ref[0], contract, preferred_element_type=jnp.float32)
        + jax.lax.dot_general(k * sin, qq_ref[1], contract, preferred_element_type=jnp.float32)
    )


def _head_expander(h: int, d: int):
    """Constant (h, h*d) matrix E with (p @ E)[:, head*d + j] == p[:, head] —
    lane-expands per-head scalars to per-channel without vector broadcasts."""
    import numpy as np

    return np.kron(np.eye(h, dtype=np.float32), np.ones((1, d), np.float32))


def _fetch_rows(live: jax.Array) -> jax.Array:
    """(B,) int32: for each row, the row whose blocks its grid steps hold — the
    row itself where ``live > 0``; for a row that reads nothing (``live == 0``)
    the NEXT row that reads, so that the pipeline fetches that row's first
    block while the reading row before is still being computed (resting on the
    row before instead leaves the fetch to the one short idle step ahead of the
    next reading row, where nothing hides it: measured, PERF.md 6, PR 37);
    past the last reading row, that last one (row 0 when no row reads at all)."""
    reads = live > 0
    rows = jnp.arange(live.shape[0])
    ahead = jax.lax.cummin(jnp.where(reads, rows, live.shape[0]), reverse=True)
    behind = jax.lax.cummax(jnp.where(reads, rows, 0))
    return jnp.where(ahead < live.shape[0], ahead, behind).astype(jnp.int32)


def _step_block(bi, i, qpos, live, fetch_row, nblocks: int, blk: int):
    """(row, KV block) whose keys, values, angles and pad mask grid step
    ``(bi, i)`` holds (the query planes follow the row). The pipeline fetches
    an operand's block only where its index differs from the step before, so
    what a step names here is what it pays for:

    * a row with ``live > 0`` names its own blocks, a dead head block (entirely
      below the live tail) aliasing the first live one: bytes follow the live
      entries;
    * a row with ``live == 0`` names ONE block, whatever ``i`` is: the first
      live block of the next reading row (fetched once, ahead of time, and
      found in place by that row's own first step), or, past the last reading
      row, that row's LAST block (what the step before held). Its steps add no
      bytes to what the reading rows move.

    Counted by walking the grid on the CPU (tests/test_decode_kernel.py)."""
    row = fetch_row[bi]
    first = jnp.clip((qpos[row] + 1 - live[row]) // blk, 0, nblocks - 1)  # first live block of ``row``
    return row, jnp.where(row == bi, jnp.maximum(i, first), jnp.where(row < bi, nblocks - 1, first))


def _kernel(qpos_ref, live_ref, fetch_ref, layer_ref, qq_ref, k_ref, v_ref, ang_ref, pad_ref, exp_ref, o_ref, m_ref, l_ref, acc_ref):
    """Grid (B, num_blocks); block i covers cache slots [i*blk, (i+1)*blk).

    qpos_ref (B,)            absolute position of the LAST query (scalar-prefetch, SMEM)
    live_ref (B,)            live (non-pad) entries per row; the live region is the
                             TAIL [qpos+1-live, qpos+1) of the valid slots. Blocks
                             entirely below it are dead: their grid steps alias the
                             first live block in the index maps (no new DMA) and
                             skip all compute — the ragged length-aware early exit.
                             A row with ``live == 0`` reads NOTHING: its steps name
                             a block some reading row needs anyway (``_step_block``),
                             run no block, and its output row is stored as zeros — the
                             serving pool's free and half-prefilled slots cost the
                             kernel neither bytes nor compute.
    fetch_ref (B,)           ``_fetch_rows(live)``; read by the index maps alone
    layer_ref (1,)           which layer of a stacked cache the K/V blocks come
                             from (scalar-prefetch); read by the K/V index map alone
    qq_ref   (2, h*d, n_q*h) block-diagonal scaled+rotated queries q and their
                             rotate-half q_hat (``_blockdiag_queries``)
    k_ref    (1, blk, h*d)   unrotated keys; they stay unrotated: the rotation
                             is applied on the query side (``_rotary_scores``)
    v_ref    (1, blk, h*d)   values
    ang_ref  (1, blk, r)     rotary angles per slot (pairwise-repeated)
    pad_ref  (1, blk, 1)     pad-slot mask (int8, 1 = pad)
    exp_ref  (h, h*d)        head->channel expander
    o_ref    (1, n_q, h*d)   output
    scratch: m, l (8, 128) VMEM (query qi's per-head stats in row qi), acc (8, h*d)
                             (query qi's output accumulator in row qi)

    Everything is a full-width 2D op: the score contractions are (blk, h*d)
    matmuls covering all heads and all queries (MXU-shaped, no per-head
    slicing), and softmax stats live in (1, h) rows that broadcast over
    sublanes — the orientations Mosaic lowers natively. The per-query loop is a
    trace-time Python unroll over static scratch rows (n_q <= 8).

    Skipping dead blocks is exact: an all-masked block contributes prob = 0 and
    rescales m/l/acc by exp(0) = 1, so omitting it leaves the flash state
    bit-identical (tests/test_decode_kernel.py pins this).
    """
    import jax.experimental.pallas as pl

    bi = pl.program_id(0)
    i = pl.program_id(1)
    nblocks = pl.num_programs(1)
    blk = k_ref.shape[1]
    h = exp_ref.shape[0]
    n_q = qq_ref.shape[2] // h
    contract = (((1,), (0,)), ((), ()))

    reads = live_ref[bi] > 0  # else the blocks in VMEM are another row's

    @pl.when(reads & (i == 0))
    def _init():
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_last = qpos_ref[bi]
    live_lo = q_last + 1 - live_ref[bi]  # first live slot (== pad count for full rows)
    dead = jnp.maximum(live_lo // blk, 0)  # fully-dead head blocks

    @pl.when(reads & (i >= dead))
    def _compute():
        sc_all = _rotary_scores(
            k_ref[0].astype(jnp.float32), ang_ref[0].astype(jnp.float32), qq_ref, h
        )  # (blk, n_q*h)
        slot = i * blk + jax.lax.broadcasted_iota(jnp.int32, (blk, 1), 0)
        not_pad = (pad_ref[0].astype(jnp.int32) == 0) & (slot >= live_lo)  # (blk, 1)
        vf = v_ref[0].astype(jnp.float32)

        for qi in range(n_q):
            sc = sc_all[:, qi * h : (qi + 1) * h]  # (blk, h)
            visible = (slot <= q_last - (n_q - 1 - qi)) & not_pad  # (blk, 1)
            sc = jnp.where(visible, sc, -jnp.inf)

            m_prev = m_ref[qi : qi + 1, :h]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=0, keepdims=True))  # (1, h)
            safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            scale = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - safe_m), 0.0)  # (1, h)
            prob = jnp.exp(jnp.where(jnp.isfinite(sc), sc - safe_m, -jnp.inf))  # (blk, h)

            prob_x = jax.lax.dot_general(prob, exp_ref[:], contract, preferred_element_type=jnp.float32)  # (blk, h*d)
            pv = jnp.sum(prob_x * vf, axis=0, keepdims=True)  # (1, h*d)
            scale_x = jax.lax.dot_general(scale, exp_ref[:], contract, preferred_element_type=jnp.float32)  # (1, h*d)

            m_ref[qi : qi + 1, :h] = m_new
            l_ref[qi : qi + 1, :h] = l_ref[qi : qi + 1, :h] * scale + jnp.sum(prob, axis=0, keepdims=True)
            acc_ref[qi : qi + 1, :] = acc_ref[qi : qi + 1, :] * scale_x + pv

    @pl.when(~reads & (i == nblocks - 1))
    def _nothing_read():
        o_ref[...] = jnp.zeros_like(o_ref)  # what an all-masked row finalizes to

    @pl.when(reads & (i == nblocks - 1))
    def _finalize():
        rows = []
        for qi in range(n_q):
            l = jnp.maximum(l_ref[qi : qi + 1, :h], 1e-30)
            l_x = jax.lax.dot_general(1.0 / l, exp_ref[:], contract, preferred_element_type=jnp.float32)
            rows.append(acc_ref[qi : qi + 1, :] * l_x)
        o_ref[0] = (rows[0] if n_q == 1 else jnp.concatenate(rows, axis=0)).astype(o_ref.dtype)


def fused_decode_attention_auto(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    rope_k: jax.Array,
    q_pos: jax.Array,
    pad_slots: jax.Array,
    live: Optional[jax.Array] = None,
    layer: Optional[jax.Array] = None,
    interpret: bool = False,
) -> jax.Array:
    """Mesh-aware dispatch: under an ambient mesh that shards batch axes, the
    kernel runs per-device inside shard_map (batch-sharded caches stay put, no
    collectives); otherwise falls through to the plain pallas call. Gating —
    batch divisibility, no sharded head axis — is decode_kernel_supported's job."""
    from perceiver_io_tpu.ops.flash import _mesh_plan

    plan = _mesh_plan()
    if plan is None or not plan[0]:
        return fused_decode_attention(
            q, k_cache, v_cache, rope_k, q_pos, pad_slots, live=live, layer=layer, interpret=interpret
        )

    from jax.sharding import PartitionSpec as P

    from perceiver_io_tpu.parallel.ring_attention import _shard_map

    b = q.shape[0]
    baxes = plan[0]
    q_pos_b = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32).reshape(-1), (b,))
    live_b = (
        jnp.broadcast_to(jnp.asarray(live, jnp.int32).reshape(-1), (b,))
        if live is not None else q_pos_b + 1  # full live region: no skipping
    )
    if layer is None:  # as in fused_decode_attention: one layer, stacked
        k_cache, v_cache, layer = k_cache[None], v_cache[None], 0
    fn = _shard_map(
        lambda q, k, v, a, pos, pad, lv, layer: fused_decode_attention(
            q, k, v, a, pos, pad, live=lv, layer=layer[0], interpret=interpret
        ),
        in_specs=(
            P(baxes, None, None, None),
            P(None, baxes, None, None),
            P(None, baxes, None, None),
            P(baxes, None, None),
            P(baxes),
            P(baxes, None),
            P(baxes),
            P(None),
        ),
        out_specs=P(baxes, None, None, None),
        mesh=None,
    )
    return fn(q, k_cache, v_cache, rope_k, q_pos_b, pad_slots, live_b, jnp.asarray(layer, jnp.int32).reshape(1))


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    rope_k: jax.Array,
    q_pos: jax.Array,
    pad_slots: jax.Array,
    live: Optional[jax.Array] = None,
    layer: Optional[jax.Array] = None,
    interpret: bool = False,
) -> jax.Array:
    """q (B, H, n_q, D) scaled (+rotated) queries, n_q <= 8; k/v_cache
    (B, cap, H*D) unrotated; rope_k (B, cap, R) angles; q_pos () or (B,)
    absolute position of the LAST query (query qi sits at q_pos - (n_q-1-qi));
    pad_slots (B, cap). ``live`` () or (B,): per-row live-entry counts — the
    live region is the tail [q_pos+1-live, q_pos+1); KV blocks entirely below
    it are skipped (no compute, no fresh DMA). Callers keep ``live``
    consistent with ``pad_slots`` (live = valid minus pad slots); None means
    fully live. A row with ``live == 0`` is not read at all (no bytes, no
    compute: ``_step_block``) and comes back zeros, so the kernel's time
    follows the rows that read, not the batch. Returns (B, H, n_q, D).

    STACKED form: with ``layer`` (a scalar, traced OK) k/v_cache are the
    per-layer caches stacked as (L, B, cap, H*D) and the kernel reads layer
    ``layer`` where it lies — the index rides the scalar-prefetch path into
    the K/V index map, so no layer-sized slice is materialised for the call
    (the serving pool's ``RingKVCache``). Everything else is per batch row and
    the same in both forms."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, n_q, d = q.shape
    if layer is None:  # the 3-D form is the stacked one with a single layer
        k_cache, v_cache, layer = k_cache[None], v_cache[None], 0
    cap = k_cache.shape[2]
    blk = _kv_block(cap, h * d, k_cache.dtype.itemsize)
    if blk is None:
        raise ValueError(
            f"no KV block of {_BLOCKS} tiles capacity {cap} within the "
            f"{_VMEM_BUDGET >> 20} MiB VMEM budget at packed width {h * d}; "
            "gate calls with decode_kernel_supported()"
        )
    nblocks = cap // blk
    r = rope_k.shape[-1]

    q_pos_arr = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32).reshape(-1), (b,))
    live_arr = (
        jnp.broadcast_to(jnp.asarray(live, jnp.int32).reshape(-1), (b,))
        if live is not None else q_pos_arr + 1  # full live region: no skipping
    )

    def _slot_map(bi, i, qpos_ref, live_ref, fetch_ref, layer_ref):
        return (*_step_block(bi, i, qpos_ref, live_ref, fetch_ref, nblocks, blk), 0)

    def _kv_map(bi, i, qpos_ref, live_ref, fetch_ref, layer_ref):
        return (layer_ref[0], *_slot_map(bi, i, qpos_ref, live_ref, fetch_ref, layer_ref))

    def _query_map(bi, i, qpos_ref, live_ref, fetch_ref, layer_ref):
        return (fetch_ref[bi], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, nblocks),
        in_specs=[
            pl.BlockSpec((None, 2, h * d, n_q * h), _query_map),
            pl.BlockSpec((None, 1, blk, h * d), _kv_map),
            pl.BlockSpec((None, 1, blk, h * d), _kv_map),
            pl.BlockSpec((1, blk, r), _slot_map),
            pl.BlockSpec((1, blk, 1), _slot_map),
            pl.BlockSpec((h, h * d), lambda bi, i, *_: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, n_q, h * d), lambda bi, i, *_: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((8, 128), jnp.float32),
            pltpu.VMEM((8, 128), jnp.float32),
            pltpu.VMEM((8, h * d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_q, h * d), q.dtype),
        interpret=interpret,
    )(
        q_pos_arr,
        live_arr,
        _fetch_rows(live_arr),
        jnp.asarray(layer, jnp.int32).reshape(1),
        _blockdiag_queries(q, r),
        k_cache,
        v_cache,
        rope_k,
        pad_slots.astype(jnp.int8)[:, :, None],
        jnp.asarray(_head_expander(h, d)),
    )
    return out.reshape(b, n_q, h, d).transpose(0, 2, 1, 3)
