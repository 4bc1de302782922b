"""Ring attention: sequence/context parallelism for long-context attention.

The reference has NO sequence parallelism (SURVEY.md §2.7) — its long-context
story is purely architectural (Perceiver AR latent compression). This module
goes beyond the reference: the prefix key/value sequence is sharded over a
``seq`` mesh axis, and attention runs as a ring — each device computes a partial
flash-style (running max/sum) attention against its local KV shard, then rotates
the shards around the ring with ``lax.ppermute`` over ICI until every device has
seen every block. Peak per-device KV memory drops from O(n) to O(n / seq_shards),
so the Perceiver AR prefix cross-attention scales to sequences that cannot fit
on one chip.

Three execution paths:

* **custom-VJP ring (default).** Forward merges per-block partial softmax
  stats; backward is a SECOND ring pass that recomputes each block's scores and
  accumulates dq locally while dk/dv travel around the ring with their blocks.
  Without this, reverse-mode AD of the forward loop (a ``lax.scan`` after
  lowering) would stash every rotated KV block — O(n) per device, silently
  defeating the ring's O(n/S) memory promise.
* **Splash blocks inside the ring shard.** On TPU each ring step classifies its
  current block against the right-aligned causal frontier: fully visible blocks
  run the fused Pallas splash kernel (``save_residuals`` gives the block's
  logsumexp for the running merge), fully hidden blocks are skipped, and only
  the O(1) diagonal blocks pay the einsum formulation. AD never sees the
  kernel — it lives inside the custom-VJP forward (splash's own
  ``save_residuals`` path is not differentiable).
* **Differentiable einsum ring with attention dropout.** Attention dropout
  (reference modules.py:163 ``nn.Dropout`` on softmax probs) needs plain AD, so
  ``dropout_rate > 0`` routes to the original formulation with a
  position-keyed Bernoulli mask per (query-shard, key-block) pair: the
  normalizer keeps the UNdropped probability mass (torch semantics — dropout is
  applied after softmax), only the value-weighted sum is dropped.

Masking supports the framework's right-aligned causal convention (query row i of
an Nq-row query block sees global key columns 0..(Nk_total - Nq + i)) and key
pad masks; blocks of the ring that are fully masked for every query are still
visited (the ring is oblivious) but contribute zero weight through the running
softmax.

Communication note: the ring permutation moves KV blocks between ICI neighbours
only (mesh axes are laid out so ``seq`` is adjacent), overlapping compute on the
current block with the transfer of the next under XLA's latency-hiding scheduler.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


class _RingCfg(NamedTuple):
    """Static (hashable) configuration threaded through the custom-VJP."""

    mesh: Optional[Mesh]
    seq_axis: str
    baxes: tuple
    causal: bool
    nq_total: int
    nk_total: int
    use_splash: bool
    interpret: bool


def _shard_map(fn, in_specs, out_specs, mesh):
    kwargs = {} if mesh is None else {"mesh": mesh}
    return jax.shard_map(fn, in_specs=in_specs, out_specs=out_specs, check_vma=False, **kwargs)


def _splash_block_ok(cfg: _RingCfg, nq: int, nkl: int, d: int) -> bool:
    """Can splash serve the full (non-diagonal) ring blocks of this shape?"""
    if not cfg.use_splash:
        return False
    from perceiver_io_tpu.ops.flash import _pick_block

    return d % 64 == 0 and nq >= 128 and nkl >= 128 and _pick_block(nq, nkl, d) is not None


def _splash_fwd_block(q, k_cur, v_cur, pad_cur, interpret):
    """Fully-visible block via the fused splash kernel: returns the block's
    normalized output and logsumexp (per query row) for the running merge."""
    import jax.experimental.pallas.ops.tpu.splash_attention as sa

    from perceiver_io_tpu.ops.flash import _kernel, _pick_block

    b, h, nq, d = q.shape
    nkl = k_cur.shape[2]
    kernel = _kernel(h, nq, nkl, _pick_block(nq, nkl, d), False, interpret, save_residuals=True)
    seg_q = jnp.ones((b, nq), jnp.int32)
    seg_kv = jnp.where(pad_cur, 0, 1).astype(jnp.int32)

    def one(q, k, v, sq, skv):
        o, (lse,) = kernel(q, k, v, segment_ids=sa.SegmentIds(sq, skv))
        return o, lse

    o_blk, lse_blk = jax.vmap(one)(q, k_cur, v_cur, seg_q, seg_kv)
    return o_blk.astype(jnp.float32), lse_blk.astype(jnp.float32)  # (b,h,nq,d), (b,h,nq)


def _einsum_block_stats(q, k_cur, pad_cur, col_global, q_pos, causal):
    """Masked fp32 scores for one block: (s, visible) with hidden entries -inf."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k_cur, preferred_element_type=jnp.float32)
    nq, nkl = q.shape[2], k_cur.shape[2]
    visible = jnp.ones((nq, nkl), bool)
    if causal:
        visible = col_global[None, :] <= q_pos[:, None]
    mask = visible[None, None] & ~pad_cur[:, None, None, :]
    return jnp.where(mask, s, -jnp.inf), mask


def _merge_unnorm(m, l, o, s, v_cur):
    """Merge one block's raw masked scores into running (m, l, o) stats."""
    m_new = jnp.maximum(m, s.max(-1, keepdims=True))
    safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    scale = jnp.where(jnp.isfinite(m), jnp.exp(m - safe), 0.0)
    p_blk = jnp.exp(jnp.where(jnp.isfinite(s), s - safe, -jnp.inf))
    l = l * scale + p_blk.sum(-1, keepdims=True)
    o = o * scale + jnp.einsum("bhqk,bhkd->bhqd", p_blk, v_cur.astype(jnp.float32))
    return m_new, l, o


def _merge_normalized(m, l, o, o_blk, lse_blk):
    """Merge a pre-normalized block result (splash output + logsumexp)."""
    lse = lse_blk[..., None]
    m_new = jnp.maximum(m, lse)
    safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    scale = jnp.where(jnp.isfinite(m), jnp.exp(m - safe), 0.0)
    w_blk = jnp.where(jnp.isfinite(lse), jnp.exp(lse - safe), 0.0)
    l = l * scale + w_blk
    o = o * scale + o_blk * w_blk
    return m_new, l, o


def _ring_fwd_local(q, k, v, pad, *, axis_name, cfg: _RingCfg):
    """shard_map forward body. q (b, h, nq_local, d), k/v (b, h, nk_local, d),
    pad (b, nk_local). Returns (out (b,h,nq,d), lse (b,h,nq))."""
    num_shards = jax.lax.psum(1, axis_name)
    me = jax.lax.axis_index(axis_name)
    b, h, nq, d = q.shape
    nk_local = k.shape[2]

    m0 = jnp.full((b, h, nq, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, nq, 1), jnp.float32)
    o0 = jnp.zeros((b, h, nq, d), jnp.float32)

    # right-aligned GLOBAL positions of this device's query rows
    q_pos = cfg.nk_total - cfg.nq_total + me * nq + jnp.arange(nq)
    splash_ok = _splash_block_ok(cfg, nq, nk_local, d)

    def accumulate(i, k_cur, v_cur, pad_cur, m, l, o):
        shard_id = (me - i) % num_shards  # global index of the block currently held
        col_global = shard_id * nk_local + jnp.arange(nk_local)

        def einsum_case(args):
            k_cur, v_cur, pad_cur, m, l, o = args
            s, _ = _einsum_block_stats(q, k_cur, pad_cur, col_global, q_pos, cfg.causal)
            return _merge_unnorm(m, l, o, s, v_cur)

        if not splash_ok:
            return einsum_case((k_cur, v_cur, pad_cur, m, l, o))

        def splash_case(args):
            k_cur, v_cur, pad_cur, m, l, o = args
            o_blk, lse_blk = _splash_fwd_block(q, k_cur, v_cur, pad_cur, cfg.interpret)
            return _merge_normalized(m, l, o, o_blk, lse_blk)

        def empty_case(args):
            _, _, _, m, l, o = args
            return m, l, o

        if not cfg.causal:
            return splash_case((k_cur, v_cur, pad_cur, m, l, o))
        # classify the block against the causal frontier: fully visible blocks
        # take the fused kernel, fully hidden ones are skipped, only the O(1)
        # diagonal blocks pay the einsum formulation
        col_min, col_max = shard_id * nk_local, shard_id * nk_local + nk_local - 1
        idx = jnp.where(col_min > q_pos[-1], 2, jnp.where(col_max <= q_pos[0], 0, 1))
        return jax.lax.switch(idx, [splash_case, einsum_case, empty_case], (k_cur, v_cur, pad_cur, m, l, o))

    def body(i, carry):
        k_cur, v_cur, pad_cur, m, l, o = carry
        m, l, o = accumulate(i, k_cur, v_cur, pad_cur, m, l, o)
        # rotate KV (and pad) blocks one step around the ring
        perm = [(j, (j + 1) % num_shards) for j in range(num_shards)]
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        pad_cur = jax.lax.ppermute(pad_cur, axis_name, perm)
        return k_cur, v_cur, pad_cur, m, l, o

    # rotate only between blocks: S-1 (compute + rotate) iterations, then a
    # final compute — no wasted last ring transfer
    k_c, v_c, pad_c, m, l, o = jax.lax.fori_loop(0, num_shards - 1, body, (k, v, pad, m0, l0, o0))
    m, l, o = accumulate(num_shards - 1, k_c, v_c, pad_c, m, l, o)
    out = (o / jnp.maximum(l, 1e-30)).astype(q.dtype)
    lse = (m + jnp.log(jnp.maximum(l, 1e-30)))[..., 0]  # -inf rows stay -inf
    return out, lse


def _ring_bwd_local(q, k, v, pad, o, lse, do, *, axis_name, cfg: _RingCfg):
    """shard_map backward body: a second ring pass. dq accumulates locally;
    dk/dv accumulate into buffers that travel WITH their kv blocks and are
    rotated one extra step at the end to land back on the owning device."""
    num_shards = jax.lax.psum(1, axis_name)
    me = jax.lax.axis_index(axis_name)
    b, h, nq, d = q.shape
    nk_local = k.shape[2]

    q_pos = cfg.nk_total - cfg.nq_total + me * nq + jnp.arange(nq)
    qf = q.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    delta = jnp.sum(dof * o.astype(jnp.float32), axis=-1, keepdims=True)  # (b,h,nq,1)
    lse_e = lse[..., None]  # (b,h,nq,1)

    def step(i, k_cur, v_cur, pad_cur, dk_cur, dv_cur, dq):
        shard_id = (me - i) % num_shards
        col_global = shard_id * nk_local + jnp.arange(nk_local)
        s, mask = _einsum_block_stats(qf, k_cur, pad_cur, col_global, q_pos, cfg.causal)
        # p = softmax probs reconstructed from the saved logsumexp
        p = jnp.where(mask, jnp.exp(s - jnp.where(jnp.isfinite(lse_e), lse_e, 0.0)), 0.0)
        dv_cur = dv_cur + jnp.einsum("bhqk,bhqd->bhkd", p, dof)
        dp = jnp.einsum("bhqd,bhkd->bhqk", dof, v_cur.astype(jnp.float32))
        ds = p * (dp - delta)
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, k_cur.astype(jnp.float32))
        dk_cur = dk_cur + jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
        return dk_cur, dv_cur, dq

    def body(i, carry):
        k_cur, v_cur, pad_cur, dk_cur, dv_cur, dq = carry
        dk_cur, dv_cur, dq = step(i, k_cur, v_cur, pad_cur, dk_cur, dv_cur, dq)
        perm = [(j, (j + 1) % num_shards) for j in range(num_shards)]
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        pad_cur = jax.lax.ppermute(pad_cur, axis_name, perm)
        dk_cur = jax.lax.ppermute(dk_cur, axis_name, perm)
        dv_cur = jax.lax.ppermute(dv_cur, axis_name, perm)
        return k_cur, v_cur, pad_cur, dk_cur, dv_cur, dq

    dk0 = jnp.zeros((b, h, nk_local, d), jnp.float32)
    dv0 = jnp.zeros((b, h, nk_local, d), jnp.float32)
    dq0 = jnp.zeros((b, h, nq, d), jnp.float32)
    k_c, v_c, pad_c, dk_c, dv_c, dq = jax.lax.fori_loop(
        0, num_shards - 1, body, (k, v, pad, dk0, dv0, dq0)
    )
    dk_c, dv_c, dq = step(num_shards - 1, k_c, v_c, pad_c, dk_c, dv_c, dq)
    # the block each device now holds is (me - (S-1)) % S = me + 1: one more
    # rotation returns every dk/dv buffer to the device that owns its shard
    perm = [(j, (j + 1) % num_shards) for j in range(num_shards)]
    dk = jax.lax.ppermute(dk_c, axis_name, perm)
    dv = jax.lax.ppermute(dv_c, axis_name, perm)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _specs(cfg: _RingCfg):
    bspec = cfg.baxes if cfg.baxes else None
    qkv = P(bspec, None, cfg.seq_axis, None)
    pad = P(bspec, cfg.seq_axis)
    lse = P(bspec, None, cfg.seq_axis)
    return qkv, pad, lse


def _ring_call(cfg: _RingCfg, q, k, v, pad):
    qkv_spec, pad_spec, lse_spec = _specs(cfg)
    fn = _shard_map(
        partial(_ring_fwd_local, axis_name=cfg.seq_axis, cfg=cfg),
        in_specs=(qkv_spec, qkv_spec, qkv_spec, pad_spec),
        out_specs=(qkv_spec, lse_spec),
        mesh=cfg.mesh,
    )
    return fn(q, k, v, pad)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ring_core(cfg: _RingCfg, q, k, v, pad):
    return _ring_call(cfg, q, k, v, pad)[0]


def _ring_core_fwd(cfg: _RingCfg, q, k, v, pad):
    o, lse = _ring_call(cfg, q, k, v, pad)
    return o, (q, k, v, pad, o, lse)


def _ring_core_bwd(cfg: _RingCfg, res, do):
    q, k, v, pad, o, lse = res
    qkv_spec, pad_spec, lse_spec = _specs(cfg)
    fn = _shard_map(
        partial(_ring_bwd_local, axis_name=cfg.seq_axis, cfg=cfg),
        in_specs=(qkv_spec, qkv_spec, qkv_spec, pad_spec, qkv_spec, lse_spec, qkv_spec),
        out_specs=(qkv_spec, qkv_spec, qkv_spec),
        mesh=cfg.mesh,
    )
    dq, dk, dv = fn(q, k, v, pad, o, lse, do)
    return dq, dk, dv, np.zeros(pad.shape, dtype=jax.dtypes.float0)


_ring_core.defvjp(_ring_core_fwd, _ring_core_bwd)


def _ring_dropout_local(q, k, v, pad, rng, *, axis_name, cfg: _RingCfg, dropout_rate: float):
    """Differentiable einsum ring with attention dropout: the Bernoulli mask for
    each (query-shard, key-block) pair is keyed by global block coordinates, so
    the pattern is well-defined regardless of ring schedule; the softmax
    normalizer keeps undropped mass (torch nn.Dropout-on-probs semantics)."""
    num_shards = jax.lax.psum(1, axis_name)
    me = jax.lax.axis_index(axis_name)
    b, h, nq, d = q.shape
    nk_local = k.shape[2]

    m0 = jnp.full((b, h, nq, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, nq, 1), jnp.float32)
    o0 = jnp.zeros((b, h, nq, 1 * d), jnp.float32)
    q_pos = cfg.nk_total - cfg.nq_total + me * nq + jnp.arange(nq)

    # fold every sharded coordinate into the key so no two devices reuse a mask
    key = rng
    for ax in cfg.baxes:
        key = jax.random.fold_in(key, jax.lax.axis_index(ax))
    key = jax.random.fold_in(key, me)

    keep = 1.0 - dropout_rate

    def accumulate(i, k_cur, v_cur, pad_cur, m, l, o):
        shard_id = (me - i) % num_shards
        col_global = shard_id * nk_local + jnp.arange(nk_local)
        s, _ = _einsum_block_stats(q, k_cur, pad_cur, col_global, q_pos, cfg.causal)

        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        scale = jnp.where(jnp.isfinite(m), jnp.exp(m - safe), 0.0)
        p_blk = jnp.exp(jnp.where(jnp.isfinite(s), s - safe, -jnp.inf))
        # normalizer accumulates UNdropped mass; only the value sum is dropped
        l = l * scale + p_blk.sum(-1, keepdims=True)
        drop = jax.random.bernoulli(jax.random.fold_in(key, shard_id), keep, p_blk.shape)
        p_drop = jnp.where(drop, p_blk / keep, 0.0)
        o = o * scale + jnp.einsum("bhqk,bhkd->bhqd", p_drop, v_cur.astype(jnp.float32))
        return m_new, l, o

    def body(i, carry):
        k_cur, v_cur, pad_cur, m, l, o = carry
        m, l, o = accumulate(i, k_cur, v_cur, pad_cur, m, l, o)
        perm = [(j, (j + 1) % num_shards) for j in range(num_shards)]
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        pad_cur = jax.lax.ppermute(pad_cur, axis_name, perm)
        return k_cur, v_cur, pad_cur, m, l, o

    k_c, v_c, pad_c, m, l, o = jax.lax.fori_loop(0, num_shards - 1, body, (k, v, pad, m0, l0, o0))
    m, l, o = accumulate(num_shards - 1, k_c, v_c, pad_c, m, l, o)
    return (o / jnp.maximum(l, 1e-30)).astype(q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Optional[Mesh] = None,
    pad_mask: Optional[jax.Array] = None,
    causal: bool = True,
    seq_axis: str = "seq",
    batch_axes=("data", "fsdp"),
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
    use_splash: Optional[bool] = None,
    interpret: bool = False,
) -> jax.Array:
    """Sequence-parallel attention over a mesh.

    q (B, H, Nq, D) — queries (e.g. Perceiver AR latents), sharded over the
        ``seq`` axis (Nq divisible by the axis size), batch-sharded over
        ``batch_axes`` present in the mesh.
    k/v (B, H, Nk, D) — keys/values with Nk sharded over ``seq``.
    pad_mask (B, Nk) True = padding.
    causal: right-aligned causal masking (the Perceiver AR convention).
    mesh: explicit mesh, or None to use the ambient one
        (``jax.sharding.set_mesh`` — the form model modules use).
    dropout_rate / dropout_rng: attention dropout on the softmax probs
        (requires a rng; runs the plain differentiable formulation).
    use_splash: None = auto (TPU + block shapes the kernel supports),
        False = einsum blocks, True = force splash (with ``interpret`` for CPU
        testing).
    """
    axis_names = (mesh if mesh is not None else jax.sharding.get_abstract_mesh()).axis_names
    if seq_axis not in axis_names:
        raise ValueError(
            f"ring attention requires an active mesh with a '{seq_axis}' axis "
            "(pass mesh= or wrap the computation in jax.sharding.set_mesh(mesh))"
        )

    if pad_mask is None:
        pad_mask = jnp.zeros(k.shape[:1] + k.shape[2:3], bool)

    baxes = tuple(a for a in batch_axes if a in axis_names)
    if use_splash is None:
        use_splash = jax.default_backend() == "tpu"
    cfg = _RingCfg(
        mesh=mesh,
        seq_axis=seq_axis,
        baxes=baxes,
        causal=causal,
        nq_total=q.shape[2],
        nk_total=k.shape[2],
        use_splash=bool(use_splash),
        interpret=interpret,
    )

    if dropout_rate > 0.0:
        if dropout_rng is None:
            raise ValueError("dropout_rate > 0 requires dropout_rng")
        qkv_spec, pad_spec, _ = _specs(cfg)
        fn = _shard_map(
            partial(_ring_dropout_local, axis_name=seq_axis, cfg=cfg, dropout_rate=float(dropout_rate)),
            in_specs=(qkv_spec, qkv_spec, qkv_spec, pad_spec, P()),
            out_specs=qkv_spec,
            mesh=mesh,
        )
        return fn(q, k, v, pad_mask, dropout_rng)

    return _ring_core(cfg, q, k, v, pad_mask)


def ring_attention_ambient(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    pad_mask: Optional[jax.Array] = None,
    causal: bool = True,
    seq_axis: str = "seq",
    batch_axes=("data", "fsdp"),
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
) -> jax.Array:
    """Alias: ring_attention with the ambient mesh."""
    return ring_attention(
        q, k, v, mesh=None, pad_mask=pad_mask, causal=causal, seq_axis=seq_axis,
        batch_axes=batch_axes, dropout_rate=dropout_rate, dropout_rng=dropout_rng,
    )
