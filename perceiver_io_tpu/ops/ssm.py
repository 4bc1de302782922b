"""State-space (Mamba-2 / SSD) primitives for a served hybrid block.

Per head, with ``A < 0`` a scalar, the recurrence over tokens ``t`` is

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        S: (P, N)
    y_t = S_t C_t                                       (the caller adds D x_t)

with ``x_t`` (P,) the head's slice of the convolved input and ``B_t``, ``C_t``
(N,) shared by the ``H // G`` heads of a group. Two forms of it live here:

``ssd_chunk_scan``
    PREFILL: the same recurrence in its chunked (state-space dual) form — inside
    a chunk of ``chunk`` tokens the outputs are a masked matmul, between chunks
    only the (H, P, N) state is carried. Plain ``jax.numpy`` (it is matmuls);
    takes and returns the state, so a prompt can be fed a chunk at a time.
    Rows whose ``dt`` is zero leave the state as it is (chunk padding).

``ssm_decode_update``
    DECODE: one step of the recurrence for every DECODING slot of a serving
    pool. The state of the whole pool, (layers, slots, H, P, N) float32, is
    the largest thing a decode tick moves after the weights, so the TPU form is
    a Pallas kernel that reads each decoding slot's state once and writes it
    once IN PLACE (the pool array is aliased to the output), and leaves every
    other slot's state as it is through a scalar-prefetched live list: free
    slots and slots in the middle of their prefill are not computed on, and
    their grid steps move no bytes (``_step_block``), so the kernel's time
    follows the slots that decode, not the pool's size. ``ssm_decode_
    update_xla`` is the same arithmetic in ``jax.numpy`` for backends without
    Mosaic (the CPU suite); ``ssm_kernel_supported`` picks between them from
    the platform and the shapes, never from a switch.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from perceiver_io_tpu.ops.flash import single_device_trace

_HIGHEST = jax.lax.Precision.HIGHEST
# most heads of one (slot, layer) state a grid step moves: 16 x (128, 256) f32
# is 2 MB a block, 8 MB with the in and out blocks double-buffered
_MAX_HEAD_BLOCK = 16
_VMEM_LIMIT = 48 * 2**20


# ----------------------------------------------------------------- prefill
def ssd_chunk_scan(
    x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
    state: jax.Array, chunk: int,
) -> Tuple[jax.Array, jax.Array]:
    """x (T, H, P), dt (T, H) (after softplus; 0 on padding rows), a (H,)
    negative, b / c (T, G, N), state (H, P, N): the state before the first
    row. Returns (y (T, H, P) without the ``D x`` term, the state after the
    last row). float32 throughout; ``T`` need not be a multiple of ``chunk``."""
    t, h, p = x.shape
    g, n = b.shape[1:]
    k = h // g  # heads of one group
    pad = -t % chunk
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1)) for v in (x, dt, b, c))
    nc = (t + pad) // chunk
    f32 = jnp.float32
    xc = x.astype(f32).reshape(nc, chunk, g, k, p)
    dtc = dt.astype(f32).reshape(nc, chunk, g, k)
    bc = b.astype(f32).reshape(nc, chunk, g, n)
    cc = c.astype(f32).reshape(nc, chunk, g, n)
    la = jnp.cumsum(dtc * a.astype(f32).reshape(g, k), axis=1)  # log decay up to and including row i

    # inside a chunk: y_i += sum_{j <= i} exp(la_i - la_j) (C_i . B_j) dt_j x_j
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))[None, :, :, None, None]
    diff = la[:, :, None] - la[:, None, :]  # (nc, i, j, g, k)
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    cb = jnp.einsum("cign,cjgn->cijg", cc, bc, precision=_HIGHEST)
    w = cb[..., None] * decay * dtc[:, None]
    y = jnp.einsum("cijgk,cjgkp->cigkp", w, xc, precision=_HIGHEST)

    # between chunks: what each chunk adds to the state at its end, and how much
    # of the state at its start is left by then
    to_end = jnp.exp(la[:, -1:] - la) * dtc  # (nc, j, g, k)
    added = jnp.einsum("cjgk,cjgn,cjgkp->cgkpn", to_end, bc, xc, precision=_HIGHEST)
    kept = jnp.exp(la[:, -1])  # (nc, g, k)

    def carry(s, inp):
        add, keep = inp
        return keep[..., None, None] * s + add, s

    last, at_start = jax.lax.scan(carry, state.astype(f32).reshape(g, k, p, n), (added, kept))
    y = y + jnp.einsum("cign,cgkpn->cigkp", cc, at_start, precision=_HIGHEST) * jnp.exp(la)[..., None]
    return y.reshape(nc * chunk, h, p)[:t], last.reshape(h, p, n)


def ssm_recurrence(
    x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array, state: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """The recurrence itself, one row at a time under ``lax.scan`` — what
    ``ssd_chunk_scan`` has to equal (tests), same arguments and results."""
    g = b.shape[1]
    k = x.shape[1] // g

    def step(s, row):
        xt, dtt, bt, ct = row
        bh, ch = jnp.repeat(bt, k, axis=0), jnp.repeat(ct, k, axis=0)  # (H, N)
        s = jnp.exp(dtt * a)[:, None, None] * s + (dtt[:, None] * xt)[:, :, None] * bh[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, ch, precision=_HIGHEST)

    f32 = jnp.float32
    last, y = jax.lax.scan(step, state.astype(f32), tuple(v.astype(f32) for v in (x, dt, b, c)))
    return y, last


# ------------------------------------------------------------------ decode
def live_list(active: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(live (S,), n_live (1,)) of a pool's ``active`` (S,) flags: the decoding
    slots first, in slot order, then the first of the others repeated: a grid
    step past ``n_live`` rests on that one idle slot, whose first head block is
    passed through unchanged (the kernel copies it once, at step ``n_live``),
    so the pool is sound whatever ``n_live`` is, zero included."""
    s = active.shape[0]
    n_live = jnp.sum(active.astype(jnp.int32))
    order = jnp.argsort(~active, stable=True).astype(jnp.int32)
    idle = order[jnp.minimum(n_live, s - 1)]
    return jnp.where(jnp.arange(s) < n_live, order, idle), n_live.reshape(1)


def ssm_decode_update_xla(
    state: jax.Array, layer: int, x: jax.Array, dt: jax.Array, a: jax.Array,
    b: jax.Array, c: jax.Array, active: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """One step for every slot of layer ``layer``: state (L, S, H, P, N), x
    (S, H, P), dt (S, H) after softplus, a (H,), b / c (S, G, N), active (S,).
    Returns (the pool with the decoding slots' states of that layer replaced,
    y (S, H, P), zero for the other slots)."""
    k = x.shape[1] // b.shape[1]
    f32 = jnp.float32
    x, dt, b, c = (v.astype(f32) for v in (x, dt, b, c))
    bh, ch = jnp.repeat(b, k, axis=1), jnp.repeat(c, k, axis=1)  # (S, H, N)
    s = state[layer]
    new = jnp.exp(dt * a.astype(f32))[..., None, None] * s + (dt[..., None] * x)[..., None] * bh[:, :, None, :]
    y = jnp.einsum("shpn,shn->shp", new, ch, precision=_HIGHEST)
    on = active[:, None, None]
    return state.at[layer].set(jnp.where(on[..., None], new, s)), jnp.where(on, y, 0.0)


def ssm_kernel_supported(num_heads: int, num_groups: int, head_dim: int, state_size: int) -> bool:
    """The Pallas form on one TPU device, for lane- and sublane-aligned
    states; anything else (the CPU suite, a sharded pool) takes the XLA form."""
    if jax.default_backend() != "tpu" or not single_device_trace():
        return False
    return head_dim % 8 == 0 and state_size % 128 == 0 and num_heads % num_groups == 0


def _head_block(num_heads: int, num_groups: int) -> int:
    """Heads a grid step moves: they share one group's B and C."""
    per_group = num_heads // num_groups
    block = min(per_group, _MAX_HEAD_BLOCK)
    while per_group % block:
        block -= 1
    return block


def _step_block(w, j, live, n_live):
    """(slot, head block) that grid step ``(w, j)`` holds: block ``j`` of slot
    ``live[w]`` while ``w < n_live``, and ONE block, the idle slot's first,
    on every step after, whatever ``j`` is. The pipeline fetches a block and
    writes the last one back only where this index differs from the step
    before, so the steps past the live list move nothing; were ``j`` let
    through on them, the idle slot's blocks would be fetched and written back
    in turn and the kernel would move the whole pool's bytes whatever decoded
    (measured: PERF.md 6, PR 33)."""
    return live[w], jnp.where(w < n_live[0], j, 0)


def _ssm_kernel(live_ref, n_live_ref, s_ref, scale_ref, b_ref, c_ref, eye_ref, so_ref, y_ref):
    """Grid (S, H // hb); step (w, j) holds the block ``_step_block`` names.

    s_ref / so_ref (hb, P, N)  the slot's state, in and (aliased) out
    scale_ref (2, hb, P)       row 0: exp(dt A), row 1: dt x, per head, P on lanes
    b_ref, c_ref (1, N)        the group's B and C
    eye_ref (P, P)             identity: moves P between lanes and sublanes exactly
    y_ref (hb, P)              S_new C per head

    The state's arithmetic is the vector unit's, in float32: two broadcast
    multiplies and an add a vreg for the update, a multiply and a lane
    reduction for ``y``. The matrix unit only transposes (a product with the
    identity at ``highest`` keeps every bit): the per-head scales from rows to
    columns on the way in, the heads' ``y`` columns back to rows on the way
    out. Both are ``A @ B^T`` products, the form Mosaic lowers for any aligned
    shape. The kernel is then bound by the state's two passes over HBM.
    """
    import jax.experimental.pallas as pl

    nt = (((1,), (1,)), ((), ()))
    hb = s_ref.shape[0]

    @pl.when((pl.program_id(0) == n_live_ref[0]) & (pl.program_id(1) == 0))
    def _pass_through():
        # the one block the steps past the live list rest on: no later step
        # writes the output buffer, so this copy is what the last write-back holds
        so_ref[...] = s_ref[...]

    @pl.when(pl.program_id(0) < n_live_ref[0])
    def _update():
        # (hb, P) rows -> (P, hb) columns: column h scales the rows of head h
        keep = jax.lax.dot_general(eye_ref[:], scale_ref[0], nt, precision=_HIGHEST,
                                   preferred_element_type=jnp.float32)
        add = jax.lax.dot_general(eye_ref[:], scale_ref[1], nt, precision=_HIGHEST,
                                  preferred_element_type=jnp.float32)
        b_row, c_row = b_ref[:], c_ref[:]
        head = jax.lax.broadcasted_iota(jnp.int32, (1, hb), 1)
        ys = jnp.zeros((s_ref.shape[1], hb), jnp.float32)  # column h: head h's y
        for h in range(hb):
            new = keep[:, h:h + 1] * s_ref[h] + add[:, h:h + 1] * b_row  # (P, N)
            so_ref[h] = new
            ys = ys + jnp.sum(new * c_row, axis=-1, keepdims=True) * (head == h).astype(jnp.float32)
        y_ref[...] = jax.lax.dot_general(jnp.eye(hb, dtype=jnp.float32), ys, nt, precision=_HIGHEST,
                                         preferred_element_type=jnp.float32)  # (hb, P)


@functools.partial(jax.jit, static_argnames=("layer", "interpret"))
def ssm_decode_update(
    state: jax.Array, layer: int, x: jax.Array, dt: jax.Array, a: jax.Array,
    b: jax.Array, c: jax.Array, active: jax.Array, interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """``ssm_decode_update_xla`` as a Pallas kernel: same arguments and results;
    the pool is updated in place and only the decoding slots' blocks of layer
    ``layer`` are moved (module docstring)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, s, h, p, n = state.shape
    g = b.shape[1]
    hb = _head_block(h, g)
    per_group = h // g
    f32 = jnp.float32
    x, dt = x.astype(f32), dt.astype(f32)
    scale = jnp.stack([jnp.broadcast_to(jnp.exp(dt * a.astype(f32))[..., None], x.shape),
                       dt[..., None] * x], axis=1)  # (S, 2, H, P)
    live, n_live = live_list(active)

    def at(place):  # the index map that puts ``place(slot, block)`` at the step's block
        return lambda *step: place(*_step_block(*step))

    state_spec = pl.BlockSpec((None, None, hb, p, n), at(lambda slot, blk: (layer, slot, blk, 0, 0)))
    group_spec = pl.BlockSpec((None, None, 1, n), at(lambda slot, blk: (slot, blk * hb // per_group, 0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s, h // hb),
        in_specs=[
            state_spec,
            pl.BlockSpec((None, 2, hb, p), at(lambda slot, blk: (slot, 0, blk, 0))),
            group_spec,
            group_spec,
            pl.BlockSpec((p, p), lambda w, j, lr, nr: (0, 0)),
        ],
        out_specs=[state_spec, pl.BlockSpec((None, hb, p), at(lambda slot, blk: (slot, blk, 0)))],
    )
    new_state, y = pl.pallas_call(
        _ssm_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype), jax.ShapeDtypeStruct((s, h, p), f32)],
        # operand 2 (after the two prefetched scalars) is the pool: written in place
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="ssm_decode_update",
    )(live, n_live, state, scale, b.astype(f32)[:, :, None, :], c.astype(f32)[:, :, None, :], jnp.eye(p, dtype=f32))
    # rows of slots the kernel skipped were never written
    return new_state, jnp.where(active[:, None, None], y, 0.0)
