"""Unified ragged paged attention: ONE Pallas program per serving tick.

A serving tick mixes chunked-prefill writes, latent finishes of finishing
slots, and the batched decode step (serving/engine.py ``ragged_tick``). The
"Ragged Paged Attention" TPU kernel recipe (PAPERS.md) collapses the
attention side of that tick into ONE kernel launch over a host-built
ragged work descriptor: a flat list of work items, each one QUERY ROW —

  * a **decode step** contributes one item: the slot's single query against
    its full live window (causal bound = window - 1);
  * a **latent finish** contributes L items, one per latent query j, each
    the same slot's page-table row with causal bound = window - L + j (latent
    j must not see latents j+1..L-1 — the finish's causal mask);
  * **prefill chunks** contribute NO attention items — a Perceiver AR chunk
    is a position-wise KV projection (no token mixing; see
    models/core/perceiver_ar.prefill_chunk_kv), so chunks exist only in the
    ENGINE's tick descriptor, not in this kernel's grid.

The kernel itself is the fused paged decode kernel
(ops/paged_decode_kernel.py) generalized from per-slot rows to per-item rows
plus a per-item CAUSAL BOUND, with the int4 nibble unpack fused in-stream.
The causal bound folds into the existing ring visibility contract instead of
adding a second mask: a query with ring offset ``start``, ``live`` live
entries and bound ``cb`` sees logical window positions
``[window - live, cb]``, and with ``cut = (window - 1) - cb`` that window is
EXACTLY the plain decode visibility of the transformed row

    eff_start = (start - cut) mod window,   eff_live = max(live - cut, 0)

(shifting the ring origin by ``cut`` relabels logical position lp as
lp + cut; positions past the bound wrap to the dead region). The transform
runs once on the host side of the dispatch, so the kernel body is the SAME
flash loop as the legacy kernel — decode items (cut = 0) are BITWISE the
legacy program (tests/test_ragged_kernel.py pins it in interpret mode), and
dead-page skip / DMA aliasing reuse ``_page_has_live`` on the transformed
offsets unchanged.

Quantized pages reach the kernel as in the legacy one (per-page-per-head
f32 scales gathered per item into a small VMEM block, fused dequant before
rotation). int4 pools (ops/paged_decode_kernel.py module docstring) arrive
nibble-packed — blocks are (ps, C // 2) uint8 — and the kernel unpacks
in-stream: low nibble minus 8 is the even logical channel's code, high
nibble the odd. Mosaic
lowers neither shifts on i8 vectors nor a lane interleave, so the bytes are
widened to int32 first and the two nibble planes, concatenated along lanes,
are put back in channel order by ONE matmul with a constant 0/1 permutation
matrix (exact: codes and 0/1 are bf16-representable, the MXU accumulates in
f32) before the scale multiply. A zero byte unpacks to code -8,
which a fresh page's zero scale dequantizes to 0 — the fresh-page-zeroing
and quarantine contracts carry through the kernel untouched.

Padded work items (live = 0, table row all trash) produce EXACT zero
outputs: every page is dead, the flash state never accumulates, and the
finalize's l clamp turns 0/eps into 0 — so the engine can dispatch a
fixed-width descriptor and ignore the padding lanes.

Kill-switch: ``PERCEIVER_IO_TPU_DISABLE_DECODE_KERNEL`` (shared with the
dense and legacy paged kernels) forces the XLA fallback.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from perceiver_io_tpu.ops.decode_kernel import _blockdiag_queries, _head_expander, _rotary_scores
from perceiver_io_tpu.ops.flash import single_device_trace
from perceiver_io_tpu.ops.paged_decode_kernel import _page_has_live


def ragged_paged_supported(
    page_size: int, num_qk: int, num_v: int, num_heads: int = 1,
    quantized: bool = False, qbits: int = 8,
) -> bool:
    """Ragged paged attention on TPU: the legacy kernel's constraints, plus
    int4 pools (which the legacy single-query kernel gates out — the nibble
    unpack only exists here). Pools sharded over a mesh still take the XLA
    fallback."""
    import os

    if os.environ.get("PERCEIVER_IO_TPU_DISABLE_DECODE_KERNEL", "0").lower() not in ("0", "false", ""):
        return False
    if jax.default_backend() != "tpu" or not single_device_trace():
        return False
    return (
        num_qk == num_v
        and num_heads <= 128  # per-head stats live in one (8, 128) scratch row
        and page_size % 8 == 0  # sublane-aligned page blocks
        and page_size >= 8
        and (not quantized or page_size % 32 == 0)  # int8/uint8 tile alignment
        # int4: the unpack concatenates the two nibble planes along lanes,
        # so each (C // 2 wide) plane must be whole 128-lane tiles
        and (qbits == 8 or num_qk % 256 == 0)
    )


def _nibble_interleave(hd: int):
    """Constant (hd, hd) 0/1 matrix P with ([lo | hi] @ P)[:, 2j] = lo[:, j]
    and [:, 2j+1] = hi[:, j]: restores logical channel order from the two
    lane-concatenated nibble planes of an int4 block."""
    import numpy as np

    perm = np.zeros((hd, hd), np.float32)
    j = np.arange(hd // 2)
    perm[j, 2 * j] = 1.0
    perm[hd // 2 + j, 2 * j + 1] = 1.0
    return perm


def _unpack_nibbles(block: jax.Array, perm: jax.Array) -> jax.Array:
    """(ps, hd // 2) uint8 -> (ps, hd) f32 integer codes in logical channel
    order; value-identical to ``_unpack_codes(block, 4)`` (module docstring)."""
    b = block.astype(jnp.int32)
    planes = jnp.concatenate([(b & 0xF) - 8, (b >> 4) - 8], axis=-1)
    return jax.lax.dot_general(
        planes.astype(jnp.float32).astype(jnp.bfloat16), perm,
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )


def _ragged_kernel(*refs, window, skip_dead_pages, quantized, qbits):
    """Grid (W, P); step (wi, i) covers physical ring positions
    [i*ps, (i+1)*ps) of work item wi, DMA'd through the item's page-table row.

    start_ref (W,)        EFFECTIVE ring offset (causal bound already folded)
    live_ref  (W,)        EFFECTIVE live count
    table_ref (W, P)      physical page ids per work item
    qq_ref    (2, h*d, h) block-diagonal scaled+rotated query of item wi and
                          its rotate-half (decode_kernel._blockdiag_queries)
    k_ref     (1, ps, c)  one pool page (c = h*d, or h*d // 2 packed int4),
                          keys unrotated: the rotation is applied on the
                          query side (decode_kernel._rotary_scores)
    v_ref     (1, ps, c)
    ang_ref   (1, ps, r)  rotary angles per PHYSICAL position of item wi
    exp_ref   (h, h*d)    head->channel expander
    kscale_ref, vscale_ref (1, P, h)  quantized pools: the item's page scales
    perm_ref  (h*d, h*d)  int4 pools: nibble-plane interleave (bf16 0/1)
    o_ref     (1, 1, h*d) output row
    scratch: m, l (8, 128) VMEM (per-head stats in row 0), acc (8, h*d)

    Identical flash loop to ops/paged_decode_kernel._paged_kernel — the grid
    walks work items instead of batch rows, and int4 blocks unpack in-stream
    before the fused dequant. Dead pages alias + skip exactly as there."""
    import jax.experimental.pallas as pl

    (start_ref, live_ref, table_ref, qq_ref, k_ref, v_ref, ang_ref,
     exp_ref, *quant_refs, o_ref, m_ref, l_ref, acc_ref) = refs
    kscale_ref, vscale_ref = quant_refs[:2] if quantized else (None, None)
    perm_ref = quant_refs[2] if quantized and qbits == 4 else None

    wi = pl.program_id(0)
    i = pl.program_id(1)
    nblocks = pl.num_programs(1)
    ps = k_ref.shape[1]
    h = exp_ref.shape[0]
    contract = (((1,), (0,)), ((), ()))

    @pl.when(i == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    start = start_ref[wi]
    live = live_ref[wi]
    compute = _page_has_live(i, start, live, window, ps) if skip_dead_pages else i >= 0

    @pl.when(compute)
    def _compute():
        if quantized and qbits == 4:
            # in-stream nibble unpack: (ps, h*d // 2) uint8 -> (ps, h*d) f32
            # integer codes (low nibble = even logical channel, high = odd)
            k = _unpack_nibbles(k_ref[0], perm_ref[:])
        else:
            k = k_ref[0].astype(jnp.float32)  # (ps, h*d)
        if quantized:
            # the fetched block IS page table_ref[wi, i] whenever compute
            # runs (live page -> no alias), whose scales are row i of this
            # item's gathered sidecar; expand head -> channels through the
            # 0/1 expander
            kscale = kscale_ref[0, pl.ds(i, 1), :]  # (1, h)
            vscale = vscale_ref[0, pl.ds(i, 1), :]
            kexp = jax.lax.dot_general(kscale, exp_ref[:], contract,
                                       preferred_element_type=jnp.float32)
            vexp = jax.lax.dot_general(vscale, exp_ref[:], contract,
                                       preferred_element_type=jnp.float32)
            k = k * kexp  # fused dequant, before the rotary products — the fallback's order
        sc = _rotary_scores(k, ang_ref[0].astype(jnp.float32), qq_ref, h)  # (ps, h)
        slot = i * ps + jax.lax.broadcasted_iota(jnp.int32, (ps, 1), 0)
        lp = jnp.mod(slot - start, window)
        visible = (lp >= window - live) & (slot < window)  # (ps, 1)
        sc = jnp.where(visible, sc, -jnp.inf)

        m_prev = m_ref[0:1, :h]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=0, keepdims=True))  # (1, h)
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        scale = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - safe_m), 0.0)  # (1, h)
        prob = jnp.exp(jnp.where(jnp.isfinite(sc), sc - safe_m, -jnp.inf))  # (ps, h)

        prob_x = jax.lax.dot_general(prob, exp_ref[:], contract, preferred_element_type=jnp.float32)
        if quantized and qbits == 4:
            v = _unpack_nibbles(v_ref[0], perm_ref[:])
        else:
            v = v_ref[0].astype(jnp.float32)
        if quantized:
            v = v * vexp  # fused value dequant
        pv = jnp.sum(prob_x * v, axis=0, keepdims=True)  # (1, h*d)
        scale_x = jax.lax.dot_general(scale, exp_ref[:], contract, preferred_element_type=jnp.float32)

        m_ref[0:1, :h] = m_new
        l_ref[0:1, :h] = l_ref[0:1, :h] * scale + jnp.sum(prob, axis=0, keepdims=True)
        acc_ref[0:1, :] = acc_ref[0:1, :] * scale_x + pv

    @pl.when(i == nblocks - 1)
    def _finalize():
        # a fully-dead item (padding lane: live = 0) never accumulated:
        # l = 0 clamps to eps and acc = 0 divides to an EXACT zero output
        l = jnp.maximum(l_ref[0:1, :h], 1e-30)
        l_x = jax.lax.dot_general(1.0 / l, exp_ref[:], contract, preferred_element_type=jnp.float32)
        o_ref[0] = (acc_ref[0:1, :] * l_x).astype(o_ref.dtype)


def fold_causal_bound(start: jax.Array, live: jax.Array,
                      causal_bound: jax.Array, window: int):
    """Fold a per-item causal bound into (start, live): the visibility window
    ``[window - live, causal_bound]`` under ``start`` equals plain decode
    visibility ``[window - eff_live, window)`` under ``eff_start`` (module
    docstring derivation). Shared by the kernel dispatch and the XLA
    reference so both mask the identical position set."""
    cut = (window - 1) - jnp.asarray(causal_bound, jnp.int32)
    eff_start = jnp.mod(jnp.asarray(start, jnp.int32) - cut, window)
    eff_live = jnp.maximum(jnp.asarray(live, jnp.int32) - cut, 0)
    return eff_start, eff_live


@functools.partial(jax.jit, static_argnames=("window", "skip_dead_pages",
                                             "interpret", "qbits"))
def fused_ragged_paged_attention(
    q: jax.Array,
    kp: jax.Array,
    vp: jax.Array,
    page_table: jax.Array,
    start: jax.Array,
    live: jax.Array,
    causal_bound: jax.Array,
    rope_k: jax.Array,
    window: int,
    skip_dead_pages: bool = True,
    interpret: bool = False,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    qbits: int = 8,
) -> jax.Array:
    """q (W, H, 1, D): one scaled+rotated query per WORK ITEM; kp/vp
    (N, ps, H*D) unrotated page pool ((N, ps, H*D // 2) uint8 nibble-packed
    when ``qbits=4``); page_table (W, P) page-table row per item (a slot
    finishing L latents contributes its row L times); start (W,) post-append
    ring offsets; live (W,) live-entry counts; causal_bound (W,) last visible
    LOGICAL window position per item (window - 1 = plain decode; a padding
    lane passes live = 0 and gets an exact zero row back); rope_k
    (W, P*ps, R) angles per PHYSICAL ring position. Returns (W, H, 1, D).

    Decode items are BITWISE ``fused_paged_decode_attention`` (same flash
    loop, same prefetch values — pinned in interpret mode); finish items pin
    against the XLA masked-softmax oracle at fp tolerance."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    w, h, n_q, d = q.shape
    assert n_q == 1, "ragged items are single-query rows (module docstring)"
    n_pages, ps, c_phys = kp.shape
    hd = h * d
    p = page_table.shape[1]
    r = rope_k.shape[-1]
    quantized = k_scale is not None
    assert (k_scale is None) == (v_scale is None), "pass both scales or neither"
    if quantized and qbits == 4:
        assert c_phys * 2 == hd, "int4 pool stores nibble-packed channel pairs"
    else:
        assert c_phys == hd

    start, live = fold_causal_bound(start, live, causal_bound, window)

    def _alias(i, start_ref, live_ref, wi):
        # dead pages alias the newest live position's page — fetched anyway,
        # and consecutive equal indices elide the DMA
        if not skip_dead_pages:
            return i
        s, lv = start_ref[wi], live_ref[wi]
        newest = jnp.mod(s - 1, window) // ps
        return jnp.where(_page_has_live(i, s, lv, window, ps), i, newest)

    def _kv_map(wi, i, start_ref, live_ref, table_ref, *_):
        return (table_ref[wi, _alias(i, start_ref, live_ref, wi)], 0, 0)

    def _ang_map(wi, i, start_ref, live_ref, table_ref, *_):
        return (wi, _alias(i, start_ref, live_ref, wi), 0)

    page_table = jnp.asarray(page_table, jnp.int32)
    prefetch = [start, live, page_table]
    in_specs = [
        pl.BlockSpec((None, 2, hd, h), lambda wi, i, *_: (wi, 0, 0, 0)),
        pl.BlockSpec((1, ps, c_phys), _kv_map),
        pl.BlockSpec((1, ps, c_phys), _kv_map),
        pl.BlockSpec((1, ps, r), _ang_map),
        pl.BlockSpec((h, hd), lambda wi, i, *_: (0, 0)),
    ]
    quant_operands = []
    if quantized:
        # per-item (P, H) scale tables gathered through the item's table row
        # (ops/paged_decode_kernel.py: the (N, H) sidecars outgrow SMEM)
        quant_operands = [jnp.asarray(k_scale, jnp.float32)[page_table],
                          jnp.asarray(v_scale, jnp.float32)[page_table]]
        in_specs += [pl.BlockSpec((1, p, h), lambda wi, i, *_: (wi, 0, 0))] * 2
        if qbits == 4:
            quant_operands.append(jnp.asarray(_nibble_interleave(hd), jnp.bfloat16))
            in_specs.append(pl.BlockSpec((hd, hd), lambda wi, i, *_: (0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(w, p),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, hd), lambda wi, i, *_: (wi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((8, 128), jnp.float32),
            pltpu.VMEM((8, 128), jnp.float32),
            pltpu.VMEM((8, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_ragged_kernel, window=window,
                          skip_dead_pages=skip_dead_pages,
                          quantized=quantized, qbits=qbits),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((w, 1, hd), q.dtype),
        interpret=interpret,
    )(
        *prefetch,
        _blockdiag_queries(q, r),  # column ``head`` scores head ``head``
        kp,
        vp,
        rope_k,
        jnp.asarray(_head_expander(h, d)),
        *quant_operands,
    )
    return out.reshape(w, 1, h, d).transpose(0, 2, 1, 3)


def ragged_reference_attention(
    q: jax.Array,
    k_dense: jax.Array,
    v_dense: jax.Array,
    start: jax.Array,
    live: jax.Array,
    causal_bound: jax.Array,
    window: int,
) -> jax.Array:
    """XLA masked-softmax oracle over DEQUANTIZED dense-gathered pages:
    q (W, H, 1, D) rotated+scaled queries, k_dense/v_dense (W, P*ps, H*D)
    ROTATED keys / values in physical ring order (PagedKVCache.gather_dense
    followed by the rope the kernel fuses). Masks the identical position set
    as the kernel — ``fold_causal_bound`` + the plain decode visibility —
    then one softmax per item. The correctness oracle tests pin against."""
    w, h, _, d = q.shape
    n_phys = k_dense.shape[1]
    eff_start, eff_live = fold_causal_bound(start, live, causal_bound, window)
    rpos = jnp.arange(n_phys)[None, :]
    lp = jnp.mod(rpos - eff_start[:, None], window)
    visible = (lp >= (window - eff_live)[:, None]) & (rpos < window)  # (W, n)
    kh = k_dense.reshape(w, n_phys, h, d)
    vh = v_dense.reshape(w, n_phys, h, d)
    sc = jnp.einsum("whqd,wnhd->whqn", q, kh)
    sc = jnp.where(visible[:, None, None, :], sc, -jnp.inf)
    # a fully-masked item (padding lane) softmaxes NaN-free to zeros
    m = jnp.max(sc, axis=-1, keepdims=True)
    safe_m = jnp.where(jnp.isfinite(m), m, 0.0)
    prob = jnp.exp(jnp.where(jnp.isfinite(sc), sc - safe_m, -jnp.inf))
    denom = jnp.maximum(jnp.sum(prob, axis=-1, keepdims=True), 1e-30)
    return jnp.einsum("whqn,wnhd->whqd", prob / denom, vh)
