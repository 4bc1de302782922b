"""Paged ragged decode attention: the fused decode kernel generalized to a
page-table-indirected KV layout (the Ragged Paged Attention recipe, PAPERS.md).

The dense decode kernel (ops/decode_kernel.py) streams a per-slot (B, cap, C)
KV ring buffer. The serving engine's slot pool pins that layout at FULL window
capacity per slot, so HBM cost scales with pool capacity rather than live
tokens. The paged layout breaks the per-slot reservation:

  * one physical **page pool** ``kp``/``vp`` of shape (num_pages, page_size, C)
    shared by every slot (page 0 is the reserved trash/garbage page — free
    slots read and write it; its contents are never harvested);
  * a per-slot **page table** (B, P) of physical page ids mapping the slot's
    logical window onto pool pages (P = ceil(window / page_size); a window the
    page size does not divide leaves the tail of the last page unused and
    permanently masked);
  * a per-slot ring offset ``start``: physical ring position r holds LOGICAL
    window position ``(r - start) mod window``. A full-window append is then
    O(1) — write the new token at ring position ``start`` (the slot that held
    the dropped oldest token) and advance ``start`` — where the dense layout
    ROLLS the whole (B, cap, C) buffer every token.

Masking collapses to one bound: with ``live`` live (non-pad) entries, logical
positions ``[window - live, window)`` are visible — no pad-slot buffer at all.
The kernel's grid walks PHYSICAL pages; the index maps gather each page
through the scalar-prefetched page table, pages with no live position alias
the newest token's page (consecutive equal indices elide the DMA, so HBM
traffic scales with live tokens), and their compute is skipped. Skipping is
exact for the same reason as the dense kernel: an all-masked page contributes
prob = 0 and rescales the flash state by exp(0) = 1, so omitting it leaves
m/l/acc bit-identical (tests/test_paging.py pins this).

The XLA fallback (the masked-softmax path in ops/attention.py's paged branch)
gathers the pages dense and applies the same visibility bound — bitwise the
same masking contract, used on CPU and wherever ``paged_decode_supported``
says no.

Quantized pages (int8; docs/serving.md "Quantized KV pages & weight
serving"): with ``kv_quant="int8"`` each (page, head) stores int8 KV plus a
per-page-per-head float32 SCALE sidecar (``k_scale``/``v_scale``, shape
(num_pages, num_heads); dequant ``x̂ = q * s``, ``s = amax / 127`` over the
page's rows of that head). Every write path quantizes: whole-page writes
(``write_pages`` — the one-shot install; ``write_rows`` — page-aligned chunk
blocks) stamp a fresh scale per page so a page's bytes are a pure function
of its tokens (the prefix-cache byte-interchange contract survives
quantization), while the per-token ring append (``append_token``) RATCHETS:
the page scale grows monotonically to cover the incoming row and the page's
existing int8 entries are requantized by the exact old/new ratio — one extra
page read-modify-write per token, marginal next to the full-window page
gather the decode attention itself performs. A freshly allocated page's
scale is reset to 0 (``reset_page_scales`` / the install's full-row scale
stamp), which makes the first ratcheted write ZERO any stale bytes a
previous tenant left — pool history can never leak into a new session's
bytes. The fused kernel gains a dequant-fused variant (each row's scales,
gathered through its page table, arrive as one small VMEM block; dead-page
skip and ring-offset semantics unchanged), pinned BITWISE in interpret mode
against feeding the XLA-dequantized f32 pool through the same kernel;
``gather_dense``/
``gather_slot`` dequantize for the XLA fallback and the prefill-finish so
CPU and sharded pools serve the same layout.

int4 pages (``kv_quant="int4"``): the same per-page-per-head scale layout
with 4-bit codes — q = clip(round(x / s), ±7), s = amax / 7 — stored OFFSET
(n = q + 8) and nibble-packed two per byte along the channel axis, so the
pool's physical last dim is C // 2 uint8 and resident KV bytes halve again
vs int8. Every write/gather path shares the int8 machinery through
``_pack_codes``/``_unpack_codes``; a freshly zeroed page's bytes unpack to
code -8 under scale 0, so the fresh-page-zeroing and quarantine contracts
carry over byte-for-byte. The unified ragged kernel
(ops/ragged_paged_kernel.py) fuses the nibble unpack + dequant in-stream;
this module's legacy single-query kernel serves int8/fp only
(``paged_decode_supported`` gates on ``qbits``) and the XLA fallback serves
int4 wherever the ragged kernel does not run.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import flax.struct
import jax
import jax.numpy as jnp

from perceiver_io_tpu.ops.decode_kernel import _blockdiag_queries, _head_expander, _rotary_scores
from perceiver_io_tpu.ops.flash import single_device_trace

# supported quantized-page modes (serving/engine.py `kv_quant` knob)
KV_QUANT_MODES = ("int8", "int4")
# int8 quantization: q = clip(round(x / s), -127, 127), s = amax / 127 —
# symmetric, -128 unused so dequant never exceeds the observed amax
_QMAX = 127.0
# int4 quantization: q = clip(round(x / s), -7, 7), s = amax / 7 — symmetric,
# codes stored OFFSET (n = q + 8 in [1, 15]) and nibble-packed two per byte
# along the channel axis, so the pool's physical last dim is C // 2 uint8.
# A zeroed byte (fresh/trash page) unpacks to code -8, which the zero scale
# of a fresh page dequantizes to 0 — the zeroing contract carries over.
_QMAX4 = 7.0


def _qmax_for(qbits: int) -> float:
    return _QMAX4 if qbits == 4 else _QMAX


def quant_mode_qbits(kv_quant: Optional[str]) -> int:
    """Code width of a ``kv_quant`` mode string (8 for fp/int8 pools — fp
    pools never consult it)."""
    return 4 if kv_quant == "int4" else 8


def _pack_codes(vals: jax.Array, qbits: int) -> jax.Array:
    """Integer code VALUES (f32, already clipped) -> stored pool bytes: int8
    for 8-bit pools, offset nibble-packed uint8 (even logical channel in the
    low nibble, odd in the high) for 4-bit pools."""
    if qbits == 8:
        return vals.astype(jnp.int8)
    n = (vals.astype(jnp.int32) + 8).astype(jnp.uint8)
    return (n[..., ::2] | (n[..., 1::2] << 4)).astype(jnp.uint8)


def _unpack_codes(blocks: jax.Array, qbits: int) -> jax.Array:
    """Stored pool bytes -> f32 integer code values; the channel axis is
    restored to its LOGICAL width for 4-bit pools (inverse of _pack_codes)."""
    if qbits == 8:
        return blocks.astype(jnp.float32)
    lo = (blocks & 0xF).astype(jnp.int32) - 8
    hi = (blocks >> 4).astype(jnp.int32) - 8
    inter = jnp.stack([lo, hi], axis=-1)
    return inter.reshape(*blocks.shape[:-1], blocks.shape[-1] * 2).astype(jnp.float32)


def _amax_per_head(rows: jax.Array, num_heads: int) -> jax.Array:
    """Per-head abs-max of ``rows`` (..., n, H*d) over the row and channel
    axes of each head -> (..., H). The quantization scope is (page, head):
    one scale covers every row and channel the head owns in that page."""
    d = rows.shape[-1] // num_heads
    r = rows.reshape(*rows.shape[:-2], rows.shape[-2], num_heads, d)
    return jnp.max(jnp.abs(r), axis=(-3, -1))


def _expand_scale(scale: jax.Array, d: int) -> jax.Array:
    """(..., H) per-head scales -> (..., H*d) per-channel (head-major channel
    order, matching the (H, d) reshape everywhere in this module)."""
    return jnp.repeat(scale, d, axis=-1)


def _quantize_values(rows_f32: jax.Array, scale: jax.Array, d: int,
                     qmax: float) -> jax.Array:
    """Integer code values (f32, NOT yet stored) of ``rows_f32`` (..., n, H*d)
    under per-head ``scale`` (..., H): q = clip(round(x / s), ±qmax); a zero
    scale (all-zero page) yields zero codes instead of a division blowup."""
    sc = _expand_scale(scale, d)[..., None, :]
    safe = jnp.where(sc > 0, sc, 1.0)
    q = jnp.where(sc > 0, jnp.round(rows_f32 / safe), 0.0)
    return jnp.clip(q, -qmax, qmax)


def _quantize_blocks(rows_f32: jax.Array, scale: jax.Array, d: int,
                     qbits: int = 8) -> jax.Array:
    """Quantize and STORE ``rows_f32`` (..., n, H*d): int8 codes for 8-bit
    pools, nibble-packed uint8 (last dim halved) for 4-bit pools."""
    return _pack_codes(
        _quantize_values(rows_f32, scale, d, _qmax_for(qbits)), qbits
    )


class PagedKVCache(flax.struct.PyTreeNode):
    """Paged cross-attention KV state for ONE batched decode pool.

    ``kp`` / ``vp``: (num_pages, page_size, C) physical page pool, shared by
        all batch rows. Page 0 is reserved as the trash page: free slots'
        table entries point at it, their per-tick writes land in it, and its
        contents are garbage by design (finite — only projected embeddings
        are ever written — but never read into a harvested output).
    ``page_table``: (B, P) int32 physical page id per logical page.
    ``start``: (B,) int32 ring offset — physical position r holds logical
        window position ``(r - start) mod window``; the NEXT append writes at
        physical position ``start``.
    ``window``: static logical window length (<= P * page_size).

    Unlike the dense ``KVCache`` there is no shared ``length``: the serving
    pool pins every slot at full window occupancy, so validity is fully encoded by the per-row
    ``live`` count threaded alongside (PagedPerceiverARCache.live).
    """

    kp: jax.Array
    vp: jax.Array
    page_table: jax.Array
    start: jax.Array
    window: int = flax.struct.field(pytree_node=False)
    # quantized mode (int8 pages): per-page-per-head float32 scale sidecars,
    # None on full-precision pools — the fp paths trace exactly as before
    k_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None
    # head count of the serving attention layer — the quantization grouping
    # (scale scope = one head's channels within one page); unused (1) on fp
    num_heads: int = flax.struct.field(pytree_node=False, default=1)
    # stored code width: 8 (int8 pools — and ignored on fp pools) or 4
    # (nibble-packed int4 pools, physical last dim = logical channels // 2)
    qbits: int = flax.struct.field(pytree_node=False, default=8)

    @property
    def page_size(self) -> int:
        return self.kp.shape[1]

    @property
    def num_pages(self) -> int:
        return self.kp.shape[0]

    @property
    def pages_per_slot(self) -> int:
        return self.page_table.shape[1]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def num_channels(self) -> int:
        """LOGICAL channel count H*d — int4 pools pack two codes per stored
        byte, so their physical last dim is half this."""
        c = self.kp.shape[-1]
        return c * 2 if (self.quantized and self.qbits == 4) else c

    @property
    def head_dim(self) -> int:
        return self.num_channels // self.num_heads

    def append_token(self, k_new: jax.Array, v_new: jax.Array) -> "PagedKVCache":
        """Write one token's (B, 1, C) keys/values at each row's ring position
        ``start`` — through the page table — and advance ``start``. O(1) per
        token: the dense layout's full-buffer roll becomes a B-row scatter.
        Rows whose table maps the write page to the trash page (free slots)
        harmlessly deposit garbage there; distinct live slots never share a
        writable page (the page pool's allocation invariant).

        Quantized pools RATCHET the write page's per-head scale: the scale
        grows (never shrinks) to cover the incoming row and the page's
        existing int8 entries are requantized by the exact ``old/new`` ratio
        (|q'| <= |q| <= 127, no clipping introduced). A fresh page's scale is
        0, so its first write zeroes whatever stale bytes the previous tenant
        left — bytes are a pure function of this slot's write history, never
        of pool history (the determinism contract chaos pins). The ratchet's
        page read-modify-write is O(page) per row — marginal next to the
        full-window page gather the decode attention performs each token."""
        b = k_new.shape[0]
        ps = self.page_size
        bidx = jnp.arange(b)
        page_ids = self.page_table[bidx, self.start // ps]
        offs = self.start % ps
        if not self.quantized:
            return self.replace(
                kp=self.kp.at[page_ids, offs].set(k_new[:, 0].astype(self.kp.dtype)),
                vp=self.vp.at[page_ids, offs].set(v_new[:, 0].astype(self.vp.dtype)),
                start=jnp.mod(self.start + 1, self.window),
            )
        h, d = self.num_heads, self.head_dim
        qmax = _qmax_for(self.qbits)

        def upd(pool, scales, row):
            row = row.astype(jnp.float32)  # (B, C)
            rmax = jnp.max(jnp.abs(row.reshape(b, h, d)), axis=-1)  # (B, H)
            old = scales[page_ids]  # (B, H)
            new = jnp.maximum(old, rmax / qmax)
            # old == 0 (fresh page) -> ratio 0: stale tenant bytes are zeroed
            ratio = jnp.where(new > 0, old / jnp.where(new > 0, new, 1.0), 0.0)
            pages = _unpack_codes(pool[page_ids], self.qbits)  # (B, ps, C)
            pages = jnp.round(pages * _expand_scale(ratio, d)[:, None, :])
            qrow = _quantize_values(row[:, None, :], new, d, qmax)[:, 0]  # (B, C)
            pages = pages.at[bidx, offs].set(qrow)
            return (pool.at[page_ids].set(_pack_codes(pages, self.qbits)),
                    scales.at[page_ids].set(new))

        kp, ks = upd(self.kp, self.k_scale, k_new[:, 0])
        vp, vs = upd(self.vp, self.v_scale, v_new[:, 0])
        return self.replace(
            kp=kp, vp=vp, k_scale=ks, v_scale=vs,
            start=jnp.mod(self.start + 1, self.window),
        )

    def write_rows(
        self,
        table_row: jax.Array,
        offset: jax.Array,
        count: jax.Array,
        k_rows: jax.Array,
        v_rows: jax.Array,
    ) -> "PagedKVCache":
        """Bulk-write ``count`` consecutive KV rows of ONE slot's ring —
        physical positions ``[offset, offset + count)`` — through the slot's
        ``table_row`` (P,), the chunked-prefill write primitive
        (docs/serving.md "Chunked prefill"). ``k_rows``/``v_rows`` are
        (C_max, channels) with a STATIC row capacity drawn from the prefill
        bucket ladder; rows at index >= ``count`` (chunk padding) are routed
        to the trash page 0 with a ZERO payload, so duplicate trash-page
        scatter indices carry identical payloads and the pool stays
        deterministic (the quarantine discipline). Real rows always map to
        allocated table entries: the engine only writes positions inside the
        slot's reservation, and never below a shared prefix's boundary.

        Quantized pools take a PAGE-BLOCK path instead of the row scatter:
        the engine guarantees every quantized chunk write starts page-aligned
        (``prefill_chunk_tokens`` must be a multiple of the page size — ctor
        validated), so rows group into whole local pages. Each page covered
        by real rows is written WHOLE (rows past ``count`` as zeros — the
        partial tail page's unwritten rows become deterministic zeros instead
        of stale garbage) with a fresh per-head scale over exactly its
        written rows; a page's bytes are therefore a pure function of its
        tokens, byte-interchangeable with an install-built page — the
        property the cross-request prefix cache keys on. Blocks with no real
        row write zero payloads + zero scales to the trash page, exactly the
        fp path's padding discipline."""
        cmax = k_rows.shape[0]
        ps = self.page_size
        p = self.page_table.shape[1]
        if not self.quantized:
            j = jnp.arange(cmax)
            phys = offset + j
            real = j < count
            pidx = jnp.clip(phys // ps, 0, p - 1)
            page_ids = jnp.where(real, table_row[pidx], 0)
            offs = jnp.where(real, phys % ps, 0)
            kz = jnp.where(real[:, None], k_rows, 0).astype(self.kp.dtype)
            vz = jnp.where(real[:, None], v_rows, 0).astype(self.vp.dtype)
            return self.replace(
                kp=self.kp.at[page_ids, offs].set(kz),
                vp=self.vp.at[page_ids, offs].set(vz),
            )
        h, d = self.num_heads, self.head_dim
        lp = -(-cmax // ps)  # local pages the static row capacity can span
        pad = lp * ps - cmax
        j = jnp.arange(lp * ps)
        real = j < count
        li = jnp.arange(lp)
        block_real = (li * ps) < count  # block l holds >= 1 real row
        pidx = jnp.clip(offset // ps + li, 0, p - 1)
        page_ids = jnp.where(block_real, table_row[pidx], 0)

        def q(rows, pool, scales):
            rz = jnp.pad(rows.astype(jnp.float32), ((0, pad), (0, 0)))
            rz = jnp.where(real[:, None], rz, 0.0)
            blocks = rz.reshape(lp, ps, h * d)
            scale = _amax_per_head(blocks, h) / _qmax_for(self.qbits)  # (lp, H)
            qb = _quantize_blocks(blocks, scale, d, self.qbits)
            return (
                pool.at[page_ids].set(qb),
                scales.at[page_ids].set(jnp.where(block_real[:, None], scale, 0.0)),
            )

        kp, ks = q(k_rows, self.kp, self.k_scale)
        vp, vs = q(v_rows, self.vp, self.v_scale)
        return self.replace(kp=kp, vp=vp, k_scale=ks, v_scale=vs)

    def write_pages(
        self, ids: jax.Array, k_blocks: jax.Array, v_blocks: jax.Array
    ) -> "PagedKVCache":
        """Overwrite whole pages ``ids`` (nb,) with ``k_blocks``/``v_blocks``
        (nb, ps, C) — the one-shot install's page scatter
        (PagedPerceiverARCache.install_slot). Quantized pools stamp a fresh
        per-head scale per page (amax over exactly the page's rows), so an
        install-built page is byte-interchangeable with a chunk-built one."""
        if not self.quantized:
            return self.replace(
                kp=self.kp.at[ids].set(k_blocks.astype(self.kp.dtype)),
                vp=self.vp.at[ids].set(v_blocks.astype(self.vp.dtype)),
            )
        h, d = self.num_heads, self.head_dim

        def q(blocks, pool, scales):
            bf = blocks.astype(jnp.float32)
            scale = _amax_per_head(bf, h) / _qmax_for(self.qbits)  # (nb, H)
            return (
                pool.at[ids].set(_quantize_blocks(bf, scale, d, self.qbits)),
                scales.at[ids].set(scale),
            )

        kp, ks = q(k_blocks, self.kp, self.k_scale)
        vp, vs = q(v_blocks, self.vp, self.v_scale)
        return self.replace(kp=kp, vp=vp, k_scale=ks, v_scale=vs)

    def reset_page_scales(self, ids: jax.Array) -> "PagedKVCache":
        """Zero the scale sidecars of pages ``ids`` — the engine runs this
        over a split admission's PRIVATE reservation before any chunk writes
        (a page's first ratcheted append then zeroes stale tenant bytes:
        scale 0 makes the requantize ratio 0). Shared prefix pages are never
        reset — their scales belong to the cached bytes. No-op on fp pools;
        duplicate ids (trash-page padding) re-zero page 0 harmlessly."""
        if not self.quantized:
            return self
        return self.replace(
            k_scale=self.k_scale.at[ids].set(0.0),
            v_scale=self.v_scale.at[ids].set(0.0),
        )

    def gather_dense(self):
        """(B, P*page_size, C) dense view through the page table — the XLA
        fallback's input. Materializes the full logical window per row; the
        kernel path exists so the serving hot loop never does. Quantized
        pools dequantize through the gathered scales (``q.astype(f32) * s``
        — the exact multiply the fused kernel performs, so fallback and
        kernel read identical values)."""
        b = self.page_table.shape[0]
        k = self.kp[self.page_table]  # (B, P, ps, C) (C//2 stored for int4)
        v = self.vp[self.page_table]
        if self.quantized:
            d = self.head_dim
            k = _unpack_codes(k, self.qbits) * _expand_scale(
                self.k_scale[self.page_table], d)[:, :, None, :]
            v = _unpack_codes(v, self.qbits) * _expand_scale(
                self.v_scale[self.page_table], d)[:, :, None, :]
        c = self.num_channels
        return (k.reshape(b, -1, c), v.reshape(b, -1, c))

    def gather_slot(self, table_row: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """ONE slot's page rows in physical ring order, (1, P*ps, C) —
        dequantized on quantized pools: the chunked-prefill FINISH reads the
        slot's already-written pages through this (models/core/perceiver_ar.
        prefill_latents_paged), so its latents see exactly the bytes decode
        will gather — quantization error included, uniformly."""
        k = self.kp[table_row]  # (P, ps, C) (C//2 stored for int4)
        v = self.vp[table_row]
        if self.quantized:
            d = self.head_dim
            k = _unpack_codes(k, self.qbits) * _expand_scale(self.k_scale[table_row], d)[:, None, :]
            v = _unpack_codes(v, self.qbits) * _expand_scale(self.v_scale[table_row], d)[:, None, :]
        c = self.num_channels
        return (k.reshape(1, -1, c), v.reshape(1, -1, c))


def paged_visibility(start: jax.Array, live: jax.Array, window: int, n_phys: int) -> jax.Array:
    """(B, n_phys) bool: physical position r is VISIBLE iff its logical window
    position ``(r - start) mod window`` lies in the live tail
    ``[window - live, window)`` and r addresses a real window slot (r <
    window — the unused tail of a partial last page is never visible). The
    single masking contract shared bit-for-bit by the kernel and the XLA
    fallback."""
    r = jnp.arange(n_phys)[None, :]
    lp = jnp.mod(r - start[:, None], window)
    return (lp >= (window - live)[:, None]) & (r < window)


def paged_decode_supported(
    page_size: int, num_qk: int, num_v: int, num_heads: int = 1, n_q: int = 1,
    quantized: bool = False, qbits: int = 8,
) -> bool:
    """Single-query paged decode on TPU: symmetric qk/v widths, sublane-aligned
    pages. Pools sharded over a mesh are not yet mapped onto this kernel (the
    paged pool is a single shared buffer; shard_map dispatch is future work)
    — the XLA fallback serves those; a one-chip pool on a many-chip host is a
    one-device trace and takes the kernel. Quantized (int8) pools additionally
    need 32-row pages (the int8 VMEM tile is (32, 128)); the XLA fallback serves
    smaller quantized pages with the identical dequant + masking contract.
    Kill-switch: PERCEIVER_IO_TPU_DISABLE_DECODE_KERNEL (shared with the
    dense kernel)."""
    import os

    if os.environ.get("PERCEIVER_IO_TPU_DISABLE_DECODE_KERNEL", "0").lower() not in ("0", "false", ""):
        return False
    if jax.default_backend() != "tpu" or not single_device_trace():
        return False
    return (
        n_q == 1  # the engine's decode mode; chunked verification stays dense
        and num_qk == num_v
        and num_heads <= 128  # per-head stats live in one (8, 128) scratch row
        and page_size % 8 == 0  # sublane-aligned page blocks
        and page_size >= 8
        and (not quantized or page_size % 32 == 0)  # int8 tile alignment
        # nibble-packed int4 pools are served by the RAGGED kernel
        # (ops/ragged_paged_kernel.py) or the XLA fallback — this legacy
        # single-query kernel only streams int8/fp blocks
        and (not quantized or qbits == 8)
    )


def _page_has_live(i, start, live, window: int, page_size: int):
    """Does physical page ``i`` contain ANY live position? The live region is
    the wrapped ring interval [start - live, start) (mod window). A page
    intersects it iff the interval's first position s0 falls inside the page,
    or the page's first row is itself live. Exact, branch-free — usable in
    index maps (traced scalars only)."""
    p0 = i * page_size
    p1 = jnp.minimum(p0 + page_size, window) - 1
    s0 = jnp.mod(start - live, window)
    return (live > 0) & (((s0 >= p0) & (s0 <= p1)) | (jnp.mod(p0 - s0, window) < live))


def _paged_kernel(*refs, window, skip_dead_pages, quantized):
    """Grid (B, P); step (bi, i) covers physical ring positions
    [i*ps, (i+1)*ps) of row bi, DMA'd through the page table.

    start_ref (B,)        post-append ring offset (scalar prefetch, SMEM)
    live_ref  (B,)        live (non-pad) entries per row
    table_ref (B, P)      physical page ids
    qq_ref    (2, h*d, h) block-diagonal scaled+rotated single query q and its
                          rotate-half q_hat (decode_kernel._blockdiag_queries)
    k_ref     (1, ps, h*d) unrotated keys of ONE pool page; they stay
                          unrotated: the rotation is applied on the query
                          side (decode_kernel._rotary_scores)
    v_ref     (1, ps, h*d)
    ang_ref   (1, ps, r)  rotary angles per PHYSICAL position (precomputed
                          from the ring logical positions; pairwise-repeated)
    exp_ref   (h, h*d)    head->channel expander
    o_ref     (1, 1, h*d) output
    scratch: m, l (8, 128) VMEM (per-head stats in row 0), acc (8, h*d)

    Pages with no live position are skipped entirely; their grid steps alias
    the newest token's page in the index maps so no fresh DMA is issued.
    Skipping is bit-exact: a fully-masked page contributes prob = 0 and
    rescales m/l/acc by exp(0) = 1 (tests/test_paging.py pins skip-on vs
    skip-off bitwise). The per-position visibility mask applies the SAME
    bound, so mid-page live boundaries are exact too.

    QUANTIZED pools add two VMEM operands after the expander — kscale_ref /
    vscale_ref (1, P, h) f32, row bi's per-page-per-head scales gathered
    through its page-table row OUTSIDE the kernel (``k_scale[page_table]``)
    — and k_ref/v_ref blocks arrive int8. The whole (N, h) sidecars cannot
    ride the scalar-prefetch path: SMEM is 1 MB on a v5e and a real pool's
    sidecars exceed it from about 1k pages. The dequant is FUSED: step i
    reads scale row i (the page the index map fetched — un-aliased whenever
    compute runs), expands it to channels through the same head expander
    the stats use, and multiplies it into the f32 upcast before the scores —
    bit-identical to feeding the XLA-dequantized f32 pool through this same
    kernel (tests pin it).
    """
    import jax.experimental.pallas as pl

    if quantized:
        (start_ref, live_ref, table_ref, qq_ref, k_ref, v_ref, ang_ref,
         exp_ref, kscale_ref, vscale_ref,
         o_ref, m_ref, l_ref, acc_ref) = refs
    else:
        (start_ref, live_ref, table_ref, qq_ref, k_ref, v_ref, ang_ref,
         exp_ref, o_ref, m_ref, l_ref, acc_ref) = refs
        kscale_ref = vscale_ref = None

    bi = pl.program_id(0)
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

    start = start_ref[bi]
    live = live_ref[bi]
    compute = _page_has_live(i, start, live, window, ps) if skip_dead_pages else i >= 0

    @pl.when(compute)
    def _compute():
        k = k_ref[0].astype(jnp.float32)  # (ps, h*d)
        if quantized:
            # whenever compute runs, the page is live and the index map did
            # not alias, so the fetched block IS page table_ref[bi, i] —
            # whose scales are row i of this batch row's gathered sidecar.
            # Expand head -> channels through the same 0/1 expander (exact
            # selection: one nonzero term per channel)
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
        l = jnp.maximum(l_ref[0:1, :h], 1e-30)
        l_x = jax.lax.dot_general(1.0 / l, exp_ref[:], contract, preferred_element_type=jnp.float32)
        o_ref[0] = (acc_ref[0:1, :] * l_x).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "skip_dead_pages", "interpret"))
def fused_paged_decode_attention(
    q: jax.Array,
    kp: jax.Array,
    vp: jax.Array,
    page_table: jax.Array,
    start: jax.Array,
    live: jax.Array,
    rope_k: jax.Array,
    window: int,
    skip_dead_pages: bool = True,
    interpret: bool = False,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """q (B, H, 1, D) scaled+rotated single query; kp/vp (N, ps, H*D)
    unrotated page pool; page_table (B, P); start (B,) POST-append ring
    offset; live (B,) live-entry counts; rope_k (B, P*ps, R) angles laid out
    per PHYSICAL ring position. Returns (B, H, 1, D).

    ``skip_dead_pages=False`` disables the dead-page alias/skip (every page is
    fetched and masked) — the bitwise-parity reference arm and the ragged
    kill-switch behavior (ragged_decode_enabled, ops/decode_kernel.py).

    ``k_scale``/``v_scale`` (N, H) switch on the FUSED-DEQUANT variant for
    int8 pools (module docstring): each row's scales are gathered through its
    page-table row here and reach the kernel as a (1, P, H) VMEM block,
    dead-page skip and ring semantics unchanged."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, n_q, d = q.shape
    assert n_q == 1, "paged decode is single-query (the engine's decode mode)"
    n_pages, ps, hd = kp.shape
    p = page_table.shape[1]
    r = rope_k.shape[-1]
    quantized = k_scale is not None
    assert (k_scale is None) == (v_scale is None), "pass both scales or neither"

    start = jnp.asarray(start, jnp.int32).reshape(-1)
    live = jnp.asarray(live, jnp.int32).reshape(-1)

    def _alias(i, start_ref, live_ref, bi):
        # dead pages alias the newest token's page — a page some step fetches
        # anyway, and consecutive equal indices elide the DMA
        if not skip_dead_pages:
            return i
        s, lv = start_ref[bi], live_ref[bi]
        newest = jnp.mod(s - 1, window) // ps
        return jnp.where(_page_has_live(i, s, lv, window, ps), i, newest)

    def _kv_map(bi, i, start_ref, live_ref, table_ref, *_):
        return (table_ref[bi, _alias(i, start_ref, live_ref, bi)], 0, 0)

    def _ang_map(bi, i, start_ref, live_ref, table_ref, *_):
        return (bi, _alias(i, start_ref, live_ref, bi), 0)

    page_table = jnp.asarray(page_table, jnp.int32)
    prefetch = [start, live, page_table]
    in_specs = [
        pl.BlockSpec((None, 2, h * d, h), lambda bi, i, *_: (bi, 0, 0, 0)),
        pl.BlockSpec((1, ps, hd), _kv_map),
        pl.BlockSpec((1, ps, hd), _kv_map),
        pl.BlockSpec((1, ps, r), _ang_map),
        pl.BlockSpec((h, h * d), lambda bi, i, *_: (0, 0)),
    ]
    row_scales = []
    if quantized:
        # per-row (P, H) scale tables, one VMEM block per batch row (the
        # block index is constant over i, so it is fetched once per row)
        row_scales = [jnp.asarray(k_scale, jnp.float32)[page_table],
                      jnp.asarray(v_scale, jnp.float32)[page_table]]
        in_specs += [pl.BlockSpec((1, p, h), lambda bi, i, *_: (bi, 0, 0))] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b, p),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, hd), lambda bi, i, *_: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((8, 128), jnp.float32),
            pltpu.VMEM((8, 128), jnp.float32),
            pltpu.VMEM((8, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, window=window,
                          skip_dead_pages=skip_dead_pages, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, hd), q.dtype),
        interpret=interpret,
    )(
        *prefetch,
        _blockdiag_queries(q, r),  # column ``head`` scores head ``head``
        kp,
        vp,
        rope_k,
        jnp.asarray(_head_expander(h, d)),
        *row_scales,
    )
    return out.reshape(b, 1, h, d).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# Grouped-query form: fewer K/V heads than query heads, absolute positions
# ---------------------------------------------------------------------------
#
# A decoder whose every layer is full causal attention keeps no ring: a slot's
# token ``t`` sits at physical position ``t`` of its page-table row for the
# slot's whole life, keys are stored ROTATED (a key's angle never changes), and
# ``length`` tokens are visible. Its ``h_q`` query heads share ``h_kv`` K/V
# heads (``n_rep = h_q // h_kv`` each), so one K/V page must be read ONCE for
# all of a slot's query heads: the query planes widen from the single-query
# kernel's ``(h*d, h)`` to ``(h_kv*d, n_rep*h_kv)`` — block-diagonal, one row
# of the block per (repeat, K/V head) — and a page's scores for every query
# head are one ``Q K^T`` product. The pool stacks the layers,
# ``(layers, num_pages, page_size, h_kv*d)``, under ONE page table. A grid step
# holds ``pages_per_step`` pages of a slot (the pool is passed that many times,
# each with its own index map), so the grid is short where slots are many and
# pages small; pages past a slot's length map to the trash page and are skipped.


def gqa_pages_per_step(pages_per_slot: int, most: int = 8) -> int:
    """The largest divisor of ``pages_per_slot`` up to ``most``."""
    return max(g for g in range(1, most + 1) if pages_per_slot % g == 0)


def paged_gqa_decode_supported(page_size: int, head_dim: int, kv_heads: int) -> bool:
    """The grouped-query paged kernel on one TPU device: sublane-aligned pages
    and a lane-aligned pool row ``kv_heads * head_dim``: heads that are whole
    lane tiles (``head_dim`` a multiple of 128), or narrower ones whose row is
    (8 heads of 64): such a slot's result leaves the kernel as whole pool rows
    and its heads are picked out after it
    (``fused_paged_decode_attention_gqa``). The XLA fallback (a dense gather
    through the page table) serves the CPU suite and sharded pools."""
    if jax.default_backend() != "tpu" or not single_device_trace():
        return False
    return (kv_heads * head_dim) % 128 == 0 and page_size % 8 == 0


def _blockdiag_gqa_queries(q: jax.Array, kv_heads: int, rows: int) -> jax.Array:
    """(B, h_q, d) scaled+rotated queries -> (B, rows, h_kv*d): row
    ``g*h_kv + kv`` holds query head ``kv*n_rep + g`` in the columns of K/V
    head ``kv`` and zeros elsewhere; rows past ``h_q`` are zero padding."""
    b, hq, d = q.shape
    n_rep = hq // kv_heads
    per = q.reshape(b, kv_heads, n_rep, d).transpose(0, 2, 1, 3)  # (B, g, kv, d)
    eye = jnp.eye(kv_heads, dtype=q.dtype)
    planes = (per[:, :, :, None, :] * eye[None, None, :, :, None]).reshape(b, hq, kv_heads * d)
    return jnp.pad(planes, ((0, 0), (0, rows - hq), (0, 0)))


def _gqa_kernel(len_ref, table_ref, q_ref, *refs, page_size, group):
    """Grid (B, P // group); step (bi, i) covers pages [i*group, (i+1)*group)
    of slot bi.

    len_ref (B,), table_ref (B, P)   scalar prefetch
    q_ref (rows, h_kv*d)             block-diagonal queries of slot bi
    k_refs / v_refs (ps, h_kv*d)     ``group`` pages each, rotated keys
    diag_ref (rows, h_kv*d)          1 where a row's K/V head owns the column
    o_ref (rows, d)                  row ``g*h_kv + kv``: query head ``kv*n_rep + g``;
                                     or (rows, h_kv*d) where ``d`` is no whole lane
                                     tile: the row's own head's columns, zeros elsewhere
    scratch m, l (rows, 128), acc (rows, h_kv*d), float32
    """
    import jax.experimental.pallas as pl

    k_refs, v_refs = refs[:group], refs[group:2 * group]
    diag_ref, o_ref, m_ref, l_ref, acc_ref = refs[2 * group:]
    bi, i = pl.program_id(0), pl.program_id(1)
    n = len_ref[bi]
    nt = (((1,), (1,)), ((), ()))

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for g in range(group):
        first = (i * group + g) * page_size

        @pl.when(first < n)
        def _page(g=g, first=first):
            k, v = k_refs[g][...], v_refs[g][...]
            s = jax.lax.dot_general(q_ref[...].astype(k.dtype), k, nt,
                                    preferred_element_type=jnp.float32)  # (rows, ps)
            pos = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(pos < n, s, -jnp.inf)
            m_prev = m_ref[:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))  # finite: the page has a visible key
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[...] = jnp.broadcast_to(alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape)
            acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(i == pl.num_programs(1) - 1)
    def _finalize():
        d = o_ref.shape[-1]
        # a slot of length 0 never accumulated: 0 / eps is an exact zero row
        own = acc_ref[...] * diag_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
        if d == own.shape[-1]:
            # heads narrower than a lane tile: the whole row goes out, lane-dense
            o_ref[...] = own.astype(o_ref.dtype)
            return
        out = own[:, :d]
        for kv in range(1, own.shape[-1] // d):
            out = out + own[:, kv * d:(kv + 1) * d]
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("layer", "interpret"))
def fused_paged_decode_attention_gqa(
    q: jax.Array,
    kp: jax.Array,
    vp: jax.Array,
    page_table: jax.Array,
    length: jax.Array,
    layer: int,
    interpret: bool = False,
) -> jax.Array:
    """q (B, h_q, d) scaled+rotated single queries; kp / vp (layers, N, ps,
    h_kv*d) pools of ROTATED keys and values; page_table (B, P); length (B,)
    visible tokens per slot, the one just appended included (0: the slot is
    skipped and its row comes back zero). Returns (B, h_q, d) in q's dtype.

    Heads of a whole lane tile (``d`` a multiple of 128) leave the kernel as
    ``(rows, d)`` blocks. Narrower ones (64) would make that block half a tile
    wide and its stores masked, so the kernel writes each row at the pool's
    width ``h_kv*d``, zeros outside the row's own head, and the head's
    columns are picked out here."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, hq, d = q.shape
    _, _, ps, c = kp.shape
    kv_heads = c // d
    n_rep = hq // kv_heads
    out_d = d if d % 128 == 0 else c
    p = page_table.shape[1]
    group = gqa_pages_per_step(p)
    rows = -(-hq // 16) * 16  # whole bf16 sublane tiles
    length = jnp.asarray(length, jnp.int32).reshape(-1)
    page_table = jnp.asarray(page_table, jnp.int32)

    def page_map(g):
        def index(bi, i, len_ref, table_ref):
            page = i * group + g
            return layer, jnp.where(page * ps < len_ref[bi], table_ref[bi, page], 0), 0, 0
        return index

    row = jnp.arange(rows)
    diag = ((row[:, None] % kv_heads) == (jnp.arange(c)[None, :] // d)) & (row[:, None] < hq)
    pools = [pl.BlockSpec((None, None, ps, c), page_map(g)) for g in range(group)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, p // group),
        in_specs=[pl.BlockSpec((None, rows, c), lambda bi, i, *_: (bi, 0, 0)), *pools, *pools,
                  pl.BlockSpec((rows, c), lambda bi, i, *_: (0, 0))],
        out_specs=pl.BlockSpec((None, rows, out_d), lambda bi, i, *_: (bi, 0, 0)),
        scratch_shapes=[pltpu.VMEM((rows, 128), jnp.float32), pltpu.VMEM((rows, 128), jnp.float32),
                        pltpu.VMEM((rows, c), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_gqa_kernel, page_size=ps, group=group),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, out_d), q.dtype),
        interpret=interpret,
        name="fused_paged_decode_attention_gqa",
    )(length, page_table, _blockdiag_gqa_queries(q, kv_heads, rows),
      *([kp] * group), *([vp] * group), diag.astype(jnp.float32))
    if out_d != d:
        # row g*h_kv + kv holds its head in the columns of K/V head kv: the diagonal of the two K/V axes
        out = jnp.einsum("bgkkd->bgkd", out[:, :hq].reshape(b, n_rep, kv_heads, kv_heads, d))
        return out.transpose(0, 2, 1, 3).reshape(b, hq, d)
    # row g*h_kv + kv -> query head kv*n_rep + g
    return out[:, :hq].reshape(b, n_rep, kv_heads, d).transpose(0, 2, 1, 3).reshape(b, hq, d)


def paged_gqa_reference_attention(
    q: jax.Array, kp: jax.Array, vp: jax.Array, page_table: jax.Array, length: jax.Array, layer: int,
) -> jax.Array:
    """The XLA form: each slot's pages gathered dense through its table row,
    masked at ``length``, one softmax per query head (float32). Same arguments
    and result as the kernel; a slot of length 0 comes back zero."""
    b, hq, d = q.shape
    c = kp.shape[-1]
    kv_heads = c // d
    k = kp[layer][page_table].reshape(b, -1, kv_heads, d).astype(jnp.float32)
    v = vp[layer][page_table].reshape(b, -1, kv_heads, d).astype(jnp.float32)
    qg = q.astype(jnp.float32).reshape(b, kv_heads, hq // kv_heads, d)
    hi = jax.lax.Precision.HIGHEST
    s = jnp.einsum("bkgd,bnkd->bkgn", qg, k, precision=hi)
    visible = (jnp.arange(k.shape[1])[None, :] < jnp.asarray(length)[:, None])[:, None, None, :]
    s = jnp.where(visible, s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    prob = jnp.exp(jnp.where(visible, s - jnp.where(jnp.isfinite(m), m, 0.0), -jnp.inf))
    prob = prob / jnp.maximum(jnp.sum(prob, axis=-1, keepdims=True), 1e-30)
    return jnp.einsum("bkgn,bnkd->bkgd", prob, v, precision=hi).reshape(b, hq, d).astype(q.dtype)
