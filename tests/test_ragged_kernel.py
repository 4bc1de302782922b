"""Unified ragged paged attention kernel (ops/ragged_paged_kernel.py; ISSUE
19 tentpole).

The composition contract: a ragged work item with causal bound = window - 1
(a decode step) is BITWISE the legacy fused paged decode kernel in interpret
mode — same flash loop, same prefetch values — for fp AND fused-dequant int8
pools; bounded items (latent-finish queries) match the XLA masked-softmax
oracle over the identical position set. The int4 contract: the in-stream
nibble unpack + dequant is BITWISE feeding the XLA-unpacked f32 pool through
the same kernel. The padding contract: live = 0 lanes return exact zeros, so
the engine's fixed-width descriptors cost nothing but the lanes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import perceiver_io_tpu.ops.paged_decode_kernel as pdk
import perceiver_io_tpu.ops.ragged_paged_kernel as rpk
from perceiver_io_tpu.ops.position import apply_rope


def _inputs(w, h, d, window, ps, n_pool, seed=0, r=None):
    rng = lambda i: jax.random.PRNGKey(seed + i)
    p = -(-window // ps)
    q = jax.random.normal(rng(0), (w, h, 1, d)) * 0.3
    kp = jax.random.normal(rng(1), (n_pool, ps, h * d)) * 0.3
    vp = jax.random.normal(rng(2), (n_pool, ps, h * d)) * 0.3
    perm = jax.random.permutation(rng(3), n_pool - 1)[: w * p] + 1
    table = jnp.asarray(np.asarray(perm).reshape(w, p), jnp.int32)
    ang = jnp.repeat(jax.random.normal(rng(4), (w, p * ps, (r or d) // 2)) * 0.5, 2, axis=-1)
    return q, kp, vp, table, ang


def _reference(q, kp, vp, table, start, live, cb, ang, window):
    """Dense-gather + rope + the module's masked-softmax oracle."""
    w, h, _, d = q.shape
    k = kp[table].reshape(w, -1, h * d)
    v = vp[table].reshape(w, -1, h * d)
    n = k.shape[1]
    kh = apply_rope(
        k.reshape(w, n, h, d).transpose(0, 2, 1, 3).astype(jnp.float32), ang
    ).transpose(0, 2, 1, 3).reshape(w, n, h * d)
    return rpk.ragged_reference_attention(
        q.astype(jnp.float32), kh, v.astype(jnp.float32), start, live, cb, window
    )


@pytest.mark.parametrize(
    "window,ps,starts,lives",
    [
        (256, 64, (0, 100, 255), (256, 40, 1)),     # saturated, mid, minimal
        (200, 64, (8, 72, 199), (200, 130, 64)),    # page does not divide window
        (256, 256, (0, 17, 128), (256, 100, 7)),    # one page per slot
    ],
)
def test_decode_items_bitwise_vs_legacy_kernel_interpret(window, ps, starts, lives):
    """Acceptance (ISSUE 19): ragged items at causal bound window - 1 are
    BITWISE the composed per-program path's decode kernel in interpret mode,
    across ring wraps and partial tail pages — dead-page skip on and off."""
    w, h, d = 3, 2, 32
    q, kp, vp, table, ang = _inputs(w, h, d, window, ps, n_pool=3 * (-(-window // ps)) + 2)
    start = jnp.asarray(starts, jnp.int32)
    live = jnp.asarray(lives, jnp.int32)
    cb = jnp.full((w,), window - 1, jnp.int32)
    for skip in (True, False):
        ragged = rpk.fused_ragged_paged_attention(
            q, kp, vp, table, start, live, cb, ang, window,
            skip_dead_pages=skip, interpret=True,
        )
        legacy = pdk.fused_paged_decode_attention(
            q, kp, vp, table, start, live, ang, window,
            skip_dead_pages=skip, interpret=True,
        )
        np.testing.assert_array_equal(np.asarray(ragged), np.asarray(legacy))


def test_bounded_items_match_masked_softmax_oracle():
    """Latent-finish items: per-item causal bounds mask exactly the logical
    positions [window - live, bound] — pinned against the XLA oracle across
    a mixed decode + finish descriptor, ring-wrapped rows included."""
    window, ps = 256, 32
    w, h, d = 5, 2, 32
    q, kp, vp, table, ang = _inputs(w, h, d, window, ps, n_pool=5 * 8 + 2, seed=3)
    # rows 0-1 decode (full bound); rows 2-4 one slot's 3-latent finish
    # (duplicated table row + ascending bounds), with a wrapped live interval
    table = table.at[3].set(table[2]).at[4].set(table[2])
    ang = ang.at[3].set(ang[2]).at[4].set(ang[2])
    start = jnp.asarray([40, 200, 10, 10, 10], jnp.int32)
    live = jnp.asarray([40, 200, 250, 250, 250], jnp.int32)
    cb = jnp.asarray([255, 255, 253, 254, 255], jnp.int32)
    out = rpk.fused_ragged_paged_attention(
        q, kp, vp, table, start, live, cb, ang, window, interpret=True
    )
    ref = _reference(q, kp, vp, table, start, live, cb, ang, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    # dead-page skip stays bitwise under causal bounds (the fold shifts the
    # ring; liveness and aliasing follow the shifted offsets exactly)
    noskip = rpk.fused_ragged_paged_attention(
        q, kp, vp, table, start, live, cb, ang, window,
        skip_dead_pages=False, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(noskip))


@pytest.mark.parametrize(
    "r,zero_angles,qbits",
    [
        pytest.param(8, False, None, id="partial-rotary-fp"),
        pytest.param(2, True, None, id="zero-angles-r2-fp"),  # the no-rotary call
        pytest.param(8, False, 8, id="partial-rotary-int8"),
        pytest.param(8, False, 4, id="partial-rotary-int4"),
    ],
)
def test_query_side_rotation_matches_oracle(r, zero_angles, qbits):
    """The rotation is applied on the query side (decode_kernel._rotary_scores):
    parity with the oracle, which rotates the gathered keys, where rotary
    covers part of a head and on the no-rotary call — decode and bounded
    items, fp and quantized pools (the dequant comes before the products)."""
    window, ps = 128, 32
    w, h, d = 4, 2, 32
    n_pool = 4 * 4 + 2
    q, kp, vp, table, ang = _inputs(w, h, d, window, ps, n_pool=n_pool, seed=13, r=r)
    ang = jnp.zeros_like(ang) if zero_angles else ang
    start = jnp.asarray([0, 100, 9, 9], jnp.int32)
    live = jnp.asarray([128, 40, 120, 120], jnp.int32)
    cb = jnp.asarray([127, 127, 126, 127], jnp.int32)
    quant = {}
    if qbits is not None:
        qc, kp_ref, vp_ref = _quant_pool(n_pool, ps, h, d, qbits, seed=13)
        kp, vp = qc.kp, qc.vp
        quant = dict(k_scale=qc.k_scale, v_scale=qc.v_scale, qbits=qbits)
    else:
        kp_ref, vp_ref = kp, vp
    out = rpk.fused_ragged_paged_attention(
        q, kp, vp, table, start, live, cb, ang, window, interpret=True, **quant
    )
    ref = _reference(q, kp_ref, vp_ref, table, start, live, cb, ang, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("qbits", [None, 8])
def test_ragged_kernel_has_no_square_operand(qbits):
    """Structure: on fp and int8 pools the traced ragged kernel holds no
    (h*d, h*d) operand — the rotate-half constant and its matmul are gone.
    (int4 pools keep ONE such operand, the nibble-plane interleave.)"""
    from tests.test_decode_kernel import has_square_operand, pallas_operand_shapes

    window, ps = 128, 32
    w, h, d = 3, 4, 32  # h*d = 128: kept apart from the window by the shapes below
    n_pool = 3 * 4 + 2
    q, kp, vp, table, ang = _inputs(w, h, d, window, ps, n_pool=n_pool)
    quant = {}
    if qbits is not None:
        qc, _, _ = _quant_pool(n_pool, ps, h, d, qbits)
        kp, vp = qc.kp, qc.vp
        quant = dict(k_scale=qc.k_scale, v_scale=qc.v_scale, qbits=qbits)
    start, live = jnp.zeros((w,), jnp.int32), jnp.full((w,), window, jnp.int32)
    cb = jnp.full((w,), window - 1, jnp.int32)
    shapes = pallas_operand_shapes(
        lambda q, kp, vp: rpk.fused_ragged_paged_attention(
            q, kp, vp, table, start, live, cb, ang, window, interpret=True, **quant
        ),
        q, kp, vp,
    )
    assert (w, 2, h * d, h) in shapes  # the query planes reached the kernel
    assert not has_square_operand(shapes, h * d)


def test_fold_causal_bound_equals_brute_force_mask():
    """The (start, live, bound) -> (eff_start, eff_live) fold selects exactly
    the positions {r : window - live <= lp(r) <= bound} — checked against the
    brute-force set over every (start, live, bound) of a small ring."""
    window = 12
    r = np.arange(window)
    for start in range(window):
        for live in range(window + 1):
            for cb in range(window):
                lp = np.mod(r - start, window)
                want = (lp >= window - live) & (lp <= cb)
                es, el = rpk.fold_causal_bound(
                    jnp.asarray([start]), jnp.asarray([live]),
                    jnp.asarray([cb]), window,
                )
                got = np.mod(r - np.asarray(es)[0], window) >= window - np.asarray(el)[0]
                np.testing.assert_array_equal(got, want, err_msg=f"{start},{live},{cb}")


def test_padding_lanes_return_exact_zeros():
    """live = 0 lanes (fixed-width descriptor padding) produce EXACT zero
    rows — the flash state never accumulates and the finalize clamp divides
    0 by eps."""
    window, ps = 64, 32
    w, h, d = 4, 2, 32
    q, kp, vp, table, ang = _inputs(w, h, d, window, ps, n_pool=4 * 2 + 2, seed=7)
    start = jnp.asarray([10, 0, 3, 0], jnp.int32)
    live = jnp.asarray([10, 0, 64, 0], jnp.int32)
    cb = jnp.asarray([63, 63, 63, 63], jnp.int32)
    for skip in (True, False):
        out = np.asarray(rpk.fused_ragged_paged_attention(
            q, kp, vp, table, start, live, cb, ang, window,
            skip_dead_pages=skip, interpret=True,
        ))
        assert (out[1] == 0).all() and (out[3] == 0).all()
        assert np.abs(out[0]).max() > 0 and np.abs(out[2]).max() > 0


def _quant_pool(n_pool, ps, h, d, qbits, seed=0):
    """A quantized page pool built through the real write path (write_pages
    stamps fresh per-head scales), plus its XLA-dequantized f32 twin."""
    rng = lambda i: jax.random.PRNGKey(seed + i)
    kpf = jax.random.normal(rng(1), (n_pool, ps, h * d)) * 0.3
    vpf = jax.random.normal(rng(2), (n_pool, ps, h * d)) * 0.3
    c_phys = h * d // 2 if qbits == 4 else h * d
    pool_dtype = jnp.uint8 if qbits == 4 else jnp.int8
    cache = pdk.PagedKVCache(
        kp=jnp.zeros((n_pool, ps, c_phys), pool_dtype),
        vp=jnp.zeros((n_pool, ps, c_phys), pool_dtype),
        page_table=jnp.zeros((1, 1), jnp.int32),
        start=jnp.zeros((1,), jnp.int32), window=ps,
        k_scale=jnp.zeros((n_pool, h), jnp.float32),
        v_scale=jnp.zeros((n_pool, h), jnp.float32),
        num_heads=h, qbits=qbits,
    )
    qc = cache.write_pages(jnp.arange(n_pool), kpf, vpf)
    ks = jnp.repeat(qc.k_scale, d, axis=-1)[:, None, :]
    vs = jnp.repeat(qc.v_scale, d, axis=-1)[:, None, :]
    from perceiver_io_tpu.ops.paged_decode_kernel import _unpack_codes

    kdeq = _unpack_codes(qc.kp, qbits) * ks
    vdeq = _unpack_codes(qc.vp, qbits) * vs
    return qc, kdeq, vdeq


@pytest.mark.parametrize("qbits", [8, 4])
def test_fused_dequant_bitwise_vs_xla_dequant_interpret(qbits):
    """Acceptance: the ragged kernel's fused dequant — int8 scale multiply
    and the int4 in-stream nibble unpack — is BITWISE feeding the
    XLA-dequantized f32 pool through the same kernel, under mixed causal
    bounds and ring wraps."""
    window, ps = 128, 32
    w, h, d = 4, 2, 32
    n_pool = 4 * 4 + 2
    q, _, _, table, ang = _inputs(w, h, d, window, ps, n_pool=n_pool, seed=11)
    qc, kdeq, vdeq = _quant_pool(n_pool, ps, h, d, qbits, seed=11)
    start = jnp.asarray([0, 100, 9, 9], jnp.int32)
    live = jnp.asarray([128, 40, 120, 120], jnp.int32)
    cb = jnp.asarray([127, 127, 126, 127], jnp.int32)
    fused = rpk.fused_ragged_paged_attention(
        q, qc.kp, qc.vp, table, start, live, cb, ang, window, interpret=True,
        k_scale=qc.k_scale, v_scale=qc.v_scale, qbits=qbits,
    )
    ref = rpk.fused_ragged_paged_attention(
        q, kdeq, vdeq, table, start, live, cb, ang, window, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(ref))
    # and against the masked-softmax oracle at fp tolerance
    oracle = _reference(q, kdeq, vdeq, table, start, live, cb, ang, window)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(oracle), atol=1e-5)


def test_int8_decode_items_bitwise_vs_legacy_fused_dequant():
    """int8 pools at full causal bound reproduce the legacy fused-dequant
    kernel BITWISE — the ragged program is a drop-in for the composed tick's
    decode dispatch on quantized pools too."""
    window, ps = 128, 32
    w, h, d = 3, 2, 32
    n_pool = 3 * 4 + 2
    q, _, _, table, ang = _inputs(w, h, d, window, ps, n_pool=n_pool, seed=5)
    qc, _, _ = _quant_pool(n_pool, ps, h, d, qbits=8, seed=5)
    start = jnp.asarray([0, 77, 127], jnp.int32)
    live = jnp.asarray([128, 50, 3], jnp.int32)
    cb = jnp.full((w,), window - 1, jnp.int32)
    ragged = rpk.fused_ragged_paged_attention(
        q, qc.kp, qc.vp, table, start, live, cb, ang, window, interpret=True,
        k_scale=qc.k_scale, v_scale=qc.v_scale,
    )
    legacy = pdk.fused_paged_decode_attention(
        q, qc.kp, qc.vp, table, start, live, ang, window, interpret=True,
        k_scale=qc.k_scale, v_scale=qc.v_scale,
    )
    np.testing.assert_array_equal(np.asarray(ragged), np.asarray(legacy))


def test_ragged_supported_gates():
    import os

    if jax.default_backend() != "tpu":
        assert not rpk.ragged_paged_supported(128, 512, 512)
    os.environ["PERCEIVER_IO_TPU_DISABLE_DECODE_KERNEL"] = "1"
    try:
        assert not rpk.ragged_paged_supported(128, 512, 512)
    finally:
        del os.environ["PERCEIVER_IO_TPU_DISABLE_DECODE_KERNEL"]
