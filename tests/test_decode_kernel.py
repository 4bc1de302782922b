"""Fused cached-decode attention kernel (ops/decode_kernel.py): interpret-mode
parity vs the XLA cached-attention formulation, and full-model integration —
forcing the kernel path (interpret mode) must reproduce the plain decode path
exactly through CausalSequenceModel.decode_step."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import perceiver_io_tpu.ops.decode_kernel as dk
from perceiver_io_tpu.ops.position import apply_rope


def xla_reference(q, k_cache, v_cache, ang, q_pos, pad):
    """q_pos is the LAST query's absolute position; query qi sits at
    q_pos - (n_q - 1 - qi) (the kernel's multi-query convention)."""
    b, h, n_q, d = q.shape
    cap = k_cache.shape[1]
    kh = apply_rope(k_cache.reshape(b, cap, h, d).transpose(0, 2, 1, 3).astype(jnp.float32), ang)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kh)
    qpos = jnp.asarray(q_pos).reshape(-1, 1) - (n_q - 1) + jnp.arange(n_q)  # (b, n_q)
    visible = (jnp.arange(cap)[None, None, :] <= qpos[:, :, None]) & ~pad[:, None, :]
    s = jnp.where(visible[:, None, :, :], s, -jnp.inf)
    vh = v_cache.reshape(b, cap, h, d).transpose(0, 2, 1, 3).astype(jnp.float32)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), vh)


@pytest.mark.parametrize(
    "b,h,d,cap,r,q_pos",
    [
        pytest.param(2, 4, 64, 1024, 32, 700, marks=pytest.mark.slow),  # multi-block, partial rotary
        (1, 2, 32, 256, 32, 0),      # single block, r == d, only slot 0 visible
        (3, 2, 16, 128, 8, 127),     # full cache visible
    ],
)
def test_fused_decode_attention_interpret_parity(b, h, d, cap, r, q_pos):
    rng = lambda i: jax.random.PRNGKey(i)
    q = jax.random.normal(rng(0), (b, h, 1, d)) * 0.3
    k = jax.random.normal(rng(1), (b, cap, h * d)) * 0.3
    v = jax.random.normal(rng(2), (b, cap, h * d)) * 0.3
    ang = jnp.repeat(jax.random.normal(rng(3), (b, cap, r // 2)) * 0.5, 2, axis=-1)
    pad = jnp.zeros((b, cap), bool).at[:, 3:5].set(True)

    out = dk.fused_decode_attention(q, k, v, ang, jnp.asarray(q_pos), pad, interpret=True)
    ref = xla_reference(q, k, v, ang, jnp.full((b,), q_pos), pad)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_fused_decode_attention_per_batch_positions():
    b, h, d, cap, r = 2, 2, 32, 256, 16
    rng = lambda i: jax.random.PRNGKey(i)
    q = jax.random.normal(rng(0), (b, h, 1, d)) * 0.3
    k = jax.random.normal(rng(1), (b, cap, h * d)) * 0.3
    v = jax.random.normal(rng(2), (b, cap, h * d)) * 0.3
    ang = jnp.repeat(jax.random.normal(rng(3), (b, cap, r // 2)) * 0.5, 2, axis=-1)
    pad = jnp.zeros((b, cap), bool)
    q_pos = jnp.asarray([5, 200], jnp.int32)
    out = dk.fused_decode_attention(q, k, v, ang, q_pos, pad, interpret=True)
    ref = xla_reference(q, k, v, ang, q_pos, pad)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def pallas_operand_shapes(fn, *args):
    """Shapes of every operand of every ``pallas_call`` that ``fn(*args)`` traces."""
    shapes = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                shapes.extend(tuple(v.aval.shape) for v in eqn.invars)
                continue
            for param in eqn.params.values():
                inner = getattr(param, "jaxpr", param)  # ClosedJaxpr -> Jaxpr
                if hasattr(inner, "eqns"):
                    walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return shapes


def has_square_operand(shapes, hd):
    """Does any traced kernel operand end in (h*d, h*d) — the shape of a
    rotate-half (or any other per-channel mixing) constant?"""
    return any(len(s) >= 2 and s[-2:] == (hd, hd) for s in shapes)


@pytest.mark.parametrize(
    "b,h,d,cap,r,n_q,q_last,lives,zero_angles,stacked",
    [
        pytest.param(2, 2, 32, 256, 8, 1, 255, None, False, False, id="partial-rotary"),
        pytest.param(2, 2, 32, 256, 2, 1, 200, None, True, False, id="zero-angles-r2"),  # the no-rotary call
        pytest.param(2, 2, 32, 256, 16, 8, 130, None, False, False, id="n_q8-partial-rotary"),
        pytest.param(2, 2, 32, 256, 2, 8, 255, None, True, False, id="n_q8-zero-angles"),
        pytest.param(2, 2, 32, 1024, 16, 1, 1023, (1024, 100), False, False, id="live-below-one-block"),  # blk 512
        pytest.param(2, 2, 32, 256, 16, 1, 255, (256, 9), False, True, id="stacked-traced-layer"),
        pytest.param(2, 3, 16, 256, 8, 8, 255, None, False, True, id="stacked-n_q8"),
        # the serving pool's call: rows that read their whole ring among rows that read nothing
        pytest.param(4, 2, 32, 256, 16, 1, 255, (0, 256, 0, 256), False, False, id="free-rows-scattered"),
        pytest.param(4, 2, 32, 256, 16, 4, 255, (256, 0, 256, 0), False, False, id="free-rows-n_q4"),
        pytest.param(4, 2, 32, 256, 16, 1, 255, (0, 256, 256, 0), False, True, id="free-rows-stacked"),
        pytest.param(4, 2, 32, 256, 16, 4, 255, (256, 0, 0, 256), False, True, id="free-rows-stacked-n_q4"),
        pytest.param(3, 2, 32, 1024, 16, 1, 1023, (0, 0, 1024), False, False, id="free-rows-front-two-blocks"),
        pytest.param(3, 2, 32, 1024, 16, 1, 1023, (1024, 100, 0), False, True, id="free-row-behind-a-dead-head-block"),
        pytest.param(2, 2, 32, 256, 8, 1, 255, (0, 0), False, False, id="no-row-reads"),
    ],
)
def test_fused_decode_attention_query_side_rotation(b, h, d, cap, r, n_q, q_last, lives, zero_angles, stacked):
    """The rotation is applied on the query side (``_rotary_scores``): parity
    with rotating the keys themselves where it differs most from the plain
    cases above — rotary on part of a head (q_hat zero on the rest), the
    no-rotary call (zero angles, r = 2), eight queries, a row whose live
    region is under one KV block, and the stacked form with a traced layer.
    A row with ``live == 0`` reads nothing and comes back zeros, and the rows
    that read their whole cache beside it get, bit for bit, what the kernel
    gives them when every row does (the call without ``live``)."""
    rng = lambda i: jax.random.PRNGKey(40 + i)
    q = jax.random.normal(rng(0), (b, h, n_q, d)) * 0.3
    layers = 3 if stacked else 1
    k = jax.random.normal(rng(1), (layers, b, cap, h * d)) * 0.3
    v = jax.random.normal(rng(2), (layers, b, cap, h * d)) * 0.3
    ang = jnp.repeat(jax.random.normal(rng(3), (b, cap, r // 2)) * 0.5, 2, axis=-1)
    ang = jnp.zeros_like(ang) if zero_angles else ang
    pad = jnp.zeros((b, cap), bool)
    live = None if lives is None else jnp.asarray(lives, jnp.int32)
    layer = 2 if stacked else 0

    def kernel(live):
        if stacked:
            return np.asarray(jax.jit(
                lambda layer: dk.fused_decode_attention(q, k, v, ang, jnp.asarray(q_last), pad, live=live, layer=layer, interpret=True)
            )(jnp.asarray(layer, jnp.int32)))
        return np.asarray(dk.fused_decode_attention(q, k[0], v[0], ang, jnp.asarray(q_last), pad, live=live, interpret=True))

    out = kernel(live)
    ref_pad = pad if lives is None else jnp.arange(cap)[None, :] < (q_last + 1 - live)[:, None]
    ref = np.asarray(xla_reference(q, k[layer], v[layer], ang, jnp.full((b,), q_last), ref_pad))
    assert out.shape == (b, h, n_q, d)
    reads = np.ones(b, bool) if lives is None else np.asarray(lives) > 0
    np.testing.assert_allclose(out[reads], ref[reads], atol=1e-5)
    if not reads.all():
        assert not out[~reads].any()
        whole = np.asarray(lives) == q_last + 1
        np.testing.assert_array_equal(out[whole], kernel(None)[whole])


@pytest.mark.parametrize("nblocks", [1, 2, 4])
@pytest.mark.parametrize("free", ["front", "back", "scattered", "all", "none"])
def test_fused_decode_attention_fetches_a_block_only_for_a_row_that_reads(free, nblocks):
    """A COUNT of what the kernel's pipeline will move, never a time: walk the
    grid (B, nblocks) in its order through the kernel's own index map
    (``dk._step_block`` over ``dk._fetch_rows``) and count the steps whose
    (row, block) differs from the step before; only those fetch. Every live
    block of every reading row is held exactly once, in row order, and the
    rows that read nothing add none (one block in all when NO row reads). A
    reading row names what it named before this map existed. Without ``live``
    (the pool's call until PR 37) the same walk counts ``B x nblocks``."""
    b, blk = 16, 128
    cap = nblocks * blk
    rows = np.arange(b)
    reads = {
        "front": rows >= 11, "back": rows < 3, "all": np.zeros(b, bool), "none": np.ones(b, bool),
        "scattered": np.isin(rows, np.random.default_rng(nblocks).permutation(b)[:5]),
    }[free]
    live = np.where(reads, cap, 0).astype(np.int32)
    if reads.any():  # one reading row whose live tail lies in its last block alone
        live[np.flatnonzero(reads)[-1]] = blk // 2
    qpos = np.full(b, cap - 1, np.int32)
    bi, i = np.repeat(rows, nblocks), np.tile(np.arange(nblocks), b)

    def fetched(row, block):  # the steps whose block differs from the step before
        held = list(zip(np.asarray(row).tolist(), np.asarray(block).tolist()))
        return [held[0]] + [now for before, now in zip(held, held[1:]) if now != before]

    def before_pr37(live):  # the maps as they were: every row names its own blocks
        dead = np.maximum((qpos[bi] + 1 - live[bi]) // blk, 0)
        return bi, np.minimum(np.maximum(i, dead), nblocks - 1)

    fetch_row = dk._fetch_rows(jnp.asarray(live))
    row, block = (np.asarray(v) for v in dk._step_block(bi, i, qpos, live, fetch_row, nblocks, blk))
    first = (cap - live) // blk  # a reading row's first live block
    wanted = [(r, k) for r in np.flatnonzero(reads).tolist() for k in range(first[r], nblocks)]
    assert fetched(row, block) == (wanted or [(0, nblocks - 1)])
    was_row, was_block = before_pr37(live)
    on = reads[bi]
    assert (row[on] == was_row[on]).all() and (block[on] == was_block[on]).all()
    assert len(fetched(*before_pr37(np.full(b, cap, np.int32)))) == b * nblocks


def test_blockdiag_queries_planes():
    """Plane 0 is the block-diagonal query, plane 1 its rotate-half on the
    rotary dims and zero elsewhere: (k*sin) @ plane 1 == (rotate_half(k)*sin) . q."""
    b, h, n_q, d, r = 2, 3, 2, 8, 4
    q = jax.random.normal(jax.random.PRNGKey(0), (b, h, n_q, d))
    qq = np.asarray(dk._blockdiag_queries(q, r))
    assert qq.shape == (b, 2, h * d, n_q * h)
    qn = np.asarray(q)
    for head in range(h):
        for qi in range(n_q):
            col = qq[:, :, :, qi * h + head]
            want = qn[:, head, qi]
            np.testing.assert_array_equal(col[:, 0, head * d : (head + 1) * d], want)
            hat = np.zeros_like(want)
            hat[:, 0:r:2], hat[:, 1:r:2] = want[:, 1:r:2], -want[:, 0:r:2]
            np.testing.assert_array_equal(col[:, 1, head * d : (head + 1) * d], hat)
            off = np.ones(h * d, bool)
            off[head * d : (head + 1) * d] = False
            assert not col[:, :, off].any()


def test_fused_decode_attention_has_no_square_operand():
    """Structure: the traced kernel holds no (h*d, h*d) operand — the
    rotate-half constant and its matmul are gone, not merely skipped."""
    b, h, d, cap, r = 2, 4, 32, 256, 16
    q = jnp.zeros((b, h, 1, d))
    kv = jnp.zeros((3, b, cap, h * d))
    ang = jnp.zeros((b, cap, r))
    pad = jnp.zeros((b, cap), bool)
    shapes = pallas_operand_shapes(
        lambda *a: dk.fused_decode_attention(*a, layer=jnp.asarray(1), interpret=True), q, kv, kv, ang, cap - 1, pad
    )
    assert (b, 2, h * d, h) in shapes  # the query planes reached the kernel
    assert not has_square_operand(shapes, h * d)


# the kernel's measured scoped-VMEM need (MiB), compiled for a described v5e
# with vmem_limit_bytes searched by bisection (PERF.md, PR 29):
# (block, packed width, cache itemsize, n_q, batch, need)
MEASURED_VMEM_MIB = [
    (512, 1280, 2, 1, 64, 6.85), (256, 1280, 2, 1, 64, 3.30), (128, 1280, 2, 1, 64, 1.56),
    (512, 1280, 2, 8, 64, 10.21), (256, 1280, 2, 8, 64, 4.92), (512, 1280, 2, 8, 8, 5.11),
    (512, 512, 2, 1, 16, 3.86), (512, 512, 2, 8, 8, 4.36), (512, 768, 2, 1, 8, 5.42), (512, 768, 2, 8, 8, 6.10),
    (512, 1280, 4, 1, 8, 11.08), (256, 1280, 4, 1, 8, 5.42), (512, 512, 4, 1, 8, 5.48), (512, 768, 4, 1, 8, 7.85),
]


@pytest.mark.parametrize(
    "capacity,hd,itemsize,block",
    [
        pytest.param(512, 1280, 2, 512, id="455m-self-attention-ring"),  # the online cell: one block a slot
        pytest.param(1024, 1280, 2, 512, id="455m-cross-attention-window"),
        pytest.param(512, 512, 2, 512, id="30m-latents"),
        pytest.param(4096, 512, 2, 512, id="30m-window"),
        pytest.param(2048, 768, 2, 512, id="134m-latents"),
        pytest.param(6144, 768, 2, 512, id="134m-window"),
        pytest.param(512, 1280, 4, 256, id="455m-float32-cache"),
    ],
)
def test_kv_block_pins(capacity, hd, itemsize, block):
    """The KV block the estimate picks at the published widths. The estimate
    describes the kernel's temporaries: an edit to those re-measures
    (MEASURED_VMEM_MIB) and restates these pins."""
    assert dk._kv_block(capacity, hd, itemsize) == block
    assert dk._vmem_estimate(block, hd, itemsize) <= dk._VMEM_BUDGET
    for blk, width, size, _, _, need in MEASURED_VMEM_MIB:
        assert dk._vmem_estimate(blk, width, size) >= need * 2**20, (blk, width, size)


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_fused_decode_attention_stacked_form_equals_3d_form_per_layer(layer):
    """The stacked, layer-indexed form (the serving pool's ring cache: the
    kernel reads layer ``layer`` of the (L, B, cap, H*D) buffers where it lies)
    equals the 3-D form on ``k[layer]``, ``v[layer]`` bit for bit, with the
    index traced and with per-row live lengths skipping blocks."""
    n_layers, b, h, d, cap, r = 3, 2, 2, 32, 256, 16
    rng = lambda i: jax.random.PRNGKey(i)
    q = jax.random.normal(rng(0), (b, h, 1, d)) * 0.3
    k = jax.random.normal(rng(1), (n_layers, b, cap, h * d)) * 0.3
    v = jax.random.normal(rng(2), (n_layers, b, cap, h * d)) * 0.3
    ang = jnp.repeat(jax.random.normal(rng(3), (b, cap, r // 2)) * 0.5, 2, axis=-1)
    pad = jnp.zeros((b, cap), bool)
    q_pos = jnp.asarray(cap - 1)
    live = jnp.asarray([cap, 90], jnp.int32)

    stacked = jax.jit(
        lambda layer: dk.fused_decode_attention(q, k, v, ang, q_pos, pad, live=live, layer=layer, interpret=True)
    )(jnp.asarray(layer, jnp.int32))
    flat = dk.fused_decode_attention(q, k[layer], v[layer], ang, q_pos, pad, live=live, interpret=True)
    np.testing.assert_array_equal(np.asarray(stacked), np.asarray(flat))
    pad_live = jnp.arange(cap)[None, :] < (cap - live)[:, None]  # the dead head as a pad mask
    ref = xla_reference(q, k[layer], v[layer], ang, jnp.full((b,), cap - 1), pad_live)
    np.testing.assert_allclose(np.asarray(stacked), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize(
    "b,h,d,cap,r,n_q,q_last",
    [
        pytest.param(2, 4, 64, 1024, 32, 4, 700, marks=pytest.mark.slow),  # multi-block, partial rotary, mid-cache
        (1, 2, 32, 256, 32, 8, 7),     # max n_q, queries at the very start
        pytest.param(2, 2, 16, 128, 8, 2, 127, marks=pytest.mark.slow),    # full cache visible to the last query
    ],
)
def test_fused_decode_attention_multi_query(b, h, d, cap, r, n_q, q_last):
    """n_q > 1 (speculative / chunked decode): each query gets its own causal
    bound q_last - (n_q-1-qi) and its own flash-stats scratch row."""
    rng = lambda i: jax.random.PRNGKey(i)
    q = jax.random.normal(rng(0), (b, h, n_q, d)) * 0.3
    k = jax.random.normal(rng(1), (b, cap, h * d)) * 0.3
    v = jax.random.normal(rng(2), (b, cap, h * d)) * 0.3
    ang = jnp.repeat(jax.random.normal(rng(3), (b, cap, r // 2)) * 0.5, 2, axis=-1)
    pad = jnp.zeros((b, cap), bool).at[:, 1:2].set(True)

    out = dk.fused_decode_attention(q, k, v, ang, jnp.asarray(q_last), pad, interpret=True)
    ref = xla_reference(q, k, v, ang, jnp.full((b,), q_last), pad)
    assert out.shape == (b, h, n_q, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_fused_decode_attention_multi_query_per_batch_positions():
    b, h, d, cap, r, n_q = 2, 2, 32, 256, 16, 3
    rng = lambda i: jax.random.PRNGKey(i)
    q = jax.random.normal(rng(0), (b, h, n_q, d)) * 0.3
    k = jax.random.normal(rng(1), (b, cap, h * d)) * 0.3
    v = jax.random.normal(rng(2), (b, cap, h * d)) * 0.3
    ang = jnp.repeat(jax.random.normal(rng(3), (b, cap, r // 2)) * 0.5, 2, axis=-1)
    pad = jnp.zeros((b, cap), bool)
    q_last = jnp.asarray([5, 200], jnp.int32)
    out = dk.fused_decode_attention(q, k, v, ang, q_last, pad, interpret=True)
    ref = xla_reference(q, k, v, ang, q_last, pad)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_cached_multi_token_attention_with_kernel_matches_plain(monkeypatch):
    """MultiHeadAttention cached causal path with an n_q=4 chunk (chunked decode
    verification): forcing the fused kernel (interpret) must match kernel-off."""
    from perceiver_io_tpu.ops.attention import KVCache, MultiHeadAttention

    b, n_ctx, n_q, ch, heads = 2, 8, 4, 32, 2
    mha = MultiHeadAttention(
        num_heads=heads, num_q_input_channels=ch, num_kv_input_channels=ch, causal_attention=True
    )
    rng = jax.random.PRNGKey(0)
    x_ctx = jax.random.normal(rng, (b, n_ctx, ch)) * 0.3
    x_new = jax.random.normal(jax.random.PRNGKey(1), (b, n_q, ch)) * 0.3
    params = mha.init(rng, x_ctx, x_ctx)
    real_fused = dk.fused_decode_attention

    def run(force_kernel):
        if force_kernel:
            monkeypatch.setattr(dk, "decode_kernel_supported", lambda n_q, *a, **kw: 1 <= n_q <= 8)
            monkeypatch.setattr(dk, "fused_decode_attention", lambda *a, **kw: real_fused(*a, interpret=True))
        else:
            monkeypatch.setattr(dk, "decode_kernel_supported", lambda *a, **kw: False)
        cache = KVCache.create(b, 16, ch, ch)
        out0, cache = mha.apply(params, x_ctx, x_ctx, kv_cache=cache)
        out1, cache = mha.apply(params, x_new, x_new, kv_cache=cache)
        return np.asarray(out1)

    plain = run(False)
    fused = run(True)
    np.testing.assert_allclose(fused, plain, atol=2e-5)


def test_ragged_live_skip_matches_masked_fallback_interpret():
    """Acceptance (ragged decode): with per-row live lengths whose dead region
    equals the pad-slot head, the block-skipping kernel is (a) BIT-identical to
    the pad-masked kernel without live lengths (skipped blocks contribute
    prob=0 / scale=1 to the flash state) and (b) matches the XLA masked-softmax
    reference that applies the same per-row bound — in interpret mode on CPU."""
    b, h, d, cap, r = 3, 2, 32, 1024, 16  # blk = 512 -> 2 blocks; rows skip 0/1/2 whole blocks
    rng = lambda i: jax.random.PRNGKey(i)
    q = jax.random.normal(rng(0), (b, h, 1, d)) * 0.3
    k = jax.random.normal(rng(1), (b, cap, h * d)) * 0.3
    v = jax.random.normal(rng(2), (b, cap, h * d)) * 0.3
    ang = jnp.repeat(jax.random.normal(rng(3), (b, cap, r // 2)) * 0.5, 2, axis=-1)
    # dead heads: row 0 none, row 1 straddles block 0 (600 pads), row 2 all but the tail
    pads = [0, 600, 1000]
    pad = np.zeros((b, cap), bool)
    for i, p in enumerate(pads):
        pad[i, :p] = True
    pad = jnp.asarray(pad)
    q_pos = jnp.full((b,), cap - 1, jnp.int32)
    live = jnp.asarray([cap - p for p in pads], jnp.int32)

    out_live = dk.fused_decode_attention(q, k, v, ang, q_pos, pad, live=live, interpret=True)
    out_mask = dk.fused_decode_attention(q, k, v, ang, q_pos, pad, interpret=True)
    np.testing.assert_array_equal(np.asarray(out_live), np.asarray(out_mask))  # bit-identical
    ref = xla_reference(q, k, v, ang, q_pos, pad)
    np.testing.assert_allclose(np.asarray(out_live), np.asarray(ref), atol=1e-5)


def test_ragged_live_bound_masks_without_pad_mask_interpret():
    """The kernel applies the live lower bound in its score mask too (not only
    via block skipping), so live alone — no pad mask — matches the fallback's
    per-row bound, including mid-block boundaries."""
    b, h, d, cap, r = 2, 2, 16, 256, 8
    rng = lambda i: jax.random.PRNGKey(i)
    q = jax.random.normal(rng(0), (b, h, 1, d)) * 0.3
    k = jax.random.normal(rng(1), (b, cap, h * d)) * 0.3
    v = jax.random.normal(rng(2), (b, cap, h * d)) * 0.3
    ang = jnp.repeat(jax.random.normal(rng(3), (b, cap, r // 2)) * 0.5, 2, axis=-1)
    no_pad = jnp.zeros((b, cap), bool)
    q_pos = jnp.full((b,), cap - 1, jnp.int32)
    live = jnp.asarray([cap - 37, cap], jnp.int32)  # mid-block dead head vs fully live

    out = dk.fused_decode_attention(q, k, v, ang, q_pos, no_pad, live=live, interpret=True)
    # reference: the live bound expressed as a pad mask
    pad = np.zeros((b, cap), bool)
    pad[0, :37] = True
    ref = xla_reference(q, k, v, ang, q_pos, jnp.asarray(pad))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_ragged_decode_kill_switch(monkeypatch):
    """PERCEIVER_IO_TPU_DISABLE_RAGGED_DECODE drops live-length masking back to
    pad masking alone (ragged_decode_enabled gates the kv_live plumbing)."""
    assert dk.ragged_decode_enabled()
    monkeypatch.setenv("PERCEIVER_IO_TPU_DISABLE_RAGGED_DECODE", "1")
    assert not dk.ragged_decode_enabled()


def test_decode_kernel_supported_gates():
    import os

    if jax.default_backend() != "tpu":
        assert not dk.decode_kernel_supported(1, 4096, 512, 512)
    # kill-switch respected regardless of backend
    os.environ["PERCEIVER_IO_TPU_DISABLE_DECODE_KERNEL"] = "1"
    try:
        assert not dk.decode_kernel_supported(1, 4096, 512, 512)
    finally:
        del os.environ["PERCEIVER_IO_TPU_DISABLE_DECODE_KERNEL"]


@pytest.mark.slow
def test_full_model_decode_with_kernel_matches_plain(monkeypatch):
    """Force the fused-kernel branch (interpret mode) through the real
    MultiHeadAttention cached path: CausalSequenceModel.decode_step logits must
    match the kernel-off decode exactly (same cache policy, same masks)."""
    from perceiver_io_tpu.models.core.config import CausalSequenceModelConfig
    from perceiver_io_tpu.models.core.perceiver_ar import CausalSequenceModel

    cfg = CausalSequenceModelConfig(
        vocab_size=50, max_seq_len=16, max_latents=8, num_channels=32, num_heads=2,
        num_self_attention_layers=2, cross_attention_dropout=0.0,
    )
    model = CausalSequenceModel(config=cfg)
    rng = jax.random.PRNGKey(0)
    x = jax.random.randint(rng, (2, 12), 0, 50)
    params = model.init(rng, x, prefix_len=4)

    real_fused = dk.fused_decode_attention

    def run_decode(force_kernel):
        if force_kernel:
            monkeypatch.setattr(dk, "decode_kernel_supported", lambda n_q, *a, **kw: n_q == 1)
            monkeypatch.setattr(
                dk, "fused_decode_attention",
                lambda *a, **kw: real_fused(*a, interpret=True),
            )
        cache = model.init_cache(batch_size=2)
        logits, cache = model.apply(params, x, 4, cache, method=CausalSequenceModel.prefill)
        outs = []
        for t in range(3):
            tok = jnp.full((2, 1), 7 + t, jnp.int32)
            logits, cache = model.apply(params, tok, cache, method=CausalSequenceModel.decode_step)
            outs.append(np.asarray(logits))
        return np.stack(outs)

    plain = run_decode(False)
    fused = run_decode(True)
    np.testing.assert_allclose(fused, plain, atol=2e-5)


@pytest.mark.slow  # full-model interpret-kernel run x2; the default tier keeps
# ragged coverage via the cheap per-batch-position kernel tests above
def test_full_model_ragged_prompts_with_kernel_matches_plain(monkeypatch):
    """RAGGED prompts (per-batch lengths via LEFT padding — the reference's
    batched-generate convention, core/huggingface.py:89-156) through the fused
    kernel: per-batch pad slots and rope angles stream through the kernel's
    (B,)-scalar-prefetch path, and both single-token and n_q=4 chunked decode
    logits must match the kernel-off formulation (NOTES r2 item 3 /
    VERDICT r4 item 3's ragged-length kernel coverage)."""
    from perceiver_io_tpu.models.core.config import CausalSequenceModelConfig
    from perceiver_io_tpu.models.core.perceiver_ar import CausalSequenceModel

    cfg = CausalSequenceModelConfig(
        vocab_size=50, max_seq_len=16, max_latents=8, num_channels=32, num_heads=2,
        num_self_attention_layers=2, cross_attention_dropout=0.0,
    )
    model = CausalSequenceModel(config=cfg)
    rng = jax.random.PRNGKey(0)
    x = jax.random.randint(rng, (2, 12), 1, 50)
    # row 0 holds an 8-token prompt (4 left pads), row 1 a full 12-token one
    pad = np.zeros((2, 12), bool)
    pad[0, :4] = True
    x = jnp.asarray(np.where(pad, 0, np.asarray(x)))
    pad = jnp.asarray(pad)
    params = model.init(rng, x, prefix_len=4)

    real_fused = dk.fused_decode_attention

    def run_decode(force_kernel):
        if force_kernel:
            monkeypatch.setattr(dk, "decode_kernel_supported", lambda n_q, *a, **kw: 1 <= n_q <= 8)
            monkeypatch.setattr(
                dk, "fused_decode_attention",
                lambda *a, **kw: real_fused(*a, interpret=True),
            )
        else:
            monkeypatch.setattr(dk, "decode_kernel_supported", lambda *a, **kw: False)
        cache = model.init_cache(batch_size=2)
        logits, cache = model.apply(params, x, 4, cache, pad_mask=pad, method=CausalSequenceModel.prefill)
        outs = [np.asarray(logits)]
        for t in range(2):
            tok = jnp.full((2, 1), 7 + t, jnp.int32)
            logits, cache = model.apply(params, tok, cache, method=CausalSequenceModel.decode_step)
            outs.append(np.asarray(logits))
        chunk = jnp.asarray([[3, 4, 5, 6], [9, 10, 11, 12]], jnp.int32)
        logits, cache = model.apply(params, chunk, cache, method=CausalSequenceModel.decode_block)
        outs.append(np.asarray(logits))
        return outs

    plain = run_decode(False)
    fused = run_decode(True)
    for p, f in zip(plain, fused):
        np.testing.assert_allclose(f, p, atol=2e-5)


@pytest.mark.parametrize("form", ["3d", "stacked"])
def test_fused_decode_attention_auto_sharded_batch(form):
    """Mesh-aware dispatch: under a batch-sharded ambient mesh the kernel runs
    per-device inside shard_map (interpret mode on the 8-virtual-device CPU
    backend) and must match the single-device reference — in the 3-D form and
    in the stacked one, whose batch axis is the second and whose layer index
    is traced."""
    from perceiver_io_tpu.parallel.mesh import make_mesh

    b, h, d, cap, r = 8, 2, 32, 256, 16
    rng = lambda i: jax.random.PRNGKey(i)
    q = jax.random.normal(rng(0), (b, h, 1, d)) * 0.3
    k = jax.random.normal(rng(1), (b, cap, h * d)) * 0.3
    v = jax.random.normal(rng(2), (b, cap, h * d)) * 0.3
    ang = jnp.repeat(jax.random.normal(rng(3), (b, cap, r // 2)) * 0.5, 2, axis=-1)
    pad = jnp.zeros((b, cap), bool)
    q_pos = jnp.asarray(200)

    mesh = make_mesh({"data": 4}, devices=jax.devices()[:4])
    with jax.sharding.set_mesh(mesh):
        if form == "3d":
            out = jax.jit(lambda *a: dk.fused_decode_attention_auto(*a, interpret=True))(
                q, k, v, ang, q_pos, pad
            )
        else:
            stack = lambda t: jnp.stack([jnp.zeros_like(t), t])  # the cache is layer 1 of 2
            out = jax.jit(lambda *a, layer: dk.fused_decode_attention_auto(*a, layer=layer, interpret=True))(
                q, stack(k), stack(v), ang, q_pos, pad, layer=jnp.asarray(1, jnp.int32)
            )
    ref = xla_reference(q, k, v, ang, jnp.full((b,), 200), pad)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_decode_kernel_supported_multichip_gates(monkeypatch):
    """Multi-chip gating: batch-mappable meshes pass only with a divisible
    batch; sharded head/seq axes are rejected."""
    from perceiver_io_tpu.parallel.mesh import make_mesh

    monkeypatch.setattr(dk.jax, "default_backend", lambda: "tpu")
    assert jax.device_count() > 1  # conftest forces 8 virtual CPU devices

    with jax.sharding.set_mesh(make_mesh({"data": 4}, devices=jax.devices()[:4])):
        assert dk.decode_kernel_supported(1, 4096, 512, 512, 8, batch_size=8)
        assert not dk.decode_kernel_supported(1, 4096, 512, 512, 8, batch_size=6)  # 6 % 4 != 0
        assert not dk.decode_kernel_supported(1, 4096, 512, 512, 8)  # unknown batch
    with jax.sharding.set_mesh(make_mesh({"tensor": 4}, devices=jax.devices()[:4])):
        assert not dk.decode_kernel_supported(1, 4096, 512, 512, 8, batch_size=8)  # head axis
    with jax.sharding.set_mesh(make_mesh({"seq": 4}, devices=jax.devices()[:4])):
        assert not dk.decode_kernel_supported(1, 4096, 512, 512, 8, batch_size=8)  # unmappable
