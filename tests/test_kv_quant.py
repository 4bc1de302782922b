"""Quantized KV pages + weight serving (docs/serving.md "Quantized KV pages
& weight serving"; ISSUE 14).

The numerics contract: per-page-per-head int8 quantization's roundtrip error
is bounded by half an LSB of the page-head scale; the fused-dequant paged
kernel is BITWISE identical (interpret mode) to feeding the XLA-dequantized
f32 pool through the same kernel — across ring-wrapped live intervals and
partial last pages — and the engine's kernel-forced tokens match its XLA
fallback exactly. The rollback contract: ``kv_quant=None`` is exact f64
parity to the pre-quantization engine (generate()'s canonical form). The determinism
contract: quantized runs are repeat-identical, cache-on == cache-off, and a
preempted/quarantined slot leaves slot-mates bit-identical with the
condemned pages' bytes AND scales zeroed.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import perceiver_io_tpu.ops.paged_decode_kernel as pdk
from perceiver_io_tpu.generation.generate import GenerationConfig, generate
from perceiver_io_tpu.models.core.config import CausalSequenceModelConfig
from perceiver_io_tpu.models.core.perceiver_ar import CausalSequenceModel
from perceiver_io_tpu.reliability import armed
from perceiver_io_tpu.serving import PagePool, PrefixCache, ServingEngine
from perceiver_io_tpu.serving.quant import (
    cast_params_bf16,
    dequantize_params,
    quantize_params_int8,
    serve_params,
    tree_bytes,
)

VOCAB = 262
WINDOW = 12
LATENTS = 6
PS = 4  # page size used by most engine tests here


def _make_model(param_dtype=jnp.float32, window=WINDOW):
    config = CausalSequenceModelConfig(
        vocab_size=VOCAB, max_seq_len=window, max_latents=LATENTS, num_channels=16,
        num_heads=2, num_self_attention_layers=2, cross_attention_dropout=0.0,
    )
    model = CausalSequenceModel(config=config, param_dtype=param_dtype)
    rng = jax.random.PRNGKey(0)
    prompt = jax.random.randint(rng, (1, 8), 0, VOCAB)
    params = jax.jit(model.init, static_argnames="prefix_len")(rng, prompt, prefix_len=2)
    return model, params


@pytest.fixture(scope="module")
def setup():
    return _make_model()


def _quant_cache(n_pool, ps, h, d, table, start, window, qbits=8):
    # int4 pools pack two codes per byte along channels: uint8, C//2 wide
    pool_dtype = jnp.uint8 if qbits == 4 else jnp.int8
    c_phys = (h * d) // 2 if qbits == 4 else h * d
    return pdk.PagedKVCache(
        kp=jnp.zeros((n_pool, ps, c_phys), pool_dtype),
        vp=jnp.zeros((n_pool, ps, c_phys), pool_dtype),
        page_table=table, start=start, window=window,
        k_scale=jnp.zeros((n_pool, h), jnp.float32),
        v_scale=jnp.zeros((n_pool, h), jnp.float32),
        num_heads=h, qbits=qbits,
    )


# ---------------------------------------------------------------- numerics
@pytest.mark.parametrize("qbits", [8, 4])
def test_per_page_per_head_roundtrip_error_bound(qbits):
    """Quantize a page, dequantize it: the error of every entry is bounded by
    half an LSB of ITS page's, ITS head's scale — amax / (2 * qmax), qmax
    127 for int8 and 7 for nibble-packed int4 — the bound's
    per-page-per-head scoping exists to keep tight (a per-tensor scale
    would smear one loud head's amax over every quiet one)."""
    n_pool, ps, h, d = 5, 8, 4, 8
    qmax = 7.0 if qbits == 4 else 127.0
    rng = np.random.RandomState(0)
    # heads at wildly different magnitudes: the per-head bound must hold per
    # head, not merely on the loudest one
    blocks = rng.randn(3, ps, h * d).astype(np.float32)
    blocks.reshape(3, ps, h, d)[:, :, 1] *= 50.0
    blocks.reshape(3, ps, h, d)[:, :, 2] *= 0.01
    cache = _quant_cache(n_pool, ps, h, d,
                         jnp.asarray([[1, 2, 3]], jnp.int32),
                         jnp.zeros((1,), jnp.int32), 3 * ps, qbits=qbits)
    qc = cache.write_pages(jnp.asarray([1, 2, 3]), jnp.asarray(blocks),
                           jnp.asarray(blocks * 0.5))
    assert qc.kp.dtype == (jnp.uint8 if qbits == 4 else jnp.int8)
    assert qc.num_channels == h * d  # logical width survives nibble packing
    k_deq, v_deq = qc.gather_slot(jnp.asarray([1, 2, 3]))
    deq = np.asarray(k_deq)[0].reshape(3, ps, h, d)
    err = np.abs(deq - blocks.reshape(3, ps, h, d)).max(axis=(1, 3))  # (3, h)
    amax = np.abs(blocks.reshape(3, ps, h, d)).max(axis=(1, 3))
    bound = amax / (2 * qmax) * (1 + 1e-5) + 1e-8
    assert (err <= bound).all(), (err, bound)
    # v pool honors its own scales (amax halved -> bound halved)
    deq_v = np.asarray(v_deq)[0].reshape(3, ps, h, d)
    err_v = np.abs(deq_v - 0.5 * blocks.reshape(3, ps, h, d)).max(axis=(1, 3))
    assert (err_v <= bound / 2).all()


def test_append_ratchet_is_saturating_and_zeroes_fresh_pages():
    """The per-token append's scale RATCHET: a fresh page (scale 0) has its
    stale bytes zeroed by the first write; a louder later row grows the
    scale and requantizes the page's earlier rows by the exact ratio —
    never clipping them."""
    n_pool, ps, h, d = 4, 4, 2, 4
    cache = _quant_cache(n_pool, ps, h, d, jnp.asarray([[1, 2, 3]], jnp.int32),
                         jnp.zeros((1,), jnp.int32), 12)
    # poison page 1 with stale tenant garbage at a stale scale
    cache = cache.replace(
        kp=cache.kp.at[1].set(77), vp=cache.vp.at[1].set(-55),
    )
    row0 = np.full((1, 1, h * d), 0.5, np.float32)
    c1 = cache.append_token(jnp.asarray(row0), jnp.asarray(row0))
    kp = np.asarray(c1.kp)
    assert (kp[1, 0] == 127).all()  # the written row, at full scale use
    assert (kp[1, 1:] == 0).all()  # stale tenant bytes zeroed by ratio-0
    # a 10x louder second row ratchets the scale; row 0 requantizes to ~1/10
    row1 = np.full((1, 1, h * d), 5.0, np.float32)
    c2 = c1.append_token(jnp.asarray(row1), jnp.asarray(row1))
    kp2 = np.asarray(c2.kp)
    assert (kp2[1, 1] == 127).all()
    assert (kp2[1, 0] == 13).all()  # round(127 * 0.5/5.0) = 13, no clipping
    k_deq, _ = c2.gather_slot(jnp.asarray([1, 2, 3]))
    got = np.asarray(k_deq)[0][:2]
    assert np.allclose(got[0], 0.5, atol=5.0 / 254 + 1e-6)
    assert np.allclose(got[1], 5.0, atol=5.0 / 254 + 1e-6)


def test_append_ratchet_int4_zeroes_fresh_pages_and_requantizes():
    """The int4 form of the ratchet contract: a fresh page's stale PACKED
    bytes are zeroed by the first write (byte 0 == code -8 paired with
    scale 0 == exact 0.0), and a louder later row requantizes earlier rows
    by the scale ratio within the int4 half-LSB bound."""
    n_pool, ps, h, d = 4, 4, 2, 4
    cache = _quant_cache(n_pool, ps, h, d, jnp.asarray([[1, 2, 3]], jnp.int32),
                         jnp.zeros((1,), jnp.int32), 12, qbits=4)
    cache = cache.replace(
        kp=cache.kp.at[1].set(0x77), vp=cache.vp.at[1].set(0x55),
    )
    row0 = np.full((1, 1, h * d), 0.5, np.float32)
    c1 = cache.append_token(jnp.asarray(row0), jnp.asarray(row0))
    kp = np.asarray(c1.kp)
    # written row: code +7 in both nibbles -> (7+8) | ((7+8)<<4) = 0xFF
    assert (kp[1, 0] == 0xFF).all()
    # stale tenant nibbles collapse to packed code -8|-8 == byte 0, which
    # dequantizes to -8 * (ratio 0 requantize) = exact 0 rows
    k_deq, v_deq = c1.gather_slot(jnp.asarray([1, 2, 3]))
    assert (np.asarray(k_deq)[0, 1:ps] == 0).all()
    assert (np.asarray(v_deq)[0, 1:ps] == 0).all()
    # 10x louder second row ratchets the scale; both rows stay within the
    # int4 bound of THEIR magnitude (no clipping of the quiet row)
    row1 = np.full((1, 1, h * d), 5.0, np.float32)
    c2 = c1.append_token(jnp.asarray(row1), jnp.asarray(row1))
    k2, _ = c2.gather_slot(jnp.asarray([1, 2, 3]))
    got = np.asarray(k2)[0][:2]
    assert np.allclose(got[0], 0.5, atol=5.0 / 14 + 1e-6)
    assert np.allclose(got[1], 5.0, atol=5.0 / 14 + 1e-6)


def _quantized_kernel_inputs(window, ps, seed=0, r=None, h=2):
    b, d = 3, 32
    p = -(-window // ps)
    n_pool = 3 * p + 2
    rng = lambda i: jax.random.PRNGKey(seed + i)
    q = jax.random.normal(rng(0), (b, h, 1, d)) * 0.3
    kpf = jax.random.normal(rng(1), (n_pool, ps, h * d)) * 0.3
    vpf = jax.random.normal(rng(2), (n_pool, ps, h * d)) * 0.3
    perm = jax.random.permutation(rng(3), n_pool - 1)[: b * p] + 1
    table = jnp.asarray(np.asarray(perm).reshape(b, p), jnp.int32)
    ang = jnp.repeat(jax.random.normal(rng(4), (b, p * ps, (r or d) // 2)) * 0.5, 2, axis=-1)
    base = _quant_cache(n_pool, ps, h, d, table, jnp.zeros((b,), jnp.int32), window)
    qc = base.write_pages(jnp.arange(n_pool), kpf, vpf)
    return q, qc, table, ang


@pytest.mark.parametrize(
    "window,ps,starts,lives",
    [
        (256, 64, (0, 100, 255), (256, 40, 1)),     # saturated, mid, minimal
        (200, 64, (8, 72, 199), (200, 130, 64)),    # page does not divide window
        (256, 256, (0, 17, 128), (256, 100, 7)),    # one page per slot
    ],
)
def test_fused_dequant_kernel_bitwise_vs_xla_dequant_interpret(window, ps, starts, lives):
    """Acceptance: the fused-dequant kernel (scales on the scalar-prefetch
    path) is BITWISE identical to XLA-dequantizing the int8 pool to f32 and
    running the same kernel — fusion is exact, across ring-wrapped live
    intervals and partial last pages. Dead-page skip stays bitwise too."""
    q, qc, table, ang = _quantized_kernel_inputs(window, ps)
    start = jnp.asarray(starts, jnp.int32)
    live = jnp.asarray(lives, jnp.int32)
    d = qc.head_dim
    # the quantize-then-dequant XLA reference pool: q.astype(f32) * scale
    ks = jnp.repeat(qc.k_scale, d, axis=-1)[:, None, :]
    vs = jnp.repeat(qc.v_scale, d, axis=-1)[:, None, :]
    kdeq = qc.kp.astype(jnp.float32) * ks
    vdeq = qc.vp.astype(jnp.float32) * vs

    fused = pdk.fused_paged_decode_attention(
        q, qc.kp, qc.vp, table, start, live, ang, window, interpret=True,
        k_scale=qc.k_scale, v_scale=qc.v_scale,
    )
    ref = pdk.fused_paged_decode_attention(
        q, kdeq, vdeq, table, start, live, ang, window, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(ref))

    noskip = pdk.fused_paged_decode_attention(
        q, qc.kp, qc.vp, table, start, live, ang, window, interpret=True,
        skip_dead_pages=False, k_scale=qc.k_scale, v_scale=qc.v_scale,
    )
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(noskip))


@pytest.mark.parametrize(
    "r,zero_angles",
    [
        pytest.param(None, False, id="full-rotary"),
        pytest.param(8, False, id="partial-rotary"),
        pytest.param(2, True, id="zero-angles-r2"),  # the no-rotary call
    ],
)
def test_fused_dequant_kernel_matches_gather_softmax_reference(r, zero_angles):
    """The quantized kernel also matches the XLA gather + masked-softmax
    fallback formulation (the engine's CPU path) to float tolerance — the
    same (start, live) visibility bound on the same dequantized values; the
    dequant comes before the query-side rotary products, with rotary on all
    of a head, on part of it, and on the no-rotary call."""
    from tests.test_paging import paged_xla_reference

    window, ps = 256, 32
    q, qc, table, ang = _quantized_kernel_inputs(window, ps, seed=9, r=r)
    ang = jnp.zeros_like(ang) if zero_angles else ang
    start = jnp.asarray([40, 200, 0], jnp.int32)
    live = jnp.asarray([40, 200, 256], jnp.int32)
    out = pdk.fused_paged_decode_attention(
        q, qc.kp, qc.vp, table, start, live, ang, window, interpret=True,
        k_scale=qc.k_scale, v_scale=qc.v_scale,
    )
    ref = paged_xla_reference(
        q,
        # the dequantized pool: the reference gathers kp[table] itself
        qc.kp.astype(jnp.float32) * jnp.repeat(qc.k_scale, qc.head_dim, -1)[:, None, :],
        qc.vp.astype(jnp.float32) * jnp.repeat(qc.v_scale, qc.head_dim, -1)[:, None, :],
        table, start, live, ang, window,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_fused_dequant_kernel_has_no_square_operand():
    """Structure: the int8 paged kernel holds no (h*d, h*d) operand either."""
    from tests.test_decode_kernel import has_square_operand, pallas_operand_shapes

    window, ps, h = 256, 64, 4  # h*d = 128: no page or table dimension equals it
    q, qc, table, ang = _quantized_kernel_inputs(window, ps, h=h)
    start, live = jnp.zeros((3,), jnp.int32), jnp.full((3,), window, jnp.int32)
    shapes = pallas_operand_shapes(
        lambda q, kp, vp, ks, vs: pdk.fused_paged_decode_attention(
            q, kp, vp, table, start, live, ang, window, interpret=True, k_scale=ks, v_scale=vs
        ),
        q, qc.kp, qc.vp, qc.k_scale, qc.v_scale,
    )
    assert (3, 2, h * qc.head_dim, h) in shapes  # the query planes reached the kernel
    assert not has_square_operand(shapes, h * qc.head_dim)


def test_supported_gate_requires_int8_tile_alignment(monkeypatch):
    """The kernel gate's quantized arm is stricter than the fp arm: int8
    VMEM tiles are (32, 128), so quantized pools need 32-row pages — smaller
    quantized pages fall back to the (identical-contract) XLA path."""
    if jax.default_backend() != "tpu":
        assert not pdk.paged_decode_supported(32, 512, 512, quantized=True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda *a, **kw: 1)
    assert pdk.paged_decode_supported(24, 512, 512)  # fp: sublane-aligned ok
    assert not pdk.paged_decode_supported(24, 512, 512, quantized=True)
    assert pdk.paged_decode_supported(32, 512, 512, quantized=True)


# ------------------------------------------------------------ engine parity
def test_kv_quant_none_is_exact_f64_parity_to_pre_quant_engine(x64):
    """Acceptance: kv_quant=None / weight_dtype=None is the pre-PR engine —
    f64 greedy token identity to generate()'s canonical form (the existing
    paged parity contract, unchanged by this PR's plumbing)."""
    from tests.test_paging import _reference_tokens

    model, params = _make_model(param_dtype=jnp.float64)
    engine = ServingEngine(model, params, num_slots=2, kv_page_size=PS,
                           kv_quant=None, weight_dtype=None)
    prompts = [[5, 6, 7], list(range(3, 12)), [9] * WINDOW]
    handles = [engine.submit(p, max_new_tokens=4) for p in prompts]
    engine.run_until_drained(max_steps=200)
    for handle, prompt in zip(handles, prompts):
        expected = _reference_tokens(model, params, prompt,
                                     GenerationConfig(max_new_tokens=4))
        assert handle.result().tolist() == expected, f"len {len(prompt)} diverged"


def test_off_values_are_fp_and_match_the_default_engine(x64):
    """``kv_quant=None, weight_dtype=None`` is fp pages + untouched params —
    tokens f64-identical to the engine built without either argument."""
    model, params = _make_model(param_dtype=jnp.float64)
    prompts = [[5, 6, 7], list(range(3, 12))]

    def run(**kw):
        engine = ServingEngine(model, params, num_slots=2, kv_page_size=PS, **kw)
        handles = [engine.submit(p, max_new_tokens=4) for p in prompts]
        engine.run_until_drained(max_steps=200)
        return [h.result().tolist() for h in handles], engine

    base, _ = run()
    off, eo = run(kv_quant=None, weight_dtype=None)
    assert off == base
    assert eo.kv_quant is None and eo.weight_dtype is None
    assert eo._cache.ca.kp.dtype == jnp.float64 and eo.params is params
    assert eo.metrics.snapshot()["kv_quant"] is None
    assert eo.metrics.snapshot()["weight_serving"] is None
    # and set, the knobs actually engage
    _, eq = run(kv_quant="int8")
    assert eq.kv_quant == "int8" and eq._cache.ca.kp.dtype == jnp.int8


def test_quant_engine_deterministic_and_compiles_decode_once(setup):
    """Quantized churn: repeat runs token-identical (the ratchet/write paths
    are pure functions of the write history), ONE decode program, pages all
    home at drain."""
    model, params = setup

    def run():
        engine = ServingEngine(model, params, num_slots=2, kv_page_size=PS,
                               kv_quant="int8")
        lengths = [2, 5, 9, 3, 7, 12, 4]
        max_new = [3, 6, 2, 5, 4, 3, 7]
        handles = []
        for i, (n, m) in enumerate(zip(lengths, max_new)):
            handles.append(engine.submit(list(range(1, n + 1)), max_new_tokens=m,
                                         rng=jax.random.PRNGKey(i)))
            engine.step()
        engine.run_until_drained(max_steps=300)
        assert all(h.done for h in handles)
        assert [len(h.output_ids) for h in handles] == max_new
        return [h.result().tolist() for h in handles], engine

    toks1, engine = run()
    toks2, _ = run()
    assert toks1 == toks2  # deterministic under churn
    assert engine.decode_compilations == 1  # THE invariant, quant included
    assert engine.prefill_compilations <= len(engine.prefill_buckets)
    assert engine._jit_ragged_tick._cache_size() == 1  # resets, chunks and finishes ride it
    assert engine._pool.pages_in_use == 0
    assert all(p is None for p in engine._slot_pages)


def test_quant_engine_kernel_forced_matches_fallback(setup, monkeypatch):
    """Force the fused-dequant kernel (interpret mode) through the real
    quantized engine decode: tokens must match the XLA-fallback quantized
    engine exactly — the full-stack form of the kernel/fallback
    equivalence."""
    model, params = setup
    real = pdk.fused_paged_decode_attention

    def run(force):
        if force:
            monkeypatch.setattr(pdk, "paged_decode_supported", lambda *a, **kw: True)
            monkeypatch.setattr(pdk, "fused_paged_decode_attention",
                                lambda *a, **kw: real(*a, **{**kw, "interpret": True}))
        else:
            monkeypatch.setattr(pdk, "paged_decode_supported", lambda *a, **kw: False)
        engine = ServingEngine(model, params, num_slots=2, kv_page_size=PS,
                               kv_quant="int8")
        handles = [engine.submit(p, max_new_tokens=5)
                   for p in ([7, 3, 9], list(range(40, 49)))]
        engine.run_until_drained(max_steps=100)
        return [h.result().tolist() for h in handles]

    assert run(True) == run(False)


def test_quant_sampled_requests_reproducible(setup):
    """Sampling on a quantized engine is seed-reproducible: the rng chain is
    untouched by the page byte layout."""
    model, params = setup

    def run():
        engine = ServingEngine(model, params, num_slots=2, kv_page_size=PS,
                               kv_quant="int8")
        h = engine.submit([1, 2, 3], rng=jax.random.PRNGKey(7),
                          config=GenerationConfig(max_new_tokens=6, do_sample=True,
                                                  temperature=0.8, top_k=50))
        engine.run_until_drained(max_steps=100)
        return h.result().tolist()

    assert run() == run()


# ------------------------------------------------- prefix cache / preemption
def test_prefix_cache_mode_seam(setup):
    """Satellite: a PrefixCache built under one quantization mode REJECTS a
    reader in another — int8 pages must never be served to an fp reader."""
    pool = PagePool(8)
    c_int8 = PrefixCache(pool, PS, kv_quant="int8")
    c_int8.ensure_mode("int8")  # matching mode passes
    with pytest.raises(ValueError, match="never serves pages across"):
        c_int8.ensure_mode(None)
    c_fp = PrefixCache(pool, PS)
    with pytest.raises(ValueError, match="never serves pages across"):
        c_fp.ensure_mode("int8")
    # the engine wires its own mode through (both directions exercised above;
    # here: construction succeeds and the cache carries the engine's mode)
    model, params = setup
    engine = ServingEngine(model, params, num_slots=2, kv_page_size=PS,
                           kv_quant="int8", prefix_cache=True)
    assert engine._prefix_cache.kv_quant == "int8"
    engine.close()


def test_quant_cache_on_off_token_identity(setup):
    """A cache-hit fork reads the donor's QUANTIZED pages; a cold engine
    quantizes the same prompt through the same whole-page writes — so
    cache-on output is token-identical to cache-off (the fp engine's pinned
    identity, surviving quantization because page bytes are a pure function
    of the page's tokens)."""
    model, params = _make_model(window=24)
    preamble = [7] * 16

    def run(prefix_cache, chunk=None):
        engine = ServingEngine(model, params, num_slots=3, kv_page_size=PS,
                               kv_quant="int8", prefix_cache=prefix_cache,
                               prefill_chunk_tokens=chunk)
        donor = engine.submit(preamble + [1], max_new_tokens=3)
        engine.run_until_drained(max_steps=300)
        fork = engine.submit(preamble + [2], max_new_tokens=3)
        engine.run_until_drained(max_steps=300)
        assert donor.ok and fork.ok
        stats = engine._prefix_cache.stats() if engine._prefix_cache else None
        return donor.result().tolist(), fork.result().tolist(), stats

    d_off, f_off, _ = run(False)
    d_on, f_on, stats = run(True)
    assert (d_on, f_on) == (d_off, f_off)
    assert stats["hits"] >= 1  # the fork really forked
    d_ch, f_ch, stats_ch = run(True, chunk=8)  # page-aligned chunks
    assert (d_ch, f_ch) == (d_off, f_off)
    assert stats_ch["hits"] >= 1


def test_quant_preempt_resume_token_identity(setup):
    """A preempted quantized session resumes token-identical to an
    uncontended quantized run: the replay re-prefills and re-quantizes
    through the same deterministic write paths."""
    model, params = setup
    kw = dict(kv_page_size=PS, kv_quant="int8")
    ref_engine = ServingEngine(model, params, num_slots=2, **kw)
    ref = ref_engine.submit(list(range(1, 9)), max_new_tokens=4,
                            rng=jax.random.PRNGKey(1))
    ref_engine.run_until_drained(max_steps=100)

    engine = ServingEngine(model, params, num_slots=1, num_kv_pages=4, **kw)
    lo = engine.submit(list(range(1, 9)), max_new_tokens=4,
                       rng=jax.random.PRNGKey(1))
    engine.step()
    hi = engine.submit([9, 9, 9], max_new_tokens=2, priority=1)
    engine.run_until_drained(max_steps=200)
    assert lo.ok and hi.ok and lo.preemptions == 1
    assert lo.result().tolist() == ref.result().tolist()
    assert engine.decode_compilations == 1


# ------------------------------------------------------------- containment
@pytest.mark.parametrize("kv_quant", ["int8", "int4"])
def test_quant_quarantine_zeroes_bytes_and_scales(setup, kv_quant):
    """Containment on a quantized pool: the condemned slot's pages have
    their code bytes (int8, or int4 nibble-packed) AND scale sidecars
    zeroed before returning to the free list, and the survivor decodes on
    bit-identical."""
    model, params = setup
    kw = dict(num_slots=2, kv_page_size=PS, kv_quant=kv_quant)
    ref_engine = ServingEngine(model, params, **kw)
    ref = ref_engine.submit([4, 5, 6], max_new_tokens=5)
    ref_engine.run_until_drained(max_steps=100)

    engine = ServingEngine(model, params, **kw)
    poisoned = engine.submit(list(range(1, 10)), max_new_tokens=6)
    survivor = engine.submit([4, 5, 6], max_new_tokens=5)
    engine.step()
    condemned = list(engine._slot_pages[poisoned.slot] or [])
    assert condemned
    with armed("serving.nan", slot=poisoned.slot):
        engine.step()
    engine.run_until_drained(max_steps=100)

    assert poisoned.status.value == "failed"
    assert survivor.ok and survivor.result().tolist() == ref.result().tolist()
    assert engine._pool.pages_in_use == 0
    ca = engine._cache.ca
    assert (np.asarray(ca.kp)[condemned] == 0).all()
    assert (np.asarray(ca.vp)[condemned] == 0).all()
    assert (np.asarray(ca.k_scale)[condemned] == 0).all()
    assert (np.asarray(ca.v_scale)[condemned] == 0).all()
    assert np.isfinite(np.asarray(ca.k_scale)).all()
    assert np.isfinite(np.asarray(ca.v_scale)).all()


# ------------------------------------------------------------ weight serving
def test_weight_serving_bytes_and_dequant_roundtrip(setup):
    """bf16 halves resident float bytes; int8 quarters matmul-grade leaves
    (per-tensor scale) with a bounded dequant error; 1-D leaves (biases,
    norms) stay full precision."""
    model, params = setup
    fp = tree_bytes(params)
    bf = tree_bytes(cast_params_bf16(params))
    assert bf < 0.6 * fp
    q = quantize_params_int8(params)
    qb = tree_bytes(q)
    assert qb < 0.35 * fp
    deq = dequantize_params(q)
    flat_p = jax.tree_util.tree_leaves(params)
    flat_d = jax.tree_util.tree_leaves(deq)
    assert len(flat_p) == len(flat_d)
    for a, b in zip(flat_p, flat_d):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and a.dtype == b.dtype
        amax = np.abs(a).max()
        assert np.abs(a - b).max() <= amax / (2 * 127.0) * (1 + 1e-5) + 1e-8
    # serve_params routes the three modes and reports honest byte counts
    _, _, b_none, fp_none = serve_params(params, None)
    assert b_none == fp_none == fp
    with pytest.raises(ValueError, match="weight_dtype"):
        serve_params(params, "fp8")


def test_weight_serving_engine_runs_and_reports(setup):
    """bf16/int8 weight engines serve the same workload (quality measured by
    the bench arm, not pinned — quantized weights ARE lossy) and the v9
    snapshot carries the dtype + byte gauges; weight_dtype=None engines
    report None."""
    model, params = setup
    prompts = [[5, 6, 7], list(range(3, 12))]

    def run(weight_dtype):
        engine = ServingEngine(model, params, num_slots=2, kv_page_size=PS,
                               weight_dtype=weight_dtype)
        handles = [engine.submit(p, max_new_tokens=4) for p in prompts]
        engine.run_until_drained(max_steps=200)
        assert all(h.ok for h in handles)
        return engine

    e_none = run(None)
    assert e_none.metrics.snapshot()["weight_serving"] is None
    for wd, factor in (("bf16", 0.6), ("int8", 0.35)):
        e = run(wd)
        ws = e.metrics.snapshot()["weight_serving"]
        assert ws["dtype"] == wd
        assert ws["param_bytes"] < factor * ws["param_bytes_fp"]
        assert e.decode_compilations == 1


# ------------------------------------------------------------- construction
def test_constructor_validation(setup):
    model, params = setup
    with pytest.raises(ValueError, match="multiple of kv_page_size"):  # no page size: one page a window
        ServingEngine(model, params, num_slots=2, kv_quant="int8", prefill_chunk_tokens=6)
    with pytest.raises(ValueError, match="kv_quant must be one of"):
        ServingEngine(model, params, num_slots=2, kv_page_size=PS, kv_quant="int2")
    with pytest.raises(ValueError, match="weight_dtype must be one of"):
        ServingEngine(model, params, num_slots=2, weight_dtype="fp4")
    with pytest.raises(ValueError, match="multiple of kv_page_size"):
        ServingEngine(model, params, num_slots=2, kv_page_size=PS,
                      kv_quant="int8", prefill_chunk_tokens=6)


# ----------------------------------------------------------------- metrics
def test_metrics_v9_sections_and_reader_backcompat(setup, tmp_path):
    """v9 snapshots carry kv_quant/weight_serving (None where off); the
    reader normalizes pre-v9 snapshots with None — 'not recorded' stays
    distinguishable from 'quantization off'."""
    from perceiver_io_tpu.serving import load_metrics_jsonl
    from perceiver_io_tpu.serving.metrics import SCHEMA

    assert SCHEMA == "serving-metrics/v13"
    model, params = setup
    path = tmp_path / "v9.jsonl"
    engine = ServingEngine(model, params, num_slots=2, kv_page_size=PS,
                           kv_quant="int8", weight_dtype="bf16",
                           metrics_jsonl=str(path))
    h = engine.submit([1, 2, 3], max_new_tokens=3)
    engine.run_until_drained(max_steps=100)
    assert h.ok
    engine.metrics.record_quant_agreement(5, 6)
    snap = engine.metrics.write_snapshot()
    engine.close()
    assert snap["schema"] == "serving-metrics/v13"
    kvq = snap["kv_quant"]
    assert kvq["mode"] == "int8"
    assert kvq["bytes_per_token"] < kvq["bytes_per_token_fp"]
    assert kvq["agreement_rate"] == round(5 / 6, 4)
    assert snap["weight_serving"]["dtype"] == "bf16"

    got = load_metrics_jsonl(str(path))
    assert got["snapshots"][-1]["kv_quant"]["mode"] == "int8"
    assert any(e["event"] == "quant_agreement" for e in got["events"])

    # features off: truthful None, same reading as a pre-v9 snapshot
    plain = ServingEngine(model, params, num_slots=2, kv_page_size=PS)
    s = plain.metrics.snapshot()
    assert s["kv_quant"] is None and s["weight_serving"] is None
    plain.close()

    # pre-v9 stream: reader fills None, not 0
    old = tmp_path / "v8.jsonl"
    old.write_text(json.dumps({"event": "snapshot",
                               "schema": "serving-metrics/v8",
                               "requests_submitted": 1}) + "\n")
    loaded = load_metrics_jsonl(str(old))
    assert loaded["snapshots"][0]["kv_quant"] is None
    assert loaded["snapshots"][0]["weight_serving"] is None


# -------------------------------------------------------------- serve_bench
def test_serve_bench_kv_quant_arm_smoke(tmp_path):
    """CI satellite: ``serve_bench --kv-quant`` writes the quantized-capacity
    section — sessions at fixed pool bytes, int8 vs fp paged, greedy
    agreement + CE deltas reported, kv_quant=None byte-identity — into the
    BENCH_serving.json artifact."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "serve_bench_kv_quant_under_test",
        os.path.join(os.path.dirname(__file__), "..", "scripts", "serve_bench.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    out = tmp_path / "SERVE_BENCH.json"
    profile_out = tmp_path / "BENCH_serving.json"
    result = mod.main([
        "--preset", "tiny", "--slots", "2", "--requests", "3",
        "--kv-quant", "8", "--kv-quant-repeats", "2", "--no-baseline",
        "--out", str(out), "--profile-out", str(profile_out),
    ])
    block = result["kv_quant"]
    assert block["page_size"] == 8
    assert block["fp_arm"]["pool_bytes"] <= block["pool_byte_budget"]
    assert block["int8_arm"]["pool_bytes"] <= block["pool_byte_budget"]
    assert block["fp_arm"]["decode_compilations"] == 1
    assert block["int8_arm"]["decode_compilations"] == 1
    assert block["int8_arm"]["kv_quant"]["mode"] == "int8"
    assert block["concurrent_sessions_ratio"] >= 1.8  # the acceptance floor
    # quality is REPORTED, never silently dropped
    assert block["quality"]["greedy_token_agreement"] is not None
    assert block["quality"]["compared_tokens"] > 0
    assert block["kv_quant_none_identical_to_pre_quant"] is True
    assert set(block["weight_serving"]) == {"fp32", "bf16", "int8"}
    assert block["weight_serving"]["int8"]["ce_delta"] is not None
    on_disk = json.loads(profile_out.read_text())
    assert on_disk["kv_quant"]["page_size"] == 8
    assert (tmp_path / "BENCH_serving.manifest.json").exists()


# -------------------------------------------------------------------- chaos
def test_chaos_quant_quarantine_scenario():
    """The quant_quarantine scenario is registered (the matrix smoke in
    test_reliability covers it in CI) and green standalone."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chaos_check_quant_under_test",
        os.path.join(os.path.dirname(__file__), "..", "scripts", "chaos_check.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert "quant_quarantine" in mod.CHECKS
    result = mod.main(["--checks", "quant_quarantine"])
    assert result["all_ok"], result["checks"]["quant_quarantine"]
