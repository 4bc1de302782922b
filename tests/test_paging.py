"""Paged KV cache subsystem tests (docs/serving.md "Paged KV cache").

The parity contract: a paged engine's greedy output is token-identical to
``generate()``'s canonical full-window form — pinned in float64 across page
sizes straddling every prefill-ladder rung (page < bucket, page = bucket,
page not dividing the window) and against the default
(``kv_page_size=None``: one page a window). The kernel contract: the paged Pallas kernel's dead-page skipping is
BIT-identical to the skip-off kernel, and both match the XLA gather + masked
softmax fallback applying the same (start, live) visibility bound. The churn
contract: paging never adds decode programs (1, pinned) and every page
returns to the free list.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import perceiver_io_tpu.ops.paged_decode_kernel as pdk
from perceiver_io_tpu.generation.generate import GenerationConfig, generate
from perceiver_io_tpu.models.core.config import CausalSequenceModelConfig
from perceiver_io_tpu.models.core.perceiver_ar import CausalSequenceModel
from perceiver_io_tpu.ops.position import apply_rope
from perceiver_io_tpu.serving import PagePool, ServingEngine, pages_for_request
from perceiver_io_tpu.serving.paging import pages_for_tokens

VOCAB = 262
WINDOW = 12
LATENTS = 6

# the ladder for this model is (6, 12); these straddle every rung:
#   3 -> page < smallest bucket;  6 -> page == bucket;  5, 8 -> page does not
#   divide the window (partial last page);  12 -> page == window (one page)
PAGE_SIZES = (3, 5, 6, 8, 12)


def _make_model(param_dtype=jnp.float32):
    config = CausalSequenceModelConfig(
        vocab_size=VOCAB, max_seq_len=WINDOW, max_latents=LATENTS, num_channels=16,
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


def _reference_tokens(model, params, prompt, config: GenerationConfig):
    n = len(prompt)
    ids = np.full((1, WINDOW), config.pad_token_id, np.int64)
    pad = np.ones((1, WINDOW), bool)
    ids[0, WINDOW - n:] = prompt
    pad[0, WINDOW - n:] = False
    out = generate(model, params, jnp.asarray(ids), num_latents=LATENTS,
                   pad_mask=jnp.asarray(pad), config=config)
    toks = np.asarray(out)[0, WINDOW:].tolist()
    if config.eos_token_id is not None and config.eos_token_id in toks:
        toks = toks[: toks.index(config.eos_token_id) + 1]
    return toks


# -------------------------------------------------------------------- pool
def test_page_pool_deterministic_allocation_and_refcounts():
    pool = PagePool(8)  # page 0 reserved (trash)
    assert pool.free_pages == 7 and pool.pages_in_use == 0
    a = pool.allocate(3)
    assert a == [1, 2, 3]  # lowest ids first, ascending — deterministic
    b = pool.allocate(2)
    assert b == [4, 5] and pool.pages_in_use == 5
    pool.release([2])
    pool.release([1])
    assert pool.allocate(2) == [1, 2]  # freed ids recycle lowest-first
    # refcounts: retained pages survive one release
    pool.retain([3])
    pool.release([3])
    assert 3 not in pool.allocate(2)  # still held -> [6, 7]
    pool.release([3])
    assert pool.allocate(1) == [3]
    with pytest.raises(ValueError, match="double free"):
        pool.release([5]); pool.release([5])
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.allocate(10)
    with pytest.raises(ValueError, match="not allocated"):
        pool.retain([0])


def test_page_pool_release_validates_before_mutating():
    """Regression (ISSUE 9 satellite): a double-free MID-LIST must leave the
    pool untouched — release/retain validate the whole list first, then
    mutate, so the raise path cannot strand earlier pages half-released."""
    pool = PagePool(8)
    held = pool.allocate(3)  # [1, 2, 3]
    pool.release([2])  # page 2 now free: [held[0], held[2]] = [1, 3] remain
    before_free = pool.free_pages
    before_use = pool.pages_in_use
    with pytest.raises(ValueError, match="double free of page 2"):
        pool.release([1, 2, 3])  # invalid mid-list: 1 and 3 must NOT release
    assert pool.free_pages == before_free and pool.pages_in_use == before_use
    pool.release([1, 3])  # still held exactly once each — state was untouched
    assert pool.pages_in_use == 0
    # duplicate ids in ONE call count against the refcount up front
    p = pool.allocate(1)[0]
    with pytest.raises(ValueError, match="double free"):
        pool.release([p, p])
    assert pool.pages_in_use == 1  # untouched by the rejected call
    # out-of-range ids are rejected before any mutation, not mid-loop
    with pytest.raises(ValueError, match="outside pool"):
        pool.release([p, 999])
    assert pool.pages_in_use == 1
    with pytest.raises(ValueError, match="outside pool"):
        pool.retain([p, -1])
    pool.release([p])
    assert pool.pages_in_use == 0


def test_page_pool_refcount_interleavings():
    """The refcount interleavings the prefix-sharing fork (ROADMAP item 3)
    will lean on: retain -> release -> release ordering, allocate-after-free
    reissuing lowest ids, retain-after-free raising, and refcount isolation
    from unrelated alloc/free churn."""
    pool = PagePool(10)
    a = pool.allocate(2)  # [1, 2]
    # retain -> release -> release: the page survives the first release
    pool.retain([a[0]])
    pool.release([a[0]])
    assert pool.pages_in_use == 2  # still held through the second reference
    assert a[0] not in pool.allocate(2)  # [3, 4]: page 1 is not free
    pool.release([a[0]])  # second release frees it
    assert pool.allocate(1) == [a[0]]  # allocate-after-free reissues lowest id
    # retain on a FREED id raises (and mutates nothing)
    pool.release([a[1]])
    with pytest.raises(ValueError, match="not allocated"):
        pool.retain([a[1]])
    assert pool.allocate(1) == [a[1]]  # still cleanly allocatable
    # refcounts are unaffected by unrelated alloc/free churn
    shared = pool.allocate(1)[0]
    pool.retain([shared])  # refcount 2
    churn = pool.allocate(3)
    pool.release(churn)
    pool.release(pool.allocate(2))
    pool.release([shared])
    assert shared not in pool._free  # one reference still held
    pool.release([shared])
    assert shared in pool._free


def test_pages_for_request_reservation():
    # bucket + generation budget, capped at the window
    assert pages_for_request(6, 4, WINDOW, 3) == pages_for_tokens(10, 3) == 4
    assert pages_for_request(6, 100, WINDOW, 3) == 4  # capped at window=12
    assert pages_for_request(12, 1, WINDOW, 5) == 3  # partial last page
    assert pages_for_request(6, 1, WINDOW, 12) == 1


# ------------------------------------------------------------------ parity
@pytest.mark.parametrize("page_size", PAGE_SIZES)
def test_paged_engine_matches_generate_across_page_sizes(x64, page_size):
    """Acceptance: paged greedy engine output token-identical to generate()'s
    canonical full-window form, in float64, for prompt lengths straddling
    every prefill-ladder rung (1, bucket, bucket+1, window) — across page
    sizes straddling every rung themselves."""
    model, params = _make_model(param_dtype=jnp.float64)
    engine = ServingEngine(model, params, num_slots=3, kv_page_size=page_size)
    assert engine.prefill_buckets == (LATENTS, WINDOW)
    lengths = sorted({1, *(n for b in engine.prefill_buckets for n in (b, min(b + 1, WINDOW))), WINDOW})
    prompts = [list(range(3, 3 + n)) for n in lengths]
    handles = [engine.submit(p, max_new_tokens=5) for p in prompts]
    engine.run_until_drained(max_steps=300)
    for handle, prompt in zip(handles, prompts):
        expected = _reference_tokens(model, params, prompt, GenerationConfig(max_new_tokens=5))
        assert handle.result().tolist() == expected, f"len {len(prompt)} diverged at page {page_size}"
        assert handle.pages_allocated == pages_for_request(
            engine._bucket_for(len(prompt)), 5, WINDOW, page_size
        )
    assert engine._pool.pages_in_use == 0  # eviction returned every page


def test_paged_off_value_is_the_dense_pool_and_matches(x64):
    """``kv_page_size=None`` is one page a window, and (greedy, float64) it
    produces page 4's tokens."""
    model, params = _make_model(param_dtype=jnp.float64)

    def run(kv_page_size):
        engine = ServingEngine(model, params, num_slots=2, kv_page_size=kv_page_size)
        handles = [engine.submit(p, max_new_tokens=4) for p in ([5, 6, 7], list(range(40, 49)))]
        engine.run_until_drained(max_steps=100)
        return [h.result().tolist() for h in handles], engine.kv_page_size, engine._pages_per_slot

    toks_paged, page, per_slot = run(4)
    toks_default, default_page, default_per_slot = run(None)
    assert (page, per_slot) == (4, 3) and (default_page, default_per_slot) == (WINDOW, 1)
    assert toks_paged == toks_default


@pytest.mark.parametrize("option", [dict(prefix_cache=True), dict(prefill_chunk_tokens=4), dict(kv_quant="int8")],
                         ids=["prefix_cache", "prefill_chunk_tokens", "kv_quant"])
def test_pool_options_need_no_page_size(x64, option):
    """What the page pool carries is accepted by an engine built without
    ``kv_page_size`` (one page a window) and serves; at full precision with
    the plain default engine's tokens."""
    model, params = _make_model(param_dtype=jnp.float64)
    prompts = ([5, 6, 7], list(range(40, 49)), list(range(40, 49)))

    def run(**pool):
        engine = ServingEngine(model, params, num_slots=2, **pool)
        handles = [engine.submit(p, max_new_tokens=4) for p in prompts]
        engine.run_until_drained(max_steps=100)
        assert all(h.ok and len(h.output_ids) == 4 for h in handles)
        return engine, [h.result().tolist() for h in handles]

    engine, tokens = run(**option)
    assert engine.kv_page_size == WINDOW and engine._pool.pages_in_use == 0
    snap = engine.metrics.snapshot()
    if "kv_quant" in option:
        assert snap["kv_quant"]["mode"] == "int8" and engine._cache.ca.kp.dtype == jnp.int8
    else:
        assert tokens == run()[1]
        block = "prefix_cache" if "prefix_cache" in option else "chunked_prefill"
        assert snap[block] is not None


def test_default_engine_is_one_page_a_window(setup):
    """``ServingEngine(model, params)`` and nothing else: a page pool of one
    page a window a slot plus the trash page, the fused tick, and a snapshot
    that carries both blocks."""
    model, params = setup
    engine = ServingEngine(model, params)
    assert engine.kv_page_size == WINDOW and engine._pages_per_slot == 1
    assert engine._pool.num_pages == engine.num_slots + 1 and engine.ragged
    handle = engine.submit([5, 6, 7], max_new_tokens=3)
    engine.run_until_drained(max_steps=50)
    snap = engine.metrics.snapshot()
    assert handle.ok and handle.pages_allocated == 1
    assert snap["page_pool"]["pages_total"] == engine.num_slots and snap["page_pool"]["pages_in_use"] == 0
    assert snap["ragged_tick"]["enabled"] and snap["ragged_tick"]["programs_per_tick"]["p50"] is not None
    assert engine.decode_compilations == 1 and not hasattr(engine, "paged")


def test_paged_sampled_requests_reproducible(setup):
    """Sampling shares the one paged decode program and stays reproducible
    under its seed (the rng chain is untouched by the cache layout)."""
    model, params = setup

    def run(page_size=None):
        kw = {} if page_size is None else {"kv_page_size": page_size}
        engine = ServingEngine(model, params, num_slots=2, **kw)
        h = engine.submit([1, 2, 3], rng=jax.random.PRNGKey(7),
                          config=GenerationConfig(max_new_tokens=6, do_sample=True,
                                                  temperature=0.8, top_k=50))
        engine.run_until_drained(max_steps=100)
        return h.result().tolist()

    assert run(page_size=4) == run(page_size=4)  # seed-reproducible
    assert run(page_size=4) == run(page_size=None)  # layout-invariant chain


# ------------------------------------------------------------------- churn
def test_paged_churn_compiles_decode_once(setup):
    """Churn with paging on: one decode program ever, installs bounded by the
    ladder, the release-pages/quarantine programs compile at most once, and
    the free list is whole again after the storm."""
    model, params = setup
    engine = ServingEngine(model, params, num_slots=2, kv_page_size=4)
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
    assert engine.scheduler.total_admissions == len(lengths)
    assert engine.decode_compilations == 1  # THE invariant, paging included
    assert engine.prefill_compilations <= len(engine.prefill_buckets)
    assert engine._jit_install._cache_size() <= len(engine.prefill_buckets)
    assert engine._jit_release_pages._cache_size() <= 1
    assert engine._pool.pages_in_use == 0
    assert all(p is None for p in engine._slot_pages)


# ------------------------------------------------------------- backpressure
def test_pool_exhaustion_is_queue_full_backpressure(setup):
    """Pool exhaustion surfaces as the existing queue_full contract: the
    head-of-line request WAITS (alloc_failure, not a crash) and is admitted
    when pages free; past the bound, submits are REJECTED/queue_full."""
    model, params = setup
    # 12/4 = 3 pages per window; pool of 4 allocatable pages fits exactly one
    # 7-token-prompt request (bucket 12 + budget -> 3 pages) at a time
    engine = ServingEngine(model, params, num_slots=2, kv_page_size=4,
                           num_kv_pages=5, max_queue_depth=1)
    first = engine.submit(list(range(1, 8)), max_new_tokens=3)
    engine.step()  # admitted: 3 of 4 pages in use
    assert first.status.value == "running" and engine._pool.pages_in_use == 3
    waiter = engine.submit(list(range(1, 8)), max_new_tokens=3)
    engine.step()  # head-blocked on pages (2 slots free, 1 page free)
    assert waiter.status.value == "queued"
    assert engine.metrics.alloc_failures >= 1
    overflow = engine.submit(list(range(1, 8)), max_new_tokens=3)  # past bound
    assert overflow.done and overflow.finish_reason == "queue_full"
    engine.run_until_drained(max_steps=100)
    assert first.ok and waiter.ok  # the waiter was admitted once pages freed
    snap = engine.metrics.snapshot()
    assert snap["page_pool"]["alloc_failures"] >= 1
    assert snap["page_pool"]["pages_in_use"] == 0
    assert snap["rejected"] == 1


def test_paged_engine_rejects_undersized_pool(setup):
    model, params = setup
    with pytest.raises(ValueError, match="num_kv_pages"):
        ServingEngine(model, params, num_slots=1, kv_page_size=4, num_kv_pages=3)
    with pytest.raises(ValueError, match="kv_page_size"):
        ServingEngine(model, params, num_slots=1, kv_page_size=WINDOW + 1)


# ------------------------------------------------------------- containment
def test_paged_nan_quarantine_zeroes_and_frees_pages(setup):
    """Containment under paging: the poisoned slot is evicted FAILED, its
    pages are ZEROED before returning to the free list (stale NaN gathered at
    weight 0 would poison a later tenant's softmax), and the survivor decodes
    on token-identical."""
    from perceiver_io_tpu.reliability import armed

    model, params = setup
    ref_engine = ServingEngine(model, params, num_slots=2, kv_page_size=4)
    ref = ref_engine.submit([4, 5, 6], max_new_tokens=5)
    ref_engine.run_until_drained(max_steps=100)

    engine = ServingEngine(model, params, num_slots=2, kv_page_size=4)
    poisoned = engine.submit([1, 2, 3], max_new_tokens=6)
    survivor = engine.submit([4, 5, 6], max_new_tokens=5)
    engine.step()
    with armed("serving.nan", slot=poisoned.slot):
        engine.step()
    engine.run_until_drained(max_steps=100)

    assert poisoned.status.value == "failed"
    assert survivor.ok and survivor.result().tolist() == ref.result().tolist()
    assert engine._pool.pages_in_use == 0
    # nothing non-finite survives anywhere in the page pool
    assert np.isfinite(np.asarray(engine._cache.ca.kp)).all()
    assert np.isfinite(np.asarray(engine._cache.ca.vp)).all()
    assert engine.decode_compilations == 1


# ----------------------------------------------------------------- metrics
def test_metrics_v5_page_pool_and_reader(tmp_path, setup):
    model, params = setup
    path = tmp_path / "paged.jsonl"
    engine = ServingEngine(model, params, num_slots=2, kv_page_size=4,
                           metrics_jsonl=str(path))
    engine.submit([1, 2, 3], max_new_tokens=3)
    engine.run_until_drained(max_steps=50)
    snap = engine.metrics.write_snapshot()
    engine.close()
    pool = snap["page_pool"]
    assert pool["pages_total"] == 2 * pages_for_tokens(WINDOW, 4)
    assert pool["pages_in_use"] == 0 and pool["alloc_failures"] == 0
    assert pool["pages_per_request"]["p50"] == 3.0  # bucket 6 + 3 new -> ceil(9/4)

    from perceiver_io_tpu.serving import load_metrics_jsonl

    got = load_metrics_jsonl(str(path))
    admit = next(e for e in got["events"] if e["event"] == "admit")
    assert admit["pages"] == 3
    assert got["snapshots"][-1]["page_pool"] == pool

    # pre-v5 snapshots normalize page_pool to None; unknown schemas still raise
    v4 = tmp_path / "v4.jsonl"
    v4.write_text(json.dumps({
        "event": "snapshot", "ts": 1.0, "schema": "serving-metrics/v4",
        "num_slots": 2, "tokens_generated": 5, "failovers": 0,
    }) + "\n")
    old = load_metrics_jsonl(str(v4))["snapshots"][0]
    assert old["page_pool"] is None and old["failovers"] == 0
    bad = tmp_path / "bad.jsonl"
    bad.write_text(json.dumps({"event": "snapshot", "schema": "serving-metrics/v99"}) + "\n")
    with pytest.raises(ValueError, match="unknown metrics schema"):
        load_metrics_jsonl(str(bad))


# ------------------------------------------------------------------ kernel
def paged_xla_reference(q, kp, vp, table, start, live, ang, window):
    """Gather-through-the-table masked softmax — the fallback formulation the
    kernel must match (same (start, live) visibility bound)."""
    b, h, n_q, d = q.shape
    k = kp[table].reshape(b, -1, h * d)
    v = vp[table].reshape(b, -1, h * d)
    n_phys = k.shape[1]
    kh = apply_rope(k.reshape(b, n_phys, h, d).transpose(0, 2, 1, 3).astype(jnp.float32), ang)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kh)
    vis = pdk.paged_visibility(start, live, window, n_phys)
    s = jnp.where(vis[:, None, None, :], s, -jnp.inf)
    vh = v.reshape(b, n_phys, h, d).transpose(0, 2, 1, 3).astype(jnp.float32)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), vh)


def _kernel_inputs(b, h, d, window, ps, n_pool, seed=0, r=None):
    rng = lambda i: jax.random.PRNGKey(seed + i)
    p = -(-window // ps)
    q = jax.random.normal(rng(0), (b, h, 1, d)) * 0.3
    kp = jax.random.normal(rng(1), (n_pool, ps, h * d)) * 0.3
    vp = jax.random.normal(rng(2), (n_pool, ps, h * d)) * 0.3
    # distinct pages per row (the allocator invariant), deliberately shuffled
    perm = jax.random.permutation(rng(3), n_pool - 1)[: b * p] + 1
    table = jnp.asarray(np.asarray(perm).reshape(b, p), jnp.int32)
    ang = jnp.repeat(jax.random.normal(rng(4), (b, p * ps, (r or d) // 2)) * 0.5, 2, axis=-1)
    return q, kp, vp, table, ang


@pytest.mark.parametrize(
    "r,zero_angles",
    [
        pytest.param(8, False, id="partial-rotary"),
        pytest.param(2, True, id="zero-angles-r2"),  # the no-rotary call
    ],
)
def test_paged_kernel_query_side_rotation_interpret(r, zero_angles):
    """The rotation is applied on the query side (decode_kernel._rotary_scores):
    parity with rotating the gathered keys where rotary covers part of a head
    (q_hat is zero on the rest) and on the no-rotary call (zero angles, r = 2),
    over wrapped live intervals and a partial last page."""
    window, ps, b, h, d = 200, 64, 3, 2, 32
    q, kp, vp, table, ang = _kernel_inputs(b, h, d, window, ps, n_pool=3 * 4 + 2, seed=21, r=r)
    ang = jnp.zeros_like(ang) if zero_angles else ang
    start = jnp.asarray((8, 72, 199), jnp.int32)
    live = jnp.asarray((200, 130, 64), jnp.int32)
    out = pdk.fused_paged_decode_attention(q, kp, vp, table, start, live, ang, window, interpret=True)
    ref = paged_xla_reference(q, kp, vp, table, start, live, ang, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_paged_kernel_has_no_square_operand():
    """Structure: the traced paged kernel holds no (h*d, h*d) operand — the
    rotate-half constant and its matmul are gone."""
    from tests.test_decode_kernel import has_square_operand, pallas_operand_shapes

    window, ps, b, h, d = 256, 64, 3, 4, 32  # h*d = 128: no page or table dimension equals it
    q, kp, vp, table, ang = _kernel_inputs(b, h, d, window, ps, n_pool=3 * 4 + 2)
    start, live = jnp.zeros((b,), jnp.int32), jnp.full((b,), window, jnp.int32)
    shapes = pallas_operand_shapes(
        lambda *a: pdk.fused_paged_decode_attention(*a, window, interpret=True), q, kp, vp, table, start, live, ang
    )
    assert (b, 2, h * d, h) in shapes  # the query planes reached the kernel
    assert not has_square_operand(shapes, h * d)


@pytest.mark.parametrize(
    "window,ps,starts,lives",
    [
        (256, 64, (0, 100, 255), (256, 40, 1)),     # saturated, mid, minimal
        (200, 64, (8, 72, 199), (200, 130, 64)),    # page does not divide window
        (256, 256, (0, 17, 128), (256, 100, 7)),    # one page per slot
    ],
)
def test_paged_kernel_matches_gather_reference_interpret(window, ps, starts, lives):
    """The paged kernel (interpret mode) matches the XLA gather + masked
    softmax fallback across ring offsets and live counts, including wrapped
    live intervals and a partial last page."""
    b, h, d = 3, 2, 32
    q, kp, vp, table, ang = _kernel_inputs(b, h, d, window, ps, n_pool=3 * (-(-window // ps)) + 2)
    start = jnp.asarray(starts, jnp.int32)
    live = jnp.asarray(lives, jnp.int32)
    out = pdk.fused_paged_decode_attention(
        q, kp, vp, table, start, live, ang, window, interpret=True
    )
    ref = paged_xla_reference(q, kp, vp, table, start, live, ang, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_paged_kernel_dead_page_skip_bitwise_interpret():
    """Acceptance (paged ragged decode): skipping pages with no live position
    leaves the flash state BIT-identical to fetching and masking them — the
    skipped pages contribute prob = 0 / scale = 1 exactly."""
    window, ps = 256, 32
    b, h, d = 3, 2, 32
    q, kp, vp, table, ang = _kernel_inputs(b, h, d, window, ps, n_pool=3 * 8 + 2, seed=9)
    # unsaturated rows: live < window with start == live (the engine's
    # admission layout — dead tail pages), plus one saturated row
    start = jnp.asarray([40, 200, 0], jnp.int32)
    live = jnp.asarray([40, 200, 256], jnp.int32)
    skip = pdk.fused_paged_decode_attention(
        q, kp, vp, table, start, live, ang, window, skip_dead_pages=True, interpret=True
    )
    full = pdk.fused_paged_decode_attention(
        q, kp, vp, table, start, live, ang, window, skip_dead_pages=False, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(skip), np.asarray(full))


def test_paged_decode_supported_gates():
    import os

    if jax.default_backend() != "tpu":
        assert not pdk.paged_decode_supported(128, 512, 512)
    os.environ["PERCEIVER_IO_TPU_DISABLE_DECODE_KERNEL"] = "1"
    try:
        assert not pdk.paged_decode_supported(128, 512, 512)
    finally:
        del os.environ["PERCEIVER_IO_TPU_DISABLE_DECODE_KERNEL"]


def test_paged_engine_with_kernel_forced_matches_fallback(setup, monkeypatch):
    """Force the paged kernel (interpret mode) through the real engine decode:
    tokens must match the XLA-fallback engine exactly — the full-stack form
    of the kernel/fallback equivalence."""
    model, params = setup
    real = pdk.fused_paged_decode_attention

    def run(force):
        if force:
            monkeypatch.setattr(pdk, "paged_decode_supported", lambda *a, **kw: True)
            monkeypatch.setattr(pdk, "fused_paged_decode_attention",
                                lambda *a, **kw: real(*a, **{**kw, "interpret": True}))
        else:
            monkeypatch.setattr(pdk, "paged_decode_supported", lambda *a, **kw: False)
        engine = ServingEngine(model, params, num_slots=2, kv_page_size=4)
        handles = [engine.submit(p, max_new_tokens=5)
                   for p in ([7, 3, 9], list(range(40, 49)))]
        engine.run_until_drained(max_steps=100)
        return [h.result().tolist() for h in handles]

    fallback = run(False)
    kernel = run(True)
    assert kernel == fallback


# -------------------------------------------------------------- serve_bench
def test_serve_bench_paging_arm_smoke(tmp_path):
    """CI satellite: ``serve_bench --page-size`` writes the paging section —
    concurrent sessions per fixed KV budget, paged vs dense — into the
    BENCH_serving.json artifact, with both arms compiling one decode program
    and the paged pool living inside the dense arm's token budget."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "serve_bench_paging_under_test",
        os.path.join(os.path.dirname(__file__), "..", "scripts", "serve_bench.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    out = tmp_path / "SERVE_BENCH.json"
    profile_out = tmp_path / "BENCH_serving.json"
    result = mod.main([
        "--preset", "tiny", "--slots", "2", "--requests", "3",
        "--page-size", "8", "--page-repeats", "2", "--no-baseline",
        "--out", str(out), "--profile-out", str(profile_out),
    ])
    paging = result["paging"]
    assert paging["page_size"] == 8
    assert paging["dense_pool"]["kv_budget_tokens"] == paging["paged_pool"]["kv_budget_tokens"]
    assert paging["paged_pool"]["num_kv_pages"] * 8 <= paging["kv_budget_tokens"]
    assert paging["dense_pool"]["decode_compilations"] == 1
    assert paging["paged_pool"]["decode_compilations"] == 1
    assert paging["paged_pool"]["peak_concurrent_sessions"] >= 1
    assert paging["concurrent_sessions_ratio"] > 0
    # merged into the tracked artifact alongside any other sections
    on_disk = json.loads(profile_out.read_text())
    assert on_disk["paging"]["page_size"] == 8
    assert (tmp_path / "BENCH_serving.manifest.json").exists()


# ------------------------------------------------------------------ rewind
def test_paged_rewind_matches_dense_rewind_contract(setup):
    """PagedPerceiverARCache.rewind un-appends exactly: decode k tokens,
    rewind k, decode again — the logits stream repeats (the speculative
    verification contract the dense cache already honors)."""
    model, params = setup
    engine = ServingEngine(model, params, num_slots=1, kv_page_size=4)
    h = engine.submit([1, 2, 3, 4], max_new_tokens=1)
    engine.step()
    engine.run_until_drained(max_steps=20)
    assert h.ok
    # drive the model method directly on the engine's (now free) pool: install
    # left the slot released, so re-admit one request and snapshot the cache
    h2 = engine.submit([5, 6, 7], max_new_tokens=8)
    engine.step_dispatch()
    engine.step_harvest()
    cache = engine._cache
    tok = jnp.asarray([[9]], jnp.int32)
    logits1, cache1 = model.apply(params, tok, cache, method=CausalSequenceModel.decode_step_paged)
    cache_rw = cache1.rewind(1)
    logits2, _ = model.apply(params, tok, cache_rw, method=CausalSequenceModel.decode_step_paged)
    np.testing.assert_array_equal(np.asarray(logits1), np.asarray(logits2))


def test_paged_rewind_round_trip_same_bytes_same_tokens(setup):
    """Append, rewind, append again, on two slots whose rings sit at different
    offsets: both rings (the page pool's and the self-attention cache's) hold
    the same bytes at the same offsets and the logits repeat. Exact for the
    one-token rewind only, on the parent's rolled cache as on the ring: the
    self-attention cache is always full, so a rewound row keeps the rejected
    token until it is appended over, and with k > 1 the steps in between read
    it as an old latent; for those, ``rewind`` steps the offsets and no more."""
    model, params = setup
    engine = ServingEngine(model, params, num_slots=2, kv_page_size=4)
    engine.submit([5, 6, 7], max_new_tokens=30)
    engine.step()
    engine.submit(list(range(40, 45)), max_new_tokens=30)  # short: the window never saturates here
    for _ in range(4):  # past both installs: the slots' rings sit mid-turn, apart
        engine.step()
    cache0 = engine._cache
    assert len(set(np.asarray(cache0.sa.start).tolist())) == 2
    tok = jnp.asarray([[9], [17]], jnp.int32)
    step = lambda cache: model.apply(params, tok, cache, method=CausalSequenceModel.decode_step_paged)

    logits1, cache1 = step(cache0)
    rewound = cache1.rewind(1)
    for ring in ("sa", "ca"):
        np.testing.assert_array_equal(
            np.asarray(getattr(rewound, ring).start), np.asarray(getattr(cache0, ring).start))
    np.testing.assert_array_equal(np.asarray(rewound.live), np.asarray(cache0.live))
    logits2, cache2 = step(rewound)
    np.testing.assert_array_equal(np.asarray(logits1), np.asarray(logits2))
    same = lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    jax.tree_util.tree_map(same, cache2, cache1)  # every buffer and offset of the pool
    # k > 1 steps the offsets back by k, modulo each ring's length
    cap, window = cache1.sa.capacity, cache1.ca.window
    back = cache1.rewind(cap + 2)
    same(back.sa.start, (np.asarray(cache1.sa.start) - 2) % cap)
    same(back.ca.start, (np.asarray(cache1.ca.start) - cap - 2) % window)


# ------------------------------------------------------ self-attention ring
# The paged pool's self-attention cache is a ring (ops/attention.RingKVCache):
# one row a slot a layer written in place, read where it lies. Toy sizes: 8
# latents and 19 decode steps a request turn every ring twice and more. The
# window is 40, not 16: a request may use the prefix cache only if its prompt
# and answer fit the window (its pages must never be appended over), and 14 +
# 19 tokens have to.
RING_WINDOW, RING_LATENTS, RING_PS, RING_SLOTS = 40, 8, 4, 4
RING_NEW = 2 * RING_LATENTS + 3
RING_PROMPTS = {
    "prefill_install": [5, 6, 7, 8, 9],  # shorter than the latents: prefill + install
    "chunks_finish": list(range(100, 130)),  # 30 tokens: chunks of 4, the finish; the page ring wraps too
    "prefix_donor": list(range(40, 54)),  # 14 tokens: its first page of 4 is cacheable
    "prefix_hit": list(range(40, 44)) + list(range(70, 80)),  # the donor's first page, then its own
    "evict_readmit": [3, 1, 4, 1, 5, 9, 2, 6, 5, 3],  # evicted mid-decode, admitted again
}
_RING_RUNS: dict = {}


def _ring_model():
    if "model" not in _RING_RUNS:
        config = CausalSequenceModelConfig(
            vocab_size=VOCAB, max_seq_len=RING_WINDOW, max_latents=RING_LATENTS, num_channels=16,
            num_heads=2, num_self_attention_layers=2, cross_attention_dropout=0.0,
        )
        model = CausalSequenceModel(config=config, param_dtype=jnp.float64)
        rng = jax.random.PRNGKey(0)
        params = jax.jit(model.init, static_argnames="prefix_len")(
            rng, jax.random.randint(rng, (1, 12), 0, VOCAB), prefix_len=4
        )
        _RING_RUNS["model"] = (model, params)
    return _RING_RUNS["model"]


def _ring_reference(name):
    """``generate()`` on the canonical left-padded full window."""
    key = ("reference", name)
    if key not in _RING_RUNS:
        model, params = _ring_model()
        prompt = RING_PROMPTS[name]
        ids = np.zeros((1, RING_WINDOW), np.int64)
        pad = np.ones((1, RING_WINDOW), bool)
        ids[0, RING_WINDOW - len(prompt):] = prompt
        pad[0, RING_WINDOW - len(prompt):] = False
        out = generate(model, params, jnp.asarray(ids), num_latents=RING_LATENTS,
                       pad_mask=jnp.asarray(pad), config=GenerationConfig(max_new_tokens=RING_NEW))
        _RING_RUNS[key] = np.asarray(out)[0, RING_WINDOW:].tolist()
    return _RING_RUNS[key]


def _ring_run():
    """One engine life, shared by the cases below: requests of mixed lengths
    admitted at different ticks, so that the slots' rings sit at different
    offsets; every admission path; one slot evicted and admitted again while
    its ring is mid-turn."""
    if "run" in _RING_RUNS:
        return _RING_RUNS["run"]
    model, params = _ring_model()
    engine = ServingEngine(model, params, num_slots=RING_SLOTS, kv_page_size=RING_PS,
                           prefill_chunk_tokens=4, prefix_cache=True)
    submit = lambda name: engine.submit(RING_PROMPTS[name], max_new_tokens=RING_NEW)
    handles, starts = {}, []

    def steps(n):
        for _ in range(n):
            engine.step()
            starts.append(np.asarray(engine._cache.sa.start).tolist())

    handles["prefill_install"] = submit("prefill_install")
    steps(2)
    handles["chunks_finish"] = submit("chunks_finish")
    steps(3)
    handles["prefix_donor"] = submit("prefix_donor")
    steps(2)
    victim = submit("evict_readmit")
    steps(6)
    assert victim.status.value == "running" and 0 < len(victim.output_ids) < RING_NEW
    victim_slot = victim.slot
    engine.evict_request(victim.request_id)
    steps(2)  # the freed slot decodes on: its ring keeps turning
    mid_ring = starts[-1][victim_slot]
    handles["evict_readmit"] = submit("evict_readmit")
    handles["prefix_hit"] = submit("prefix_hit")
    steps(1)
    engine.run_until_drained(max_steps=400)
    run = {
        "handles": handles, "starts": starts, "mid_ring": mid_ring, "victim_slot": victim_slot,
        "prefix_hits": engine._prefix_cache.hits, "decode_compilations": engine.decode_compilations,
        "pages_in_use": engine._pool.pages_in_use, "cached_pages": engine._prefix_cache.cached_pages,
    }
    _RING_RUNS["run"] = run
    return run


@pytest.mark.parametrize("phase", sorted(RING_PROMPTS))
def test_paged_sa_ring_tokens_match_generate(x64, phase):
    """Acceptance of the ring (f64, greedy): whatever a slot's ring offset and
    however the request got there — prefill + install, chunks + finish, behind
    a prefix-cache hit, into a slot evicted mid-turn — its tokens are
    ``generate()``'s over ``2 * max_latents + 3`` decode steps."""
    run = _ring_run()
    handle = run["handles"][phase]
    assert handle.ok and len(handle.output_ids) == RING_NEW
    assert handle.result().tolist() == _ring_reference(phase), f"{phase} diverged"
    # the scenario did what the cases are named for
    assert any(len(set(row)) > 1 for row in run["starts"])  # slots at different offsets
    assert run["mid_ring"] != 0 and run["handles"]["evict_readmit"].slot is None
    assert run["prefix_hits"] >= 1 and run["decode_compilations"] == 1
    assert run["pages_in_use"] == run["cached_pages"]  # only the cache's own pages stay


def _cache_sized_results(jaxpr, shapes, found):
    """Every equation of ``jaxpr`` (sub-programs included) with a result of one
    of ``shapes``: (primitive, shape) pairs; and for every ``scan`` the shapes
    of its scanned inputs and stacked outputs (per iteration for the inputs)."""
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            if getattr(var.aval, "shape", None) in shapes:
                found["results"].add((eqn.primitive.name, var.aval.shape))
        if eqn.primitive.name == "scan":
            carried = eqn.params["num_consts"] + eqn.params["num_carry"]
            found["scanned"].update(v.aval.shape for v in eqn.invars[carried:])
            found["scanned"].update(v.aval.shape for v in eqn.outvars[eqn.params["num_carry"]:])
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (tuple, list)) else (param,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _cache_sized_results(sub, shapes, found)
    return found


# programs a cache-sized value may pass through, and the only two writes
_CONTAINERS = {"scan", "pjit", "jit", "closed_call", "core_call", "cond", "while", "remat", "checkpoint", "custom_jvp_call"}
_WRITES = {"scatter", "dynamic_update_slice"}


@pytest.mark.parametrize("program", ["decode_step-kernel", "decode_step-xla", "ragged_tick-kernel"])
def test_paged_decode_moves_no_cache_sized_buffer(program, monkeypatch):
    """The guard against the roll coming back (a CPU time guards nothing): in
    the paged decode step no equation other than the row write (a scatter; an
    install's row update in the tick) has a result of a layer's cache shape
    or of the stacked shape — no roll, select, concatenate or slice of it —
    and the layer loop carries the stacked buffers: it has no scanned input
    or output of their shape. With the fused kernel (what the chip compiles)
    the kernel call takes the STACKED buffers; the XLA formulation (the CPU,
    unsupported shapes) reads the layer through one ``dynamic_slice``."""
    import perceiver_io_tpu.ops.decode_kernel as dk

    name, form = program.split("-")
    model, params = _make_model()
    layers, slots, cap, ch = 2, 3, LATENTS, 16
    stacked, layer = (layers, slots, cap, ch), (slots, cap, ch)
    shapes = {stacked, layer, (1, *layer)}
    monkeypatch.setattr(dk, "decode_kernel_supported", lambda *a, **kw: form == "kernel")
    engine = ServingEngine(model, params, num_slots=slots, kv_page_size=4)
    assert engine._cache.sa.k.shape == stacked
    if name == "decode_step":
        fn = lambda p, tok, cache: model.apply(p, tok, cache, method=CausalSequenceModel.decode_step_paged)
        jaxpr = jax.make_jaxpr(fn)(params, jnp.zeros((slots, 1), jnp.int32), engine._cache)
    else:
        args = engine._ragged_args(True, engine._forced_none, engine._use_forced_none)
        jaxpr = jax.make_jaxpr(engine._jit_ragged_tick)(*args)
    found = _cache_sized_results(jaxpr.jaxpr, shapes, {"results": set(), "scanned": set()})

    by_primitive = {}
    for primitive, shape in found["results"]:
        by_primitive.setdefault(primitive, set()).add(shape)
    assert "scatter" in by_primitive and by_primitive["scatter"] == {stacked}  # the append, in the stacked buffer
    moved = set(by_primitive) - _CONTAINERS - _WRITES
    if form == "kernel":
        assert not moved, f"cache-sized results besides the row write: { {p: by_primitive[p] for p in moved} }"
        assert "pallas_call" in str(jaxpr)
    else:
        # the XLA formulation's one read of the layer, and its view as heads
        assert moved <= {"dynamic_slice", "squeeze", "reshape", "transpose"}, moved
        assert by_primitive["dynamic_slice"] == {(1, *layer)}
    assert not (found["scanned"] & shapes), "the layer loop scans over the cache instead of carrying it"
