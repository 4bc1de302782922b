"""Chunked prefill + cross-request radix prefix cache tests
(docs/serving.md "Chunked prefill" / "Prefix cache").

The parity contract: engine output is f64 token-identical with the cache
warm, cold, off (``prefix_cache=False``), or mid-evicted, and with
admission chunked or one-shot — across prompt lengths straddling every
prefill-ladder rung, greedy and sampled. The sharing contract:
``PagePool.retain()`` finally has its second caller — a fork's pages outlive
the origin session, a preemption victim's release leaves the sharer intact,
and a double-release of a shared run cannot strand the sharer. The
accounting contract: shared pages are counted ONCE (an 80%-shared workload
admits strictly more concurrent sessions than dense accounting would allow)
and cached-but-unreferenced pages yield to live reservations before
admission reports backpressure. The churn contract: chunking + caching add
at most the ladder's worth of chunk programs and ONE finish program, decode
stays a single program, and the pool's free list is whole after drain.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.generation.generate import GenerationConfig, generate
from perceiver_io_tpu.models.core.config import CausalSequenceModelConfig
from perceiver_io_tpu.models.core.perceiver_ar import CausalSequenceModel
from perceiver_io_tpu.serving import (
    PagePool,
    PrefixCache,
    ServingEngine,
    page_keys_for_prompt,
    pages_for_request,
)

VOCAB = 262
WINDOW = 24
LATENTS = 6
PS = 3  # page size: divides the window, straddles no rung exactly

# ladder (6, 12, 24); lengths straddle every rung + the window
PARITY_LENGTHS = (1, 6, 7, 12, 13, 24)


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


@pytest.fixture(scope="module")
def setup64(x64):
    return _make_model(param_dtype=jnp.float64)


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


# ---------------------------------------------------------------- page keys
def test_page_keys_latent_boundary_gate():
    """Only FULL pages strictly below the latent-region boundary
    (position n - max_latents) are cacheable: latent-region KV rows are
    q_norm-normalized by the one-shot prefill, so their content depends on
    the prompt length, not just the prefix."""
    prompt = list(range(100, 120))  # n=20, boundary 14 -> 4 full pages of 3
    keys = page_keys_for_prompt(prompt, PS, LATENTS)
    assert keys == tuple(tuple(prompt[k * PS:(k + 1) * PS]) for k in range(4))
    # boundary at/below zero -> nothing cacheable
    assert page_keys_for_prompt(list(range(6)), PS, LATENTS) == ()
    assert page_keys_for_prompt([], PS, LATENTS) == ()
    # a partial trailing page below the boundary is NOT a key
    assert len(page_keys_for_prompt(list(range(22)), PS, LATENTS)) == 5  # 16//3


# --------------------------------------------------------------- trie unit
def test_prefix_cache_probe_insert_lru_and_refcount_aware_evict():
    pool = PagePool(10)
    cache = PrefixCache(pool, PS)
    keys = ((1, 2, 3), (4, 5, 6), (7, 8, 9))
    pages = pool.allocate(3)  # [1, 2, 3]
    assert cache.probe(keys) == [] and cache.misses == 1
    assert cache.insert(keys, pages) == 3  # each page gains the cache's ref
    assert cache.cached_pages == 3 and pool.refcount(pages[0]) == 2
    # the origin releases its run: pages survive on the cache's reference
    pool.release(pages)
    assert pool.pages_in_use == 3 and cache.reclaimable_pages() == 3
    # a shorter probe matches the prefix run, not the whole chain
    assert cache.probe(keys[:2]) == pages[:2] and cache.hits == 1
    # a diverging key stops the match at the shared head
    assert cache.probe(((1, 2, 3), (9, 9, 9))) == pages[:1]
    # peek never skews hits/misses or LRU stamps
    h, m = cache.hits, cache.misses
    assert cache.peek_match_pages(keys) == list(pages)
    assert (cache.hits, cache.misses) == (h, m)
    # eviction is leaf-first LRU, cascading to parents that become leaves
    assert cache.evict(2) == 2
    assert cache.cached_pages == 1 and pool.pages_in_use == 1
    assert cache.peek_match(keys) == 1  # the root page survived
    assert cache.evict(5) == 1  # drains to empty, reports what it freed
    assert pool.pages_in_use == 0 and cache.evictions == 2


def test_prefix_cache_evict_skips_pages_live_sessions_share():
    """Refcount-aware LRU: a cached page a live session still shares is NOT
    released — freeing it would reclaim nothing now and forfeit future
    hits."""
    pool = PagePool(10)
    cache = PrefixCache(pool, PS)
    keys = ((1, 1, 1), (2, 2, 2))
    pages = pool.allocate(2)
    cache.insert(keys, pages)
    pool.release([pages[0]])  # origin keeps sharing only the SECOND page...
    # ...wait: leaf [1] (pages[1]) still held by origin (refcount 2); the
    # parent (pages[0]) is cache-only but not a leaf -> nothing reclaimable
    assert cache.reclaimable_page_ids() == [pages[0]]
    assert cache.evict(2) == 0  # leaf is shared, parent is not a leaf
    assert cache.cached_pages == 2
    pool.release([pages[1]])  # the sharer leaves
    assert cache.evict(2) == 2  # now the whole chain reclaims, leaf first
    assert pool.pages_in_use == 0


def test_prefix_cache_invalidate_subtree_and_clear():
    pool = PagePool(12)
    cache = PrefixCache(pool, PS)
    a = pool.allocate(3)
    b = pool.allocate(2)
    cache.insert(((1,), (2,), (3,)), a)
    cache.insert(((9,), (8,)), b)
    pool.release(a), pool.release(b)
    # invalidate drops everything routed through keys[0] — deeper prefixes
    # include the suspect page, siblings under other roots are untouched
    assert cache.invalidate(((1,),)) == 3
    assert cache.peek_match(((1,), (2,))) == 0
    assert cache.peek_match(((9,), (8,))) == 2
    assert cache.invalidate(((1,),)) == 0  # idempotent on a missing root
    assert cache.clear() == 2
    assert cache.cached_pages == 0 and pool.pages_in_use == 0


def test_prefix_cache_insert_shorter_pages_raises():
    pool = PagePool(6)
    cache = PrefixCache(pool, PS)
    pages = pool.allocate(1)
    with pytest.raises(ValueError, match="shorter than keys"):
        cache.insert(((1,), (2,)), pages)
    assert cache.cached_pages == 0  # nothing half-inserted
    pool.release(pages)


# ----------------------------------------------------- retain second caller
def test_retain_fork_outlives_origin_session():
    """The fork primitive end to end at pool level: a consumer retains the
    donor's run, the donor releases (session evicted), the consumer's pages
    survive; the consumer's own release finally frees them."""
    pool = PagePool(10)
    donor = pool.allocate(4)
    shared = donor[:2]
    pool.retain(shared)  # the fork
    pool.release(donor)  # donor session evicted whole
    assert pool.pages_in_use == 2  # the forked prefix outlives its origin
    churn = pool.allocate(3)
    assert not set(shared) & set(churn)
    pool.release(shared)
    assert pool.pages_in_use == 3  # only the churn allocation remains


def test_double_release_of_shared_run_leaves_sharer_intact():
    """Validate-then-mutate under SHARING (extends the ISSUE 9 regression):
    a buggy double-release of a run that includes an already-freed page must
    leave the sharer's references untouched — not half-decrement the shared
    pages before raising."""
    pool = PagePool(10)
    run = pool.allocate(3)
    pool.retain(run)  # sharer's references
    pool.release(run)  # origin's release: pages still held by the sharer
    pool.release([run[0]])  # sharer drops ONE page; run[0] now free
    with pytest.raises(ValueError, match="double free"):
        pool.release(run)  # invalid mid-list: run[1:] must NOT release
    assert pool.refcount(run[1]) == 1 and pool.refcount(run[2]) == 1
    pool.release(run[1:])  # exactly one reference each — state was untouched
    assert pool.pages_in_use == 0


def test_preemption_victim_releases_fork_sharer_pages_intact(setup):
    """A preemption victim holding a prefix fork releases only its OWN
    references: the cache and the sharer keep theirs, the victim resumes
    and re-forks, and the drain leaves the free list whole."""
    model, params = setup
    preamble = [7] * 18  # boundary for n>=20: >=14 -> 4 cacheable pages
    # each shared request: bucket 24 -> 8 pages reserved, 4 shared on a hit;
    # 12 allocatable pages = the shared run + exactly two private remainders
    engine = ServingEngine(model, params, num_slots=3, kv_page_size=PS,
                           num_kv_pages=13, prefix_cache=True)
    donor = engine.submit(preamble + [1, 2], max_new_tokens=4)
    engine.run_until_drained(max_steps=200)
    assert donor.ok and engine._prefix_cache.cached_pages == 4
    cached = engine._prefix_cache.peek_match_pages(
        page_keys_for_prompt(preamble + [1, 2], PS, LATENTS))
    bg = [engine.submit(preamble + [t], max_new_tokens=5, rng=jax.random.PRNGKey(i))
          for i, t in enumerate((3, 4))]
    engine.step()
    assert all(h.status.value == "running" for h in bg)
    # both forks live: every cached page carries cache + 2 session references
    assert all(engine._pool.refcount(p) == 3 for p in cached)
    assert engine._pool.free_pages == 0  # forks saturated the pool
    hi = engine.submit(preamble + [5], max_new_tokens=4, priority=2)
    engine.step()  # page-blocked head preempts the cheapest victim
    victims = [h for h in bg if h.preemptions > 0]
    assert len(victims) == 1 and hi.status.value == "running"
    # the victim released its fork; the sharer and the cache keep theirs
    # (hi re-forked the run, so the count is back at 3)
    assert all(engine._pool.refcount(p) == 3 for p in cached)
    engine.run_until_drained(max_steps=400)
    assert all(h.ok for h in bg + [hi, donor])
    # free list whole after drain: only the cache's references remain
    assert engine._pool.pages_in_use == engine._prefix_cache.cached_pages == 4
    assert engine._prefix_cache.clear() == 4
    assert engine._pool.pages_in_use == 0
    engine.close()


# ------------------------------------------------------------------ parity
def test_prefix_cache_parity_warm_cold_off(setup64):
    """Acceptance: cache-on output is f64 token-identical to cache-off
    (``prefix_cache=False``: no trie built) — cold (first pass), warm (every
    prompt extends a cached prefix), mid-evicted — greedy and sampled,
    across ladder-straddling prompt lengths."""
    model, params = setup64
    preamble = [11] * 18
    prompts = [list(range(3, 3 + n)) for n in PARITY_LENGTHS]
    prompts += [preamble + [1, 2], preamble + [3, 4, 5], preamble + list(range(30, 36))]

    def submit_all(engine):
        handles = [engine.submit(p, max_new_tokens=4) for p in prompts]
        handles.append(engine.submit(preamble + [9], rng=jax.random.PRNGKey(7),
                                     config=GenerationConfig(max_new_tokens=5,
                                                             do_sample=True,
                                                             temperature=0.8,
                                                             top_k=50)))
        engine.run_until_drained(max_steps=500)
        return [h.result().tolist() for h in handles]

    off_engine = ServingEngine(model, params, num_slots=3, kv_page_size=PS,
                               prefix_cache=False)
    assert off_engine._prefix_cache is None
    expected = submit_all(off_engine)
    # greedy rows are additionally anchored to generate()'s canonical form
    for toks, prompt in zip(expected[: len(PARITY_LENGTHS)], prompts):
        assert toks == _reference_tokens(model, params, prompt,
                                         GenerationConfig(max_new_tokens=4))
    off_engine.close()

    engine = ServingEngine(model, params, num_slots=3, kv_page_size=PS,
                           prefix_cache=True)
    cold = submit_all(engine)  # cold: donors insert as they admit
    assert cold == expected
    stats = engine._prefix_cache.stats()
    assert stats["hits"] >= 1 and stats["cached_pages"] >= 4
    warm = submit_all(engine)  # warm: every shared prompt forks
    assert warm == expected
    assert engine._prefix_cache.stats()["hits"] > stats["hits"]
    # mid-evicted: drop part of the cached run, outputs still identical
    engine._prefix_cache.evict(2)
    assert submit_all(engine) == expected
    assert engine._pool.pages_in_use == engine._prefix_cache.cached_pages
    engine._prefix_cache.clear()
    assert engine._pool.pages_in_use == 0
    engine.close()


def test_chunked_prefill_parity_with_unchunked(setup64):
    """Acceptance: chunked admission is f64 token-identical to unchunked
    (``prefill_chunk_tokens=None``) — chunk sizes straddling the ladder,
    greedy and sampled."""
    model, params = setup64
    prompts = [list(range(3, 3 + n)) for n in PARITY_LENGTHS]

    def submit_all(engine):
        handles = [engine.submit(p, max_new_tokens=4) for p in prompts]
        handles.append(engine.submit(list(range(60, 80)),
                                     rng=jax.random.PRNGKey(3),
                                     config=GenerationConfig(max_new_tokens=5,
                                                             do_sample=True,
                                                             temperature=0.8,
                                                             top_k=50)))
        engine.run_until_drained(max_steps=500)
        return [h.result().tolist() for h in handles]

    baseline = ServingEngine(model, params, num_slots=3, kv_page_size=PS,
                             prefill_chunk_tokens=None)
    assert not baseline.chunked
    expected = submit_all(baseline)
    assert baseline.metrics.chunks_dispatched == 0
    baseline.close()

    for chunk in (4, 6, 11):  # < rung, = rung, straddling
        engine = ServingEngine(model, params, num_slots=3, kv_page_size=PS,
                               prefill_chunk_tokens=chunk)
        assert engine.chunked
        assert submit_all(engine) == expected, f"chunk={chunk} diverged"
        assert engine.metrics.chunks_dispatched > 0
        assert engine._pool.pages_in_use == 0
        engine.close()


def test_chunked_prefill_interleaves_running_decode(setup):
    """The bounded-stall contract: while a window-length prompt
    chunk-prefills, running slots keep emitting one token per tick — the
    prompt's admission spreads over ~(window/chunk) ticks instead of
    landing whole inside one."""
    model, params = setup
    engine = ServingEngine(model, params, num_slots=2, kv_page_size=PS,
                           prefill_chunk_tokens=6)
    bg = engine.submit([1, 2, 3], max_new_tokens=20)
    engine.step()
    assert bg.status.value == "running"
    long = engine.submit(list(range(100, 100 + WINDOW)), max_new_tokens=2)
    chunk_ticks = 0
    while long.admitted_at is None:
        before = len(bg.output_ids)
        engine.step()
        chunk_ticks += 1
        assert len(bg.output_ids) == before + 1  # decode never stalled a tick
        assert chunk_ticks < 10
    assert chunk_ticks >= 3  # 24 tokens / 6-token chunks: the phase is real
    engine.run_until_drained(max_steps=200)
    assert bg.ok and long.ok
    snap = engine.metrics.snapshot()
    assert snap["chunked_prefill"]["chunks_dispatched"] == 4
    assert snap["chunked_prefill"]["chunked_admissions"] == 1
    engine.close()


def test_wrap_gated_request_never_probes_or_inserts(setup):
    """A session whose prompt + generation budget exceeds the window wraps
    its ring mid-decode, overwriting its own oldest pages — such a request
    must neither share nor donate (page_keys stays None)."""
    model, params = setup
    engine = ServingEngine(model, params, num_slots=2, kv_page_size=PS,
                           prefix_cache=True)
    wrapping = engine.submit([5] * 20, max_new_tokens=10)  # 30 > window
    fitting = engine.submit([5] * 20, max_new_tokens=4)
    assert wrapping.page_keys is None and len(fitting.page_keys) == 4
    engine.run_until_drained(max_steps=200)
    assert wrapping.ok and fitting.ok
    # only the fitting request donated
    assert engine._prefix_cache.cached_pages == 4
    engine._prefix_cache.clear()
    assert engine._pool.pages_in_use == 0
    engine.close()


# -------------------------------------------------------------- accounting
def test_shared_accounting_admits_strictly_more_sessions(setup):
    """The shared-reservation seam fix: a prefix-cache hit makes part of a
    reservation shared, so `can_admit`/`load` count those pages ONCE — an
    80%-shared workload holds strictly more concurrent sessions at a fixed
    pool than the dense accounting allows."""
    model, params = setup
    preamble = [7] * 18  # 4 cacheable pages below the latent boundary
    dense = pages_for_request(WINDOW, 4, WINDOW, PS)  # 8 pages per session
    num_pages = 2 * dense + 1  # 16 allocatable + trash

    def peak_sessions(cache_on):
        engine = ServingEngine(model, params, num_slots=6, kv_page_size=PS,
                               num_kv_pages=num_pages, prefix_cache=cache_on)
        donor = engine.submit(preamble + [1], max_new_tokens=4)
        engine.run_until_drained(max_steps=200)  # warm the cache
        assert donor.ok
        handles = [engine.submit(preamble + [10 + i], max_new_tokens=4)
                   for i in range(5)]
        peak = 0
        while engine.step():
            peak = max(peak, engine.scheduler.active_slots)
        assert all(h.ok for h in handles)
        snap = engine.metrics.snapshot()
        if cache_on:
            assert snap["prefix_cache"]["hits"] >= 5
            engine._prefix_cache.clear()
        assert engine._pool.pages_in_use == 0
        engine.close()
        return peak

    dense_peak = peak_sessions(False)
    shared_peak = peak_sessions(True)
    # dense: 16 free / 8 = 2 concurrent; shared: 12 free / 4 private = 3
    assert shared_peak > dense_peak, (shared_peak, dense_peak)


def test_cache_eviction_yields_to_live_reservations_before_queue_full(setup):
    """Refcount-aware LRU under pool pressure: a pool full of stale cached
    pages yields to a live reservation — the request admits instead of
    head-blocking or rejecting."""
    model, params = setup
    dense = pages_for_request(WINDOW, 4, WINDOW, PS)  # 8 pages
    engine = ServingEngine(model, params, num_slots=2, kv_page_size=PS,
                           num_kv_pages=dense + 3, prefix_cache=True)
    donor = engine.submit([7] * 18 + [1], max_new_tokens=4)
    engine.run_until_drained(max_steps=200)
    assert donor.ok and engine._prefix_cache.cached_pages == 4
    # 10 allocatable, 4 held by stale cache: a distinct dense request needs
    # 8 > 6 free — admission must evict the stale run, not backpressure
    fresh = engine.submit(list(range(200, 220)), max_new_tokens=4)
    engine.step()
    assert fresh.status.value == "running"
    engine.run_until_drained(max_steps=200)
    assert fresh.ok
    stats = engine._prefix_cache.stats()
    assert stats["evictions"] >= 1 and stats["evicted_pages"] >= 2
    snap = engine.metrics.snapshot()
    assert snap["page_pool"]["alloc_failures"] == 0
    engine._prefix_cache.clear()
    assert engine._pool.pages_in_use == 0
    engine.close()


def test_quarantine_zeroes_cache_shared_pages_before_free(setup):
    """NaN containment x prefix sharing (review regression): a poisoned
    slot's cacheable prefix pages shared with the CACHE ALONE must still be
    zeroed before returning to the free list — invalidation drops the
    cache's references FIRST, so the pages leave through the quarantine's
    zeroing row, not the shared-page trash filter. Filtering before
    invalidating released them refcount-0 with the NaN bytes intact, and a
    later tenant's pages would gather them."""
    model, params = setup
    engine = ServingEngine(model, params, num_slots=2, kv_page_size=PS,
                           prefix_cache=True)
    prompt = list(range(2, 15))  # n=13: two full cacheable pages below boundary
    ref = _reference_tokens(model, params, list(range(100, 108)),
                            GenerationConfig(max_new_tokens=4))
    donor = engine.submit(prompt, max_new_tokens=2)
    engine.run_until_drained(max_steps=100)
    assert donor.ok and engine._prefix_cache.cached_pages == 2
    fork = engine.submit(prompt + [5], max_new_tokens=4)  # extends the run
    engine.step()
    assert fork.status.value == "running"
    shared = [p for p in engine._slot_pages[fork.slot]
              if engine._pool.refcount(p) >= 2]
    assert len(shared) == 2  # fork + cache hold them; no live sibling
    # poison the shared pages' device bytes — the hazard the quarantine's
    # zeroing exists for; the NaN propagates through the next decode step's
    # cross-attention into non-finite logits, firing containment naturally
    ca = engine._cache.ca
    engine._cache = engine._cache.replace(
        ca=ca.replace(kp=ca.kp.at[jnp.asarray(shared)].set(jnp.nan))
    )
    engine.run_until_drained(max_steps=100)
    assert fork.status.value == "failed"
    assert engine._prefix_cache.cached_pages == 0  # tainted run invalidated
    assert engine._pool.pages_in_use == 0
    # nothing non-finite survived into the free pool...
    assert np.isfinite(np.asarray(engine._cache.ca.kp)).all()
    # ...and a tenant reallocating the freed pages decodes clean
    fresh = engine.submit(list(range(100, 108)), max_new_tokens=4)
    engine.run_until_drained(max_steps=100)
    assert fresh.ok and fresh.result().tolist() == ref
    engine.close()


# ------------------------------------------------------------------- churn
def test_churn_compile_counts_with_chunking_and_cache(setup):
    """Compile-geometry acceptance: chunked + cached churn keeps the tick —
    chunks and finishes inside it — at ONE program, prefill/install programs
    each bounded by the ladder length."""
    model, params = setup
    engine = ServingEngine(model, params, num_slots=2, kv_page_size=PS,
                           prefix_cache=True, prefill_chunk_tokens=5)
    preamble = [7] * 18
    lengths = [2, 7, 19, 24, 13, 20]
    handles = []
    for i, n in enumerate(lengths):
        handles.append(engine.submit(list(range(1, n + 1)), max_new_tokens=3,
                                     rng=jax.random.PRNGKey(i)))
        handles.append(engine.submit(preamble + [40 + i], max_new_tokens=3))
        engine.step()
    engine.run_until_drained(max_steps=500)
    assert all(h.ok for h in handles)
    ladder = len(engine.prefill_buckets)
    assert engine.decode_compilations == 1  # THE invariant, unchanged
    assert engine.prefill_compilations <= ladder
    assert engine._jit_install._cache_size() <= ladder
    assert engine._jit_ragged_tick._cache_size() == 1
    engine._prefix_cache.clear()
    assert engine._pool.pages_in_use == 0
    assert all(p is None for p in engine._slot_pages)
    engine.close()


# ----------------------------------------------------------------- metrics
def test_metrics_v8_sections_and_reader_backcompat(setup, tmp_path):
    """v8 snapshots carry prefix_cache/chunked_prefill sections (None where
    the feature is off); the reader normalizes pre-v8 snapshots with None —
    'not recorded' stays distinguishable from 'feature off'."""
    from perceiver_io_tpu.serving import load_metrics_jsonl
    from perceiver_io_tpu.serving.metrics import SCHEMA

    assert SCHEMA == "serving-metrics/v13"
    model, params = setup
    path = tmp_path / "v8.jsonl"
    engine = ServingEngine(model, params, num_slots=2, kv_page_size=PS,
                           prefix_cache=True, prefill_chunk_tokens=6,
                           metrics_jsonl=str(path))
    donor = engine.submit([7] * 18 + [1], max_new_tokens=3)
    engine.run_until_drained(max_steps=200)
    fork = engine.submit([7] * 18 + [2], max_new_tokens=3)
    long = engine.submit(list(range(100, 124)), max_new_tokens=2)
    engine.run_until_drained(max_steps=200)
    assert donor.ok and fork.ok and long.ok
    snap = engine.metrics.write_snapshot()
    assert snap["schema"] == "serving-metrics/v13"
    pc = snap["prefix_cache"]
    assert pc["hits"] >= 1 and pc["cached_pages"] >= 4
    assert "shared_pages_in_use" in pc
    cp = snap["chunked_prefill"]
    assert cp["chunk_tokens"] == 6 and cp["chunks_dispatched"] >= 4
    engine.close()

    got = load_metrics_jsonl(str(path))
    events = {e["event"] for e in got["events"]}
    assert {"prefix_hit", "chunk"} <= events
    assert got["snapshots"][-1]["prefix_cache"]["hits"] >= 1
    # admit events on shared/chunked admissions carry the v8 fields
    admits = [e for e in got["events"] if e["event"] == "admit"]
    assert any(e.get("shared_pages") for e in admits)
    assert any(e.get("chunks") for e in admits)

    # features off: truthful None, same reading as a pre-v8 snapshot
    plain = ServingEngine(model, params, num_slots=2, kv_page_size=PS)
    s = plain.metrics.snapshot()
    assert s["prefix_cache"] is None and s["chunked_prefill"] is None
    plain.close()

    # pre-v8 stream: reader fills None, not 0
    old = tmp_path / "v7.jsonl"
    old.write_text(json.dumps({"event": "snapshot",
                               "schema": "serving-metrics/v7",
                               "requests_submitted": 1}) + "\n")
    loaded = load_metrics_jsonl(str(old))
    assert loaded["snapshots"][0]["prefix_cache"] is None
    assert loaded["snapshots"][0]["chunked_prefill"] is None


# ------------------------------------------------------------- constructor
def test_constructor_validation(setup):
    model, params = setup
    with pytest.raises(ValueError, match="must be >= 1"):  # with and without a page size
        ServingEngine(model, params, num_slots=2, kv_page_size=PS,
                      prefill_chunk_tokens=0)
    with pytest.raises(ValueError, match="must be >= 1"):
        ServingEngine(model, params, num_slots=2, prefill_chunk_tokens=0)
    with pytest.raises(ValueError, match="max_prefill_slots"):
        ServingEngine(model, params, num_slots=2, kv_page_size=PS,
                      max_prefill_slots=0)
