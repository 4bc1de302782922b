"""Serving-engine tests: batched-vs-single parity, scheduler churn with a
compile-once assertion, metrics schema, and the serve_bench smoke path.

The parity contract (docs/serving.md): greedy engine decode of N mixed-length
prompts is token-identical to N independent ``generate()`` calls on the
engine's canonical form (prompt left-padded to the full window,
``num_latents = max_latents``) — pinned in float64 where cached-vs-uncached
equality is exact, mirroring tests/test_chunked_decode.py's methodology.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.generation.generate import GenerationConfig, generate
from perceiver_io_tpu.models.core.config import CausalSequenceModelConfig
from perceiver_io_tpu.models.core.perceiver_ar import CausalSequenceModel
from perceiver_io_tpu.serving import ServingEngine, SlotScheduler
from perceiver_io_tpu.serving.metrics import SCHEMA, EngineMetrics

VOCAB = 262
WINDOW = 12
LATENTS = 6


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


def _left_padded(prompt, width, pad_token_id):
    ids = np.full((1, width), pad_token_id, np.int64)
    pad = np.ones((1, width), bool)
    ids[0, width - len(prompt):] = prompt
    pad[0, width - len(prompt):] = False
    return jnp.asarray(ids), jnp.asarray(pad)


def _reference_tokens(model, params, prompt, config: GenerationConfig, rng=None):
    """generate() on the engine's canonical form, truncated at EOS inclusive
    (generate pads past EOS; the engine evicts instead)."""
    if len(prompt) < LATENTS:
        toks = _short_prompt_tokens(model, params, prompt, config)
    else:
        ids, pad = _left_padded(prompt, WINDOW, config.pad_token_id)
        out = generate(model, params, ids, num_latents=LATENTS, pad_mask=pad, rng=rng, config=config)
        toks = np.asarray(out)[0, WINDOW:].tolist()
    if config.eos_token_id is not None and config.eos_token_id in toks:
        toks = toks[: toks.index(config.eos_token_id) + 1]
    return toks


def _short_prompt_tokens(model, params, prompt, config: GenerationConfig):
    """The canonical form of a prompt SHORTER than ``max_latents``, greedy, on
    generate()'s own cache and steps: prefilled left-padded to ``max_latents``
    (no prefix), then widened into the tail of a full window for the decode.
    A padded latent sees no key and so attends uniformly over every slot of
    what it was prefilled in, and self-attention masks no padded latent: the
    width of the padding shows in the real rows. Padded to the WINDOW (six
    more all-padding prefix slots) [7, 3, 9] gives 132 where this form, the
    engine on either page size and the independent float32 reference
    (benchmark/families/perceiver_ar/reference.py: "left-padded to it") give 9
    (PERF.md 6, PR 47). From ``max_latents`` tokens on no latent is padded and
    the two forms are one."""
    assert not config.do_sample
    off = WINDOW - LATENTS
    ids, pad = _left_padded(prompt, LATENTS, config.pad_token_id)
    cache = model.init_cache(batch_size=1, dtype=jnp.float64, max_seq_len=LATENTS)
    logits, cache = model.apply(params, ids, 0, cache, pad_mask=pad, method=type(model).prefill)
    head = lambda x: jnp.zeros((1, off, x.shape[-1]), x.dtype)
    cache = cache.replace(
        ca=cache.ca.replace(k=jnp.concatenate([head(cache.ca.k), cache.ca.k], axis=1),
                            v=jnp.concatenate([head(cache.ca.v), cache.ca.v], axis=1),
                            length=jnp.asarray(WINDOW, jnp.int32)),
        pad_slots=jnp.concatenate([jnp.ones((1, off), bool), cache.pad_slots], axis=1),
        shift=cache.shift + off,
    )
    toks = []
    for _ in range(config.max_new_tokens):
        toks.append(int(jnp.argmax(logits[0, -1])))
        logits, cache = model.apply(params, jnp.asarray([[toks[-1]]]), cache, method=type(model).decode_step)
    return toks


# ------------------------------------------------------------------ parity
def test_greedy_engine_matches_generate_mixed_lengths(x64):
    """Acceptance: greedy engine output token-identical to per-request
    generate(), across mixed prompt lengths and max_new_tokens, in float64."""
    model, params = _make_model(param_dtype=jnp.float64)
    engine = ServingEngine(model, params, num_slots=3)
    prompts = [[7, 3, 9], [40, 41, 42, 43, 44, 45, 46], list(range(100, 112)), [250]]
    max_new = [5, 3, 6, 4]
    handles = [engine.submit(p, max_new_tokens=m) for p, m in zip(prompts, max_new)]
    engine.run_until_drained(max_steps=200)
    for handle, prompt, m in zip(handles, prompts, max_new):
        expected = _reference_tokens(model, params, prompt, GenerationConfig(max_new_tokens=m))
        assert handle.result().tolist() == expected, f"prompt {prompt} diverged"
        assert handle.finish_reason == "length"


def test_bucketed_prefill_parity_at_bucket_boundaries(x64):
    """Acceptance: greedy engine output stays token-identical to generate()'s
    canonical full-window form for prompt lengths straddling EVERY bucket
    boundary of the ladder (1, bucket, bucket + 1, window), in float64 — the
    bucketed-prefill + install_slot tail-scatter must be positionally invisible."""
    model, params = _make_model(param_dtype=jnp.float64)
    engine = ServingEngine(model, params, num_slots=2)
    assert engine.prefill_buckets == (LATENTS, WINDOW)  # the default halving ladder
    lengths = sorted({1, *(n for b in engine.prefill_buckets for n in (b, min(b + 1, WINDOW))), WINDOW})
    prompts = [list(range(3, 3 + n)) for n in lengths]
    handles = [engine.submit(p, max_new_tokens=4) for p in prompts]
    engine.run_until_drained(max_steps=300)
    for handle, prompt in zip(handles, prompts):
        expected = _reference_tokens(model, params, prompt, GenerationConfig(max_new_tokens=4))
        assert handle.result().tolist() == expected, f"len {len(prompt)} diverged"
    # every admission compiled at most one program per bucket
    assert engine.prefill_compilations <= len(engine.prefill_buckets)


def test_single_window_ladder_matches_bucketed(x64):
    """``prefill_buckets=(window,)`` is the single-window ladder and (greedy,
    float64) produces the same tokens as the bucketed engine."""
    model, params = _make_model(param_dtype=jnp.float64)

    def run(prefill_buckets):
        engine = ServingEngine(model, params, num_slots=2, prefill_buckets=prefill_buckets)
        handles = [engine.submit(p, max_new_tokens=4) for p in ([5, 6, 7], list(range(40, 49)))]
        engine.run_until_drained(max_steps=100)
        return [h.result().tolist() for h in handles], engine.prefill_buckets

    bucketed, ladder = run(None)
    pinned, single = run((WINDOW,))
    assert bucketed == pinned
    assert len(ladder) > 1 and single == (WINDOW,)


def test_eos_early_stop_matches_generate(x64):
    """EOS parity: the engine emits exactly generate()'s tokens up to and
    including EOS, then frees the slot (finish_reason='eos')."""
    model, params = _make_model(param_dtype=jnp.float64)
    prompt = [7, 3, 9, 11]
    greedy = _reference_tokens(model, params, prompt, GenerationConfig(max_new_tokens=8))
    eos = greedy[1]  # force the 2nd generated token to be EOS
    config = GenerationConfig(max_new_tokens=8, eos_token_id=eos, pad_token_id=0)
    expected = _reference_tokens(model, params, prompt, config)
    assert expected[-1] == eos and len(expected) < 8  # the stop actually engages

    engine = ServingEngine(model, params, num_slots=2)
    handle = engine.submit(prompt, config=config)
    filler = engine.submit([5, 6], max_new_tokens=8)  # slot-mate keeps decoding after the evict
    engine.run_until_drained(max_steps=200)
    assert handle.result().tolist() == expected
    assert handle.finish_reason == "eos"
    assert filler.finish_reason == "length" and len(filler.output_ids) == 8


def test_sampled_requests_reproducible_and_mixed_with_greedy(setup):
    """Per-slot sampling configs coexist in one compiled step: a sampled
    request is reproducible under its seed and keys don't leak across slots."""
    model, params = setup

    def run():
        engine = ServingEngine(model, params, num_slots=2)
        sampled = engine.submit([1, 2, 3], rng=jax.random.PRNGKey(7),
                                config=GenerationConfig(max_new_tokens=6, do_sample=True,
                                                        temperature=0.8, top_k=50))
        greedy = engine.submit([9, 8, 7, 6], max_new_tokens=6)
        engine.run_until_drained(max_steps=100)
        return sampled.result().tolist(), greedy.result().tolist()

    s1, g1 = run()
    s2, g2 = run()
    assert s1 == s2 and g1 == g2  # same seeds -> same tokens
    # greedy slot-mate unaffected by the sampler's presence
    solo = ServingEngine(model, params, num_slots=1)
    h = solo.submit([9, 8, 7, 6], max_new_tokens=6)
    solo.run_until_drained(max_steps=100)
    assert h.result().tolist() == g1


# ------------------------------------------------------------------- churn
def test_scheduler_churn_compiles_decode_once(setup):
    """Acceptance: > B staggered requests through B slots — every request
    completes, slots are reused, and the decode step compiles exactly ONCE
    across all admissions/evictions (the static-shape contract)."""
    model, params = setup
    engine = ServingEngine(model, params, num_slots=2)
    lengths = [2, 5, 9, 3, 7, 12, 4]
    max_new = [3, 6, 2, 5, 4, 3, 7]
    handles = []
    # staggered submission: a new request lands every other step
    for i, (n, m) in enumerate(zip(lengths, max_new)):
        handles.append(engine.submit(list(range(1, n + 1)), max_new_tokens=m,
                                     rng=jax.random.PRNGKey(i)))
        engine.step()
    engine.run_until_drained(max_steps=300)

    assert all(h.done for h in handles)
    assert [len(h.output_ids) for h in handles] == max_new  # no EOS: exact lengths
    assert engine.scheduler.total_admissions == len(lengths)  # > 2 slots' worth
    assert engine.scheduler.active_slots == 0 and engine.scheduler.queue_depth == 0
    # THE tentpole invariant: request churn never recompiled the decode step
    assert engine.decode_compilations == 1
    # and the prefill/install compile count stays bounded by the bucket ladder
    # (the lengths above straddle every bucket, so every rung gets exercised)
    assert {engine._bucket_for(n) for n in lengths} == set(engine.prefill_buckets)
    assert engine.prefill_compilations <= len(engine.prefill_buckets)
    assert engine._jit_install._cache_size() <= len(engine.prefill_buckets)


def test_scheduler_fifo_and_slot_reuse():
    sched = SlotScheduler(2)
    sched.enqueue("a"); sched.enqueue("b"); sched.enqueue("c")
    admitted = list(sched.pop_admissible())
    assert admitted == [(0, "a"), (1, "b")]  # FIFO into lowest free slots
    assert sched.queue_depth == 1 and sched.active_slots == 2
    assert list(sched.pop_admissible()) == []  # no free slot
    assert sched.release(0) == "a"
    assert list(sched.pop_admissible()) == [(0, "c")]  # freed slot reused
    assert sched.total_admissions == 3
    assert sched.release(1) == "b"
    with pytest.raises(ValueError, match="not occupied"):
        sched.release(1)  # double free
    assert sched.has_work and sched.active_slots == 1  # "c" still running


def test_submit_validation(setup):
    model, params = setup
    engine = ServingEngine(model, params, num_slots=1)
    with pytest.raises(ValueError, match="non-empty"):
        engine.submit([])
    # a WELL-FORMED but unservable request is an admission outcome, not a
    # crash: over-long prompts are rejected at submit (docs/reliability.md)
    too_long = engine.submit(list(range(WINDOW + 1)), max_new_tokens=2)
    assert too_long.done and not too_long.ok
    assert too_long.finish_reason == "prompt_too_long"
    with pytest.raises(ValueError, match="beam"):
        engine.submit([1, 2], config=GenerationConfig(max_new_tokens=2, num_beams=3))
    with pytest.raises(ValueError, match="contrastive"):
        engine.submit([1, 2], config=GenerationConfig(max_new_tokens=2, top_k=4, penalty_alpha=0.5))
    with pytest.raises(ValueError, match="speculation"):
        engine.submit([1, 2], config=GenerationConfig(max_new_tokens=2, decode_chunk=4))
    with pytest.raises(ValueError, match="config or keyword"):
        engine.submit([1, 2], config=GenerationConfig(), max_new_tokens=2)
    # sampling still requires a positive temperature
    with pytest.raises(ValueError, match="temperature"):
        engine.submit([1, 2], config=GenerationConfig(max_new_tokens=2, do_sample=True, temperature=0.0))
    with pytest.raises(ValueError, match="prefill_buckets"):
        ServingEngine(model, params, num_slots=1, prefill_buckets=[2])  # < max_latents


def test_greedy_temperature_zero_served_and_neutral(setup):
    """Satellite: temperature <= 0 is irrelevant under greedy decoding — the
    request is admitted (not rejected) and decodes identically to the default
    temperature (the neutral 1.0 encoding is installed)."""
    model, params = setup
    engine = ServingEngine(model, params, num_slots=2)
    h_zero = engine.submit([5, 6, 7], config=GenerationConfig(max_new_tokens=5, temperature=0.0))
    h_neg = engine.submit([5, 6, 7], config=GenerationConfig(max_new_tokens=5, temperature=-1.5))
    h_ref = engine.submit([5, 6, 7], max_new_tokens=5)
    engine.run_until_drained(max_steps=100)
    assert h_zero.result().tolist() == h_neg.result().tolist() == h_ref.result().tolist()
    # generate() agrees: the same config decodes on BOTH paths (the pipeline
    # routes by batch size, so engine and direct behavior must not diverge)
    out_zero = _reference_tokens(model, params, [5, 6, 7],
                                 GenerationConfig(max_new_tokens=5, temperature=0.0))
    out_one = _reference_tokens(model, params, [5, 6, 7], GenerationConfig(max_new_tokens=5))
    assert out_zero == out_one
    # greedy also neutralizes top_k/top_p at install (argmax survives the
    # filters, and a greedy slot must not keep the batch-wide vocab-sort
    # branches of process_logits_batched live)
    h = engine.submit([5, 6, 7], config=GenerationConfig(max_new_tokens=2, top_k=50, top_p=0.9))
    engine.step()
    slot = h.slot
    assert int(np.asarray(engine._state.top_k)[slot]) == 0
    assert float(np.asarray(engine._state.top_p)[slot]) == 1.0
    engine.run_until_drained(max_steps=50)
    assert h.result().tolist()[:2] == h_ref.result().tolist()[:2]


def test_release_zeroes_freed_slot_state(setup):
    """Satellite: a freed slot's rng and its carried hidden row are zeroed (with
    the sampling fields already neutral) so pool dumps are reproducible."""
    model, params = setup
    engine = ServingEngine(model, params, num_slots=2)
    h = engine.submit([3, 1, 4], config=GenerationConfig(max_new_tokens=3, do_sample=True,
                                                         temperature=0.7, top_k=9),
                      rng=jax.random.PRNGKey(11))
    engine.run_until_drained(max_steps=50)
    assert h.done
    state = engine._state
    assert not bool(state.active.any())
    assert np.asarray(state.rng).sum() == 0
    assert state.next_hidden.shape == (2, model.config.num_channels)
    assert np.abs(np.asarray(state.next_hidden)).sum() == 0
    assert np.asarray(state.do_sample).sum() == 0
    np.testing.assert_array_equal(np.asarray(state.temperature), 1.0)
    np.testing.assert_array_equal(np.asarray(state.top_k), 0)
    np.testing.assert_array_equal(np.asarray(state.top_p), 1.0)


# ----------------------------------------------------------------- metrics
def test_metrics_snapshot_schema_and_jsonl(setup, tmp_path):
    model, params = setup
    log = tmp_path / "engine.jsonl"
    engine = ServingEngine(model, params, num_slots=2, metrics_jsonl=str(log))
    engine.submit([1, 2, 3], max_new_tokens=2)
    engine.submit([4, 5], max_new_tokens=3)
    engine.submit([6], max_new_tokens=2)  # queued behind the first two
    engine.run_until_drained(max_steps=100)
    snap = engine.metrics.write_snapshot()

    assert snap["schema"] == SCHEMA
    assert snap["requests_submitted"] == snap["requests_finished"] == 3
    assert snap["tokens_generated"] == 2 + 3 + 2
    assert snap["prefills"] == 3 and snap["queue_depth"] == 0
    assert 0 < snap["mean_slot_occupancy"] <= 1
    assert snap["decode_tokens_per_s"] > 0 and snap["wall_tokens_per_s"] > 0
    assert snap["queue_wait_s"]["max"] >= snap["queue_wait_s"]["mean"] > 0

    events = [json.loads(line) for line in log.read_text().splitlines()]
    kinds = {e["event"] for e in events}
    assert {"submit", "admit", "decode_step", "finish", "snapshot"} <= kinds
    # the queued request waited at least one decode step before admission
    admits = [e for e in events if e["event"] == "admit"]
    assert len(admits) == 3 and admits[-1]["wait_s"] >= 0


def test_metrics_standalone_counters():
    m = EngineMetrics(num_slots=4)
    m.record_submit(0, prompt_len=5)
    m.record_admit(0, slot=1, wait_s=0.5, prefill_s=0.1, bucket=8)
    m.record_decode_step(active_slots=2, seconds=0.2, tokens=2)
    m.record_finish(0, slot=1, new_tokens=1, reason="length")
    snap = m.snapshot()
    assert snap["schema"] == "serving-metrics/v13"
    assert snap["rejected"] == snap["timed_out"] == snap["failed"] == 0
    assert snap["page_pool"] is None  # standalone metrics (a router's): no pool exists
    assert snap["mean_slot_occupancy"] == 0.5
    assert snap["tokens_generated"] == 2 and snap["decode_steps"] == 1
    assert snap["queue_wait_s"] == {"mean": 0.5, "max": 0.5, "p50": 0.5, "p95": 0.5}
    assert snap["prefill_s"] == {"mean": 0.1, "max": 0.1, "p50": 0.1, "p95": 0.1}
    assert snap["decode_step_s"] == {"mean": 0.2, "max": 0.2, "p50": 0.2, "p95": 0.2}


def test_metrics_percentiles_over_population():
    """p50/p95 follow numpy.percentile's linear-interpolation semantics over
    the per-event populations."""
    import numpy as _np

    m = EngineMetrics(num_slots=2)
    waits = [0.1, 0.4, 0.2, 0.9, 0.3]
    for i, w in enumerate(waits):
        m.record_submit(i, prompt_len=1)
        m.record_admit(i, slot=0, wait_s=w, prefill_s=w / 10)
    snap = m.snapshot()
    assert snap["queue_wait_s"]["p50"] == pytest.approx(float(_np.percentile(waits, 50)), abs=1e-6)
    assert snap["queue_wait_s"]["p95"] == pytest.approx(float(_np.percentile(waits, 95)), abs=1e-6)
    assert snap["prefill_s"]["p95"] <= snap["prefill_s"]["max"]


def test_metrics_jsonl_reader_tolerates_v1(tmp_path):
    """Satellite: the version-tolerant reader returns v2 snapshots verbatim and
    normalizes v1 snapshots (missing percentile dicts filled with None);
    unknown schemas fail loudly."""
    from perceiver_io_tpu.serving import load_metrics_jsonl

    v1 = tmp_path / "v1.jsonl"
    v1.write_text(
        json.dumps({"event": "submit", "ts": 1.0, "request_id": 0, "prompt_len": 3}) + "\n"
        + json.dumps({"event": "snapshot", "ts": 2.0, "schema": "serving-metrics/v1",
                      "num_slots": 2, "tokens_generated": 5,
                      "queue_wait_s": {"mean": 0.1, "max": 0.2}}) + "\n"
    )
    got = load_metrics_jsonl(str(v1))
    assert len(got["events"]) == 2 and len(got["snapshots"]) == 1
    snap = got["snapshots"][0]
    assert snap["tokens_generated"] == 5
    assert snap["queue_wait_s"] == {"mean": 0.1, "max": 0.2, "p50": None, "p95": None}
    assert snap["prefill_s"]["p95"] is None and snap["decode_step_s"]["p50"] is None

    v2 = tmp_path / "v2.jsonl"
    m = EngineMetrics(num_slots=2, jsonl_path=str(v2))
    m.record_submit(0, prompt_len=3)
    m.record_admit(0, slot=0, wait_s=0.5, prefill_s=0.1, bucket=4)
    m.write_snapshot()
    m.close()
    got2 = load_metrics_jsonl(str(v2))
    assert got2["snapshots"][0]["schema"] == SCHEMA
    assert got2["snapshots"][0]["queue_wait_s"]["p95"] == 0.5
    admits = [e for e in got2["events"] if e["event"] == "admit"]
    assert admits[0]["bucket"] == 4

    bad = tmp_path / "bad.jsonl"
    bad.write_text(json.dumps({"event": "snapshot", "schema": "something/v9"}) + "\n")
    with pytest.raises(ValueError, match="unknown metrics schema"):
        load_metrics_jsonl(str(bad))


# -------------------------------------------------------------- serve_bench
def test_serve_bench_smoke(tmp_path, monkeypatch):
    """Acceptance: serve_bench emits the metrics JSON on the synthetic
    workload under JAX_PLATFORMS=cpu (imported, not subprocessed — the jax
    import tax is already paid)."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "serve_bench_under_test",
        os.path.join(os.path.dirname(__file__), "..", "scripts", "serve_bench.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    out = tmp_path / "SERVE_BENCH.json"
    log = tmp_path / "engine.jsonl"
    result = mod.main([
        "--preset", "tiny", "--slots", "2", "--requests", "4",
        "--out", str(out), "--metrics-jsonl", str(log), "--no-warmup",
    ])
    assert out.exists()
    on_disk = json.loads(out.read_text())
    assert on_disk["engine"]["metrics"]["schema"] == SCHEMA
    assert on_disk["engine"]["new_tokens"] == sum(on_disk["workload"]["max_new_tokens"])
    assert on_disk["engine"]["tokens_per_s"] > 0
    assert on_disk["baseline_single_request"]["tokens_per_s"] > 0
    assert "engine_vs_baseline" in on_disk
    assert result["engine"]["decode_compilations"] == 1
    assert result["engine"]["prefill_compilations"] <= len(result["engine"]["prefill_buckets"])
    assert result["engine"]["decode_tokens_per_s"] > 0  # prefill/decode split reported
    assert result["engine"]["admission_prompt_tokens_per_s"] > 0
    assert log.exists() and log.read_text().strip()


@pytest.mark.slow  # ~30 s of compiles: 4 engines (2 arms x 2 workloads)
def test_serve_bench_profile_smoke(tmp_path):
    """--profile emits BENCH_serving.json with per-workload bucketed vs
    full-window admission/decode throughput splits (the per-PR perf artifact)."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "serve_bench_profile_under_test",
        os.path.join(os.path.dirname(__file__), "..", "scripts", "serve_bench.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    out = tmp_path / "BENCH_serving.json"
    result = mod.main(["--profile", "--preset", "tiny", "--requests", "3",
                       "--slots", "2", "--profile-out", str(out)])
    on_disk = json.loads(out.read_text())
    assert set(on_disk["workloads"]) == {"short", "fullwindow"}
    for w in on_disk["workloads"].values():
        for arm in ("bucketed", "fullwindow_baseline"):
            assert w[arm]["admission"]["prompt_tokens_per_s"] > 0
            assert w[arm]["decode"]["decode_tokens_per_s"] > 0
            assert w[arm]["prefill_compilations"] <= len(w[arm]["prefill_buckets"])
        assert w["admission_speedup"] > 0
    # the baseline arm pins the single full-window bucket (tiny preset: 64)
    assert result["workloads"]["fullwindow"]["fullwindow_baseline"]["prefill_buckets"] == [64]
    # acceptance (ISSUE 6): the --profile artifact carries the per-phase time
    # breakdown and runtime compile counts, plus a run manifest sibling
    telemetry = on_disk["telemetry"]
    assert "serving.tick" in telemetry["phases"]
    assert telemetry["compile"]["per_function"]["serving.ragged_tick"]["compilations"] == 1
    assert telemetry["compile"]["unexpected"] == []
    manifest = json.loads((tmp_path / "BENCH_serving.manifest.json").read_text())
    assert manifest["schema"] == "run-manifest/v1" and manifest["versions"]["jax"]


# ---------------------------------------------------------------- pipeline
def test_pipeline_routes_batches_through_engine():
    from perceiver_io_tpu.models.text.clm import CausalLanguageModel, CausalLanguageModelConfig
    from perceiver_io_tpu.pipelines import TextGenerationPipeline

    cfg = CausalLanguageModelConfig(
        vocab_size=262, max_seq_len=32, max_latents=8, num_channels=16, num_heads=2,
        num_self_attention_layers=1, cross_attention_dropout=0.0,
    )
    model = CausalLanguageModel(config=cfg)
    params = jax.jit(model.init, static_argnames="prefix_len")(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32), prefix_len=8
    )
    pipe = TextGenerationPipeline(model, params, tokenizer="bytes")
    outs = pipe(["Hi", "A longer prompt"], config=GenerationConfig(max_new_tokens=4))
    assert len(outs) == 2 and outs[0].startswith("Hi") and outs[1].startswith("A longer prompt")
    engine = pipe._engine_inst
    assert engine is not None, "multi-prompt greedy batch should have used the engine"
    assert engine.decode_compilations == 1
    assert not engine.finished and not engine._requests  # drained: no per-request residue

    # a second, LARGER batch reuses the same engine (extra requests queue) —
    # still exactly one compiled decode program
    outs2 = pipe(["abc", "de", "fghij"], config=GenerationConfig(max_new_tokens=3))
    assert len(outs2) == 2 + 1 and all(o.startswith(p) for o, p in zip(outs2, ["abc", "de", "fghij"]))
    assert pipe._engine_inst is engine and engine.decode_compilations == 1

    # typed PRNG keys are accepted on the (default) engine path
    outs_k = pipe(["Hi", "yo"], rng=jax.random.key(3),
                  config=GenerationConfig(max_new_tokens=2, do_sample=True))
    assert len(outs_k) == 2

    # beam configs are not servable: auto-routing falls back to generate()
    outs3 = pipe(["Hi", "yo"], config=GenerationConfig(max_new_tokens=2, num_beams=2))
    assert len(outs3) == 2
    with pytest.raises(ValueError, match="use_engine=True"):
        pipe(["Hi", "yo"], use_engine=True, config=GenerationConfig(max_new_tokens=2, num_beams=2))
    # an explicit num_latents pins the direct generate() path (the engine
    # always decodes the canonical max_latents form)
    outs4 = pipe(["Hi", "yo"], num_latents=4, config=GenerationConfig(max_new_tokens=2))
    assert len(outs4) == 2
    with pytest.raises(ValueError, match="num_latents"):
        pipe(["Hi", "yo"], num_latents=4, use_engine=True, config=GenerationConfig(max_new_tokens=2))
    # a batch containing an empty prompt stays on the direct path (the
    # engine cannot prefill a zero-token request; generate() decodes the
    # all-pad row)
    outs5 = pipe(["", "yo"], config=GenerationConfig(max_new_tokens=2))
    assert len(outs5) == 2 and outs5[1].startswith("yo")
    with pytest.raises(ValueError, match="empty prompt"):
        pipe(["", "yo"], use_engine=True, config=GenerationConfig(max_new_tokens=2))
