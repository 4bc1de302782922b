"""Unified ragged tick: one fused program per steady-state tick (ISSUE 19).

The contract: on a paged engine every steady-state tick — prefill chunks,
latent finishes, fault poison, batched decode, quantized-page scale resets —
dispatches as ONE compiled program whose lanes are a host-built fixed-shape
work descriptor, and the emitted token streams are ``generate()``'s in
float64 (near-tie argmax flips cannot mask a real bug; int8 / int4 pools
under the same tick: tests/test_kv_quant.py). The compile-count invariant is
"the tick program compiles exactly once, ever" and the serving-metrics
``ragged_tick`` block pins programs-per-tick at 1.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.generation.generate import GenerationConfig
from perceiver_io_tpu.models.core.config import CausalSequenceModelConfig
from perceiver_io_tpu.models.core.perceiver_ar import CausalSequenceModel
from perceiver_io_tpu.serving import ServingEngine
from perceiver_io_tpu.serving.metrics import SCHEMA, load_metrics_jsonl
from tests.test_paging import _reference_tokens  # the same toy model: window 12 over 6 latents

VOCAB = 262
WINDOW = 12
LATENTS = 6
PS = 4


def _make_model(param_dtype=jnp.float32):
    config = CausalSequenceModelConfig(
        vocab_size=VOCAB, max_seq_len=WINDOW, max_latents=LATENTS,
        num_channels=16, num_heads=2, num_self_attention_layers=2,
        cross_attention_dropout=0.0,
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


# prompts chosen to straddle the prefill ladder rungs AND the page grid:
# shorter than the latent floor (classic path), mid-ladder, partial tail
# page (9 = 2 pages + 1 row), and the full window (ring-wrap territory once
# decode appends roll the oldest page)
CHURN_PROMPTS = [[5, 6, 7], [2] * 5, list(range(3, 12)), [9] * WINDOW,
                 [41, 40, 39, 38], list(range(60, 67))]
CHURN_NEW = [6, 3, 5, 8, 4, 7]


def _run_churn(model, params, **engine_kw):
    engine = ServingEngine(model, params, num_slots=3, kv_page_size=PS,
                           **engine_kw)
    assert engine.ragged
    handles = []
    for i, (p, m) in enumerate(zip(CHURN_PROMPTS, CHURN_NEW)):
        handles.append(engine.submit(p, max_new_tokens=m,
                                     rng=jax.random.PRNGKey(i)))
        engine.step()
    engine.run_until_drained(max_steps=400)
    assert all(h.done for h in handles)
    assert [len(h.output_ids) for h in handles] == CHURN_NEW
    return [h.result().tolist() for h in handles], engine


CHURN_ADMISSION = {"one_shot": {}, "chunked": {"prefill_chunk_tokens": 4, "max_prefill_slots": 2}}
_CHURN_RUNS: dict = {}  # admission -> the churn's tokens (one engine life a mode)


@pytest.mark.parametrize("admission", sorted(CHURN_ADMISSION))
@pytest.mark.parametrize("request_no", range(len(CHURN_PROMPTS)))
def test_ragged_tick_f64_matches_generate(setup64, request_no, admission):
    """The headline parity: fused-tick tokens == ``generate()``'s in float64,
    across ladder-straddling lengths, ring wraps, partial tail pages,
    interleaved admissions — with and without chunked admission."""
    model, params = setup64
    if admission not in _CHURN_RUNS:
        _CHURN_RUNS[admission], _ = _run_churn(model, params, **CHURN_ADMISSION[admission])
    expected = _reference_tokens(model, params, CHURN_PROMPTS[request_no],
                                 GenerationConfig(max_new_tokens=CHURN_NEW[request_no]))
    assert _CHURN_RUNS[admission][request_no] == expected


def test_ragged_tick_sampled_rng_chain_identical(setup):
    """Sampling: the per-slot rng split chain is part of the fused decode
    phase, and a finish lane installs its request's key — sampled streams
    match the dense pool's seed for seed, on both admission paths."""
    model, params = setup

    def run(**pool):
        engine = ServingEngine(model, params, num_slots=2, **pool)
        handles = [
            engine.submit(p, max_new_tokens=6, do_sample=True, temperature=0.8,
                          top_k=20, rng=jax.random.PRNGKey(7 + i))
            for i, p in enumerate(([5, 6, 7], list(range(3, 12))))
        ]
        engine.run_until_drained(max_steps=200)
        return [h.result().tolist() for h in handles]

    assert run(kv_page_size=PS) == run()


def test_ragged_tick_one_program_ever(setup):
    """THE perf invariant: steady-state churn — mixed admissions, chunked
    prefill, evictions — compiles the fused tick program exactly once, the
    watchdog budget of 1 holds, and the v11 metrics pin programs-per-tick
    at 1 for decode-carrying ticks."""
    model, params = setup
    toks, engine = _run_churn(model, params,
                              prefill_chunk_tokens=4, max_prefill_slots=2)
    assert engine._jit_ragged_tick._cache_size() == 1
    assert engine.decode_compilations == 1  # the property pins the fused jit
    if engine.watchdog is not None:
        engine.watchdog.check()  # ragged_tick budget=1 holds after churn
    # no per-phase program exists beside it: the tick is the one dispatcher
    assert engine._jit_decode is None and not hasattr(engine, "_jit_chunk_kv")
    snap = engine.metrics.snapshot()
    assert snap["ragged_tick"]["enabled"] is True
    assert snap["ragged_tick"]["ticks"] > 0
    assert snap["ragged_tick"]["programs_per_tick"]["p50"] == 1.0
    assert snap["ragged_tick"]["descriptor_build_s"]["p95"] >= 0.0
    # pages all home, slots clear — the descriptor leaked nothing
    assert engine._pool.pages_in_use == 0
    assert all(p is None for p in engine._slot_pages)
    assert not engine._tick_chunks and not engine._tick_finishes


def test_ragged_preempt_and_quarantine_drop_buffered_lanes(setup):
    """An admission evicted the same tick it buffered descriptor lanes must
    take those lanes with it (its pages return to the pool mid-tick): churn
    with deadline-expired work stays deterministic and drains whole."""
    model, params = setup

    def run():
        engine = ServingEngine(model, params, num_slots=2, kv_page_size=PS,
                               prefill_chunk_tokens=4, max_prefill_slots=2,
                               default_deadline_s=60.0)
        handles = [engine.submit(p, max_new_tokens=4, rng=jax.random.PRNGKey(i))
                   for i, p in enumerate(CHURN_PROMPTS[:4])]
        engine.run_until_drained(max_steps=300)
        return [h.result().tolist() for h in handles], engine

    toks1, e1 = run()
    toks2, _ = run()
    assert toks1 == toks2
    assert e1._pool.pages_in_use == 0
    # exercise _drop_tick_work directly: buffered lanes for a slot vanish
    e1._tick_chunks.append((1, None, 0, 0, 0, None))
    e1._tick_finishes.append((1, None, None, 0, None, None))
    e1._tick_resets.append((0, None))
    e1._tick_poison = 1
    e1._drop_tick_work(1)
    assert not e1._tick_chunks and not e1._tick_finishes
    assert e1._tick_resets and e1._tick_poison is None
    e1._drop_tick_work(0)
    assert not e1._tick_resets


# -------------------------------------------------------------------- chaos
def test_chaos_ragged_tick_churn_scenario():
    """The ragged_tick_churn scenario is registered (the matrix smoke in
    test_reliability covers it in CI) and green standalone: quarantine +
    preemption inside the fused tick, survivors f64-identical to the
    same engine run uncontended, free list whole at drain."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chaos_check_ragged_under_test",
        os.path.join(os.path.dirname(__file__), "..", "scripts", "chaos_check.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert "ragged_tick_churn" in mod.CHECKS
    result = mod.main(["--checks", "ragged_tick_churn"])
    assert result["all_ok"], result["checks"]["ragged_tick_churn"]


# -------------------------------------------------------------- serve_bench
def test_serve_bench_ragged_arm_smoke(tmp_path):
    """CI satellite: ``serve_bench --ragged`` writes the ragged_tick section
    — tokens/s + inter-token p95 under the fused tick, its one program a
    tick, and the int4 sessions-at-fixed-HBM comparison with its
    >= 1.8x-vs-fp acceptance — into the BENCH_serving.json artifact."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "serve_bench_ragged_under_test",
        os.path.join(os.path.dirname(__file__), "..", "scripts", "serve_bench.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    out = tmp_path / "SERVE_BENCH.json"
    profile_out = tmp_path / "BENCH_serving.json"
    result = mod.main([
        "--preset", "tiny", "--slots", "2", "--requests", "3",
        "--ragged", "--ragged-repeats", "2", "--no-baseline",
        "--out", str(out), "--profile-out", str(profile_out),
    ])
    block = result["ragged_tick"]
    # the structural headline: ONE program per steady ragged tick
    assert block["ragged_arm"]["programs_per_tick"]["p50"] == 1.0
    assert block["ragged_arm"]["tick_compilations"] == 1
    assert block["ragged_arm"]["descriptor_build_s"]["p95"] >= 0.0
    cap = block["int4_capacity"]
    for arm in ("fp", "int8", "int4"):
        assert cap[f"{arm}_arm"]["pool_bytes"] <= cap["pool_byte_budget"]
    assert cap["int4_arm"]["kv_quant"]["mode"] == "int4"
    assert cap["int4_vs_fp_sessions_ratio"] >= 1.8  # the acceptance floor
    assert cap["int4_vs_int8_sessions_ratio"] > 1.0
    assert cap["meets_1p8x_fp"] is True
    # quality is REPORTED, never silently dropped
    assert cap["quality"]["greedy_token_agreement_vs_fp"] is not None
    assert cap["quality"]["compared_tokens"] > 0
    on_disk = json.loads(profile_out.read_text())
    assert on_disk["ragged_tick"]["ragged_arm"]["programs_per_tick"]["p50"] == 1.0
    assert (tmp_path / "BENCH_serving.manifest.json").exists()


def test_schema_v11_and_reader_normalizes_pre_v11(tmp_path):
    """The writer stamps serving-metrics/v12; the reader backfills
    ragged_tick: None onto pre-v11 snapshots (and dense engines truthfully
    report None — 'not recorded' stays indistinguishable from 'no tick
    dispatcher exists', the schema's long-standing discipline)."""
    assert SCHEMA == "serving-metrics/v13"
    path = tmp_path / "old.jsonl"
    path.write_text(json.dumps({
        "event": "snapshot", "schema": "serving-metrics/v10",
        "requests_submitted": 1,
    }) + "\n")
    snaps = load_metrics_jsonl(str(path))["snapshots"]
    assert snaps[0]["ragged_tick"] is None
