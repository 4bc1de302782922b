"""Falcon-H1 through ``ServingEngine`` on the CPU at a toy size: the engine's greedy
tokens against the plain reference with requests joining mid run, one compilation of the
tick, a slot's two kinds of state on the books, the options the model does not carry yet
refused at construction, and the benchmark's new cell under ``--rehearse``."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark.families.falcon_h1 import reference
from perceiver_io_tpu.serving import ServingEngine, ServingRouter
from perceiver_io_tpu.serving.engine import TICK_SCOPES
from tests.falcon_h1_toy import SIZES, build

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENGINE = dict(num_slots=3, kv_page_size=8, prefill_chunk_tokens=8, num_kv_pages=40)


@pytest.fixture(scope="module")
def toy():
    return build()


@pytest.fixture(scope="module")
def served(toy):
    """Six requests of mixed lengths, two of them submitted after four ticks, run to
    their end on one engine: (engine, handles, prompts, answer lengths)."""
    model, params, _ = toy
    engine = ServingEngine(model, params, **ENGINE)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, SIZES["vocab_size"], size=n).astype(np.int32) for n in (5, 8, 9, 23, 17, 31)]
    news = [6, 4, 9, 5, 7, 3]
    handles = [engine.submit(p, max_new_tokens=m) for p, m in zip(prompts[:4], news[:4])]
    for _ in range(4):
        engine.step()
    handles += [engine.submit(p, max_new_tokens=m) for p, m in zip(prompts[4:], news[4:])]
    engine.run_until_drained(max_steps=500)
    return engine, handles, prompts, news


@pytest.mark.parametrize("request_no", range(6))
def test_greedy_tokens_are_the_references_argmax(toy, served, request_no):
    _, _, weights = toy
    _, handles, prompts, news = served
    handle = handles[request_no]
    tokens = np.asarray(handle.output_ids)
    assert handle.ok and len(tokens) == news[request_no]
    assert handle.slot_claimed_at <= handle.admitted_at <= handle.first_token_at <= handle.finished_at
    logits = np.asarray(reference.score_served(weights, SIZES, prompts[request_no], tokens, pad_to=16))
    assert np.array_equal(logits.argmax(axis=-1), tokens)


def test_one_tick_program_and_the_normal_path(served):
    engine = served[0]
    assert engine.ragged and engine.decode_compilations == 1
    # more slots than requests at once: every slot was reused, and nothing else compiled
    assert engine.prefill_compilations == 0
    assert set(TICK_SCOPES) >= {"decode/ssm_update", "decode/attention", "decode/mlp",
                                "chunk_lanes/ssd_scan", "chunk_lanes/attention"}


def test_the_snapshot_books_the_recurrent_state(served):
    engine, _, prompts, _ = served
    block = engine.metrics.snapshot()["recurrent_state"]
    layers, per_layer = SIZES["num_hidden_layers"], 4 * SIZES["mamba_n_heads"] * SIZES["mamba_d_head"] * SIZES["mamba_d_state"]
    assert block["bytes"] == ENGINE["num_slots"] * layers * per_layer and block["slots"] == ENGINE["num_slots"]
    chunks = sum(-(-len(p) // ENGINE["prefill_chunk_tokens"]) for p in prompts)
    assert block["resets"] == len(prompts) and block["chunks_carried"] == chunks - len(prompts)
    assert 0 < block["decoding_slots"]["p50"] <= block["decoding_slots"]["p95"] <= ENGINE["num_slots"]


def test_the_tick_names_the_models_scopes(toy):
    model, params, _ = toy
    engine = ServingEngine(model, params, **ENGINE)
    text = engine.lower_tick().as_text(debug_info=True)
    for scope in ("decode/ssm_update", "decode/attention", "decode/mlp", "chunk_lanes/ssd_scan", "chunk_lanes/attention"):
        phase, part = TICK_SCOPES[scope].split("/")
        assert f"{phase}/" in text and f"/{part}" in text, scope


@pytest.mark.parametrize("option,value,names", [
    ("prefix_cache", True, "snapshotted at page boundaries"),
    ("kv_quant", "int8", "full-precision pages"),
    ("handle_preemption", True, "snapshotted"),
    ("journal", "DIR", "journal replay"),
    ("kv_page_size", None, "one page a window: served, and with the configured page's tokens"),
    ("router.prefix_cache", True, "snapshotted at page boundaries"),
])
def test_options_the_model_does_not_carry_are_refused_at_construction(toy, tmp_path, option, value, names):
    model, params, _ = toy
    kwargs = dict(ENGINE)
    if option == "kv_page_size":
        # no refusal: the page pool is the engine's one pool, and no page size means a slot's window in one page
        def serve(**engine_kwargs):
            engine = ServingEngine(model, params, **engine_kwargs)
            handles = [engine.submit(np.arange(1, 1 + n, dtype=np.int32), max_new_tokens=4) for n in (3, 11, 20)]
            engine.run_until_drained(max_steps=200)
            return engine, [h.result().tolist() for h in handles]

        engine, tokens = serve(num_slots=3)
        assert engine.kv_page_size == model.serving_traits().window and engine._pages_per_slot == 1
        assert tokens == serve(**ENGINE)[1] == serve(num_slots=3, prefill_chunk_tokens=8)[1]
        return
    if value == "DIR":
        value = str(tmp_path / "journal")
    with pytest.raises(ValueError) as refusal:
        if option.startswith("router."):
            ServingRouter(model, params, num_replicas=1, **{**kwargs, option.split(".")[1]: value})
        else:
            ServingEngine(model, params, **{**kwargs, option: value})
    assert "cannot be served with" in str(refusal.value) and names in str(refusal.value)
    if option == "journal":
        assert not (tmp_path / "journal").exists()


@pytest.mark.parametrize("trace", [0, 1])
def test_the_new_cell_rehearses_through_run_py(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", "serve-falcon-h1-chat", "--seed",
         str(2**31 + 7), "--seconds", "2", "--trace", str(trace), "--rehearse"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)
    assert done.returncode == 4, done.stderr[-2000:]
    would = [json.loads(line) for line in done.stdout.splitlines() if '"rehearsal-result"' in line]
    line = json.loads(would[-1]["would_print"])
    assert line["correct"] is True and line["failed"] == 0
    if trace:
        assert 0 < line["metrics"]["recurrent_state.decoding_slot_pct"]["value"] <= 100
