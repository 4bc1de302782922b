"""LFM2 mixture-of-experts through ``ServingEngine`` on the CPU at a toy size: the engine's
greedy tokens against the plain reference with requests joining mid run, one compilation
of the tick, the experts' counters in the tick's one readback, on the snapshot and on the
tick's record, a model without experts paying nothing, the options the model does not
carry yet refused at construction, and the benchmark's new cell under ``--rehearse``."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark.families.lfm2_moe import reference
from perceiver_io_tpu.obs.core import TelemetryRecorder
from perceiver_io_tpu.serving import ServingEngine, ServingRouter
from perceiver_io_tpu.serving.engine import TICK_SCOPES, TickRecord
from tests.lfm2_moe_toy import SIZES, build

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENGINE = dict(num_slots=3, kv_page_size=8, prefill_chunk_tokens=8, num_kv_pages=40)
EXPERT_LAYERS = SIZES["num_hidden_layers"] - SIZES["num_dense_layers"]
SCOPES = ("moe/route", "moe/experts", "short_conv", "attention")


@pytest.fixture(scope="module")
def toy():
    return build()


@pytest.fixture(scope="module")
def served(toy):
    """Six requests of mixed lengths, two of them submitted after four ticks, run to
    their end on one engine with telemetry on: (engine, recorder, handles, prompts, answer lengths)."""
    model, params, _ = toy
    recorder = TelemetryRecorder()
    engine = ServingEngine(model, params, **ENGINE, telemetry=recorder)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, SIZES["vocab_size"], size=n).astype(np.int32) for n in (5, 8, 9, 23, 17, 31)]
    news = [6, 4, 9, 5, 7, 3]
    handles = [engine.submit(p, max_new_tokens=m) for p, m in zip(prompts[:4], news[:4])]
    for _ in range(4):
        engine.step()
    handles += [engine.submit(p, max_new_tokens=m) for p, m in zip(prompts[4:], news[4:])]
    engine.run_until_drained(max_steps=500)
    return engine, recorder, handles, prompts, news


@pytest.mark.parametrize("request_no", range(6))
def test_greedy_tokens_are_the_references_argmax(toy, served, request_no):
    _, _, weights = toy
    _, _, handles, prompts, news = served
    handle = handles[request_no]
    tokens = np.asarray(handle.output_ids)
    assert handle.ok and len(tokens) == news[request_no]
    logits = np.asarray(reference.score_served(weights, SIZES, prompts[request_no], tokens, pad_to=16))
    assert np.array_equal(logits.argmax(axis=-1), tokens)


def test_one_tick_program_and_the_normal_path(served):
    engine = served[0]
    assert engine.ragged and engine.decode_compilations == 1
    # more slots than requests at once: every slot was reused, and nothing else compiled
    assert engine.prefill_compilations == 0
    assert set(TICK_SCOPES) >= {f"{phase}/{part}" for phase in ("decode", "chunk_lanes") for part in SCOPES}


def test_the_snapshot_books_the_experts_and_the_convolution_columns(served):
    engine, _, _, prompts, news = served
    snapshot = engine.metrics.snapshot()
    block = snapshot["experts"]
    assert block["layers"] == EXPERT_LAYERS and block["experts"] == SIZES["num_experts"]
    assignments = np.asarray(block["assignments"])
    # every prompt token and every sampled token (the tick runs the model's step on the token it has
    # just sampled, a request's last included) is routed to ``num_experts_per_tok`` experts a layer
    tokens = sum(len(p) for p in prompts) + sum(news)
    assert assignments.shape == (EXPERT_LAYERS, SIZES["num_experts"])
    assert (assignments.sum(axis=-1) == tokens * SIZES["num_experts_per_tok"]).all()
    touched = block["touched_per_step"]
    assert 0 < touched["p50"] <= touched["p95"] <= SIZES["num_experts"] and touched["mean"] > 0
    assert 1.0 <= block["load_max_over_mean"] <= SIZES["num_experts"]
    state = snapshot["recurrent_state"]
    conv_layers = SIZES["layer_types"].count("conv")
    assert state["bytes"] == ENGINE["num_slots"] * conv_layers * (SIZES["conv_L_cache"] - 1) * SIZES["hidden_size"] * 2
    assert state["resets"] == len(prompts)


def test_the_ticks_record_and_spans_carry_the_counters(served):
    """The counters ride the token readback: the harvest span of every decoding tick and the
    tick span's end carry them, the sample_sync span (begun before the readback) does not."""
    _, recorder, _, prompts, news = served
    events = [e for e in recorder.chrome_trace()["traceEvents"] if e.get("ph") == "X"]
    harvests = [e for e in events if e["name"].endswith(".harvest")]
    syncs = [e for e in events if e["name"].endswith(".sample_sync")]
    assert harvests and len(harvests) == len(syncs)
    assert all({"tick", "expert_assignments", "experts_touched"} <= set(e["args"]) for e in harvests)
    assert all("expert_assignments" not in e["args"] and "decoding" in e["args"] for e in syncs)
    tokens = sum(len(p) for p in prompts) + sum(news)
    assert sum(e["args"]["expert_assignments"] for e in harvests) == tokens * SIZES["num_experts_per_tok"] * EXPERT_LAYERS
    assert all(0 < e["args"]["experts_touched"] <= SIZES["num_experts"] for e in harvests)
    ticks = [e for e in events if e["name"].endswith(".tick") and e["args"].get("decoding")]
    # the tick span ends with the record's ten fields, then the three the readback brought
    both = TickRecord._fields + ("expert_assignments", "experts_touched", "experts_held_assignments")
    assert ticks and all(tuple(e["args"]) == both for e in ticks)
    assert all(e["args"]["expert_assignments"] == h["args"]["expert_assignments"]
               for e in ticks for h in harvests if h["args"]["tick"] == e["args"]["tick"])
    assert all(tuple(e["args"]) == TickRecord._fields for e in syncs)


def riding_lanes_book(served):
    """What holds of a served run of ANY model that states ``serving_api.py`` (h); ``served`` = (engine,
    recorder, handles, prompts, ...). Nemotron-H's serving tests call it too."""
    engine, recorder, handles, prompts = served[:4]
    assert engine._traits.chunk_rides_decode
    events = [e for e in recorder.chrome_trace()["traceEvents"] if e.get("ph") == "X"]
    ticks = [e["args"] for e in events if e["name"].endswith(".tick") and "chunk_lanes" in e["args"]]
    lane_ticks_that_decoded = [t for t in ticks if t["chunk_lanes"] and t["decoding"]]
    assert len(lane_ticks_that_decoded) >= 5
    assert all(t["riding_chunk_lanes"] == 1 for t in lane_ticks_that_decoded)
    assert all(t["riding_chunk_lanes"] == 0 for t in ticks if not (t["chunk_lanes"] and t["decoding"]))
    block = engine.metrics.snapshot()["ragged_tick"]
    assert block["riding_chunk_lanes"] == len(lane_ticks_that_decoded)
    # every prompt ended in a finish lane, and no tick harvested a slot it finished
    assert sum(t["finish_lanes"] for t in ticks) == len(prompts)
    syncs = {e["args"]["tick"]: e["args"] for e in events if e["name"].endswith(".sample_sync")}
    assert all(syncs[t["tick"]]["riding_chunk_lanes"] == t["riding_chunk_lanes"] for t in ticks if t["tick"] in syncs)
    assert all(h.first_token_at > h.admitted_at for h in handles)


def test_the_riding_lanes_counter_is_the_lane_ticks_that_decoded(served):
    """ISSUE 46: the tick's record says how many of its chunk lanes rode the decode step (the
    first, in every tick that carried one and decoded), the snapshot their total; a slot whose
    finish lane rode a tick is not among that tick's decoding slots."""
    riding_lanes_book(served)


def test_a_model_that_states_nothing_has_no_riding_lane():
    from tests.falcon_h1_toy import build as build_falcon

    model, params, _ = build_falcon()
    assert not model.serving_traits().chunk_rides_decode
    engine = ServingEngine(model, params, **ENGINE)
    first = engine.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=12)
    engine.step()
    engine.step()
    late = engine.submit(np.arange(1, 20, dtype=np.int32), max_new_tokens=3)
    engine.run_until_drained(max_steps=100)
    assert first.ok and late.ok
    block = engine.metrics.snapshot()["ragged_tick"]
    assert block["chunk_lanes"]["mean"] >= 1 and block["riding_chunk_lanes"] == 0


def test_a_model_without_experts_has_no_experts_block_and_its_tick_returns_what_it_did():
    from tests.falcon_h1_toy import build as build_falcon

    model, params, _ = build_falcon()
    assert model.serving_traits().expert_counters is None
    recorder = TelemetryRecorder()
    engine = ServingEngine(model, params, **ENGINE, telemetry=recorder)
    handle = engine.submit(np.arange(1, 12, dtype=np.int32), max_new_tokens=4)
    engine.run_until_drained(max_steps=100)
    assert handle.ok and engine.metrics.snapshot()["experts"] is None
    # the tick's token output is the slots' tokens and nothing else
    out = engine.lower_tick().out_info
    assert out[0].shape == (ENGINE["num_slots"],)
    spans = [e for e in recorder.chrome_trace()["traceEvents"] if e.get("ph") == "X" and "args" in e]
    assert not any("experts_touched" in e["args"] or "expert_assignments" in e["args"] for e in spans)


def test_the_counters_lengthen_the_token_output_and_add_no_output(toy):
    model, params, _ = toy
    engine = ServingEngine(model, params, **ENGINE)
    out = engine.lower_tick().out_info
    assert out[0].shape == (ENGINE["num_slots"] + 2 * EXPERT_LAYERS * SIZES["num_experts"],)
    assert out[1].shape == (ENGINE["num_slots"],)  # finite: the tick's other readback, as before


def test_the_tick_names_the_models_scopes(toy):
    model, params, _ = toy
    engine = ServingEngine(model, params, **ENGINE)
    text = engine.lower_tick().as_text(debug_info=True)
    for phase in ("decode", "chunk_lanes"):
        for part in SCOPES:
            assert f"tick.{phase}/" in text and f"/{part}/" in text, (phase, part)


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
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", "serve-lfm2-moe-assist", "--seed",
         str(2**31 + 7), "--seconds", "2", "--trace", str(trace), "--rehearse"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)
    assert done.returncode == 4, done.stderr[-2000:]
    would = [json.loads(line) for line in done.stdout.splitlines() if '"rehearsal-result"' in line]
    line = json.loads(would[-1]["would_print"])
    assert line["correct"] is True and line["failed"] == 0
    if trace:
        assert 0 < line["metrics"]["experts.touched_per_step"]["value"] <= 8
        assert line["metrics"]["experts.load_max_over_mean"]["value"] >= 1
