"""Nemotron-H through ``ServingEngine`` on the CPU at a toy size: the engine's greedy tokens
against the plain reference with requests joining mid run, one compilation of the tick in
the ride order (``serving_api.py`` (h)) and its counter of riding lanes, the counters' book knowing which experts are held, the model's
scopes in the lowered tick, the options it does not carry refused, and the benchmark's new
cell under ``--rehearse``."""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark.families.nemotron_h import reference
from perceiver_io_tpu.models.core.lfm2_moe import Lfm2MoeForCausalLM
from perceiver_io_tpu.obs.core import TelemetryRecorder
from perceiver_io_tpu.serving import ServingEngine
from perceiver_io_tpu.serving.engine import TICK_SCOPES
from perceiver_io_tpu.serving.metrics import EngineMetrics
from tests.nemotron_h_toy import SIZES, build
from tests.test_lfm2_moe_serving import riding_lanes_book

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENGINE = dict(num_slots=3, kv_page_size=8, prefill_chunk_tokens=8, num_kv_pages=40)
PATTERN = SIZES["hybrid_override_pattern"]
EXPERT_LAYERS, HELD, ROUTED, TOP_K = PATTERN.count("E"), SIZES["n_routed_experts"], SIZES["router_experts"], SIZES["num_experts_per_tok"]
SCOPES = ("moe/route", "moe/experts", "moe/shared", "ssm", "attention")


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


def test_one_tick_program_in_the_ride_order(served):
    engine = served[0]
    traits = engine._traits
    assert engine.decode_compilations == 1 and engine.prefill_compilations == 0
    # (h) is stated, from the configuration: the head and the sampler, the rows (the decode step riding the first
    # carried chunk lane), the finish lanes; (g) names the ``M`` layers' in_proj alone
    assert traits.chunk_rides_decode
    assert traits.row_major_leaves == tuple(f"params/layers_{i}_in_proj" for i, k in enumerate(PATTERN) if k == "M")
    assert traits.expert_counters == (EXPERT_LAYERS, ROUTED) and traits.experts_held == (0, HELD)
    assert engine.metrics.snapshot()["ragged_tick"]["riding_chunk_lanes"] > 0
    # the lanes' loop is the expert model's, taken by reference: one function, no second copy
    assert type(engine.model).serving_ride_phase is Lfm2MoeForCausalLM.serving_ride_phase
    assert not hasattr(type(engine.model), "serving_chunk_phase")


def test_a_stack_without_expert_layers_states_nothing(toy):
    """The statement follows from the configuration: with no ``E`` layer no call reads its weights once for
    few rows, and the tick keeps the plain order."""
    model = toy[0]
    plain = type(model)(config=dataclasses.replace(model.config, hybrid_override_pattern="MM*M", num_hidden_layers=4))
    traits = plain.serving_traits()
    assert not traits.chunk_rides_decode and traits.expert_counters is None and traits.experts_held is None


def test_the_riding_lanes_counter_is_the_lane_ticks_that_decoded(served):
    """LFM2's twin: every lane tick that decoded rode exactly one lane, the snapshot counts them,
    and no tick harvested a slot it finished."""
    riding_lanes_book(served)


def test_the_snapshot_books_the_held_experts_and_the_state(served):
    engine, _, _, prompts, news = served
    snapshot = engine.metrics.snapshot()
    block = snapshot["experts"]
    assert block["layers"] == EXPERT_LAYERS and block["experts"] == ROUTED and block["held"] == [0, HELD]
    assignments = np.asarray(block["assignments"])
    # the router's width, all of it: every prompt token and every sampled token is routed to
    # ``num_experts_per_tok`` of ALL the router's experts a layer, held here or not
    tokens = sum(len(p) for p in prompts) + sum(news)
    assert assignments.shape == (EXPERT_LAYERS, ROUTED) and (assignments.sum(axis=-1) == tokens * TOP_K).all()
    assert assignments[:, HELD:].sum() > 0
    # the matrices a decode step READ are the held experts that received a row
    touched = block["touched_per_step"]
    assert 0 < touched["p50"] <= touched["p95"] <= HELD
    assert 0 < block["held_assignment_pct"] < 100
    state = snapshot["recurrent_state"]
    per_slot = PATTERN.count("M") * 4 * SIZES["mamba_num_heads"] * SIZES["mamba_head_dim"] * SIZES["ssm_state_size"]
    assert state["bytes"] == ENGINE["num_slots"] * per_slot and state["resets"] == len(prompts)


def test_the_harvest_span_carries_the_held_assignments(served):
    _, recorder, _, _, _ = served
    events = [e for e in recorder.chrome_trace()["traceEvents"] if e.get("ph") == "X"]
    harvests = [e["args"] for e in events if e["name"].endswith(".harvest")]
    assert harvests and all({"experts_touched", "experts_held_assignments", "expert_assignments"} <= set(a) for a in harvests)
    assert all(0 <= a["experts_held_assignments"] <= a["expert_assignments"] for a in harvests)
    assert all(a["experts_touched"] <= HELD for a in harvests)
    assert 0 < sum(a["experts_held_assignments"] for a in harvests) < sum(a["expert_assignments"] for a in harvests)


@pytest.mark.parametrize("held,touched,share", [(None, 2.5, 100.0), ((0, 4), 1.5, 50.0), ((4, 4), 1.0, 50.0)])
def test_the_book_counts_touched_and_computed_among_the_held(held, touched, share):
    """``record_expert_counts`` over two layers of eight experts: a model that states no share
    holds them all (LFM2's reading, as it was); a share counts its own experts' rows alone."""
    metrics = EngineMetrics(num_slots=4)
    metrics.set_expert_counters(2, 8, held)
    counts = np.zeros((2, 2, 8), np.int64)
    counts[0, 0, [0, 1, 5]] = (2, 1, 3)  # the decode step, layer 0
    counts[0, 1, [3, 6]] = (3, 3)  # layer 1
    counts[1, 0, 7] = 9  # a chunk lane's: in the totals, in no decode book
    total, got_touched, computed = metrics.record_expert_counts(counts)
    assert total == 21 and got_touched == touched and computed == round(12 * share / 100)
    block = metrics.snapshot()["experts"]
    assert block["held_assignment_pct"] == share and block["held"] == list(held or (0, 8))


def test_the_counters_lengthen_the_token_output_by_the_routers_width(toy):
    model, params, _ = toy
    out = ServingEngine(model, params, **ENGINE).lower_tick().out_info
    assert out[0].shape == (ENGINE["num_slots"] + 2 * EXPERT_LAYERS * ROUTED,)


def test_the_tick_names_the_models_scopes(toy):
    model, params, _ = toy
    text = ServingEngine(model, params, **ENGINE).lower_tick().as_text(debug_info=True)
    for phase in ("decode", "chunk_lanes"):
        for part in SCOPES:
            assert f"tick.{phase}/" in text and f"/{part}/" in text, (phase, part)
    assert "/head/" in text
    assert set(TICK_SCOPES) >= {f"{phase}/{part}" for phase in ("decode", "chunk_lanes") for part in SCOPES}


@pytest.mark.parametrize("option,value,names", [
    ("prefix_cache", True, "snapshotted at page boundaries"),
    ("kv_quant", "int8", "full-precision pages"),
    ("handle_preemption", True, "snapshotted"),
    ("journal", "DIR", "journal replay"),
])
def test_options_the_model_does_not_carry_are_refused_at_construction(toy, tmp_path, option, value, names):
    model, params, _ = toy
    if value == "DIR":
        value = str(tmp_path / "journal")
    with pytest.raises(ValueError) as refusal:
        ServingEngine(model, params, **{**ENGINE, option: value})
    assert "cannot be served with" in str(refusal.value) and names in str(refusal.value)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_new_cell_rehearses_through_run_py(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", "serve-nemotron3-nano-agent", "--seed",
         str(2**31 + 7), "--seconds", "2", "--trace", str(trace), "--rehearse"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)
    assert done.returncode == 4, done.stderr[-2000:]
    would = [json.loads(line) for line in done.stdout.splitlines() if '"rehearsal-result"' in line]
    line = json.loads(would[-1]["would_print"])
    assert line["correct"] is True and line["failed"] == 0
    if trace:
        assert 0 < line["metrics"]["experts.touched_per_step"]["value"] <= 4
        assert 0 < line["metrics"]["experts.held_assignment_pct"]["value"] < 100
