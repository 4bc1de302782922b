"""Unified-telemetry tests (docs/observability.md): fake-clock determinism of
the recorder core, Chrome-trace artifact validity, the compile watchdog
catching a deliberately induced recompile while staying silent across engine
churn, the zero-overhead/inertness contract of the disabled recorder (f64
parity of serving tokens and training loss, recorder-on vs recorder-off), the
train-metrics/v1 bus, run manifests, close-guard hardening, and the
obs_report end-to-end smoke."""

import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.models.core.config import CausalSequenceModelConfig
from perceiver_io_tpu.models.core.perceiver_ar import CausalSequenceModel
from perceiver_io_tpu.obs import (
    CompileWatchdog,
    build_run_manifest,
    load_chrome_trace,
    validate_chrome_trace,
    write_run_manifest,
)
from perceiver_io_tpu.obs.core import (
    NULL_RECORDER,
    TELEMETRY_ENV,
    NullRecorder,
    TelemetryRecorder,
    resolve_recorder,
)
from perceiver_io_tpu.serving import ServingEngine
from perceiver_io_tpu.training.fit import Trainer, TrainerConfig
from perceiver_io_tpu.training.metrics import (
    SCHEMA as TRAIN_SCHEMA,
    TrainMetricsWriter,
    load_metrics_jsonl,
)
from perceiver_io_tpu.training.trainer import (
    TrainState,
    build_optimizer,
    make_causal_lm_train_step,
)

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


# ------------------------------------------------------------ recorder core


def test_fake_clock_spans_and_histograms_are_deterministic():
    """Injectable clock: span durations, histogram stats, and trace
    timestamps are EXACTLY the fake clock's arithmetic — no wall time."""
    t = [100.0]
    rec = TelemetryRecorder(clock=lambda: t[0])
    for dur in (0.25, 0.5, 0.25, 1.0):
        with rec.span("phase.a", tag="x"):
            t[0] += dur
        t[0] += 0.125  # gap between spans must not leak into durations
    rec.span_begin("phase.b")
    t[0] += 2.0
    rec.span_end("phase.b")
    rec.counter_inc("n", 3)
    rec.counter_inc("n")
    rec.gauge_set("g", 0.75)

    s = rec.summary()
    a = s["phases"]["phase.a"]
    assert a["count"] == 4
    assert a["total_s"] == pytest.approx(2.0, abs=1e-12)
    assert a["mean_s"] == pytest.approx(0.5, abs=1e-12)
    assert a["max_s"] == pytest.approx(1.0, abs=1e-12)
    # numpy-style linear interpolation over the sorted window
    # [0.25, 0.25, 0.5, 1.0]: position 1.5 -> midway 0.25..0.5
    assert a["p50_s"] == pytest.approx(0.375, abs=1e-9)
    assert s["phases"]["phase.b"]["total_s"] == pytest.approx(2.0, abs=1e-12)
    assert s["counters"] == {"n": 4}
    assert s["gauges"] == {"g": 0.75}

    # trace timestamps: offsets from recorder construction, in order
    trace = rec.chrome_trace()
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X" and e["name"] == "phase.a"]
    assert [e["ts"] for e in xs] == [0.0, 375000.0, 1000000.0, 1375000.0]
    assert [e["dur"] for e in xs] == [250000.0, 500000.0, 250000.0, 1000000.0]


def test_chrome_trace_artifact_is_valid(tmp_path):
    """Write-side contract: the trace file parses, timestamps are
    non-negative, complete events carry durations, async begin/end balance."""
    t = [0.0]
    rec = TelemetryRecorder(clock=lambda: t[0])
    with rec.span("tick"):
        t[0] += 0.01
        rec.async_begin("request", 1, prompt_len=4)
        rec.async_instant("request", 1, "queued")
        t[0] += 0.02
        rec.async_end("request", 1, status="finished")
    rec.instant("marker", note="hello")
    path = tmp_path / "trace.json"
    rec.write_chrome_trace(str(path))
    trace = load_chrome_trace(str(path))
    assert validate_chrome_trace(trace) == []
    phases = {e["ph"] for e in trace["traceEvents"]}
    assert {"X", "b", "n", "e", "i"} <= phases
    assert trace["metadata"]["schema"] == "chrome-trace/v1"
    assert "tick" in trace["metadata"]["summary"]["phases"]


def test_validator_catches_unbalanced_and_negative():
    bad = {"traceEvents": [
        {"ph": "b", "cat": "r", "id": 1, "ts": 5.0},
        {"ph": "X", "name": "x", "ts": -1.0, "dur": 2.0},
    ]}
    problems = validate_chrome_trace(bad)
    assert any("never ended" in p for p in problems)
    assert any("negative ts" in p for p in problems)


def test_validator_tolerates_truncated_trace_imbalance():
    """A bounded-buffer trace that EVICTED old events (events_dropped > 0)
    legitimately holds async ends whose begins were dropped — tolerated, so
    long-run traces do not read as corrupt; real defects still flag."""
    truncated = {
        "traceEvents": [
            {"ph": "e", "cat": "request", "id": 3, "ts": 9.0},  # begin evicted
            {"ph": "n", "cat": "request", "name": "prefill", "id": 4, "ts": 2.0},
        ],
        "metadata": {"events_dropped": 17},
    }
    assert validate_chrome_trace(truncated) == []
    # the same imbalance WITHOUT recorded drops is still a defect
    truncated["metadata"]["events_dropped"] = 0
    assert validate_chrome_trace(truncated) != []


def test_null_recorder_is_shared_and_inert():
    assert resolve_recorder(None)[0] is NULL_RECORDER
    assert resolve_recorder(False)[0] is NULL_RECORDER
    span = NULL_RECORDER.span("anything", k=1)
    assert span is NULL_RECORDER.span("other")  # one shared no-op object
    with span:
        pass
    assert NULL_RECORDER.summary() == {}
    assert not NullRecorder.enabled


def test_env_enables_telemetry(monkeypatch, tmp_path):
    monkeypatch.setenv(TELEMETRY_ENV, "1")
    rec, owned = resolve_recorder(None)
    assert rec.enabled and owned
    rec.close()
    path = str(tmp_path / "env_trace.json")
    monkeypatch.setenv(TELEMETRY_ENV, path)
    rec, owned = resolve_recorder(None)
    assert rec.enabled and owned and rec.trace_path == path
    rec.close()
    assert os.path.exists(path)
    # explicit False beats the env
    assert resolve_recorder(False)[0] is NULL_RECORDER


def test_recorder_flush_thread_writes_and_joins(tmp_path):
    """The background flush thread keeps the trace file current and is
    ALWAYS joined by close() (the conftest leak fixture double-checks)."""
    path = str(tmp_path / "flush_trace.json")
    rec = TelemetryRecorder(trace_path=path, flush_interval_s=0.02)
    with rec.span("alive"):
        pass
    deadline = threading.Event()
    for _ in range(100):  # wait for at least one periodic flush
        if os.path.exists(path):
            break
        deadline.wait(0.02)
    assert os.path.exists(path)
    assert any(t.name == "perceiver-telemetry-flush" for t in threading.enumerate())
    rec.close()
    assert not any(t.name == "perceiver-telemetry-flush" for t in threading.enumerate())
    assert validate_chrome_trace(load_chrome_trace(path)) == []


def test_recorder_and_metrics_double_close(tmp_path):
    from perceiver_io_tpu.serving.metrics import EngineMetrics

    rec = TelemetryRecorder(trace_path=str(tmp_path / "t.json"))
    rec.close()
    rec.close()  # idempotent
    m = EngineMetrics(num_slots=1, jsonl_path=str(tmp_path / "m.jsonl"))
    m.record_submit(0, 3)
    m.close()
    m.close()  # idempotent
    m.record_submit(1, 3)  # post-close events are dropped, not a resurrection
    with open(tmp_path / "m.jsonl") as f:
        assert len(f.readlines()) == 1


# ----------------------------------------------------------- compile watchdog


def test_watchdog_catches_induced_recompile():
    rec = TelemetryRecorder()
    wd = CompileWatchdog(recorder=rec)
    fn = jax.jit(lambda x: x * 2 + 1)
    wd.watch("victim", fn, budget=1)
    fn(jnp.ones(3))
    assert wd.check() == []  # first compile is within budget
    fn(jnp.ones(5))  # deliberately induced recompile: new shape
    violations = wd.check()
    assert violations and violations[0]["kind"] == "budget_exceeded"
    assert violations[0]["function"] == "victim"
    assert wd.check() == []  # deduplicated: same overage is not re-reported
    assert rec.counters["compile.unexpected"] == 1
    wd.close()
    wd.close()  # idempotent


def test_watchdog_steady_state_flags_late_compiles():
    wd = CompileWatchdog()
    fn = jax.jit(lambda x: x - 3)
    wd.watch("fn", fn)  # unbudgeted: policed only after steady
    fn(jnp.ones(2))
    fn(jnp.ones(4))
    assert wd.check() == []  # warmup compiles are legitimate
    wd.mark_steady()
    fn(jnp.ones(2))  # cache hit: silent
    assert wd.check() == []
    fn(jnp.ones(8))  # recompile after steady: flagged
    kinds = {v["kind"] for v in wd.check()}
    assert "recompile_after_steady" in kinds or "backend_compile_after_steady" in kinds
    wd.close()


def test_watchdog_silent_across_engine_churn(x64):
    """The serving invariant as a runtime signal: admitting/evicting a churn
    of mixed-length requests through a telemetry-on engine never flags — one
    decode program, <= one prefill+install program per bucket."""
    model, params = _make_model(param_dtype=jnp.float64)
    engine = ServingEngine(model, params, num_slots=2, telemetry=True)
    prompts = [[7, 3, 9], [40, 41, 42, 43, 44, 45, 46], list(range(100, 112)), [250], [1, 2]]
    for i, p in enumerate(prompts):
        engine.submit(p, max_new_tokens=3 + (i % 3))
    engine.run_until_drained(max_steps=200)
    assert engine.watchdog.violations == []
    summary = engine.telemetry_summary()
    assert summary["compile"]["unexpected"] == []
    assert summary["compile"]["per_function"]["serving.ragged_tick"]["compilations"] == 1
    assert "serving.tick" in summary["phases"]
    engine.close()


def test_watchdog_registry_does_not_pin_dropped_instances():
    """The dispatcher's live-set holds WEAK refs: dropping a watchdog without
    close() (owner crashed mid-setup) must not pin it — and its watched
    programs and recorder buffers — in the process-global set forever."""
    import gc
    import weakref

    from perceiver_io_tpu.obs import watchdog as wd_mod

    wd = CompileWatchdog()
    ref = weakref.ref(wd)
    assert wd in wd_mod._LIVE_WATCHDOGS
    del wd
    gc.collect()
    assert ref() is None  # the set did not keep it alive


def test_two_engines_sharing_one_recorder_do_not_collide(x64):
    """Lifecycle spans are namespaced per engine: request ids restart at 0 in
    every engine, so a shared caller-owned recorder must still yield a valid
    (balanced, joinable) trace."""
    model, params = _make_model(param_dtype=jnp.float64)
    rec = TelemetryRecorder()
    engines = [ServingEngine(model, params, num_slots=1, telemetry=rec) for _ in range(2)]
    for engine in engines:
        engine.submit([5, 6, 7], max_new_tokens=2)
        engine.run_until_drained(max_steps=50)
    trace = rec.chrome_trace()
    assert validate_chrome_trace(trace) == []
    cats = {e.get("cat") for e in trace["traceEvents"] if e.get("ph") == "b"}
    assert len(cats) == 2  # one namespace per engine
    for engine in engines:
        engine.close()
    rec.close()


# ------------------------------------------------- inertness / parity pins


def test_engine_disabled_telemetry_is_null_and_token_identical(x64):
    """Zero-overhead pin: with telemetry off the engine holds the SHARED
    null recorder and no watchdog — the instrumented tick path degenerates to
    no-op method calls — and greedy f64 tokens are bitwise identical to a
    telemetry-ON engine (spans only time host calls, never touch values)."""
    model, params = _make_model(param_dtype=jnp.float64)
    prompts = [[7, 3, 9], list(range(40, 49)), [250]]

    def run(telemetry):
        engine = ServingEngine(model, params, num_slots=2, telemetry=telemetry)
        handles = [engine.submit(p, max_new_tokens=5) for p in prompts]
        engine.run_until_drained(max_steps=200)
        tokens = [h.result().tolist() for h in handles]
        engine.close()
        return engine, tokens

    engine_off, tokens_off = run(False)
    assert engine_off.telemetry is NULL_RECORDER
    assert engine_off.watchdog is None
    assert engine_off.telemetry_summary() is None
    engine_on, tokens_on = run(True)
    assert tokens_on == tokens_off
    # ... THROUGH the spans that tile the tick (tests/test_tick_spans.py pins the
    # paged engine the same way)
    phases = engine_on.telemetry.summary()["phases"]
    for name in ("serving.tick", "serving.schedule", "serving.decode_dispatch", "serving.sample_sync",
                 "serving.harvest", "serving.evict", "serving.host_gap"):
        assert phases[name]["count"] > 0, name
    # same compile geometry: telemetry adds host-side timers, not programs
    assert engine_on.decode_compilations == engine_off.decode_compilations == 1


def _fit_loss_trajectory(telemetry, metrics_path=None, trainer_out=None):
    config = CausalSequenceModelConfig(
        vocab_size=64, max_seq_len=16, max_latents=8, num_channels=16,
        num_heads=2, num_self_attention_layers=1, cross_attention_dropout=0.0,
    )
    model = CausalSequenceModel(config=config, deterministic=True, param_dtype=jnp.float64)
    rng = jax.random.PRNGKey(0)
    params = jax.jit(model.init, static_argnames="prefix_len")(
        rng, jnp.zeros((2, 16), jnp.int32), prefix_len=8
    )
    tx = build_optimizer(1e-3)

    def loader():
        r = np.random.RandomState(0)
        for _ in range(20):
            ids = r.randint(1, 64, size=(2, 16)).astype(np.int32)
            yield {"input_ids": ids, "labels": np.roll(ids, -1, axis=1)}

    lines = []
    cfg = TrainerConfig(max_steps=6, log_every=1, eval_every=10 ** 9,
                        prefetch_depth=2, telemetry=telemetry,
                        metrics_jsonl=metrics_path)
    trainer = Trainer(cfg, log_fn=lambda line: lines.append(json.loads(line)))
    state = TrainState.create(params, tx)
    trainer.fit(state, make_causal_lm_train_step(model, tx, max_latents=8), loader)
    trainer.close()
    if trainer_out is not None:
        trainer_out.append(trainer)
    return [line["loss"] for line in lines if "loss" in line]


def test_training_loss_trajectory_parity_recorder_on_vs_off(x64):
    """f64 bitwise pin: the per-step loss trajectory with telemetry ON equals
    the trajectory with telemetry OFF — the spans around fetch/dispatch/sync
    never alter a device value."""
    out = []
    on = _fit_loss_trajectory(True, trainer_out=out)
    off = _fit_loss_trajectory(False)
    assert on == off
    trainer = out[0]
    assert trainer.telemetry_summary is not None
    assert "train.fetch_wait" in trainer.telemetry_summary["phases"]
    assert "train.step_dispatch" in trainer.telemetry_summary["phases"]
    assert "train.log_sync" in trainer.telemetry_summary["phases"]
    assert trainer.telemetry_summary["compile"]["unexpected"] == []
    assert "train.fetch_wait_frac" in trainer.telemetry_summary["gauges"]


def test_watchdog_quiet_when_eval_compiles_after_first_log_window(x64):
    """eval_every > log_every must not flag the FIRST eval pass as a mid-run
    recompile: steady-marking waits for it (the eval step and the trainer's
    eval-fold jits legitimately compile then)."""
    config = CausalSequenceModelConfig(
        vocab_size=64, max_seq_len=16, max_latents=8, num_channels=16,
        num_heads=2, num_self_attention_layers=1, cross_attention_dropout=0.0,
    )
    model = CausalSequenceModel(config=config, deterministic=True, param_dtype=jnp.float64)
    rng = jax.random.PRNGKey(0)
    params = jax.jit(model.init, static_argnames="prefix_len")(
        rng, jnp.zeros((2, 16), jnp.int32), prefix_len=8
    )
    from perceiver_io_tpu.training.trainer import make_causal_lm_eval_step

    tx = build_optimizer(1e-3)

    def loader():
        r = np.random.RandomState(0)
        for _ in range(16):
            ids = r.randint(1, 64, size=(2, 16)).astype(np.int32)
            yield {"input_ids": ids, "labels": np.roll(ids, -1, axis=1)}

    cfg = TrainerConfig(max_steps=8, log_every=2, eval_every=6, telemetry=True,
                        prefetch_depth=0)
    trainer = Trainer(cfg, log_fn=lambda _: None)
    trainer.fit(
        TrainState.create(params, tx),
        make_causal_lm_train_step(model, tx, max_latents=8),
        loader,
        eval_step=make_causal_lm_eval_step(model, max_latents=8),
        eval_loader_fn=lambda: loader(),
    )
    # logs at 2 and 4 precede the first eval at 6: the eval compiles there
    # must not surface as violations
    assert trainer.telemetry_summary["compile"]["unexpected"] == []


def test_fit_called_inside_except_handler_closes_telemetry_normally():
    """The finally's unwinding detection must not mistake a CALLER's in-flight
    exception (fit invoked from an except block — e.g. retrain-after-failure)
    for fit itself failing: telemetry still closes on the success path, after
    the final work."""
    try:
        raise RuntimeError("caller-level failure fit must ignore")
    except RuntimeError:
        out = []
        losses = _fit_loss_trajectory(True, trainer_out=out)
    assert losses  # the fit ran to completion
    assert out[0].telemetry_summary is not None
    assert "train.step_dispatch" in out[0].telemetry_summary["phases"]


# ------------------------------------------------------- train-metrics/v1 bus


def test_train_metrics_writer_flushes_per_line(tmp_path):
    path = str(tmp_path / "train.jsonl")
    writer = TrainMetricsWriter(path)
    writer.write("train_log", {"step": 5, "loss": 2.5})
    # readable WHILE the handle is open: the per-line flush is the SIGTERM
    # durability contract — nothing sits in a block buffer
    with open(path) as f:
        rec = json.loads(f.readline())
    assert rec["schema"] == TRAIN_SCHEMA and rec["event"] == "train_log"
    assert rec["step"] == 5 and "ts" in rec
    writer.close()
    writer.close()
    writer.write("train_log", {"step": 6})  # dropped, not resurrected
    with open(path) as f:
        assert len(f.readlines()) == 1


def test_train_metrics_reader_versions(tmp_path):
    path = tmp_path / "mixed.jsonl"
    lines = [
        {"schema": TRAIN_SCHEMA, "event": "train_log", "ts": 1.0, "step": 10, "loss": 1.0},
        {"step": 20, "val_loss": 0.5},  # legacy print-JSON line, schema-less
        {"checkpoint": "best", "loss": 0.4},
    ]
    path.write_text("".join(json.dumps(line) + "\n" for line in lines))
    loaded = load_metrics_jsonl(str(path))
    assert [e["event"] for e in loaded["events"]] == ["train_log", "val", "checkpoint"]
    assert loaded["events"][1]["schema"] is None
    assert len(loaded["by_kind"]["train_log"]) == 1
    path.write_text(json.dumps({"schema": "train-metrics/v99", "event": "x"}) + "\n")
    with pytest.raises(ValueError, match="unknown train-metrics schema"):
        load_metrics_jsonl(str(path))


def test_fit_routes_logs_through_versioned_stream(tmp_path):
    metrics_path = str(tmp_path / "fit.jsonl")
    losses = _fit_loss_trajectory(False, metrics_path=metrics_path)
    loaded = load_metrics_jsonl(metrics_path)
    logs = loaded["by_kind"]["train_log"]
    assert [line["loss"] for line in logs] == losses
    assert all(e["schema"] == TRAIN_SCHEMA for e in loaded["events"])


# ------------------------------------------------------------- run manifests


def test_run_manifest_contents(tmp_path):
    artifact = tmp_path / "BENCH_x.json"
    artifact.write_text("{}\n")
    path = write_run_manifest(str(artifact), config={"preset": "tiny", "slots": 4})
    assert path == str(tmp_path / "BENCH_x.manifest.json")
    manifest = json.loads(open(path).read())
    assert manifest["schema"] == "run-manifest/v1"
    assert manifest["versions"]["jax"] == jax.__version__
    assert manifest["devices"]["count"] >= 1 and manifest["devices"]["backend"]
    assert manifest["config"] == {"preset": "tiny", "slots": 4}
    assert manifest["artifact_schemas"]["serving_metrics"] == "serving-metrics/v13"
    assert manifest["artifact_schemas"]["train_metrics"] == "train-metrics/v1"
    # config objects that are not JSON-encodable degrade to repr, never raise
    weird = build_run_manifest(config={"fn": open})  # a builtin is unencodable
    json.dumps(weird)


# ------------------------------------------------------ obs_report end-to-end


def test_obs_report_end_to_end_smoke(tmp_path, capsys):
    """Fast-tier smoke: a tiny telemetry-on engine drain + fit run produce
    real artifacts, and obs_report renders the phase table from all of them
    without error (the docs/observability.md workflow, end to end)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "obs_report_under_test",
        os.path.join(os.path.dirname(__file__), "..", "scripts", "obs_report.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    report_main = mod.main

    # engine side: trace + serving-metrics JSONL
    model, params = _make_model()
    trace_path = str(tmp_path / "engine_trace.json")
    metrics_path = str(tmp_path / "serving.jsonl")
    engine = ServingEngine(model, params, num_slots=2, telemetry=trace_path,
                           metrics_jsonl=metrics_path)
    for i, prompt in enumerate([[5, 6, 7], [9, 8]]):
        engine.submit(prompt, max_new_tokens=2, rng=jax.random.PRNGKey(i))
    engine.run_until_drained(max_steps=50)
    engine.metrics.write_snapshot()
    engine.close()  # owns the recorder (path knob): writes the trace

    # training side: train-metrics stream
    train_metrics = str(tmp_path / "train.jsonl")
    _fit_loss_trajectory(False, metrics_path=train_metrics)

    report = report_main([
        "--trace", trace_path,
        "--serving-metrics", metrics_path,
        "--train-metrics", train_metrics,
    ])
    out = capsys.readouterr().out
    assert "phase breakdown" in out and "serving.tick" in out
    assert report["traces"][0]["validation_problems"] == []
    assert report["traces"][0]["phases"]["serving.tick"]["count"] > 0
    assert report["serving_metrics"][0]["last_snapshot"]["requests_finished"] == 2
    assert report["train_metrics"][0]["train_log_windows"] > 0
