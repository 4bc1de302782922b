"""Failure-domain hardening tests (docs/reliability.md): the fault-injection
harness itself (determinism, env arming, no-fault inertness), transient-IO
retry, crash-safe checkpoint lineage with fallback restore (sync + async
writer paths, corrupt + kill-mid-write), SIGTERM preemption with exact resume,
skip_nonfinite_updates f64 parity, and serving admission control (queue bound,
deadlines, NaN containment, drain) with f64 survivor parity."""

import json
import os
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from perceiver_io_tpu.data.loader import DataLoader
from perceiver_io_tpu.data.prefetch import DevicePrefetcher
from perceiver_io_tpu.reliability import (
    FAULTS,
    KilledMidWrite,
    RetryError,
    RetryPolicy,
    TransientIOError,
    armed,
    retry_call,
)
from perceiver_io_tpu.reliability.faults import FAULT_ENV, corrupt_checkpoint_dir, poison_batch
from perceiver_io_tpu.training.checkpoint import (
    AsyncCheckpointWriter,
    CheckpointCorruptError,
    restore_latest_valid,
    save_checkpoint_lineage,
    verify_checkpoint,
)
from perceiver_io_tpu.training.fit import Trainer, TrainerConfig
from perceiver_io_tpu.training.trainer import TrainState, _finalize_step


@pytest.fixture(autouse=True)
def _fault_isolation():
    """No arming may leak between tests (the registry is process-global)."""
    FAULTS.reset()
    yield
    FAULTS.reset()


# ------------------------------------------------------------------ retry unit


def test_retry_absorbs_transients_deterministically_and_preserves_chain():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise TransientIOError(f"attempt {calls['n']}")
        return "ok"

    delays = []
    assert retry_call(flaky, policy=RetryPolicy(attempts=3), sleep=delays.append) == "ok"
    assert calls["n"] == 3 and len(delays) == 2
    assert delays[1] > delays[0] > 0  # exponential growth survives the jitter

    # the jitter schedule is deterministic: a second identical sequence sleeps
    # exactly the same amounts (reliability/retry.py seeds per call)
    calls["n"] = 0
    delays2 = []
    retry_call(flaky, policy=RetryPolicy(attempts=3), sleep=delays2.append)
    assert delays2 == delays

    # exhaustion raises RetryError FROM the last failure (chain preserved)
    def always(): raise TransientIOError("persistent")
    with pytest.raises(RetryError, match="after 2 attempts") as ei:
        retry_call(always, policy=RetryPolicy(attempts=2, base_delay_s=0.0), sleep=lambda _: None)
    assert isinstance(ei.value.__cause__, TransientIOError)

    # non-retryable errors propagate immediately, uncounted
    def broken(): raise ValueError("bug")
    with pytest.raises(ValueError, match="bug"):
        retry_call(broken, policy=RetryPolicy(attempts=5), sleep=lambda _: None)


# ----------------------------------------------------------- fault registry


def test_fault_registry_counters_are_deterministic():
    spec = FAULTS.arm("loader.fetch.flaky", after=2, times=2)
    pattern = [FAULTS.fire("loader.fetch.flaky") is not None for _ in range(6)]
    assert pattern == [False, False, True, True, False, False]  # after=2, times=2
    assert spec.hits == 6 and spec.fired == 2
    FAULTS.disarm("loader.fetch.flaky")
    assert FAULTS.fire("loader.fetch.flaky") is None
    with pytest.raises(ValueError, match="unknown fault point"):
        FAULTS.arm("no.such.point")


def test_fault_env_arming(monkeypatch):
    monkeypatch.setenv(FAULT_ENV, "batch.nan:after=1,times=3;serving.nan:slot=1,times=inf")
    FAULTS.reset()  # re-read env on next fire
    assert FAULTS.fire("batch.nan") is None  # after=1: first hit skipped
    assert FAULTS.fire("batch.nan") is not None
    spec = FAULTS.fire("serving.nan")
    assert spec is not None and spec.slot == 1 and spec.times is None

    monkeypatch.setenv(FAULT_ENV, "definitely.not.a.point:times=1")
    FAULTS.reset()
    with pytest.raises(ValueError, match="unknown fault point"):
        FAULTS.fire("batch.nan")
    monkeypatch.delenv(FAULT_ENV)
    FAULTS.reset()


def test_no_fault_armed_is_inert():
    """The inertness pin: with nothing armed, every hook is a pass-through —
    poison_batch returns the SAME object (not a copy), fire() is None at every
    point, and an engine built with reliability knobs engaged serves exactly
    as before (the f64 parity suites in test_serving/test_prefetch run
    THROUGH these hooks and pin the numerics)."""
    batch = {"x": np.ones((2, 3), np.float32)}
    assert poison_batch(batch) is batch
    from perceiver_io_tpu.reliability.faults import POINTS

    assert all(FAULTS.fire(p) is None for p in POINTS)
    assert FAULTS.armed_points() == []


# ------------------------------------------------------------- loader faults


def _float_loader(n=12, batch_size=2, seed=3):
    rs = np.random.RandomState(seed)
    examples = [rs.randn(4).astype(np.float32) for _ in range(n)]
    return DataLoader(examples, batch_size, collate_fn=lambda ex: {"x": np.stack(ex)},
                      shuffle=True, rng=np.random.default_rng(seed))


def test_prefetcher_retries_flaky_fetch_and_surfaces_persistent_failure():
    expected = [np.asarray(b["x"]).tolist() for b in _float_loader()]
    with armed("loader.fetch.flaky", times=2):  # two transient failures
        got = [np.asarray(b["x"]).tolist() for b in DevicePrefetcher(_float_loader(), depth=2)]
    assert got == expected  # absorbed: nothing skipped, nothing repeated

    with armed("loader.fetch.flaky", times=None):  # persistent: must surface
        with pytest.raises(RetryError):
            list(DevicePrefetcher(_float_loader(), depth=2))


# --------------------------------------------------- skip_nonfinite_updates


def _regression_step(skip):
    tx = optax.adamw(1e-2)

    def step(state, batch):
        def loss_fn(p):
            loss = jnp.mean((batch["x"] @ p["w"]) ** 2)
            return loss, {"loss": loss}

        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(state.params)
        return _finalize_step(state, tx, grads, loss, metrics, skip)

    return tx, jax.jit(step)


def test_skip_nonfinite_f64_parity_and_poisoned_step_skipped(x64):
    """Knob ON with finite data is BITWISE identical to knob OFF (f64-pinned);
    a batch.nan-poisoned step is skipped (params/opt state kept, step/rng
    stream advanced, skip counted) and the run continues finite — while the
    unguarded arm proves the same poison destroys the params."""
    rs = np.random.RandomState(0)
    batches = [{"x": jnp.asarray(rs.randn(2, 4))} for _ in range(5)]

    def run(skip, poison_at=None):
        tx, step = _regression_step(skip)
        state = TrainState.create({"w": jnp.ones((4,), jnp.float64)}, tx)
        losses, skipped = [], 0.0
        for i, b in enumerate(batches):
            if poison_at == i:
                b = jax.tree.map(lambda x: jnp.full_like(x, jnp.nan), b)
            state, m = step(state, b)
            losses.append(float(m["loss"]))
            skipped += float(m.get("skipped_nonfinite", 0.0))
        return state, losses, skipped

    s_off, losses_off, _ = run(skip=False)
    s_on, losses_on, skipped = run(skip=True)
    assert losses_on == losses_off  # bitwise in f64
    np.testing.assert_array_equal(np.asarray(s_on.params["w"]), np.asarray(s_off.params["w"]))
    assert skipped == 0.0

    s_poison, losses_p, skipped_p = run(skip=True, poison_at=2)
    assert skipped_p == 1.0 and np.isnan(losses_p[2])
    assert np.isfinite(losses_p[3]) and np.isfinite(losses_p[4])  # run survives
    assert np.isfinite(np.asarray(s_poison.params["w"])).all()
    assert int(s_poison.step) == 5  # the skipped step still advances the rng stream

    s_unguarded, losses_u, _ = run(skip=False, poison_at=2)
    assert np.isnan(np.asarray(s_unguarded.params["w"])).any()  # poison is real


def test_fit_loop_poison_hook_with_skip_enabled():
    """End-to-end through Trainer.fit: the batch.nan fault point fires inside
    the hot loop, the guarded step skips it, and the logged window metrics
    carry the skipped_nonfinite count."""
    tx, _ = _regression_step(True)

    def train_step(state, batch):
        def loss_fn(p):
            loss = jnp.mean((batch["x"] @ p["w"]) ** 2)
            return loss, {"loss": loss}

        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(state.params)
        return _finalize_step(state, tx, grads, loss, metrics, True)

    lines = []
    trainer = Trainer(
        TrainerConfig(max_steps=6, log_every=1, eval_every=10_000, prefetch_depth=2),
        log_fn=lambda line: lines.append(json.loads(line)),
    )
    with armed("batch.nan", after=2, times=1):
        state = trainer.fit(
            TrainState.create({"w": jnp.ones((4,), jnp.float32)}, tx),
            train_step, lambda: _float_loader(),
        )
    assert sum(l.get("skipped_nonfinite", 0) for l in lines) == 1
    assert np.isfinite(np.asarray(state.params["w"])).all()


# ------------------------------------------------------- checkpoint lineage


def _mk_state(step):
    tx = optax.sgd(1e-2)
    return TrainState.create({"w": jnp.arange(4.0) + step}, tx).replace(
        step=jnp.asarray(step, jnp.int32)
    )


def test_manifest_verify_detects_corruption_and_restore_falls_back(tmp_path):
    """Sync-path acceptance: corrupt the newest checkpoint -> verify raises,
    restore_latest_valid falls back to the rotated previous generation with
    its iterator snapshot, and records what it skipped."""
    d = str(tmp_path)
    last = os.path.join(d, "last")
    save_checkpoint_lineage(last, _mk_state(2), step=2,
                            aux_files={os.path.join(d, "last_iterator.json"): {"batches_consumed": 2}})
    save_checkpoint_lineage(last, _mk_state(4), step=4,
                            aux_files={os.path.join(d, "last_iterator.json"): {"batches_consumed": 4}})
    # both generations on disk, both manifest-valid
    assert verify_checkpoint(last)["step"] == 4
    assert verify_checkpoint(os.path.join(d, "last.prev"))["step"] == 2
    state, info = restore_latest_valid(d, _mk_state(0))
    assert int(state.step) == 4 and info["name"] == "last" and info["validated"] == "manifest"
    with open(info["iterator_path"]) as f:
        assert json.load(f)["batches_consumed"] == 4

    corrupt_checkpoint_dir(last)
    with pytest.raises(CheckpointCorruptError):
        verify_checkpoint(last)
    state, info = restore_latest_valid(d, _mk_state(0))
    assert int(state.step) == 2 and info["name"] == "last.prev"
    assert info["skipped"] and "last" in info["skipped"][0]
    with open(info["iterator_path"]) as f:
        assert json.load(f)["batches_consumed"] == 2  # iterator tracks the fallback

    # nothing valid at all -> loud failure, not a silent cold start
    corrupt_checkpoint_dir(os.path.join(d, "last.prev"))
    os.remove(os.path.join(d, "last.manifest.json"))
    os.remove(os.path.join(d, "last.prev.manifest.json"))
    corrupt_checkpoint_dir(last)  # ensure the weak path cannot load it either
    with pytest.raises(CheckpointCorruptError, match="no valid checkpoint"):
        restore_latest_valid(d, _mk_state(0))


def test_async_writer_lineage_corrupt_newest_falls_back(tmp_path):
    """Async-path acceptance: the same fallback contract holds when the
    generations were written by the AsyncCheckpointWriter thread."""
    d = str(tmp_path)
    last = os.path.join(d, "last")
    writer = AsyncCheckpointWriter()
    writer.submit(last, _mk_state(2), lineage=True, step=2)
    writer.wait()  # generation 2 fully committed before 4 begins
    writer.submit(last, _mk_state(4), lineage=True, step=4)
    writer.close()
    corrupt_checkpoint_dir(last)
    state, info = restore_latest_valid(d, _mk_state(0))
    assert int(state.step) == 2 and info["name"] == "last.prev" and info["validated"] == "manifest"


def test_kill_mid_write_leaves_restorable_ancestor(tmp_path):
    """checkpoint.write.kill: the save dies after rotation with a partial
    destination on disk (exactly a preemption mid-orbax-flush); restore falls
    back past the partial dir to the rotated valid generation."""
    d = str(tmp_path)
    last = os.path.join(d, "last")
    save_checkpoint_lineage(last, _mk_state(2), step=2)
    with armed("checkpoint.write.kill"):
        with pytest.raises(KilledMidWrite):
            save_checkpoint_lineage(last, _mk_state(4), step=4)
    assert os.path.isdir(last)  # the partial destination exists...
    state, info = restore_latest_valid(d, _mk_state(0))
    assert int(state.step) == 2 and info["name"] == "last.prev"  # ...and is skipped


def test_partial_generation_never_rotates_over_valid_ancestor(tmp_path):
    """Second-failure safety: after a kill left a partial manifest-less
    ``last`` next to a valid ``last.prev``, the NEXT save must not rotate the
    partial over the ancestor (that would rmtree the only restorable
    checkpoint for the whole serialization window) — the partial is dropped,
    the ancestor stays, and a kill during the new save still falls back to
    it."""
    d = str(tmp_path)
    last = os.path.join(d, "last")
    save_checkpoint_lineage(last, _mk_state(2), step=2)
    with armed("checkpoint.write.kill"):
        with pytest.raises(KilledMidWrite):
            save_checkpoint_lineage(last, _mk_state(4), step=4)  # partial last + valid .prev
    # the next save is ALSO killed — the worst case the rotation must survive
    with armed("checkpoint.write.kill"):
        with pytest.raises(KilledMidWrite):
            save_checkpoint_lineage(last, _mk_state(6), step=6)
    state, info = restore_latest_valid(d, _mk_state(0))
    assert int(state.step) == 2 and info["name"] == "last.prev"  # ancestor survived both
    assert verify_checkpoint(os.path.join(d, "last.prev"))["step"] == 2
    # and once a save completes, normal rotation resumes
    save_checkpoint_lineage(last, _mk_state(8), step=8)
    assert verify_checkpoint(last)["step"] == 8


def test_mid_rotation_kill_never_deletes_the_only_data(tmp_path):
    """A kill between the manifest rename and the data rename leaves the
    manifest under the .prev name while the complete data still sits at
    ``last``. The next save must not mistake that for a partial-over-ancestor
    case and delete the only data copy: the data survives (weakly
    restorable) even when the next save is itself killed."""
    d = str(tmp_path)
    last = os.path.join(d, "last")
    save_checkpoint_lineage(last, _mk_state(2), step=2)
    # emulate the mid-rotation kill window
    os.replace(last + ".manifest.json", last + ".prev.manifest.json")
    with armed("checkpoint.write.kill"):
        with pytest.raises(KilledMidWrite):
            save_checkpoint_lineage(last, _mk_state(4), step=4)
    state, info = restore_latest_valid(d, _mk_state(0))
    assert int(state.step) == 2  # gen-2 data survived the whole sequence
    assert info["name"] == "last.prev" and info["validated"] == "restore-only"


def test_async_writer_retries_flaky_serialization(tmp_path):
    """checkpoint.write.flaky: transient serialization failures are absorbed
    by the writer's retry policy — the save lands, nothing surfaces — and the
    retry replays ONLY the commit stage: the rotated ``.prev`` ancestor must
    survive the retried attempts with its manifest intact (a retried rotation
    would have destroyed it)."""
    d = str(tmp_path)
    last = os.path.join(d, "last")
    save_checkpoint_lineage(last, _mk_state(2), step=2)  # the ancestor generation
    writer = AsyncCheckpointWriter(retry_policy=RetryPolicy(attempts=3, base_delay_s=0.0))
    with armed("checkpoint.write.flaky", times=2):
        writer.submit(last, _mk_state(3), lineage=True, step=3)
        writer.close()  # re-raises on failure; must NOT raise here
    state, info = restore_latest_valid(d, _mk_state(0))
    assert int(state.step) == 3 and info["validated"] == "manifest"
    assert verify_checkpoint(os.path.join(d, "last.prev"))["step"] == 2  # ancestor intact


def test_torn_manifest_with_intact_data_still_restores(tmp_path):
    """A corrupt manifest SIDECAR (data fine) must not brick restore: the
    candidate falls through to restore-only validation instead of failing
    manifest verification forever."""
    d = str(tmp_path)
    last = os.path.join(d, "last")
    save_checkpoint_lineage(last, _mk_state(7), step=7)
    with open(last + ".manifest.json", "w") as f:
        f.write('{"schema": "ckpt-manifest/v1", "step": 7, "lea')  # torn mid-write
    state, info = restore_latest_valid(d, _mk_state(0))
    assert int(state.step) == 7 and info["validated"] == "restore-only"


# ------------------------------------------------------ SIGTERM preemption


def _id_loader(n=60, batch_size=2, seed=5):
    return DataLoader(list(range(n)), batch_size,
                      collate_fn=lambda ex: {"ids": np.asarray(ex, np.int64)},
                      shuffle=True, rng=np.random.default_rng(seed))


def _id_setup():
    tx = optax.sgd(1e-2)
    make_params = lambda: {"w": jnp.zeros((4,), jnp.float32)}  # noqa: E731

    def train_step(state, batch):
        grads = jax.tree.map(jnp.zeros_like, state.params)
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        return (
            state.replace(step=state.step + 1, params=params, opt_state=opt_state),
            {"loss": jnp.float32(0.0), "first_id": batch["ids"][0].astype(jnp.float32)},
        )

    return make_params, tx, train_step


def test_sigterm_mid_fit_clean_exit_and_exact_resume(tmp_path):
    """Acceptance: SIGTERM mid-fit (batches in flight on the prefetcher) stops
    the loop gracefully — the writer drains, the prefetcher joins, a final
    synchronous lineage checkpoint lands — fit RETURNS (no exception), and a
    resume from that checkpoint replays exactly the batches an uninterrupted
    run would have seen. The handler is once-only: after it fires, and again
    after fit exits, the process's previous handlers are back."""
    make_params, tx, train_step = _id_setup()
    prev_term = signal.getsignal(signal.SIGTERM)

    def run(loader, cfg, state, preempt_at=None):
        ids = []

        def log_fn(line):
            rec = json.loads(line)
            if "first_id" in rec:
                ids.append(int(rec["first_id"]))
                if preempt_at is not None and rec["step"] == preempt_at:
                    # delivered to the main thread mid-loop, like a real
                    # preemption notice — deterministic at step boundaries
                    signal.raise_signal(signal.SIGTERM)
        trainer = Trainer(cfg, log_fn=log_fn)
        trainer.fit(state, train_step, lambda: loader)
        return ids, trainer

    full_ids, _ = run(
        _id_loader(),
        TrainerConfig(max_steps=12, log_every=1, eval_every=10_000, prefetch_depth=3),
        TrainState.create(make_params(), tx),
    )

    d = str(tmp_path)
    killed_ids, trainer = run(
        _id_loader(),
        TrainerConfig(max_steps=12, log_every=1, eval_every=10_000, prefetch_depth=3,
                      checkpoint_dir=d, checkpoint_every=100),  # only the final save
        TrainState.create(make_params(), tx),
        preempt_at=5,
    )
    assert trainer.preempted and killed_ids == full_ids[:5]
    assert signal.getsignal(signal.SIGTERM) == prev_term  # once-only + restored
    import threading
    assert not any(t.name.startswith("perceiver-") for t in threading.enumerate())

    state, info = Trainer.restore_latest_valid(d, TrainState.create(make_params(), tx))
    assert int(state.step) == 5 and info["validated"] == "manifest"
    resumed_loader = _id_loader()
    Trainer.restore_iterator(info["iterator_path"], resumed_loader)
    resumed_ids, _ = run(
        resumed_loader,
        TrainerConfig(max_steps=12, log_every=1, eval_every=10_000, prefetch_depth=3),
        state,
    )
    assert resumed_ids == full_ids[5:]  # exact: nothing skipped, nothing repeated


# --------------------------------------------------- serving admission control


def _serving_model(param_dtype=jnp.float32):
    from perceiver_io_tpu.models.core.config import CausalSequenceModelConfig
    from perceiver_io_tpu.models.core.perceiver_ar import CausalSequenceModel

    config = CausalSequenceModelConfig(
        vocab_size=262, max_seq_len=12, max_latents=6, num_channels=16,
        num_heads=2, num_self_attention_layers=2, cross_attention_dropout=0.0,
    )
    model = CausalSequenceModel(config=config, param_dtype=param_dtype)
    rng = jax.random.PRNGKey(0)
    prompt = jax.random.randint(rng, (1, 8), 0, 262)
    params = jax.jit(model.init, static_argnames="prefix_len")(rng, prompt, prefix_len=2)
    return model, params


def test_queue_bound_rejection_and_backpressure_counters():
    from perceiver_io_tpu.serving import RequestStatus, ServingEngine

    model, params = _serving_model()
    engine = ServingEngine(model, params, num_slots=1, max_queue_depth=1)
    running = engine.submit([1, 2], max_new_tokens=3)
    engine.step()  # occupies the only slot
    queued = engine.submit([3, 4], max_new_tokens=2)
    rejected = engine.submit([5, 6], max_new_tokens=2)  # queue at its bound
    assert rejected.status is RequestStatus.REJECTED and rejected.done and not rejected.ok
    assert rejected.finish_reason == "queue_full"
    drained = engine.run_until_drained(max_steps=100)
    assert running.ok and queued.ok
    assert rejected in drained  # one terminal handle per submit
    snap = engine.metrics.snapshot()
    assert snap["rejected"] == 1 and snap["queue_depth"] == 0
    assert snap["requests_finished"] == 2


def test_queue_bound_counts_free_slots_for_idle_bursts():
    """The bound limits backlog BEYOND free slot capacity: a burst into an
    idle engine is absorbed by the free slots first — even max_queue_depth=0
    accepts num_slots requests between ticks."""
    from perceiver_io_tpu.serving import ServingEngine

    model, params = _serving_model()
    engine = ServingEngine(model, params, num_slots=2, max_queue_depth=0)
    burst = [engine.submit([1, 2], max_new_tokens=2) for _ in range(3)]
    assert [h.ok or not h.done for h in burst] == [True, True, False]  # 2 slots' worth accepted
    assert burst[2].finish_reason == "queue_full"
    engine.run_until_drained(max_steps=50)
    assert burst[0].ok and burst[1].ok

    engine2 = ServingEngine(model, params, num_slots=2, max_queue_depth=1)
    burst2 = [engine2.submit([1, 2], max_new_tokens=2) for _ in range(4)]
    assert [not h.done for h in burst2] == [True, True, True, False]  # slots + 1 queued
    engine2.run_until_drained(max_steps=50)
    assert all(h.ok for h in burst2[:3])


def test_drain_finishes_active_rejects_backlog_and_closes_admission():
    from perceiver_io_tpu.serving import ServingEngine

    model, params = _serving_model()
    engine = ServingEngine(model, params, num_slots=1)
    active = engine.submit([1, 2], max_new_tokens=4)
    engine.step()
    backlog = engine.submit([3, 4], max_new_tokens=2)
    drained = engine.drain(max_steps=100)
    assert active.ok and len(active.output_ids) == 4  # in-flight work finished
    assert backlog.finish_reason == "draining" and not backlog.ok
    assert {h.request_id for h in drained} == {active.request_id, backlog.request_id}
    post = engine.submit([5, 6], max_new_tokens=2)
    assert post.finish_reason == "draining"  # admission stays closed


def test_deadline_eviction_and_survivor_parity(x64):
    """Acceptance: a deadline-expired request is evicted TIMED_OUT at a tick
    boundary with its partial output intact, and the surviving slot-mate's
    tokens are f64 token-identical to a fault-free run — eviction must not
    perturb the pool."""
    from perceiver_io_tpu.serving import RequestStatus, ServingEngine

    model, params = _serving_model(param_dtype=jnp.float64)
    reference = ServingEngine(model, params, num_slots=2)
    ref = reference.submit([40, 41, 42], max_new_tokens=6)
    reference.run_until_drained(max_steps=100)

    engine = ServingEngine(model, params, num_slots=2)
    doomed = engine.submit([7, 3, 9], max_new_tokens=50, deadline_s=0.05)
    survivor = engine.submit([40, 41, 42], max_new_tokens=6)
    with armed("serving.deadline", times=1, value=0.1):  # deterministic overrun
        engine.run_until_drained(max_steps=200)
    assert doomed.status is RequestStatus.TIMED_OUT and doomed.finish_reason == "deadline"
    assert len(doomed.output_ids) < 50  # expired mid-decode, partial output kept
    assert survivor.ok
    assert survivor.result().tolist() == ref.result().tolist()
    snap = engine.metrics.snapshot()
    assert snap["timed_out"] == 1 and snap["requests_finished"] == 1

    # queued expiry: a deadline that lapses before any slot frees never costs
    # a prefill and is reported the same way
    engine2 = ServingEngine(model, params, num_slots=1)
    blocker = engine2.submit([1, 2], max_new_tokens=8)
    engine2.step()
    lapsed = engine2.submit([3, 4], max_new_tokens=2, deadline_s=0.0)
    engine2.run_until_drained(max_steps=100)
    assert lapsed.status is RequestStatus.TIMED_OUT and lapsed.output_ids == []
    assert blocker.ok and len(blocker.output_ids) == 8


def test_nan_containment_failed_eviction_and_survivor_parity(x64):
    """Acceptance: poisoned logits evict exactly the poisoned slot as FAILED
    (its garbage token never emitted, its pool rows zeroed), while the
    surviving slot-mate's tokens stay f64 token-identical to an unpoisoned
    run — and the default deadline knob composes with containment."""
    from perceiver_io_tpu.serving import RequestStatus, ServingEngine

    model, params = _serving_model(param_dtype=jnp.float64)
    reference = ServingEngine(model, params, num_slots=2)
    ref = reference.submit([40, 41, 42], max_new_tokens=6)
    reference.run_until_drained(max_steps=100)

    engine = ServingEngine(model, params, num_slots=2, default_deadline_s=120.0)
    poisoned = engine.submit([7, 3, 9], max_new_tokens=10)
    survivor = engine.submit([40, 41, 42], max_new_tokens=6)
    engine.step()  # both admitted, one clean token each
    tokens_before = len(poisoned.output_ids)
    with armed("serving.nan", slot=poisoned.slot):
        engine.step()  # the poisoned tick
    engine.run_until_drained(max_steps=100)

    assert poisoned.status is RequestStatus.FAILED
    assert poisoned.finish_reason == "nonfinite_logits"
    assert len(poisoned.output_ids) == tokens_before  # garbage token not emitted
    assert survivor.ok and survivor.result().tolist() == ref.result().tolist()
    # quarantine: nothing non-finite survives anywhere in the pool
    assert np.isfinite(np.asarray(engine._state.next_hidden)).all()
    assert np.isfinite(np.asarray(engine._cache.ca.kp)).all()
    assert np.isfinite(np.asarray(engine._cache.ca.vp)).all()
    snap = engine.metrics.snapshot()
    assert snap["failed"] == 1 and snap["requests_finished"] == 1
    # useful-tokens accounting: the quarantined slot's garbage sample is not
    # counted, so the snapshot agrees with what the handles actually received
    assert snap["tokens_generated"] == len(poisoned.output_ids) + len(survivor.output_ids)
    # containment must not have recompiled anything
    assert engine.decode_compilations == 1


def test_metrics_v3_reader_normalizes_older_snapshots(tmp_path):
    """v3 snapshots round-trip; v2 (and v1) snapshots are normalized with
    None for the counters their writers did not record."""
    from perceiver_io_tpu.serving import EngineMetrics, load_metrics_jsonl
    from perceiver_io_tpu.serving.metrics import SCHEMA

    assert SCHEMA == "serving-metrics/v13"
    path = tmp_path / "v3.jsonl"
    m = EngineMetrics(num_slots=2, jsonl_path=str(path))
    m.record_submit(0, prompt_len=3)
    m.record_reject(0, reason="queue_full")
    m.record_submit(1, prompt_len=2)
    m.record_admit(1, slot=0, wait_s=0.1, prefill_s=0.01, bucket=8)
    m.record_finish(1, slot=0, new_tokens=0, reason="deadline", status="timed_out")
    m.write_snapshot()
    m.close()
    got = load_metrics_jsonl(str(path))
    snap = got["snapshots"][0]
    assert snap["rejected"] == 1 and snap["timed_out"] == 1 and snap["failed"] == 0
    assert snap["queue_depth"] == 0
    events = {e["event"] for e in got["events"]}
    assert "reject" in events
    finishes = [e for e in got["events"] if e["event"] == "finish"]
    assert finishes[0]["status"] == "timed_out"

    v2 = tmp_path / "v2.jsonl"
    v2.write_text(json.dumps({
        "event": "snapshot", "ts": 1.0, "schema": "serving-metrics/v2",
        "num_slots": 2, "tokens_generated": 5, "queue_depth": 0,
        "queue_wait_s": {"mean": 0.1, "max": 0.2, "p50": 0.1, "p95": 0.2},
        "prefill_s": {"mean": 0.0, "max": 0.0, "p50": 0.0, "p95": 0.0},
        "decode_step_s": {"mean": 0.0, "max": 0.0, "p50": 0.0, "p95": 0.0},
    }) + "\n")
    snap2 = load_metrics_jsonl(str(v2))["snapshots"][0]
    assert snap2["rejected"] is None and snap2["timed_out"] is None and snap2["failed"] is None
    # pre-v4 snapshots also get None (not 0) for the multi-replica counters
    assert snap2["failovers"] is None and snap2["shed_infeasible"] is None
    assert snap["failovers"] == 0 and snap["breaker_transitions"] == {}  # v4 engine: real zeros


# ------------------------------------------------------------- chaos driver


def _load_chaos():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chaos_check_under_test",
        os.path.join(os.path.dirname(__file__), "..", "scripts", "chaos_check.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# the journal group (and the chunked-prefill recovery + migration-window
# crash scenarios, which ride the same subprocess kill harness, plus the
# rolling-restart scenario's two full fleet drains, plus the process-replica
# scenarios that spawn REAL worker processes) runs in its own tests
# below — real subprocess kills and four compaction recovery cycles blow the
# 120s per-test alarm budget when stacked on the rest of the matrix;
# together the tests cover every scenario
_JOURNAL_CHECKS = ("journal_crash_restart", "journal_torn_tail",
                   "journal_compaction_crash", "chunked_prefill_recovery",
                   "migrate_crash_midflight", "rolling_restart_under_load",
                   "proc_replica_kill9", "transport_torn_frame")


def test_chaos_check_matrix_green(tmp_path):
    """Acceptance: the chaos matrix — every fault point armed in turn plus
    the no-fault inertness scenario — recovers per contract on CPU
    (imported, not subprocessed — the jax import tax is already paid). The
    journal scenarios run in their own tests; the split is asserted closed,
    so a new scenario cannot silently fall out of CI coverage."""
    mod = _load_chaos()
    names = [n for n in mod.CHECKS if n not in _JOURNAL_CHECKS]
    assert set(names) | set(_JOURNAL_CHECKS) == set(mod.CHECKS)
    out = tmp_path / "CHAOS_CHECK.json"
    result = mod.main(["--checks", ",".join(names), "--out", str(out)])
    assert result["all_ok"], {k: v for k, v in result["checks"].items() if not v["ok"]}
    assert set(result["checks"]) == set(names)  # every non-journal scenario ran
    on_disk = json.loads(out.read_text())
    assert on_disk["all_ok"] is True


def test_chaos_journal_torn_tail_and_compaction_crash():
    """Journal chaos, in-process half (ISSUE 10): torn tails truncate and
    recover deterministically; compaction kills at both swap stages lose
    nothing."""
    mod = _load_chaos()
    result = mod.main(["--checks", "journal_torn_tail,journal_compaction_crash"])
    assert result["all_ok"], {k: v for k, v in result["checks"].items() if not v["ok"]}


def test_chaos_journal_crash_restart_real_sigkill():
    """Journal chaos, real-process half (ISSUE 10 acceptance): a child
    serving process SIGKILLed mid-tick is recovered by a fresh process —
    every accepted request completes f64 token-identical (greedy and
    sampled), zero extra compiled programs, repeat-run deterministic."""
    mod = _load_chaos()
    result = mod.main(["--checks", "journal_crash_restart"])
    assert result["all_ok"], result["checks"]["journal_crash_restart"]


def test_chaos_proc_replica_kill9_real_sigkill():
    """Process-replica chaos (ISSUE 20 acceptance): a REAL ``kill -9`` on an
    out-of-process worker mid-decode is healed by the supervisor through
    journal recovery — victim sessions f64 token-identical on the respawned
    worker with zero failovers, siblings bit-identical, the victim recovered
    exactly once, repeat-run deterministic."""
    mod = _load_chaos()
    result = mod.main(["--checks", "proc_replica_kill9"])
    check = result["checks"]["proc_replica_kill9"]
    assert result["all_ok"], check
    assert check["victim_recovered_exactly_once"]
    assert check["repeat_deterministic"]


def test_chaos_transport_torn_frame():
    """Transport chaos (ISSUE 20): a CRC-torn frame is NACKed without
    executing and absorbed by the retry schedule; a persistently torn channel
    exhausts retries, strikes the breaker, and fails sessions over — tokens
    identical in both arms (no corrupt state)."""
    mod = _load_chaos()
    result = mod.main(["--checks", "transport_torn_frame"])
    check = result["checks"]["transport_torn_frame"]
    assert result["all_ok"], check
    assert check["retries_single_tear"] >= 1
    assert check["persistent_tear_breaker_open"] == 1


def test_chaos_chunked_prefill_recovery_real_sigkill():
    """Chunked-prefill chaos (ISSUE 11): a child running the paged +
    chunked-prefill engine is SIGKILLed while a window-length prompt is
    still mid chunked-prefill; a fresh process recovers the half-prefilled
    session from its journaled accept alone, f64 token-identical to an
    uninterrupted dense run, decode still one compiled program."""
    mod = _load_chaos()
    result = mod.main(["--checks", "chunked_prefill_recovery"])
    check = result["checks"]["chunked_prefill_recovery"]
    assert result["all_ok"], check
    assert check["prefilling_at_kill"] > 0  # the kill really landed mid-chunk


def test_chaos_migrate_crash_midflight_real_sigkill():
    """Fleet-ops chaos (ISSUE 15 acceptance): a child ROUTER process
    self-SIGKILLs inside a planned migration's double-live window
    (destination accept durable, origin journal entry still live); fleet
    recovery dedupes by session id — every accepted session finishes
    exactly once, f64 token-identical (greedy + sampled), zero extra
    compiled programs, repeat-run deterministic."""
    mod = _load_chaos()
    result = mod.main(["--checks", "migrate_crash_midflight"])
    assert result["all_ok"], result["checks"]["migrate_crash_midflight"]


def test_chaos_rolling_restart_under_load():
    """Fleet-ops chaos (ISSUE 15 acceptance, kill-free): a journaled fleet
    takes a rolling restart under sustained load — every replica recycles,
    no breaker trips, every accepted session finishes exactly once f64
    token-identical to an undisturbed run, repeat-run deterministic."""
    mod = _load_chaos()
    result = mod.main(["--checks", "rolling_restart_under_load"])
    assert result["all_ok"], result["checks"]["rolling_restart_under_load"]
