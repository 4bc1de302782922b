"""Chaos smoke driver: arm each fault point, run a short fit + serve loop on
CPU, and assert the recovery invariants of docs/reliability.md.

This is the executable form of the reliability contract — CI runs it (via the
fast-tier pytest smoke in tests/test_reliability.py) so the failure paths are
exercised on every change, not just when production finds them:

  * ``no_fault_inert``     nothing armed: every request FINISHED, no reliability
                           counter moves, and a repeat run is token-identical
                           (the harness itself perturbs nothing)
  * ``flaky_loader``       transient fetch failures are absorbed by the retry
                           policy; training completes with finite loss
  * ``slow_loader``        injected fetch stalls land on the worker thread;
                           training completes
  * ``nan_batch_skip``     a NaN-poisoned batch is skipped by
                           ``skip_nonfinite_updates`` (params stay finite,
                           the skip is counted); the UNguarded arm proves the
                           poison is real (params go NaN)
  * ``checkpoint_kill``    a kill mid-flush of the newest checkpoint falls
                           back to the rotated previous generation
  * ``checkpoint_corrupt`` a torn write of the newest checkpoint fails
                           manifest validation and falls back
  * ``serving_deadline``   an injected tick stall expires a deadlined request
                           (TIMED_OUT); its slot-mate's tokens are identical
                           to a fault-free run
  * ``serving_nan``        poisoned logits evict exactly the poisoned slot
                           (FAILED); the survivor's tokens are identical to an
                           unpoisoned run
  * ``queue_bound``        submits past ``max_queue_depth`` are REJECTED with
                           backpressure counters; ``drain()`` finishes active
                           slots and refuses new work
  * ``paging_pool_exhaustion`` admissions past the KV page pool's capacity
                           head-block then shed deterministically as
                           queue_full (no crash, no request lost); survivors
                           are f64 token-identical to an uncontended run
  * ``preempt_storm``      low-priority long sessions saturate a small page
                           pool; a high-priority deadline request admits via
                           PREEMPTION the very next tick; victims resume and
                           finish f64-identical to an uncontended run;
                           repeat runs pin identical statuses, tokens, AND
                           victim identity; no request lost
  * ``preempt_disabled_inert`` PERCEIVER_IO_TPU_DISABLE_PREEMPTION=1 makes
                           the same priority-bearing workload bit-identical
                           to the pre-priority FIFO engine (plain queue_full
                           backpressure, zero preemptions)
  * ``journal_crash_restart`` a REAL child serving process SIGKILLed
                           mid-tick; a fresh process recovers every accepted
                           request from the write-ahead journal, f64
                           token-identical (greedy + sampled) to an
                           uninterrupted run, zero extra compiled programs
                           (scripts/journal_crash_harness.py)
  * ``journal_torn_tail``  a power loss mid-append leaves a half-written
                           journal record; recovery truncates at the torn
                           record, reports it, and replays everything before
                           it f64-identical
  * ``journal_compaction_crash`` a kill at either stage of a journal
                           compaction (before/after the atomic generation
                           rename) loses nothing — whichever generation is
                           durable recovers identically
  * ``prefix_fork_churn``  shared-prefix sessions fork the radix prefix
                           cache's pages under pool pressure — admitted,
                           preempted, resumed, and cache-evicted in one run;
                           every survivor is f64 token-identical to an
                           UNCACHED uncontended run, repeat runs pin
                           statuses/tokens/victim identity, and the drain
                           leaves the free list whole (no page leaked)
  * ``chunked_prefill_recovery`` a REAL child serving process SIGKILLed
                           while a window-length prompt is still MID
                           chunked-prefill; a fresh process recovers the
                           half-prefilled session from its journaled accept
                           alone, f64 token-identical to an uninterrupted
                           dense run (scripts/journal_crash_harness.py
                           --chunked)
  * ``ragged_tick_churn``  quarantine + priority preemption INSIDE the
                           fused ragged tick under page pressure: the
                           poisoned slot's buffered descriptor lanes drop
                           with it, survivors finish f64 token-identical
                           to the same engine running uncontended (ample
                           pool, no fault), repeat runs pin statuses/tokens/
                           victims, and the drain leaves the free list
                           whole and the tick buffers empty

  * ``rolling_restart_under_load`` (kill-free) a journaled 2-replica fleet
                           takes a rolling restart while requests keep
                           arriving: every replica recycles (sessions
                           migrated to siblings, engines journal-recovered
                           fresh), zero breaker transitions, and every
                           accepted session finishes exactly once, f64
                           token-identical to an undisturbed run —
                           repeat-run deterministic
  * ``migrate_crash_midflight`` a REAL child router process SIGKILLs
                           ITSELF inside a planned migration's double-live
                           window (destination accept fsynced, origin close
                           record unwritten — ``router.migrate.kill``);
                           fleet recovery dedupes the twice-live session by
                           its fleet id and every accepted session finishes
                           exactly once, token-identically, decode still one
                           program (scripts/journal_crash_harness.py
                           migrate-proof)

Router group (docs/serving.md, multi-replica router; ``ServingRouter``):

  * ``router_crash_failover`` a replica crashed mid-decode loses nothing:
                           the victim's continuation is f64 token-identical
                           to the fault-free run after failover (prefill +
                           forced replay), survivors on healthy replicas are
                           bit-identical throughout, every request reaches a
                           terminal status
  * ``router_stall_breaker`` a stalled replica trips the slow-tick detector:
                           breaker CLOSED -> OPEN (requests failed over) ->
                           tick-counted cooldown -> HALF_OPEN probe ->
                           CLOSED; the recovered replica serves again
  * ``router_shed_overload`` under overload, a deadline the windowed latency
                           estimates say is infeasible is shed at admission
                           (REJECTED/shed_infeasible) instead of queueing
                           doomed work; feasible requests still complete
  * ``router_drain``       fleet drain rejects every backlog, finishes every
                           active slot, and keeps admission closed

Process-replica group (out-of-process workers; serving/transport.py,
``ServingRouter(replica_mode="process")``):

  * ``proc_replica_kill9`` a REAL ``kill -9`` lands on a worker process
                           mid-decode (``transport.worker.kill``); the
                           supervisor respawns it through journal recovery —
                           the victim's sessions finish f64 token-identical
                           on the NEW process with zero failovers, siblings
                           bit-identical, the victim recovered exactly once,
                           repeat-run deterministic
  * ``transport_torn_frame`` a CRC-torn RPC frame is NACKed by the worker
                           WITHOUT executing and absorbed by the retry
                           schedule (tokens identical, breakers closed); a
                           channel tearing EVERY frame exhausts retries,
                           the wedged worker is put down, the breaker
                           strikes, and sessions fail over — no corrupt
                           state either way

Every scenario is deterministic: fault firing is counter-based (no clocks, no
randomness — reliability/faults.py), model/workload seeds are fixed, so a
failure here reproduces exactly.

Usage: ``JAX_PLATFORMS=cpu python scripts/chaos_check.py [--checks a,b] [--out CHAOS_CHECK.json]``
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
from contextlib import contextmanager

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import jax
import jax.numpy as jnp
import numpy as np
import optax

from perceiver_io_tpu.reliability import armed
from perceiver_io_tpu.reliability.faults import FAULTS, KilledMidWrite


# --------------------------------------------------------------- tiny fixtures


def _serving_setup(param_dtype=None):
    """One tiny CausalSequenceModel shared by every serving check."""
    from perceiver_io_tpu.models.core.config import CausalSequenceModelConfig
    from perceiver_io_tpu.models.core.perceiver_ar import CausalSequenceModel

    config = CausalSequenceModelConfig(
        vocab_size=60, max_seq_len=12, max_latents=6, num_channels=16,
        num_heads=2, num_self_attention_layers=1, cross_attention_dropout=0.0,
    )
    kw = {} if param_dtype is None else {"param_dtype": param_dtype}
    model = CausalSequenceModel(config=config, **kw)
    rng = jax.random.PRNGKey(0)
    params = jax.jit(model.init, static_argnames="prefix_len")(
        rng, jax.random.randint(rng, (1, 8), 0, 60), prefix_len=2
    )
    return model, params


@contextmanager
def _x64():
    """Enable float64 for the duration of a parity-pinned scenario (the
    token-identity claims are only EXACT where float equality is exact)."""
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    try:
        yield
    finally:
        jax.config.update("jax_enable_x64", prev)


def _engine(model, params, **kwargs):
    from perceiver_io_tpu.serving import ServingEngine

    return ServingEngine(model, params, **kwargs)


def _loader(n=24, batch_size=2, seed=3):
    from perceiver_io_tpu.data.loader import DataLoader

    rs = np.random.RandomState(seed)
    examples = [rs.randn(4).astype(np.float32) for _ in range(n)]
    return DataLoader(
        examples, batch_size,
        collate_fn=lambda ex: {"x": np.stack(ex)},
        shuffle=True, rng=np.random.default_rng(seed),
    )


def _train_setup(skip_nonfinite: bool):
    """Tiny float-feature regression step (differentiable, poisonable by
    ``batch.nan``) driven through the REAL Trainer.fit loop."""
    from perceiver_io_tpu.training.trainer import TrainState, _finalize_step

    tx = optax.sgd(1e-2)

    def train_step(state, batch):
        def loss_fn(p):
            loss = jnp.mean((batch["x"] @ p["w"]) ** 2)
            return loss, {"loss": loss}

        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(state.params)
        return _finalize_step(state, tx, grads, loss, metrics, skip_nonfinite)

    make_state = lambda: TrainState.create({"w": jnp.ones((4,), jnp.float32)}, tx)  # noqa: E731
    return make_state, train_step


def _fit(train_step, make_state, steps=6, **cfg_kwargs):
    from perceiver_io_tpu.training.fit import Trainer, TrainerConfig

    lines = []
    trainer = Trainer(
        TrainerConfig(max_steps=steps, log_every=1, eval_every=10_000,
                      prefetch_depth=2, **cfg_kwargs),
        log_fn=lambda line: lines.append(json.loads(line)),
    )
    state = trainer.fit(make_state(), train_step, lambda: _loader())
    return state, lines


def _greedy_tokens(engine, prompts, max_new=5, **submit_kwargs):
    handles = [engine.submit(p, max_new_tokens=max_new, **submit_kwargs) for p in prompts]
    engine.run_until_drained(max_steps=200)
    return handles


# --------------------------------------------------------------------- checks


def check_no_fault_inert() -> dict:
    """Nothing armed: the reliability layer must be invisible — all requests
    FINISHED, zero reliability counters, repeat runs token-identical."""
    model, params = _serving_setup()

    def serve_once():
        engine = _engine(model, params, num_slots=2, max_queue_depth=8, default_deadline_s=60.0)
        handles = _greedy_tokens(engine, [[1, 2, 3], [4, 5], [6, 7, 8, 9]])
        snap = engine.metrics.snapshot()
        return [h.result().tolist() for h in handles], [h.status.value for h in handles], snap

    toks1, statuses, snap = serve_once()
    toks2, _, _ = serve_once()
    make_state, train_step = _train_setup(skip_nonfinite=True)
    state, lines = _fit(train_step, make_state)
    losses = [l["loss"] for l in lines if "loss" in l]
    return {
        "ok": (
            toks1 == toks2
            and all(s == "finished" for s in statuses)
            and snap["rejected"] == snap["timed_out"] == snap["failed"] == 0
            and len(losses) == 6
            and all(np.isfinite(losses))
            and not FAULTS.armed_points()
        ),
        "statuses": statuses,
        "repeat_identical": toks1 == toks2,
        "reliability_counters": {k: snap[k] for k in ("rejected", "timed_out", "failed")},
    }


def check_flaky_loader() -> dict:
    make_state, train_step = _train_setup(skip_nonfinite=False)
    with armed("loader.fetch.flaky", times=2):
        state, lines = _fit(train_step, make_state)
    losses = [l["loss"] for l in lines if "loss" in l]
    return {"ok": len(losses) == 6 and all(np.isfinite(losses)), "steps": len(losses)}


def check_slow_loader() -> dict:
    make_state, train_step = _train_setup(skip_nonfinite=False)
    with armed("loader.fetch.slow", times=3, value=0.05):
        state, lines = _fit(train_step, make_state)
    losses = [l["loss"] for l in lines if "loss" in l]
    return {"ok": len(losses) == 6 and all(np.isfinite(losses)), "steps": len(losses)}


def check_nan_batch_skip() -> dict:
    # guarded arm: the poisoned step is skipped, params stay finite
    make_state, train_step = _train_setup(skip_nonfinite=True)
    with armed("batch.nan", after=2, times=1):
        state, lines = _fit(train_step, make_state)
    skipped = sum(l.get("skipped_nonfinite", 0) for l in lines)
    guarded_finite = bool(np.isfinite(np.asarray(state.params["w"])).all())
    # unguarded arm: the same poison must destroy the run — proves injection
    make_state_u, train_step_u = _train_setup(skip_nonfinite=False)
    with armed("batch.nan", after=2, times=1):
        state_u, _ = _fit(train_step_u, make_state_u)
    unguarded_nan = bool(np.isnan(np.asarray(state_u.params["w"])).any())
    return {
        "ok": guarded_finite and skipped == 1 and unguarded_nan,
        "skipped_nonfinite": skipped,
        "unguarded_params_went_nan": unguarded_nan,
    }


def check_checkpoint_kill() -> dict:
    from perceiver_io_tpu.training.checkpoint import restore_latest_valid, save_checkpoint_lineage
    from perceiver_io_tpu.training.trainer import TrainState

    tx = optax.sgd(1e-2)
    mk = lambda s: TrainState.create({"w": jnp.arange(4.0) + s}, tx).replace(  # noqa: E731
        step=jnp.asarray(s, jnp.int32)
    )
    d = tempfile.mkdtemp(prefix="chaos-kill-")
    try:
        save_checkpoint_lineage(os.path.join(d, "last"), mk(2), step=2)
        killed = False
        try:
            with armed("checkpoint.write.kill"):
                save_checkpoint_lineage(os.path.join(d, "last"), mk(4), step=4)
        except KilledMidWrite:
            killed = True
        state, info = restore_latest_valid(d, mk(0))
        return {
            "ok": killed and int(state.step) == 2 and info["validated"] == "manifest",
            "restored": info["name"],
            "restored_step": int(state.step),
        }
    finally:
        shutil.rmtree(d, ignore_errors=True)


def check_checkpoint_corrupt() -> dict:
    from perceiver_io_tpu.training.checkpoint import restore_latest_valid, save_checkpoint_lineage
    from perceiver_io_tpu.training.trainer import TrainState

    tx = optax.sgd(1e-2)
    mk = lambda s: TrainState.create({"w": jnp.arange(4.0) + s}, tx).replace(  # noqa: E731
        step=jnp.asarray(s, jnp.int32)
    )
    d = tempfile.mkdtemp(prefix="chaos-corrupt-")
    try:
        save_checkpoint_lineage(os.path.join(d, "last"), mk(2), step=2)
        with armed("checkpoint.corrupt"):
            save_checkpoint_lineage(os.path.join(d, "last"), mk(4), step=4)
        state, info = restore_latest_valid(d, mk(0))
        return {
            "ok": int(state.step) == 2 and info["name"] == "last.prev" and bool(info["skipped"]),
            "restored": info["name"],
            "skipped": info["skipped"],
        }
    finally:
        shutil.rmtree(d, ignore_errors=True)


def check_serving_deadline() -> dict:
    model, params = _serving_setup()
    # fault-free reference for the survivor
    ref = _greedy_tokens(_engine(model, params, num_slots=2), [[4, 5, 6]])[0]
    engine = _engine(model, params, num_slots=2)
    doomed = engine.submit([1, 2, 3], max_new_tokens=50, deadline_s=0.05)
    survivor = engine.submit([4, 5, 6], max_new_tokens=5)
    with armed("serving.deadline", times=1, value=0.1):
        engine.run_until_drained(max_steps=200)
    snap = engine.metrics.snapshot()
    return {
        "ok": (
            doomed.status.value == "timed_out"
            and survivor.ok
            and survivor.result().tolist() == ref.result().tolist()
            and snap["timed_out"] == 1
        ),
        "doomed": doomed.status.value,
        "survivor_identical": survivor.result().tolist() == ref.result().tolist(),
    }


def check_serving_nan() -> dict:
    model, params = _serving_setup()
    ref = _greedy_tokens(_engine(model, params, num_slots=2), [[4, 5, 6]])[0]
    engine = _engine(model, params, num_slots=2)
    poisoned = engine.submit([1, 2, 3], max_new_tokens=6)
    survivor = engine.submit([4, 5, 6], max_new_tokens=5)
    engine.step()  # both admitted, one token decoded
    with armed("serving.nan", slot=poisoned.slot):
        engine.step()
    engine.run_until_drained(max_steps=100)
    snap = engine.metrics.snapshot()
    pool_finite = bool(np.isfinite(np.asarray(engine._state.next_hidden)).all())
    return {
        "ok": (
            poisoned.status.value == "failed"
            and survivor.ok
            and survivor.result().tolist() == ref.result().tolist()
            and snap["failed"] == 1
            and pool_finite
        ),
        "poisoned": poisoned.status.value,
        "survivor_identical": survivor.result().tolist() == ref.result().tolist(),
        "pool_finite_after_quarantine": pool_finite,
    }


def check_quant_quarantine() -> dict:
    """NaN containment on an int8-QUANTIZED page pool (docs/serving.md
    "Quantized KV pages & weight serving"): the poisoned slot is evicted
    FAILED, its pages' int8 BYTES *and* their per-page-per-head SCALE
    sidecars are zeroed before the pages return to the free list (a NaN that
    reached the quantizer lands in the scale, and dequant multiplies every
    byte of the page by it — zeroing bytes alone would leave the poison),
    and slot-mates decode on BIT-identical to an unpoisoned quantized run."""
    model, params = _serving_setup()
    kw = dict(num_slots=2, kv_page_size=4, kv_quant="int8")
    ref = _greedy_tokens(_engine(model, params, **kw), [[4, 5, 6]])[0]
    engine = _engine(model, params, **kw)
    poisoned = engine.submit(list(range(1, 10)), max_new_tokens=6)
    survivor = engine.submit([4, 5, 6], max_new_tokens=5)
    engine.step()  # both admitted, one token decoded
    condemned_pages = [p for p in (engine._slot_pages[poisoned.slot] or [])]
    with armed("serving.nan", slot=poisoned.slot):
        engine.step()
    engine.run_until_drained(max_steps=100)
    snap = engine.metrics.snapshot()
    ca = engine._cache.ca
    kp, vp = np.asarray(ca.kp), np.asarray(ca.vp)
    ks, vs = np.asarray(ca.k_scale), np.asarray(ca.v_scale)
    bytes_zeroed = bool((kp[condemned_pages] == 0).all()
                        and (vp[condemned_pages] == 0).all())
    scales_zeroed = bool((ks[condemned_pages] == 0).all()
                         and (vs[condemned_pages] == 0).all())
    scales_finite = bool(np.isfinite(ks).all() and np.isfinite(vs).all())
    return {
        "ok": (
            poisoned.status.value == "failed"
            and survivor.ok
            and survivor.result().tolist() == ref.result().tolist()
            and snap["failed"] == 1
            and snap["kv_quant"] is not None
            and bytes_zeroed and scales_zeroed and scales_finite
            and engine._pool.pages_in_use == 0
        ),
        "poisoned": poisoned.status.value,
        "survivor_identical": survivor.result().tolist() == ref.result().tolist(),
        "condemned_bytes_zeroed": bytes_zeroed,
        "condemned_scales_zeroed": scales_zeroed,
        "scales_finite": scales_finite,
    }


def check_queue_bound() -> dict:
    model, params = _serving_setup()
    engine = _engine(model, params, num_slots=1, max_queue_depth=1)
    running = engine.submit([1, 2], max_new_tokens=4)
    engine.step()  # occupies the only slot
    queued = engine.submit([3, 4], max_new_tokens=2)
    rejected = engine.submit([5, 6], max_new_tokens=2)  # past the bound
    drained = engine.drain(max_steps=100)
    post = engine.submit([7, 8], max_new_tokens=2)  # draining engines refuse work
    snap = engine.metrics.snapshot()
    return {
        "ok": (
            rejected.finish_reason == "queue_full"
            and running.ok
            and queued.finish_reason == "draining"
            and post.finish_reason == "draining"
            and snap["rejected"] == 3
            and snap["queue_depth"] == 0
            and len(drained) == 3  # running + queued-rejected + bound-rejected
        ),
        "reasons": [rejected.finish_reason, queued.finish_reason, post.finish_reason],
        "rejected_count": snap["rejected"],
    }


def check_paging_pool_exhaustion() -> dict:
    """Drive admissions past the KV page pool's capacity (docs/serving.md,
    paging section): overflow submits are DETERMINISTICALLY rejected as
    queue_full (backpressure, not a crash), a head-blocked request waits
    (alloc_failure counted) and admits once pages free, no request is lost,
    and every survivor's tokens are f64-identical to an uncontended run."""
    with _x64():
        model, params = _serving_setup(param_dtype=jnp.float64)
        prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [10, 11], [12, 13, 14], [15, 16]]

        def run(num_kv_pages, max_queue_depth):
            # page 2 over the 12-token window: every request here reserves 5
            # pages (bucket 6 + 4 new); 11 pages (10 allocatable) fit two
            # concurrent requests, the default pool fits everything
            engine = _engine(model, params, num_slots=3, kv_page_size=2,
                             num_kv_pages=num_kv_pages, max_queue_depth=max_queue_depth)
            handles = [engine.submit(p, max_new_tokens=4) for p in prompts]
            engine.run_until_drained(max_steps=300)
            snap = engine.metrics.snapshot()
            return ([h.status.value for h in handles],
                    [h.result().tolist() for h in handles], snap)

        # uncontended reference: default pool, unbounded queue
        ref_statuses, ref_tokens, _ = run(num_kv_pages=None, max_queue_depth=None)
        statuses, tokens, snap = run(num_kv_pages=11, max_queue_depth=1)
        statuses2, tokens2, _ = run(num_kv_pages=11, max_queue_depth=1)  # repeat: deterministic

    assert ref_statuses == ["finished"] * len(prompts)
    finished = [i for i, s in enumerate(statuses) if s == "finished"]
    rejected = [i for i, s in enumerate(statuses) if s == "rejected"]
    survivors_identical = all(tokens[i] == ref_tokens[i] for i in finished)
    accounted = (
        snap["requests_submitted"]
        == snap["requests_finished"] + snap["rejected"] + snap["timed_out"] + snap["failed"]
    )
    return {
        "ok": (
            len(rejected) > 0 and len(finished) >= 3
            and (statuses, tokens) == (statuses2, tokens2)
            and survivors_identical
            and accounted
            and snap["page_pool"]["alloc_failures"] >= 1
            and snap["page_pool"]["pages_in_use"] == 0
            and snap["rejected"] == len(rejected)
        ),
        "statuses": statuses,
        "deterministic_repeat": (statuses, tokens) == (statuses2, tokens2),
        "survivors_identical_to_uncontended": survivors_identical,
        "alloc_failures": snap["page_pool"]["alloc_failures"],
        "no_request_lost": accounted,
    }


def check_preempt_storm() -> dict:
    """Priority pressure on a saturated page pool (docs/serving.md "Priority
    classes & preemption"): low-priority long sessions hold every page; a
    high-priority deadline-bearing request admits via PREEMPTION on its first
    tick instead of waiting out a whole session; the victim resumes as a
    forced replay and finishes f64 token-identical to an uncontended run;
    repeat runs pin statuses, tokens, and exact victim identity; every
    request reaches a terminal status."""
    with _x64():
        model, params = _serving_setup(param_dtype=jnp.float64)
        prompts = [[1, 2, 3], [4, 5], [6, 7, 8]]  # bg, bg, hi

        # uncontended reference: same page geometry, default (ample) pool
        ref_engine = _engine(model, params, num_slots=3, kv_page_size=2)
        ref_handles = [ref_engine.submit(p, max_new_tokens=4) for p in prompts]
        ref_engine.run_until_drained(max_steps=300)
        ref_tokens = [h.result().tolist() for h in ref_handles]

        def run():
            # page 2: each (bucket 6 + 4 new) reservation is 5 pages; 10
            # allocatable pages -> the two background sessions hold them ALL
            engine = _engine(model, params, num_slots=3, kv_page_size=2,
                             num_kv_pages=11)
            bg = [engine.submit(p, max_new_tokens=4) for p in prompts[:2]]
            engine.step()  # both admitted, pool saturated
            hi = engine.submit(prompts[2], max_new_tokens=4, priority=2,
                               deadline_s=60.0)
            engine.step()  # page-blocked -> preempts one victim, admits NOW
            admitted_first_tick = hi.status.value == "running"
            victims = [h.request_id for h in bg if h.preemptions > 0]
            engine.run_until_drained(max_steps=400)
            snap = engine.metrics.snapshot()
            handles = bg + [hi]
            return {
                "statuses": [h.status.value for h in handles],
                "tokens": [h.result().tolist() for h in handles],
                "victims": victims,
                "admitted_first_tick": admitted_first_tick,
                "snap": snap,
            }

        r1, r2 = run(), run()
    snap = r1["snap"]
    accounted = (
        snap["requests_submitted"]
        == snap["requests_finished"] + snap["rejected"] + snap["timed_out"] + snap["failed"]
    )
    repeat_identical = (
        (r1["statuses"], r1["tokens"], r1["victims"])
        == (r2["statuses"], r2["tokens"], r2["victims"])
    )
    return {
        "ok": (
            r1["admitted_first_tick"]
            and r1["statuses"] == ["finished"] * 3
            and r1["tokens"] == ref_tokens
            and len(r1["victims"]) == 1
            and snap["preemptions"] == 1
            and snap["preempted_replays"] == 1
            and repeat_identical
            and accounted
            and snap["page_pool"]["pages_in_use"] == 0
        ),
        "statuses": r1["statuses"],
        "hi_admitted_via_preemption_first_tick": r1["admitted_first_tick"],
        "victims_resumed_identical": r1["tokens"] == ref_tokens,
        "deterministic_repeat": repeat_identical,
        "victim_ids": r1["victims"],
        "no_request_lost": accounted,
    }


def check_preempt_disabled_inert() -> dict:
    """Kill-switch inertness: with PERCEIVER_IO_TPU_DISABLE_PREEMPTION=1 the
    SAME priority-bearing workload behaves bit-identically to the pre-PR
    engine (all-default-priority FIFO): the high-priority request waits its
    turn, overflow submits reject as plain queue_full backpressure, and
    nothing is ever preempted."""
    with _x64():
        model, params = _serving_setup(param_dtype=jnp.float64)
        prompts = [[1, 2, 3], [4, 5], [6, 7, 8], [9, 10]]

        def run(disable, hi_priority):
            from perceiver_io_tpu.utils import env_override

            with env_override("PERCEIVER_IO_TPU_DISABLE_PREEMPTION",
                              "1" if disable else None):
                engine = _engine(model, params, num_slots=3, kv_page_size=2,
                                 num_kv_pages=11, max_queue_depth=1)
            bg = [engine.submit(p, max_new_tokens=4) for p in prompts[:2]]
            engine.step()  # pool saturated
            hi = engine.submit(prompts[2], max_new_tokens=4, priority=hi_priority)
            engine.step()
            overflow = engine.submit(prompts[3], max_new_tokens=4)  # past bound
            engine.run_until_drained(max_steps=400)
            handles = bg + [hi, overflow]
            return ([h.status.value for h in handles],
                    [h.result().tolist() for h in handles],
                    [h.finish_reason for h in handles],
                    engine.metrics.snapshot())

        # kill-switch arm: priorities present but inert
        sts_off, toks_off, reasons_off, snap_off = run(True, hi_priority=2)
        # pre-PR baseline: the same workload at all-default priorities
        sts_pre, toks_pre, reasons_pre, snap_pre = run(False, hi_priority=0)
    return {
        "ok": (
            (sts_off, toks_off, reasons_off) == (sts_pre, toks_pre, reasons_pre)
            and snap_off["preemptions"] == 0 == snap_pre["preemptions"]
            and reasons_off[-1] == "queue_full"  # the pre-PR backpressure
        ),
        "bit_identical_to_pre_pr": (sts_off, toks_off) == (sts_pre, toks_pre),
        "statuses": sts_off,
        "overflow_reason": reasons_off[-1],
        "preemptions": [snap_off["preemptions"], snap_pre["preemptions"]],
    }


def _load_crash_harness():
    """Import scripts/journal_crash_harness.py as a module (scripts/ is not
    a package)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "journal_crash_harness",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "journal_crash_harness.py"),
    )
    harness = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(harness)
    return harness


def check_journal_crash_restart() -> dict:
    """Process death is survivable (docs/serving.md "Request journal"): a
    REAL child serving process is SIGKILLed mid-tick and a fresh process
    recovers from the write-ahead journal — every accepted request (greedy
    AND sampled) completes with output f64 token-identical to an
    uninterrupted run, and replay compiles zero programs beyond the standard
    set. Run twice into fresh directories: the recovered outputs are pinned
    to the same deterministic reference both times, whatever tick the kill
    actually landed on."""
    harness = _load_crash_harness()

    runs, shared = [], None
    # the harness enables x64 (its reference/recovery math is f64); the
    # context restores the flag so later scenarios see their own default
    with _x64():
        for _ in range(2):
            d = tempfile.mkdtemp(prefix="chaos-journal-crash-")
            try:
                result = harness.run_crash_restart(d, shared=shared)
                shared = result.pop("_shared")  # reuse the deterministic reference
                runs.append(result)
            finally:
                shutil.rmtree(d, ignore_errors=True)
    return {
        "ok": all(r["ok"] for r in runs),
        "runs": [
            {k: r[k] for k in ("sessions_recovered", "outputs_identical",
                               "all_finished", "decode_compilations",
                               "ticks_at_kill")}
            for r in runs
        ],
    }


def check_journal_torn_tail() -> dict:
    """A power loss mid-append leaves a half-written record at the journal's
    tail (injected via ``serving.journal.torn_write``): recovery TRUNCATES at
    the torn record — everything before it (all fully-accepted requests)
    recovers f64 token-identical to an uninterrupted run, the torn accept is
    reported (truncated flag + dropped count), and repeat runs are
    identical."""
    from perceiver_io_tpu.serving import JournalTornWrite, ServingEngine

    with _x64():
        model, params = _serving_setup(param_dtype=jnp.float64)
        prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9]]
        ref = _greedy_tokens(_engine(model, params, num_slots=2), prompts)
        expected = [h.result().tolist() for h in ref]

        def run():
            d = tempfile.mkdtemp(prefix="chaos-journal-torn-")
            try:
                engine = _engine(model, params, num_slots=2,
                                 journal=os.path.join(d, "j"))
                handles = [engine.submit(p, max_new_tokens=5) for p in prompts]
                for _ in range(2):
                    engine.step()
                torn = False
                with armed("serving.journal.torn_write", times=1):
                    try:
                        engine.submit([50, 51], max_new_tokens=5)
                    except JournalTornWrite:
                        torn = True  # the "process dies mid-append" moment
                # the engine object is ABANDONED here (no close — a dead
                # process flushes nothing further); recover from disk
                engine2, info = ServingEngine.recover(
                    model, params, os.path.join(d, "j"), num_slots=2)
                engine2.run_until_drained(max_steps=300)
                outs = [h.result().tolist() for h in info["handles"]]
                return {
                    "torn": torn,
                    "sessions": info["sessions"],
                    "truncated": info["truncated"],
                    "dropped": info["dropped_records"],
                    "outputs": outs,
                    "statuses": [h.status.value for h in info["handles"]],
                }
            finally:
                shutil.rmtree(d, ignore_errors=True)

        r1, r2 = run(), run()
    return {
        "ok": (
            r1["torn"]
            and r1["sessions"] == len(prompts)  # the torn 4th accept is gone
            and r1["truncated"] and r1["dropped"] >= 1
            and r1["outputs"] == expected
            and r1["statuses"] == ["finished"] * len(prompts)
            and r1 == r2
        ),
        "truncated_reported": r1["truncated"],
        "recovered_sessions": r1["sessions"],
        "outputs_identical": r1["outputs"] == expected,
        "deterministic_repeat": r1 == r2,
    }


def check_journal_compaction_crash() -> dict:
    """A kill at either stage of a journal compaction (before the atomic
    generation rename, or after it but before old-generation deletion —
    ``serving.journal.compact.kill`` slot 0/1) loses nothing: recovery reads
    whichever generation is the durable truth and every live session
    completes f64 token-identical to an uncontended run; repeat runs are
    identical per stage."""
    from perceiver_io_tpu.reliability.faults import KilledMidWrite
    from perceiver_io_tpu.serving import ServingEngine

    with _x64():
        model, params = _serving_setup(param_dtype=jnp.float64)
        # enough requests that several are terminal (compaction has records
        # to drop) while the last ones are still live at the crash
        prompts = [[i + 1, i + 2] for i in range(6)]
        ref = _greedy_tokens(_engine(model, params, num_slots=2), prompts, max_new=3)
        expected = [h.result().tolist() for h in ref]

        def run(stage):
            d = tempfile.mkdtemp(prefix="chaos-journal-compact-")
            try:
                from perceiver_io_tpu.serving import RequestJournal

                # tiny segments: the rotation check trips mid-run, and with
                # terminal requests accumulated it COMPACTS — where the kill
                # is armed
                journal = RequestJournal(os.path.join(d, "j"),
                                         segment_max_records=6)
                engine = _engine(model, params, num_slots=2, journal=journal)
                handles = [engine.submit(p, max_new_tokens=3) for p in prompts]
                killed = False
                with armed("serving.journal.compact.kill", slot=stage, times=1):
                    try:
                        engine.run_until_drained(max_steps=300)
                    except KilledMidWrite:
                        killed = True
                # abandoned mid-compaction; a fresh process recovers
                engine2, info = ServingEngine.recover(
                    model, params, os.path.join(d, "j"), num_slots=2)
                engine2.run_until_drained(max_steps=300)
                finished = {tuple(h.prompt_ids.tolist()): h.result().tolist()
                            for h in info["handles"]}
                # completed-before-crash requests are terminal in the journal
                # and rightly NOT recovered; every recovered one must match
                # the reference for its prompt
                identical = all(
                    finished[tuple(p)] == want
                    for p, want in zip(prompts, expected)
                    if tuple(p) in finished
                )
                return {"killed": killed, "sessions": info["sessions"],
                        "identical": identical,
                        "statuses": [h.status.value for h in info["handles"]],
                        "finished": sorted(finished)}
            finally:
                shutil.rmtree(d, ignore_errors=True)

        results = {}
        for stage in (0, 1):
            r1, r2 = run(stage), run(stage)
            results[stage] = {
                "r": r1,
                "repeat_identical": r1 == r2,
            }
    return {
        "ok": all(
            res["r"]["killed"]
            and res["r"]["identical"]
            and all(s == "finished" for s in res["r"]["statuses"])
            and res["repeat_identical"]
            for res in results.values()
        ),
        "pre_rename": results[0]["r"],
        "post_rename": results[1]["r"],
        "deterministic_repeat": all(res["repeat_identical"]
                                    for res in results.values()),
    }


def check_prefix_fork_churn() -> dict:
    """Shared-prefix sessions fork the radix prefix cache's pages under pool
    pressure (docs/serving.md "Prefix cache"): a donor warms the cache, two
    forks saturate the pool, a high-priority fork admits via PREEMPTION of a
    fork-holder, the victim resumes, and distinct dense traffic then forces
    refcount-aware cache eviction instead of backpressure. Every request
    finishes f64 token-identical to an UNCACHED uncontended run, repeat runs
    pin statuses/tokens/victim identity, and after the drain the pool's free
    list is whole — the only references left are the cache's own, and
    clearing it returns the pool to empty (no page leaked)."""
    with _x64():
        model, params = _serving_setup(param_dtype=jnp.float64)
        # preamble of 9: prompts are n=10, latent boundary 4 -> the first 2
        # full pages ([7,7],[7,7]) are the shared cacheable run
        preamble = [7] * 9
        shared_prompts = [preamble + [t] for t in (1, 2, 3, 4)]
        dense_prompts = [list(range(13, 24)), list(range(30, 41))]

        def reference():
            # uncached, uncontended: ample default pool, cache off
            engine = _engine(model, params, num_slots=3, kv_page_size=2)
            handles = [engine.submit(p, max_new_tokens=2) for p in shared_prompts]
            handles += [engine.submit(p, max_new_tokens=1) for p in dense_prompts]
            engine.run_until_drained(max_steps=300)
            assert all(h.ok for h in handles)
            return [h.result().tolist() for h in handles]

        def churn():
            # page 2 over the 12-token window: each shared request reserves 6
            # pages, 2 of them shared on a hit; 11 pages (10 allocatable) =
            # the cached run (2) + exactly two private remainders (4 + 4)
            engine = _engine(model, params, num_slots=3, kv_page_size=2,
                             num_kv_pages=11, prefix_cache=True)
            donor = engine.submit(shared_prompts[0], max_new_tokens=2)
            engine.run_until_drained(max_steps=300)  # warm: 2 pages cached
            bg = [engine.submit(p, max_new_tokens=2) for p in shared_prompts[1:3]]
            engine.step()  # both forks running, pool saturated
            hi = engine.submit(shared_prompts[3], max_new_tokens=2, priority=2)
            engine.step()  # page-blocked head preempts the cheapest fork
            victims = [i for i, h in enumerate(bg) if h.preemptions > 0]
            hi_via_preemption = hi.status.value == "running" and bool(victims)
            engine.run_until_drained(max_steps=400)  # victim resumes, finishes
            # eviction leg: concurrent dense reservations outgrow what is
            # free; the stale cached run must yield, not backpressure
            dense = [engine.submit(p, max_new_tokens=1) for p in dense_prompts]
            engine.run_until_drained(max_steps=300)
            handles = [donor] + bg + [hi] + dense
            snap = engine.metrics.snapshot()
            stats = snap["prefix_cache"]
            free_list_whole = (engine._pool.pages_in_use
                               == engine._prefix_cache.cached_pages)
            cleared = engine._prefix_cache.clear()
            free_list_whole = free_list_whole and engine._pool.pages_in_use == 0
            engine.close()
            return {
                "statuses": [h.status.value for h in handles],
                "tokens": [h.result().tolist() for h in handles],
                "victims": victims,
                "hi_admitted_via_preemption": hi_via_preemption,
                "hits": stats["hits"],
                "evictions": stats["evictions"],
                "preemptions": snap["preemptions"],
                "free_list_whole": free_list_whole,
                "cleared_pages": cleared,
            }

        expected = reference()
        r1, r2 = churn(), churn()

    survivors_identical = r1["tokens"] == expected
    return {
        "ok": (
            all(s == "finished" for s in r1["statuses"])
            and survivors_identical
            and r1 == r2
            and r1["hi_admitted_via_preemption"]
            and len(r1["victims"]) == 1
            and r1["hits"] >= 3
            and r1["evictions"] >= 1
            and r1["free_list_whole"]
        ),
        "survivors_identical_to_uncached": survivors_identical,
        "deterministic_repeat": r1 == r2,
        "victims": r1["victims"],
        "hits": r1["hits"],
        "evictions": r1["evictions"],
        "preemptions": r1["preemptions"],
        "free_list_whole": r1["free_list_whole"],
    }


def check_chunked_prefill_recovery() -> dict:
    """A REAL child serving process running the paged + chunked-prefill
    engine is SIGKILLed while a window-length prompt is still MID
    chunked-prefill (the parent aims its kill at a tick whose progress file
    reports an in-flight split admission): a fresh process recovers every
    accepted request from the write-ahead journal — the half-prefilled
    session restarts from its journaled accept alone (chunk installs are
    device state, not journal state) and completes f64 token-identical to an
    uninterrupted PLAIN dense run, with decode still ONE compiled program."""
    harness = _load_crash_harness()
    with _x64():
        d = tempfile.mkdtemp(prefix="chaos-chunked-prefill-")
        try:
            result = harness.run_crash_restart(d, chunked=True)
            result.pop("_shared")
        finally:
            shutil.rmtree(d, ignore_errors=True)
    return {
        "ok": result["ok"],
        **{k: result[k] for k in ("sessions_recovered", "outputs_identical",
                                  "all_finished", "decode_compilations",
                                  "ticks_at_kill", "prefilling_at_kill")},
    }


def check_ragged_tick_churn() -> dict:
    """Fault churn INSIDE the unified ragged tick (docs/serving.md "Unified
    ragged tick"): a poisoned slot is quarantined out of a MIXED tick — its
    buffered descriptor lanes dropped with it — while chunked prefill lanes
    are still streaming, and a high-priority request then admits via
    preemption under page pressure. Every survivor finishes f64
    token-identical to the same engine running uncontended (ample pool, no
    fault armed: what the scenario claims is that faults leave the
    survivors' tokens untouched), repeat runs pin statuses, tokens AND
    victim identity, and the drain leaves the free list whole and the tick
    buffers empty — a dropped lane leaks no page."""
    with _x64():
        model, params = _serving_setup(param_dtype=jnp.float64)
        # short (classic path, n < latents), window-length chunk-streamed,
        # and the high-priority head — plus the doomed poisoned session
        survivor_prompts = [[4, 5, 6], list(range(1, 11)), [7] * 12]
        new = [4, 3, 5]
        # n < latents: the classic prefill+install path, so the slot is
        # ACTIVE (installed logits) when the poison fires — a mid-split slot
        # has no decode state to poison yet
        poisoned_prompt = [20, 21, 22]

        def build(**kw):
            return _engine(model, params, num_slots=3, kv_page_size=2, **kw)

        def reference():
            # the same engine, ample pool, no faults: the oracle
            engine = build()
            hs = [engine.submit(p, max_new_tokens=m, rng=jax.random.PRNGKey(i))
                  for i, (p, m) in enumerate(zip(survivor_prompts, new))]
            engine.run_until_drained(max_steps=300)
            assert all(h.ok for h in hs)
            tokens = [h.result().tolist() for h in hs]
            engine.close()
            return tokens

        def churn():
            # 17 pages (16 allocatable): short (bucket 6 + 4 new = 5 pages)
            # + the chunk-streamed session (6) + poisoned (5) fill the pool
            # exactly; the quarantine hands 5 back, one short of the hi
            # head's 6 — the head page-blocks and must preempt, all while
            # chunk lanes are still streaming
            engine = build(num_kv_pages=17,
                           prefill_chunk_tokens=4, max_prefill_slots=2)
            short = engine.submit(survivor_prompts[0], max_new_tokens=new[0],
                                  rng=jax.random.PRNGKey(0))
            long = engine.submit(survivor_prompts[1], max_new_tokens=new[1],
                                 rng=jax.random.PRNGKey(1))
            poisoned = engine.submit(poisoned_prompt, max_new_tokens=4,
                                     rng=jax.random.PRNGKey(9))
            for _ in range(6):  # classic-path poisoned slot active; long
                engine.step()   # still mid chunk-stream (deterministic walk)
                if poisoned.status.value == "running":
                    break
            assert poisoned.status.value == "running"
            with armed("serving.nan", slot=poisoned.slot):
                engine.step()  # poison folds into a MIXED fused tick
            hi = engine.submit(survivor_prompts[2], max_new_tokens=new[2],
                               rng=jax.random.PRNGKey(2), priority=2)
            engine.run_until_drained(max_steps=400)
            handles = [short, long, hi]
            victims = [i for i, h in enumerate(handles) if h.preemptions > 0]
            snap = engine.metrics.snapshot()
            out = {
                "statuses": ([h.status.value for h in handles]
                             + [poisoned.status.value]),
                "tokens": [h.result().tolist() for h in handles],
                "victims": victims,
                "preemptions": snap["preemptions"],
                "failed": snap["failed"],
                "ragged_p50": snap["ragged_tick"]["programs_per_tick"]["p50"],
                "free_list_whole": engine._pool.pages_in_use == 0,
                "buffers_empty": not (engine._tick_chunks
                                      or engine._tick_finishes
                                      or engine._tick_resets),
            }
            engine.close()
            return out

        expected = reference()
        r1, r2 = churn(), churn()

    survivors_identical = r1["tokens"] == expected
    return {
        "ok": (
            r1["statuses"] == ["finished", "finished", "finished", "failed"]
            and survivors_identical
            and r1 == r2
            and r1["failed"] == 1
            and r1["preemptions"] >= 1
            and r1["free_list_whole"]
            and r1["buffers_empty"]
        ),
        "statuses": r1["statuses"],
        "survivors_identical_to_uncontended": survivors_identical,
        "deterministic_repeat": r1 == r2,
        "victims": r1["victims"],
        "preemptions": r1["preemptions"],
        "programs_per_tick_p50": r1["ragged_p50"],
        "free_list_whole": r1["free_list_whole"],
        "tick_buffers_empty": r1["buffers_empty"],
    }


def check_rolling_restart_under_load() -> dict:
    """Zero-downtime fleet ops (docs/serving.md "Fleet operations"): a
    journaled 2-replica fleet takes a rolling restart UNDER LOAD — requests
    keep arriving while each replica drains (sessions migrate to its
    sibling or park durably), recycles (fresh engine, journal-recovered),
    and re-admits. Every accepted session finishes exactly once, f64
    token-identical to an undisturbed run; no breaker ever trips (a planned
    recycle is not a failure); repeat runs are identical."""
    from perceiver_io_tpu.serving import ServingEngine, ServingRouter

    with _x64():
        model, params = _serving_setup(param_dtype=jnp.float64)
        prompts = [[1, 2, 3], [4, 5], [6, 7, 8], [9, 10], [11, 12, 13], [14, 15]]
        engine = ServingEngine(model, params, num_slots=len(prompts))
        refs = [engine.submit(p, max_new_tokens=8) for p in prompts]
        engine.run_until_drained(max_steps=300)
        expected = [h.result().tolist() for h in refs]

        def run():
            d = tempfile.mkdtemp(prefix="chaos-rolling-")
            try:
                router = ServingRouter(model, params, num_replicas=2,
                                       num_slots=2,
                                       journal=os.path.join(d, "r{i}"))
                handles = [router.submit(p, max_new_tokens=8)
                           for p in prompts[:3]]
                for _ in range(2):
                    router.step()
                assert router.begin_rolling_restart()
                i, steps = 3, 0
                while router.restart_in_progress and steps < 200:
                    if i < len(prompts):  # sustained load during the restart
                        handles.append(router.submit(prompts[i],
                                                     max_new_tokens=8))
                        i += 1
                    router.step()
                    steps += 1
                while i < len(prompts):
                    handles.append(router.submit(prompts[i], max_new_tokens=8))
                    i += 1
                router.run_until_drained(max_steps=500)
                snap = router.snapshot()
                router.close()
                return {
                    "statuses": [h.status.value for h in handles],
                    "tokens": [h.result().tolist() for h in handles],
                    "recycles": snap["fleet_ops"]["recycles"],
                    "breaker_transitions": snap["breaker_transitions"],
                    "submitted": snap["requests_submitted"],
                    "finished": snap["requests_finished"],
                }
            finally:
                shutil.rmtree(d, ignore_errors=True)

        r1, r2 = run(), run()
    return {
        "ok": (
            r1["statuses"] == ["finished"] * len(prompts)
            and r1["tokens"] == expected
            and r1["recycles"] == 2
            and r1["breaker_transitions"] == {}
            and r1["submitted"] == r1["finished"] == len(prompts)
            and r1 == r2
        ),
        "statuses": r1["statuses"],
        "outputs_identical": r1["tokens"] == expected,
        "recycles": r1["recycles"],
        "breaker_transitions": r1["breaker_transitions"],
        "sessions_lost": r1["submitted"] - r1["finished"],
        "deterministic_repeat": r1 == r2,
    }


def check_migrate_crash_midflight() -> dict:
    """A REAL child router process dies (self-SIGKILL, no flush) inside a
    planned migration's double-live window — after the destination's
    fsynced accept, before the origin journal's close record. Fleet
    recovery dedupes the twice-live session by its fleet-unique id: every
    accepted session finishes exactly ONCE, f64 token-identical (greedy +
    sampled), zero extra compiled programs. Run twice into fresh
    directories against one deterministic reference."""
    harness = _load_crash_harness()
    runs, shared = [], None
    with _x64():
        for _ in range(2):
            d = tempfile.mkdtemp(prefix="chaos-migrate-crash-")
            try:
                result = harness.run_migrate_crash(d, shared=shared)
                shared = result.pop("_shared")
                runs.append(result)
            finally:
                shutil.rmtree(d, ignore_errors=True)
    return {
        "ok": all(r["ok"] for r in runs),
        "runs": [
            {k: r[k] for k in ("double_live", "sessions_recovered", "deduped",
                               "outputs_identical", "all_finished",
                               "decode_compilations")}
            for r in runs
        ],
    }


def check_router_crash_failover() -> dict:
    """A replica crashed mid-decode loses nothing: the victim finishes
    token-identical (f64) to the fault-free run after failover, the survivor
    on the healthy replica is bit-identical throughout, and every submitted
    request reaches a terminal status."""
    from perceiver_io_tpu.serving import ServingRouter

    with _x64():
        import jax.numpy as jnp

        model, params = _serving_setup(param_dtype=jnp.float64)
        ref_v = _greedy_tokens(_engine(model, params, num_slots=1), [[1, 2, 3]], max_new=6)[0]
        ref_s = _greedy_tokens(_engine(model, params, num_slots=1), [[4, 5, 6]], max_new=6)[0]

        router = ServingRouter(model, params, num_replicas=2, num_slots=1,
                               breaker_cooldown_ticks=2)
        victim = router.submit([1, 2, 3], max_new_tokens=6)
        survivor = router.submit([4, 5, 6], max_new_tokens=6)
        router.step()
        router.step()  # two tokens decoded on each replica: crash is MID-decode
        with armed("replica.crash", slot=victim.replica, times=1):
            router.run_until_drained(max_steps=300)
        snap = router.snapshot()
        router.close()
    victim_identical = victim.result().tolist() == ref_v.result().tolist()
    survivor_identical = survivor.result().tolist() == ref_s.result().tolist()
    accounted = (
        snap["requests_submitted"]
        == snap["requests_finished"] + snap["rejected"] + snap["timed_out"] + snap["failed"]
    )
    return {
        "ok": (
            victim.ok and victim.failovers == 1 and victim_identical
            and survivor.ok and survivor.failovers == 0 and survivor_identical
            and snap["failovers"] == 1
            and snap["breaker_transitions"].get("closed->open") == 1
            and accounted
        ),
        "victim_identical_after_failover": victim_identical,
        "survivor_bit_identical": survivor_identical,
        "failovers": snap["failovers"],
        "no_request_lost": accounted,
    }


def check_router_stall_breaker() -> dict:
    """A stalled replica trips the slow-tick detector: breaker opens (its
    requests fail over), cooldown elapses in ticks, the HALF_OPEN probe
    closes it, and the recovered replica serves new work."""
    from perceiver_io_tpu.serving import ServingRouter
    from perceiver_io_tpu.serving.router import BREAKER_CLOSED

    model, params = _serving_setup()
    router = ServingRouter(
        model, params, num_replicas=2, num_slots=1,
        slow_tick_threshold_s=0.25, slow_ticks_to_open=2, breaker_cooldown_ticks=2,
    )
    warm = [router.submit([1, 2], max_new_tokens=1) for _ in range(2)]
    router.run_until_drained(max_steps=20)  # compile ticks: exempt, no strikes
    victim = router.submit([1, 2, 3], max_new_tokens=10)
    survivor = router.submit([4, 5, 6], max_new_tokens=10)
    router.step()
    with armed("replica.stall", slot=victim.replica, times=2, value=0.4):
        router.step()
        router.step()  # second strike opens the breaker, victim fails over
    router.run_until_drained(max_steps=300)
    recovered = router.submit([7, 8], max_new_tokens=2)
    router.run_until_drained(max_steps=50)
    snap = router.snapshot()
    trans = snap["breaker_transitions"]
    all_closed = all(r.breaker == BREAKER_CLOSED for r in router.replicas)
    router.close()
    return {
        "ok": (
            all(h.ok for h in warm)
            and victim.ok and victim.failovers == 1 and len(victim.output_ids) == 10
            and survivor.ok and survivor.failovers == 0
            and trans.get("closed->open") == 1
            and trans.get("open->half_open") == 1
            and trans.get("half_open->closed") == 1
            and all_closed and recovered.ok
        ),
        "transitions": trans,
        "victim_failovers": victim.failovers,
        "recovered_serves_again": recovered.ok,
    }


def check_router_shed_overload() -> dict:
    """Under overload (slow ticks, deep queue-wait history), a deadline the
    windowed p95 estimates call infeasible is shed at admission instead of
    queueing doomed work; feasible requests still complete."""
    from perceiver_io_tpu.serving import ServingRouter

    model, params = _serving_setup()
    router = ServingRouter(model, params, num_replicas=1, num_slots=1,
                           shed_min_samples=1)
    with armed("replica.slow_tick", times=None, value=0.05):
        backlog = [router.submit([1, 2], max_new_tokens=6) for _ in range(4)]
        router.run_until_drained(max_steps=300)  # serial drain builds real queue waits
    doomed = router.submit([5, 6], max_new_tokens=6, deadline_s=0.001)
    feasible = router.submit([7, 8], max_new_tokens=2, deadline_s=120.0)
    router.run_until_drained(max_steps=100)
    snap = router.snapshot()
    router.close()
    return {
        "ok": (
            all(h.ok for h in backlog)
            and doomed.finish_reason == "shed_infeasible" and not doomed.ok
            and feasible.ok
            and snap["shed_infeasible"] == 1 and snap["rejected"] == 1
        ),
        "shed_reason": doomed.finish_reason,
        "feasible_completed": feasible.ok,
        "shed_counter": snap["shed_infeasible"],
    }


def check_router_drain() -> dict:
    """Fleet drain: every backlog rejected, every active slot finished,
    admission closed for good."""
    from perceiver_io_tpu.serving import ServingRouter

    model, params = _serving_setup()
    router = ServingRouter(model, params, num_replicas=2, num_slots=1)
    active = [router.submit([1, 2], max_new_tokens=4) for _ in range(2)]
    router.step()  # one per replica, both admitted
    backlog = router.submit([3, 4], max_new_tokens=2)
    drained = router.drain(max_steps=200)
    post = router.submit([5, 6], max_new_tokens=2)
    snap = router.snapshot()
    router.close()
    return {
        "ok": (
            all(h.ok and len(h.output_ids) == 4 for h in active)
            and backlog.finish_reason == "draining"
            and post.finish_reason == "draining"
            and len(drained) == 3
            and snap["rejected"] == 2
            and snap["requests_finished"] == 2
        ),
        "reasons": [backlog.finish_reason, post.finish_reason],
        "drained": len(drained),
    }


def check_proc_replica_kill9() -> dict:
    """A REAL ``kill -9`` lands on an out-of-process replica worker
    mid-decode (``transport.worker.kill``): the router's supervisor respawns
    the worker through journal recovery — the victim's sessions finish f64
    token-identical on the NEW process with zero failovers, survivors on the
    sibling replica are bit-identical throughout, the victim is recovered
    exactly once, and a repeat run pins identical statuses/tokens."""
    from perceiver_io_tpu.serving import ServingRouter

    prompts = [[1, 2, 3], [4, 5, 6], [2, 4]]
    with _x64():
        model, params = _serving_setup(param_dtype=jnp.float64)

        # in-process reference: the token-identity target for every session
        ref = ServingRouter(model, params, num_replicas=2, num_slots=2)
        ref_handles = [ref.submit(p, max_new_tokens=6) for p in prompts]
        ref.run_until_drained(max_steps=300)
        ref_tokens = [list(h.output_ids) for h in ref_handles]
        ref.close()

        def run_once(tmp):
            router = ServingRouter(
                model, params, num_replicas=2, num_slots=2,
                journal=os.path.join(tmp, "r{i}"), replica_mode="process",
            )
            try:
                handles = [router.submit(p, max_new_tokens=6) for p in prompts]
                for _ in range(3):
                    router.step()  # several tokens in: the kill is MID-decode
                victim_rid = handles[0].replica
                # the fault point fires a REAL os.kill(pid, SIGKILL) on the
                # worker at the victim replica's next RPC
                with armed("transport.worker.kill", slot=victim_rid, times=1):
                    router.run_until_drained(max_steps=300)
                snap = router.snapshot()
                transport = snap["transport"]
                return {
                    "statuses": [h.status.value for h in handles],
                    "tokens": [list(h.output_ids) for h in handles],
                    "failovers": [h.failovers for h in handles],
                    "respawns": transport["worker_respawns"],
                    "workers_alive": transport["workers_alive"],
                    "fleet_failovers": snap["failovers"],
                    "breaker_transitions": dict(snap["breaker_transitions"]),
                    "accounted": (
                        snap["requests_submitted"]
                        == snap["requests_finished"] + snap["rejected"]
                        + snap["timed_out"] + snap["failed"]
                    ),
                }
            finally:
                router.close()

        with tempfile.TemporaryDirectory() as tmp_a, \
                tempfile.TemporaryDirectory() as tmp_b:
            first = run_once(tmp_a)
            second = run_once(tmp_b)  # repeat-run determinism

    token_identical = first["tokens"] == ref_tokens
    recovered_once = first["respawns"] == 1
    return {
        "ok": (
            all(s == "finished" for s in first["statuses"])
            and token_identical
            and recovered_once
            and first["fleet_failovers"] == 0
            and all(f == 0 for f in first["failovers"])
            and first["breaker_transitions"] == {}
            and first["workers_alive"] == 2
            and first["accounted"]
            and second == first
        ),
        "token_identical_after_respawn": token_identical,
        "victim_recovered_exactly_once": recovered_once,
        "failovers": first["fleet_failovers"],
        "breaker_transitions": first["breaker_transitions"],
        "repeat_deterministic": second == first,
    }


def check_transport_torn_frame() -> dict:
    """A torn RPC frame (``transport.send.torn`` corrupts the CRC) is NACKed
    by the worker WITHOUT executing and absorbed by the deterministic retry
    schedule — tokens f64-identical, breakers closed. A replica whose channel
    tears EVERY frame exhausts retries, is put down as wedged, strikes its
    breaker, and its sessions fail over — no corrupt state either way."""
    from perceiver_io_tpu.serving import ServingRouter

    prompts = [[1, 2, 3], [4, 5, 6]]
    with _x64():
        model, params = _serving_setup(param_dtype=jnp.float64)

        ref = ServingRouter(model, params, num_replicas=2, num_slots=1)
        ref_handles = [ref.submit(p, max_new_tokens=6) for p in prompts]
        ref.run_until_drained(max_steps=300)
        ref_tokens = [list(h.output_ids) for h in ref_handles]
        ref.close()

        # arm 1 — ONE torn frame: NACK -> retry resends -> absorbed
        router = ServingRouter(model, params, num_replicas=2, num_slots=1,
                               replica_mode="process")
        try:
            handles = [router.submit(p, max_new_tokens=6) for p in prompts]
            router.step()
            with armed("transport.send.torn", times=1):
                router.run_until_drained(max_steps=300)
            snap1 = router.snapshot()
            t1 = snap1["transport"]
            one_tokens = [list(h.output_ids) for h in handles]
        finally:
            router.close()

        # arm 2 — EVERY frame to replica 1 torn: retries exhaust, the wedged
        # worker is killed by the client, the breaker strikes, sessions fail
        # over to the healthy replica (cooldown long enough that no HALF_OPEN
        # probe re-enters the torn channel during the drain)
        router = ServingRouter(model, params, num_replicas=2, num_slots=1,
                               replica_mode="process",
                               breaker_cooldown_ticks=500)
        try:
            handles2 = [router.submit(p, max_new_tokens=6) for p in prompts]
            router.step()
            with armed("transport.send.torn", slot=1, times=None):
                router.run_until_drained(max_steps=300)
            snap2 = router.snapshot()
            two_tokens = [list(h.output_ids) for h in handles2]
        finally:
            router.close()

    one_identical = one_tokens == ref_tokens
    two_identical = two_tokens == ref_tokens
    return {
        "ok": (
            all(h.ok for h in handles) and one_identical
            and t1["rpc_retries"] >= 1
            and snap1["failovers"] == 0
            and t1["worker_respawns"] == 0
            and snap1["breaker_transitions"] == {}
            and all(h.ok for h in handles2) and two_identical
            and snap2["breaker_transitions"].get("closed->open") == 1
            and snap2["failovers"] >= 1
        ),
        "retry_absorbed_tokens_identical": one_identical,
        "retries_single_tear": t1["rpc_retries"],
        "persistent_tear_breaker_open": snap2["breaker_transitions"].get("closed->open"),
        "persistent_tear_failed_over_ok": two_identical,
    }


CHECKS = {
    "no_fault_inert": check_no_fault_inert,
    "flaky_loader": check_flaky_loader,
    "slow_loader": check_slow_loader,
    "nan_batch_skip": check_nan_batch_skip,
    "checkpoint_kill": check_checkpoint_kill,
    "checkpoint_corrupt": check_checkpoint_corrupt,
    "serving_deadline": check_serving_deadline,
    "serving_nan": check_serving_nan,
    "queue_bound": check_queue_bound,
    "quant_quarantine": check_quant_quarantine,
    "paging_pool_exhaustion": check_paging_pool_exhaustion,
    "preempt_storm": check_preempt_storm,
    "preempt_disabled_inert": check_preempt_disabled_inert,
    "journal_crash_restart": check_journal_crash_restart,
    "journal_torn_tail": check_journal_torn_tail,
    "journal_compaction_crash": check_journal_compaction_crash,
    "prefix_fork_churn": check_prefix_fork_churn,
    "chunked_prefill_recovery": check_chunked_prefill_recovery,
    "ragged_tick_churn": check_ragged_tick_churn,
    "router_crash_failover": check_router_crash_failover,
    "proc_replica_kill9": check_proc_replica_kill9,
    "transport_torn_frame": check_transport_torn_frame,
    "router_stall_breaker": check_router_stall_breaker,
    "router_shed_overload": check_router_shed_overload,
    "router_drain": check_router_drain,
    "rolling_restart_under_load": check_rolling_restart_under_load,
    "migrate_crash_midflight": check_migrate_crash_midflight,
}


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--checks", default=None,
                    help=f"comma-separated subset of: {','.join(CHECKS)}")
    ap.add_argument("--out", default=None,
                    help="optional JSON artifact path (atomic write)")
    args = ap.parse_args(argv)

    names = list(CHECKS) if args.checks is None else [s.strip() for s in args.checks.split(",")]
    unknown = [n for n in names if n not in CHECKS]
    if unknown:
        raise SystemExit(f"unknown checks {unknown} (known: {sorted(CHECKS)})")

    results = {}
    for name in names:
        FAULTS.reset()  # isolation: no arming leaks between scenarios
        t0 = time.perf_counter()
        try:
            results[name] = CHECKS[name]()
        except Exception as e:  # noqa: BLE001 — a crash IS a failed check
            results[name] = {"ok": False, "error": f"{type(e).__name__}: {e}"}
        results[name]["seconds"] = round(time.perf_counter() - t0, 3)
    FAULTS.reset()

    out = {
        "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "backend": jax.default_backend(),
        "all_ok": all(r["ok"] for r in results.values()),
        "checks": results,
    }
    if args.out:
        from perceiver_io_tpu.obs import write_run_manifest
        from perceiver_io_tpu.training.checkpoint import atomic_write_json

        atomic_write_json(args.out, out, indent=1)
        manifest = write_run_manifest(args.out, config=vars(args))
        print(f"wrote {args.out} (+ {manifest})", file=sys.stderr)
    print(json.dumps(out, indent=1))
    if not out["all_ok"]:
        bad = [n for n, r in results.items() if not r["ok"]]
        print(f"CHAOS CHECK FAILED: {bad}", file=sys.stderr)
        if __name__ == "__main__":
            raise SystemExit(1)
    return out


if __name__ == "__main__":
    main()
