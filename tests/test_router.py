"""Multi-replica router tests: dispatch parity, deterministic failover
(token-identity at bucket boundaries, float64), circuit-breaker state
machine, SLO shedding, churn/compile bounds, serving-metrics/v10, and the
SIGTERM/SIGINT graceful drain.

The failover contract (docs/serving.md, router section): after a replica is
lost mid-decode, the router re-prefills ``prompt + already-emitted tokens``
on a healthy replica and the greedy continuation is token-identical to the
uninterrupted run — the widened ``install_slot`` left-pad path at a different
covering bucket is the risk, so prompt AND continuation lengths straddle
every ladder boundary here, in float64 where equality is exact.
"""

import json
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.generation.generate import GenerationConfig
from perceiver_io_tpu.models.core.config import CausalSequenceModelConfig
from perceiver_io_tpu.models.core.perceiver_ar import CausalSequenceModel
from perceiver_io_tpu.reliability import armed
from perceiver_io_tpu.serving import (
    RequestStatus,
    RouterMetrics,
    ServingEngine,
    ServingRouter,
    load_metrics_jsonl,
)
from perceiver_io_tpu.serving.router import (
    BREAKER_CLOSED,
    BREAKER_HALF_OPEN,
    BREAKER_OPEN,
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


@pytest.fixture(scope="module")
def setup():
    return _make_model()


def _engine_reference(model, params, prompts, max_new):
    """Uninterrupted single-engine run — the fault-free baseline every
    failover scenario is pinned against."""
    engine = ServingEngine(model, params, num_slots=max(len(prompts), 1))
    handles = [engine.submit(p, max_new_tokens=m) for p, m in zip(prompts, max_new)]
    engine.run_until_drained(max_steps=500)
    return [h.result().tolist() for h in handles]


# ------------------------------------------------------------------- parity
def test_router_greedy_parity_mixed_lengths(x64):
    """Dispatch across replicas is invisible to outputs: greedy router
    results are f64 token-identical to uninterrupted engine runs."""
    model, params = _make_model(param_dtype=jnp.float64)
    prompts = [[7, 3, 9], [40, 41, 42, 43, 44, 45, 46], list(range(100, 112)), [250]]
    max_new = [5, 3, 6, 4]
    expected = _engine_reference(model, params, prompts, max_new)
    router = ServingRouter(model, params, num_replicas=2, num_slots=2)
    handles = [router.submit(p, max_new_tokens=m) for p, m in zip(prompts, max_new)]
    router.run_until_drained(max_steps=300)
    for handle, want, prompt in zip(handles, expected, prompts):
        assert handle.ok and handle.result().tolist() == want, f"prompt {prompt} diverged"
        assert handle.failovers == 0
    # load-based dispatch actually spread the work
    snap = router.snapshot()
    assert snap["schema"] == "serving-metrics/v13"
    assert all(s["requests_admitted"] > 0 for s in snap["replicas"].values())
    assert snap["failovers"] == 0 and snap["breaker_transitions"] == {}
    router.close()


def test_failover_token_identity_at_bucket_boundaries(x64):
    """Acceptance: crash a replica after k emitted tokens and the failed-over
    continuation (re-prefill of prompt + k tokens, possibly at a DIFFERENT
    covering bucket) is f64 token-identical to the uninterrupted run, for
    prompt/continuation lengths straddling every ladder boundary."""
    model, params = _make_model(param_dtype=jnp.float64)
    k, max_new = 2, 5
    bucket = LATENTS  # the default halving ladder here is (6, 12)
    # prompt lengths putting PROMPT and CONTINUATION (= n + k) at 1 / bucket /
    # bucket+1 / window: the bucket-crossing re-prefill is the risk path
    lengths = sorted({1, bucket - k, bucket, bucket + 1 - k, bucket + 1, WINDOW - k})
    prompts = [list(range(3, 3 + n)) for n in lengths]
    expected = {n: _engine_reference(model, params, [p], [max_new])[0]
                for n, p in zip(lengths, prompts)}

    router = ServingRouter(model, params, num_replicas=2, num_slots=1,
                           breaker_cooldown_ticks=1)
    for n, prompt in zip(lengths, prompts):
        victim = router.submit(prompt, max_new_tokens=max_new)
        assert router.replicas[victim.replica].breaker == BREAKER_CLOSED
        for _ in range(k):
            router.step()
        assert len(victim.output_ids) == k
        with armed("replica.crash", slot=victim.replica, times=1):
            router.run_until_drained(max_steps=300)
        assert victim.ok and victim.failovers == 1, f"len {n}: {victim.status}"
        assert victim.result().tolist() == expected[n], f"len {n} diverged after failover"
        # the fleet fully recovers before the next case (1-tick cooldown)
        for _ in range(4):
            router.step()
        assert all(r.breaker == BREAKER_CLOSED for r in router.replicas)
    snap = router.snapshot()
    assert snap["failovers"] == len(lengths)
    router.close()


def test_paged_failover_replays_at_victims_page_count(x64):
    """Satellite (docs/serving.md, paging section): with paging on, a
    failover replay re-prefills at the victim's covering bucket and allocates
    EXACTLY the victim's page reservation on the new replica — same bucket +
    same generation budget, never a full-window fallback — while the
    continuation stays f64 token-identical to the default engine's uninterrupted run."""
    model, params = _make_model(param_dtype=jnp.float64)
    prompt, max_new = [7, 3, 9], 2
    expected = _engine_reference(model, params, [prompt], [max_new])[0]

    # page 3 over window 12: a full-window reservation would be 4 pages; this
    # request's (bucket 6 + 2 new -> ceil(8/3)) is 3 — the counts distinguish
    # the replay path from any full-window fallback
    router = ServingRouter(model, params, num_replicas=2, num_slots=1,
                           kv_page_size=3, breaker_cooldown_ticks=1)
    assert all(r.engine.kv_page_size == 3 for r in router.replicas)
    victim = router.submit(prompt, max_new_tokens=max_new)
    router.step()  # one token decoded: the crash is mid-request
    victim_pages = victim._engine_handle.pages_allocated
    assert victim_pages == 3  # < the 4-page full-window reservation
    victim_replica = victim.replica
    with armed("replica.crash", slot=victim_replica, times=1):
        router.run_until_drained(max_steps=300)
    assert victim.ok and victim.failovers == 1
    assert victim.result().tolist() == expected  # layout + failover invisible
    assert victim.replica != victim_replica
    # the replayed admission reserved exactly the victim's page count on the
    # NEW replica's own pool, and eviction returned every page
    assert victim._engine_handle.pages_allocated == victim_pages
    new_engine = router.replicas[victim.replica].engine
    assert new_engine._pool.pages_in_use == 0
    snap = router.snapshot()
    assert snap["page_pool"] is None  # router has no pool of its own
    assert snap["replicas"][f"r{victim.replica}"]["page_pool"]["pages_in_use"] == 0
    router.close()


def test_quantized_fleet_failover_token_identity(x64):
    """Satellite (docs/serving.md "Quantized KV pages & weight serving"):
    the router forwards ``kv_quant``/``weight_dtype`` per-replica, and a
    failover replay across an int8-quantized fleet is token-identical to an
    UNCONTENDED quantized single-engine run — the replay re-quantizes the
    victim's prompt + emitted tokens on the new replica's pool through the
    same deterministic write paths, so the quantization error is replayed
    byte-for-byte, not merely approximated."""
    model, params = _make_model(param_dtype=jnp.float64)
    prompt, max_new = list(range(3, 12)), 4
    kw = dict(kv_page_size=3, kv_quant="int8")

    ref_engine = ServingEngine(model, params, num_slots=1, **kw)
    ref = ref_engine.submit(prompt, max_new_tokens=max_new)
    ref_engine.run_until_drained(max_steps=200)
    assert ref.ok

    router = ServingRouter(model, params, num_replicas=2, num_slots=1,
                           breaker_cooldown_ticks=1, **kw)
    assert all(r.engine.kv_quant == "int8" for r in router.replicas)
    victim = router.submit(prompt, max_new_tokens=max_new)
    for _ in range(2):
        router.step()
    assert len(victim.output_ids) == 2
    victim_replica = victim.replica
    with armed("replica.crash", slot=victim_replica, times=1):
        router.run_until_drained(max_steps=300)
    assert victim.ok and victim.failovers == 1
    assert victim.replica != victim_replica
    assert victim.result().tolist() == ref.result().tolist()
    snap = router.snapshot()
    assert snap["kv_quant"] is None  # pools are per-engine; router has none
    assert snap["replicas"][f"r{victim.replica}"]["kv_quant"]["mode"] == "int8"
    router.close()

    # weight_dtype forwards the same way (each replica holds its own served
    # copy); the router itself truthfully reports no weight_serving gauge
    wrouter = ServingRouter(model, params, num_replicas=2, num_slots=1,
                            weight_dtype="bf16")
    assert all(r.engine.weight_dtype == "bf16" for r in wrouter.replicas)
    wsnap = wrouter.snapshot()
    assert wsnap["weight_serving"] is None
    assert all(s["weight_serving"]["dtype"] == "bf16"
               for s in wsnap["replicas"].values())
    wrouter.close()


def test_failover_bounded_and_partial_output_preserved(x64):
    """A request that keeps losing replicas terminates FAILED after
    max_failovers re-dispatches, with every token emitted so far preserved on
    the handle (the TIMED_OUT partial-output discipline)."""
    model, params = _make_model(param_dtype=jnp.float64)
    expected = _engine_reference(model, params, [[7, 3, 9]], [8])[0]
    router = ServingRouter(model, params, num_replicas=2, num_slots=1,
                           max_failovers=1, breaker_cooldown_ticks=64)
    victim = router.submit([7, 3, 9], max_new_tokens=8)
    router.step()
    router.step()  # two tokens on r0
    first_replica = victim.replica
    seen = len(victim.output_ids)
    with armed("replica.crash", slot=first_replica, times=1):
        router.step()  # crash -> failover #1 to the sibling
    assert victim.failovers == 1 and not victim.done
    for _ in range(2):
        router.step()  # a couple of continuation tokens on the new replica
        # the streaming view is MONOTONIC through the replay: the salvage
        # buffer answers until the new engine's stream overtakes it
        assert len(victim.output_ids) >= seen
        seen = len(victim.output_ids)
    emitted_before = list(victim.output_ids)
    assert len(emitted_before) >= 3
    with armed("replica.crash", slot=victim.replica, times=1):
        router.step()  # second loss exceeds max_failovers=1
    assert victim.status is RequestStatus.FAILED
    assert victim.finish_reason == "max_failovers"
    assert victim.failovers == 2
    # partial output preserved, and it is a PREFIX of the fault-free stream
    assert victim.result().tolist() == emitted_before == expected[: len(emitted_before)]
    router.close()


def test_failover_parks_on_backpressure_not_rejected(setup):
    """A failover continuation is ACCEPTED work: when every surviving queue
    is momentarily at its bound it parks and retries, it is never terminally
    REJECTED/queue_full the way a fresh submit would be."""
    model, params = setup
    router = ServingRouter(model, params, num_replicas=2, num_slots=1,
                           max_queue_depth=0, breaker_cooldown_ticks=64)
    a = router.submit([1, 2, 3], max_new_tokens=6)
    b = router.submit([4, 5], max_new_tokens=8)
    router.step()  # both running, one per replica
    with armed("replica.crash", slot=a.replica, times=1):
        router.step()  # crash -> failover; survivor's queue is at bound 0
    assert not a.done and a.failovers == 1
    assert a.status is RequestStatus.QUEUED  # parked at the router, not killed
    router.run_until_drained(max_steps=300)
    assert a.ok and len(a.output_ids) == 6  # completed once the slot freed
    assert b.ok and len(b.output_ids) == 8
    router.close()


# ------------------------------------------------------------------ breaker
def test_breaker_stall_opens_then_half_open_recovery(setup):
    """Acceptance: a stalled replica trips the slow-tick detector, its
    breaker OPENs (requests failed over), cooldown is counted in ticks, the
    HALF_OPEN probe closes it again, and it then serves new work."""
    model, params = setup
    router = ServingRouter(
        model, params, num_replicas=2, num_slots=1,
        # threshold far above a healthy tiny-model tick, far below the
        # injected stall — strikes come only from the fault
        slow_tick_threshold_s=0.25, slow_ticks_to_open=2,
        breaker_cooldown_ticks=2,
    )
    # warm both replicas first; compile ticks ARE slow, but the detector's
    # compile-tick exemption (engine program count moved) must absorb them —
    # no strikes may survive warmup
    warm = [router.submit([1, 2], max_new_tokens=1) for _ in range(2)]
    router.run_until_drained(max_steps=20)
    assert all(h.ok for h in warm)
    assert all(r.consecutive_slow == 0 for r in router.replicas), \
        "compile ticks must not strike the stall detector"
    victim = router.submit([1, 2, 3], max_new_tokens=12)
    survivor = router.submit([4, 5, 6], max_new_tokens=12)
    router.step()
    r0 = router.replicas[victim.replica]
    assert r0.consecutive_slow == 0  # healthy ticks are under the threshold
    with armed("replica.stall", slot=r0.rid, times=2, value=0.4):
        router.step()  # strike 1
        assert r0.breaker == BREAKER_CLOSED
        router.step()  # strike 2 -> OPEN, victim fails over to the survivor's replica
    assert r0.breaker == BREAKER_OPEN
    assert victim.failovers == 1 and not victim.done  # failed over, still decoding
    router.step()  # cooldown tick 1
    assert r0.breaker == BREAKER_OPEN
    router.step()  # cooldown elapsed -> HALF_OPEN, probe runs this tick
    assert r0.breaker in (BREAKER_HALF_OPEN, BREAKER_CLOSED)
    router.step()  # probe succeeded (fault exhausted): CLOSED
    assert r0.breaker == BREAKER_CLOSED
    router.run_until_drained(max_steps=200)
    assert victim.ok and survivor.ok
    assert len(victim.output_ids) == 12 and len(survivor.output_ids) == 12
    trans = router.snapshot()["breaker_transitions"]
    assert trans["closed->open"] == 1
    assert trans["open->half_open"] == 1 and trans["half_open->closed"] == 1
    # a recovered replica receives new work again
    after = router.submit([9, 9], max_new_tokens=2)
    router.run_until_drained(max_steps=50)
    assert after.ok
    router.close()


def test_breaker_crash_failover_survivor_bit_identical(x64):
    """Survivors on healthy replicas are bit-identical through a sibling's
    crash-and-failover — the router never perturbs an unaffected engine."""
    model, params = _make_model(param_dtype=jnp.float64)
    expected = _engine_reference(model, params, [[7, 3, 9], [40, 41, 42]], [6, 6])
    router = ServingRouter(model, params, num_replicas=2, num_slots=1,
                           breaker_cooldown_ticks=8)
    victim = router.submit([7, 3, 9], max_new_tokens=6)
    survivor = router.submit([40, 41, 42], max_new_tokens=6)
    router.step()
    with armed("replica.crash", slot=victim.replica, times=1):
        router.run_until_drained(max_steps=200)
    assert victim.ok and victim.result().tolist() == expected[0]
    assert survivor.ok and survivor.failovers == 0
    assert survivor.result().tolist() == expected[1]
    router.close()


def test_nan_failures_open_breaker(setup):
    """Repeated NaN containments on one replica open its breaker: the sick
    engine stops receiving work and its healthy requests fail over."""
    model, params = setup
    router = ServingRouter(model, params, num_replicas=2, num_slots=2,
                           nan_failures_to_open=1, breaker_cooldown_ticks=64)
    a = router.submit([1, 2, 3], max_new_tokens=10)   # -> r0
    b = router.submit([4, 5], max_new_tokens=10)      # -> r1
    c = router.submit([6, 7, 8], max_new_tokens=10)   # -> r0 (slot 2)
    router.step()
    r0 = router.replicas[a.replica]
    assert c.replica == a.replica != b.replica
    # poison r0's first occupied slot next tick (times=1: r0 ticks first)
    with armed("serving.nan", times=1):
        router.step()
    assert a.status is RequestStatus.FAILED and a.finish_reason == "nonfinite_logits"
    assert r0.breaker == BREAKER_OPEN  # threshold 1 tripped at harvest
    assert c.failovers == 1 and not c.done  # healthy slot-mate moved, not lost
    router.run_until_drained(max_steps=200)
    assert b.ok and c.ok and len(c.output_ids) == 10
    snap = router.snapshot()
    assert snap["replicas"][f"r{r0.rid}"]["breaker"] == BREAKER_OPEN
    assert snap["breaker_transitions"]["closed->open"] == 1
    router.close()


# ----------------------------------------------------------------- shedding
def test_shed_infeasible_deadline_rejected_at_admission(setup):
    """A deadlined request whose completion estimate (windowed p95 queue wait
    + prefill + max_new x p95 decode) exceeds its deadline is REJECTED as
    shed_infeasible at submit; requests without deadlines never shed."""
    model, params = setup
    router = ServingRouter(model, params, num_replicas=2, num_slots=1,
                           shed_min_samples=1)
    # prime every replica's latency window with measured-slow history
    for r in router.replicas:
        m = r.engine.metrics
        for i in range(4):
            m.record_submit(1000 + i, prompt_len=2)
            m.record_admit(1000 + i, slot=0, wait_s=0.5, prefill_s=0.05)
            m.record_decode_step(active_slots=1, seconds=0.2, tokens=1)
    # estimate ~= 0.5 + 0.05 + 10 * 0.2 = 2.55s >> 0.5s deadline -> shed
    shed = router.submit([1, 2], max_new_tokens=10, deadline_s=0.5)
    assert shed.status is RequestStatus.REJECTED
    assert shed.finish_reason == "shed_infeasible"
    # feasible deadline and no-deadline requests still admit
    ok_deadline = router.submit([1, 2], max_new_tokens=1, deadline_s=60.0)
    ok_plain = router.submit([3, 4], max_new_tokens=2)
    router.run_until_drained(max_steps=100)
    assert ok_deadline.ok and ok_plain.ok
    snap = router.snapshot()
    assert snap["shed_infeasible"] == 1 and snap["rejected"] == 1
    # the JSONL-free path still reports the estimate through metrics counters
    assert router.metrics.shed_infeasible == 1
    router.close()


def test_shed_disabled_and_cold_fleet_never_sheds(setup):
    model, params = setup
    cold = ServingRouter(model, params, num_replicas=1, num_slots=1)
    h = cold.submit([1, 2], max_new_tokens=2, deadline_s=30.0)  # cold: no estimates yet
    cold.run_until_drained(max_steps=50)
    assert h.ok
    cold.close()

    off = ServingRouter(model, params, num_replicas=1, num_slots=1,
                        shed_infeasible=False, shed_min_samples=1)
    m = off.replicas[0].engine.metrics
    m.record_submit(999, prompt_len=2)
    m.record_admit(999, slot=0, wait_s=5.0, prefill_s=0.5)
    m.record_decode_step(active_slots=1, seconds=5.0, tokens=1)
    h2 = off.submit([1, 2], max_new_tokens=2, deadline_s=0.0001)
    # not shed (knob off) — it will time out on its own deadline instead
    assert h2.finish_reason != "shed_infeasible"
    off.run_until_drained(max_steps=50)
    off.close()


# ------------------------------------------------------------ drain / churn
def test_router_drain_rejects_backlog_finishes_active(setup):
    model, params = setup
    router = ServingRouter(model, params, num_replicas=2, num_slots=1)
    active = [router.submit([1, 2], max_new_tokens=4) for _ in range(2)]
    assert all(h.status is RequestStatus.QUEUED for h in active)
    router.step()  # both admitted (one per replica)
    # the handle mirrors the engine surface: RUNNING once a slot is held
    assert all(h.status is RequestStatus.RUNNING for h in active)
    backlog = router.submit([3, 4], max_new_tokens=2)
    drained = router.drain(max_steps=100)
    assert all(h.ok and len(h.output_ids) == 4 for h in active)
    assert backlog.status is RequestStatus.REJECTED
    assert backlog.finish_reason == "draining"
    post = router.submit([5, 6], max_new_tokens=2)
    assert post.finish_reason == "draining"  # admission stays closed
    assert {h.request_id for h in drained} == {h.request_id for h in active} | {backlog.request_id}
    router.close()


def test_router_churn_compile_bounds_no_per_failover_recompiles(setup):
    """Acceptance: adding replicas adds at most one ladder of prefill/install
    programs per replica and one decode program per replica, and a
    crash-failover cycle compiles NOTHING new — failover re-prefill rides
    the existing bucket ladder."""
    model, params = setup
    router = ServingRouter(model, params, num_replicas=2, num_slots=2,
                           breaker_cooldown_ticks=1)
    # churn across every bucket of the ladder on both replicas
    lengths = [2, 5, 9, 3, 7, 12, 4, 11]
    handles = []
    for i, n in enumerate(lengths):
        handles.append(router.submit(list(range(1, n + 1)), max_new_tokens=3,
                                     rng=jax.random.PRNGKey(i)))
        router.step()
    router.run_until_drained(max_steps=300)
    assert all(h.ok for h in handles)

    def compile_counts():
        return [
            (r.engine.decode_compilations, r.engine.prefill_compilations,
             r.engine._jit_install._cache_size())
            for r in router.replicas
        ]

    before = compile_counts()
    for decode, prefill, install in before:
        assert decode == 1
        assert prefill <= len(router.replicas[0].engine.prefill_buckets)
        assert install <= len(router.replicas[0].engine.prefill_buckets)

    # crash/failover churn: same programs, zero new compilations
    victim = router.submit(list(range(1, 8)), max_new_tokens=5)
    router.step()
    with armed("replica.crash", slot=victim.replica, times=1):
        router.run_until_drained(max_steps=300)
    assert victim.ok and victim.failovers == 1
    for _ in range(4):
        router.step()  # recovery probe
    assert compile_counts() == before, "failover must not compile new programs"
    router.close()


def test_engine_evict_request_api(setup):
    """The engine-level eviction API the router's recovery path uses: queued
    and running requests cancel cleanly with partial output preserved."""
    model, params = setup
    engine = ServingEngine(model, params, num_slots=1)
    running = engine.submit([1, 2, 3], max_new_tokens=10)
    queued = engine.submit([4, 5], max_new_tokens=4)
    engine.step()
    assert len(running.output_ids) == 1
    got_q = engine.evict_request(queued.request_id, "cancelled",
                                 status=RequestStatus.REJECTED)
    assert got_q is queued and queued.status is RequestStatus.REJECTED
    assert queued.finish_reason == "cancelled"
    got_r = engine.evict_request(running.request_id, "cancelled",
                                 status=RequestStatus.FAILED)
    assert got_r is running and running.status is RequestStatus.FAILED
    assert running.output_ids == got_r.output_ids and len(running.output_ids) == 1
    assert engine.evict_request(running.request_id) is None  # already terminal
    assert engine.evict_request(10_000) is None  # unknown id
    assert engine.scheduler.active_slots == 0 and engine.scheduler.queue_depth == 0
    snap = engine.metrics.snapshot()
    assert snap["rejected"] == 1 and snap["failed"] == 1


# ------------------------------------------------------------------ metrics
def test_router_metrics_v4_jsonl_and_reader(tmp_path):
    """RouterMetrics emits v4 snapshots with per-replica sections; the reader
    round-trips them and still rejects unknown schemas."""
    from perceiver_io_tpu.serving import EngineMetrics

    path = tmp_path / "router.jsonl"
    rm = RouterMetrics(num_replicas=2, jsonl_path=str(path))
    rm.record_submit(0, prompt_len=3)
    rm.record_dispatch(0, replica=1, load=-1)
    rm.record_failover(0, from_replica=1, emitted_tokens=2, failover_n=1)
    rm.record_breaker(1, "closed", "open", tick=5)
    rm.record_shed(1, deadline_s=0.5, estimate_s=2.5)
    rm.record_finish(0, "finished", "length", new_tokens=6, failovers=1)
    em = EngineMetrics(num_slots=2)
    em.record_decode_step(active_slots=1, seconds=0.1, tokens=1)
    rm.write_snapshot({"r0": em.snapshot(), "r1": EngineMetrics(num_slots=2).snapshot()})
    rm.close()

    got = load_metrics_jsonl(str(path))
    events = {e["event"] for e in got["events"]}
    assert {"submit", "dispatch", "failover", "breaker", "shed", "finish", "snapshot"} <= events
    snap = got["snapshots"][0]
    assert snap["schema"] == "serving-metrics/v13"
    assert snap["failovers"] == 1 and snap["shed_infeasible"] == 1
    assert snap["breaker_transitions"] == {"closed->open": 1}
    assert snap["tokens_generated"] == 1  # aggregated over replica sections
    assert set(snap["replicas"]) == {"r0", "r1"}
    assert snap["replicas"]["r0"]["schema"] == "serving-metrics/v13"

    bad = tmp_path / "bad.jsonl"
    bad.write_text(json.dumps({"event": "snapshot", "schema": "serving-metrics/v99"}) + "\n")
    with pytest.raises(ValueError, match="unknown metrics schema"):
        load_metrics_jsonl(str(bad))


def test_router_submit_validation(setup):
    model, params = setup
    router = ServingRouter(model, params, num_replicas=1, num_slots=1)
    with pytest.raises(ValueError, match="non-empty"):
        router.submit([])
    with pytest.raises(ValueError, match="beam"):
        router.submit([1, 2], config=GenerationConfig(max_new_tokens=2, num_beams=3))
    with pytest.raises(ValueError, match="config or keyword"):
        router.submit([1, 2], config=GenerationConfig(), max_new_tokens=2)
    too_long = router.submit(list(range(WINDOW + 1)), max_new_tokens=2)
    assert too_long.status is RequestStatus.REJECTED
    assert too_long.finish_reason == "prompt_too_long"
    with pytest.raises(ValueError, match="num_replicas"):
        ServingRouter(model, params, num_replicas=0)
    router.close()


# -------------------------------------------------------- telemetry / bench
def test_router_shared_trace_per_replica_report(setup, tmp_path):
    """One shared recorder, per-replica span namespaces: the router summary
    carries serving.rN phases + merged compile report, and obs_report splits
    the trace into per-replica phase tables and per-category lifetimes."""
    import importlib.util
    import os

    model, params = setup
    trace_path = tmp_path / "router_trace.json"
    router = ServingRouter(model, params, num_replicas=2, num_slots=1,
                           telemetry=str(trace_path))
    handles = [router.submit([i + 1, i + 2], max_new_tokens=3) for i in range(3)]
    router.run_until_drained(max_steps=100)
    summary = router.telemetry_summary()
    assert "serving.r0.tick" in summary["phases"]
    assert "serving.r1.tick" in summary["phases"]
    assert "router.tick" in summary["phases"]
    assert summary["compile"]["per_function"]["serving.r0.ragged_tick"]["compilations"] == 1
    assert summary["compile"]["per_function"]["serving.r1.ragged_tick"]["compilations"] == 1
    assert summary["compile"]["unexpected"] == []
    router.close()  # writes the Chrome trace
    assert all(h.ok for h in handles)

    spec = importlib.util.spec_from_file_location(
        "obs_report_under_router_test",
        os.path.join(os.path.dirname(__file__), "..", "scripts", "obs_report.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rep = mod.report_trace(str(trace_path))
    assert rep["validation_problems"] == []
    # per-replica request namespaces (request.eN) + the router's own category
    assert len(rep["request_lifetimes_by_cat"]) >= 3
    groups = mod.split_replica_phases(rep["phases"])
    assert {"serving.r0", "serving.r1"} <= set(groups)
    tables = mod.replica_phase_tables(rep["phases"], "t")
    assert any("[serving.r0]" in line for line in tables)
    assert any("[serving.r1]" in line for line in tables)


@pytest.mark.slow  # ~3 routers' worth of compiles
def test_serve_bench_replica_scaling_smoke(tmp_path):
    """--replicas merges the scaling arm (1 vs N replica routers, shed and
    failover counters included) into the BENCH_serving.json artifact with a
    manifest sibling."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "serve_bench_replicas_under_test",
        os.path.join(os.path.dirname(__file__), "..", "scripts", "serve_bench.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    out = tmp_path / "SERVE_BENCH.json"
    pout = tmp_path / "BENCH_serving.json"
    result = mod.main([
        "--preset", "tiny", "--slots", "1", "--requests", "4",
        "--replicas", "2", "--replica-repeats", "1",
        "--no-baseline", "--no-warmup",
        "--out", str(out), "--profile-out", str(pout),
    ])
    scaling = result["replica_scaling"]
    assert scaling["replicas_1"]["tokens_per_s"] > 0
    assert scaling["replicas_2"]["tokens_per_s"] > 0
    assert scaling["admission_speedup"] > 0 and scaling["throughput_speedup"] > 0
    # no shed/failover on the healthy workload, counters reported
    for arm in ("replicas_1", "replicas_2"):
        assert scaling[arm]["failovers"] == 0 and scaling[arm]["shed_infeasible"] == 0
    on_disk = json.loads(pout.read_text())
    assert on_disk["replica_scaling"]["replicas_2"]["slots_per_replica"] == 1
    manifest = json.loads((tmp_path / "BENCH_serving.manifest.json").read_text())
    assert manifest["schema"] == "run-manifest/v1"


# ------------------------------------------------------------------ signals
def test_sigterm_graceful_drain_flushes_metrics(setup, tmp_path):
    """Satellite: SIGTERM mid-serve closes admission, rejects the backlog,
    finishes active slots, and flushes the terminal metrics snapshot — then
    the previous handlers are back (once-only)."""
    model, params = setup
    prev_term = signal.getsignal(signal.SIGTERM)
    log = tmp_path / "router.jsonl"
    router = ServingRouter(model, params, num_replicas=2, num_slots=1,
                           handle_preemption=True, metrics_jsonl=str(log),
                           replica_metrics_jsonl=str(tmp_path / "eng.r{i}.jsonl"))
    active = [router.submit([1, 2, 3], max_new_tokens=6) for _ in range(2)]
    router.step()  # both admitted
    backlog = router.submit([4, 5], max_new_tokens=2)
    signal.raise_signal(signal.SIGTERM)  # delivered to the main thread
    assert signal.getsignal(signal.SIGTERM) == prev_term  # once-only: restored as it fired
    drained = router.run_until_drained(max_steps=100)
    assert router.preempted
    assert all(h.ok and len(h.output_ids) == 6 for h in active)  # in-flight finished
    assert backlog.finish_reason == "draining" and not backlog.ok
    assert len(drained) == 3
    post = router.submit([6], max_new_tokens=1)
    assert post.finish_reason == "draining"
    # the terminal snapshot landed in the JSONL before exit
    got = load_metrics_jsonl(str(log))
    assert got["snapshots"], "preemption must flush the final snapshot"
    assert got["snapshots"][-1]["requests_finished"] == 2
    # per-replica engine streams were written via the {i} template
    for i in range(2):
        eng_log = load_metrics_jsonl(str(tmp_path / f"eng.r{i}.jsonl"))
        assert any(e["event"] == "admit" for e in eng_log["events"])
    router.close()  # idempotent after the preemption flush


def test_engine_sigterm_graceful_drain(setup, tmp_path):
    """The engine-level handler mirrors the router's: drain + flush."""
    model, params = setup
    prev_term = signal.getsignal(signal.SIGTERM)
    log = tmp_path / "engine.jsonl"
    engine = ServingEngine(model, params, num_slots=1, handle_preemption=True,
                           metrics_jsonl=str(log))
    active = engine.submit([1, 2], max_new_tokens=5)
    engine.step()
    backlog = engine.submit([3, 4], max_new_tokens=2)
    signal.raise_signal(signal.SIGINT)
    while engine.step():
        pass
    assert engine.preempted
    assert active.ok and len(active.output_ids) == 5
    assert backlog.finish_reason == "draining"
    assert signal.getsignal(signal.SIGTERM) == prev_term
    got = load_metrics_jsonl(str(log))
    assert got["snapshots"] and got["snapshots"][-1]["requests_finished"] == 1
    engine.close()


# --------------------------------------------------- pending expiry (ISSUE 10)
def test_expire_pending_terminal_event_carries_partial_tokens(setup, tmp_path):
    """ISSUE 10 satellite: a TTL-expired PARKED failover continuation — held
    in the router queue because no replica is healthy — goes TIMED_OUT with
    its already-emitted partial tokens on both the handle and the terminal
    metrics event, mirroring the parked-deadline contract PR 9 pinned for
    preempted continuations. A silent loss (or a zero-token terminal event)
    here would make the failover salvage unauditable."""
    import time as _time

    model, params = setup
    log = tmp_path / "router.jsonl"
    router = ServingRouter(model, params, num_replicas=1, num_slots=1,
                           breaker_cooldown_ticks=512,  # stays OPEN throughout
                           metrics_jsonl=str(log))
    warm = router.submit([9, 9], max_new_tokens=1)  # compile outside the TTL
    router.run_until_drained(max_steps=50)
    assert warm.ok
    victim = router.submit([1, 2, 3], max_new_tokens=10, deadline_s=2.0)
    k = 3
    for _ in range(k):
        router.step()
    assert len(victim.output_ids) == k
    with armed("replica.crash", slot=victim.replica, times=1):
        router.step()  # replica lost; the only replica -> continuation PARKS
    assert not victim.done and victim.status is RequestStatus.QUEUED
    assert len(victim.output_ids) == k  # salvage kept while parked

    deadline = _time.perf_counter() + 10.0
    while not victim.done and _time.perf_counter() < deadline:
        router.step()  # the fleet is down; only _expire_pending can act
        _time.sleep(0.02)
    assert victim.status is RequestStatus.TIMED_OUT
    assert victim.finish_reason == "deadline"
    assert victim.result().tolist() and len(victim.result()) == k  # partials kept

    got = load_metrics_jsonl(str(log))
    finish = next(e for e in got["events"]
                  if e["event"] == "finish" and e["request_id"] == victim.request_id)
    assert finish["status"] == "timed_out"
    assert finish["new_tokens"] == k  # the terminal EVENT carries the salvage
    router.close()


# ------------------------------------------------------- journal recovery
def test_router_journal_recovery_f64_identity(x64, tmp_path):
    """ISSUE 10: ``ServingRouter.recover`` rebuilds the whole fleet from the
    per-replica journals after process death — every accepted session
    completes f64 token-identical to an uninterrupted run, placement
    preserved, and a post-recovery drain finishes in-flight continuations
    while rejecting only never-admitted backlog."""
    model, params = _make_model(param_dtype=jnp.float64)
    prompts = [[7, 3, 9], [40, 41, 42, 43], [100, 101], [250]]
    max_new = [5, 4, 6, 3]
    expected = _engine_reference(model, params, prompts, max_new)

    template = str(tmp_path / "r{i}")
    router = ServingRouter(model, params, num_replicas=2, num_slots=1,
                           journal=template)
    handles = [router.submit(p, max_new_tokens=m)
               for p, m in zip(prompts, max_new)]
    for _ in range(2):
        router.step()  # two running (one per replica), two queued, mid-decode
    # process death: the router object is abandoned; recover a fresh fleet
    router2, info = ServingRouter.recover(model, params, template,
                                          num_replicas=2, num_slots=1)
    assert info["sessions"] == 4
    router2.run_until_drained(max_steps=500)
    by_prompt = {tuple(h.prompt_ids.tolist()): h for h in info["handles"]}
    for p, want in zip(prompts, expected):
        h = by_prompt[tuple(p)]
        assert h.ok, f"prompt {p}: {h.status}"
        assert h.result().tolist() == want, f"prompt {p} diverged after recovery"
    # zero extra compiled programs during replay, fleet-wide
    for r in router2.replicas:
        assert r.engine.decode_compilations == 1
    snap = router2.snapshot()
    assert snap["requests_submitted"] == 4 == snap["requests_finished"]
    router2.close()


def test_router_journal_template_validation(setup, tmp_path):
    model, params = setup
    with pytest.raises(ValueError, match="template"):
        ServingRouter(model, params, num_replicas=2,
                      journal=str(tmp_path / "flat"))
    with pytest.raises(ValueError, match="template"):
        ServingRouter.recover(model, params, str(tmp_path / "flat"),
                              num_replicas=2)


def test_dispatch_journal_failure_contained_as_replica_fault(setup, tmp_path):
    """Code-review fix: a journal append failure inside a replica's
    ``submit()`` (real ENOSPC/EIO, or a fail-stopped journal refusing
    appends) is contained as a REPLICA fault — breaker strike, request
    placed on a healthy sibling — instead of propagating out of
    ``router.submit()`` and crashing the fleet on one replica's disk."""
    model, params = setup
    template = str(tmp_path / "r{i}")
    router = ServingRouter(model, params, num_replicas=2, num_slots=1,
                           journal=template)
    # the torn write hits r0's journal (least-loaded tie -> lowest index)
    with armed("serving.journal.torn_write", times=1):
        h = router.submit([1, 2, 3], max_new_tokens=3)
    assert h.replica == 1  # contained: landed on the healthy sibling
    assert router.replicas[0].engine.journal.failed
    router.run_until_drained(max_steps=200)
    assert h.ok and len(h.result()) == 3
    # the fail-stopped journal refuses appends FOREVER: every later dispatch
    # attempt at r0 strikes its breaker, and the fleet keeps serving
    handles = [router.submit([i + 2, i + 3], max_new_tokens=2)
               for i in range(6)]
    router.run_until_drained(max_steps=400)
    assert all(hh.ok for hh in handles)
    assert all(hh.replica == 1 for hh in handles)
    snap = router.snapshot()
    assert snap["requests_submitted"] == 7 == snap["requests_finished"]
    router.close()


def test_failover_origin_closed_no_duplicate_recovery(x64, tmp_path):
    """Code-review fix: once a failover LANDS on a new replica (fresh accept
    journaled there, replay prefix included), the origin replica's journal
    entry is closed — a process death in that window must recover the
    session exactly ONCE. Previously both journals held it live and
    ``ServingRouter.recover`` executed the same logical request twice."""
    from perceiver_io_tpu.serving import read_journal

    model, params = _make_model(param_dtype=jnp.float64)
    prompts = [[7, 3, 9]]
    expected = _engine_reference(model, params, prompts, [6])
    template = str(tmp_path / "r{i}")
    router = ServingRouter(model, params, num_replicas=2, num_slots=1,
                           journal=template)
    victim = router.submit(prompts[0], max_new_tokens=6)
    for _ in range(2):
        router.step()  # running on r0, mid-decode
    assert victim.replica == 0
    with armed("replica.crash", slot=0, times=1):
        router.step()  # r0 lost; the failover LANDS on healthy r1
    assert victim.replica == 1
    # the origin entry is closed: r0's journal holds no live session, r1's
    # fresh accept is now the continuation's one durable copy
    assert read_journal(template.format(i=0)).sessions == []
    assert len(read_journal(template.format(i=1)).sessions) == 1
    # process death NOW (the duplicate-execution window): recover the fleet
    router2, info = ServingRouter.recover(model, params, template,
                                          num_replicas=2, num_slots=1)
    assert info["sessions"] == 1  # exactly once, not once per journal
    router2.run_until_drained(max_steps=300)
    h = info["handles"][0]
    assert h.ok
    assert h.result().tolist() == expected[0]
    snap = router2.snapshot()
    assert snap["requests_submitted"] == 1 == snap["requests_finished"]
    router2.close()


def test_parked_continuation_durable_across_process_death(x64, tmp_path):
    """Code-review fix: a failover continuation PARKED at the router (no
    healthy replica to land on) keeps its origin replica's journal entry
    LIVE — it is the session's only durable copy. Process death while parked
    recovers the session from that journal, token-identical, instead of
    losing accepted work."""
    from perceiver_io_tpu.serving import read_journal

    model, params = _make_model(param_dtype=jnp.float64)
    prompts = [[7, 3, 9]]
    expected = _engine_reference(model, params, prompts, [6])
    template = str(tmp_path / "r{i}")
    router = ServingRouter(model, params, num_replicas=1, num_slots=1,
                           journal=template, breaker_cooldown_ticks=512)
    victim = router.submit(prompts[0], max_new_tokens=6)
    for _ in range(2):
        router.step()  # mid-decode
    with armed("replica.crash", slot=0, times=1):
        router.step()  # only replica lost -> continuation PARKS
    assert not victim.done and victim.status is RequestStatus.QUEUED
    assert victim.replica is None
    # parked: the origin journal still holds the session live
    assert len(read_journal(template.format(i=0)).sessions) == 1
    # process death while parked: the origin journal recovers the session
    router2, info = ServingRouter.recover(model, params, template,
                                          num_replicas=1, num_slots=1)
    assert info["sessions"] == 1
    router2.run_until_drained(max_steps=300)
    h = info["handles"][0]
    assert h.ok
    assert h.result().tolist() == expected[0]
    router2.close()


def test_parked_expiry_closes_origin_journal_entry(setup, tmp_path):
    """Code-review fix companion: a parked continuation that resolves
    terminally at the ROUTER (TTL expiry) closes its origin journal entry
    with the real outcome — a later recovery must not resurrect a request
    the caller already saw go terminal."""
    import time as _time

    from perceiver_io_tpu.serving import read_journal

    model, params = setup
    template = str(tmp_path / "r{i}")
    router = ServingRouter(model, params, num_replicas=1, num_slots=1,
                           journal=template, breaker_cooldown_ticks=512)
    warm = router.submit([9, 9], max_new_tokens=1)  # compile outside the TTL
    router.run_until_drained(max_steps=50)
    assert warm.ok
    victim = router.submit([1, 2, 3], max_new_tokens=10, deadline_s=1.5)
    for _ in range(2):
        router.step()
    with armed("replica.crash", slot=0, times=1):
        router.step()  # only replica lost -> continuation PARKS
    assert victim.status is RequestStatus.QUEUED
    deadline = _time.perf_counter() + 10.0
    while not victim.done and _time.perf_counter() < deadline:
        router.step()
        _time.sleep(0.02)
    assert victim.status is RequestStatus.TIMED_OUT
    # the origin entry closed with the real outcome: nothing to resurrect
    assert read_journal(template.format(i=0)).sessions == []
    router2, info = ServingRouter.recover(model, params, template,
                                          num_replicas=1, num_slots=1)
    assert info["sessions"] == 0
    router2.close()
    router.close()
