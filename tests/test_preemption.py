"""Priority-aware admission + deterministic session preemption/resume
(docs/serving.md "Priority classes & preemption"; ISSUE 9).

The parity contract: a preempted-and-resumed request — greedy AND sampled —
is f64 token-identical to an uncontended run (rng chain included), at prompt
lengths straddling every prefill-ladder rung. The determinism contract:
victim selection is a pure function of (priority, admission order, page
count), so repeat runs pin exact victim identity. The churn contract: a
preempt/resume cycle compiles NOTHING new (1 decode program, <= ladder
prefill/install programs). The kill-switch contract: with
PERCEIVER_IO_TPU_DISABLE_PREEMPTION=1 the engine is bit-identical to the
pre-priority FIFO engine.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.generation.generate import GenerationConfig
from perceiver_io_tpu.models.core.config import CausalSequenceModelConfig
from perceiver_io_tpu.models.core.perceiver_ar import CausalSequenceModel
from perceiver_io_tpu.serving import (
    RequestStatus,
    ServingEngine,
    ServingRouter,
    SlotScheduler,
    load_metrics_jsonl,
    preemption_enabled,
)

VOCAB = 262
WINDOW = 12
LATENTS = 6
PAGE = 2  # 5 pages per (bucket 6 + 4 new) reservation; 6 per full window


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


def _uncontended(model, params, prompts, max_new=4, rngs=None, configs=None):
    """Reference run with the default (uncontended) pool: pressure and
    preemption must be invisible in the tokens."""
    engine = ServingEngine(model, params, num_slots=len(prompts), kv_page_size=PAGE)
    handles = []
    for i, p in enumerate(prompts):
        kw = {"config": configs[i]} if configs else {"max_new_tokens": max_new}
        if rngs:
            kw["rng"] = rngs[i]
        handles.append(engine.submit(p, **kw))
    engine.run_until_drained(max_steps=400)
    assert all(h.ok for h in handles)
    return [h.result().tolist() for h in handles]


def _contended_pool_kwargs(reservation_pages=5, fits=2):
    """A pool sized to hold exactly ``fits`` reservations (+ trash page)."""
    return dict(kv_page_size=PAGE, num_kv_pages=fits * reservation_pages + 1)


# ---------------------------------------------------------------- scheduler
def test_scheduler_priority_order_and_fifo_within_class():
    s = SlotScheduler(2)
    s.enqueue("low-a", priority=0)
    s.enqueue("hi-a", priority=1)
    s.enqueue("low-b", priority=0)
    s.enqueue("hi-b", priority=1)
    # higher class first; FIFO (enqueue order) within a class
    assert list(s.pop_admissible()) == [(0, "hi-a"), (1, "hi-b")]
    assert s.peek() == "low-a"
    s.release(0)
    assert list(s.pop_admissible()) == [(0, "low-a")]
    # queued() is the admission-order view
    assert list(s.queued()) == ["low-b"]


def test_scheduler_seq_restores_seniority():
    """A re-queued entry carrying its original seq (the engine passes its
    request id) resumes its original FIFO position within its class."""
    s = SlotScheduler(1)
    s.enqueue("r0", priority=0, seq=0)
    s.enqueue("r1", priority=0, seq=1)
    s.enqueue("r2", priority=0, seq=2)
    assert list(s.pop_admissible()) == [(0, "r0")]
    # r1 is "preempted" elsewhere and re-queued mid-flight: seq 1 puts it
    # back AHEAD of r2, not at the back
    removed = s.prune_queue(lambda r: r == "r1")
    assert removed == ["r1"]
    s.enqueue("r1", priority=0, seq=1)
    assert list(s.queued()) == ["r1", "r2"]


def test_scheduler_aging_promotes_starved_entries():
    s = SlotScheduler(1, aging_ticks=2)
    s.enqueue("old-low", priority=0)
    for _ in range(4):
        s.advance_tick()
    # a fresh class-1 arrival would normally outrank class 0, but the starved
    # entry has aged two classes (4 ticks / aging_ticks=2)
    s.enqueue("fresh-hi", priority=1)
    assert s.peek() == "old-low"
    # without aging the fresh high-class entry wins
    s2 = SlotScheduler(1)
    s2.enqueue("old-low", priority=0)
    for _ in range(4):
        s2.advance_tick()
    s2.enqueue("fresh-hi", priority=1)
    assert s2.peek() == "fresh-hi"
    with pytest.raises(ValueError, match="aging_ticks"):
        SlotScheduler(1, aging_ticks=0)


def test_preemption_enabled_kill_switch(monkeypatch):
    monkeypatch.delenv("PERCEIVER_IO_TPU_DISABLE_PREEMPTION", raising=False)
    assert preemption_enabled()
    monkeypatch.setenv("PERCEIVER_IO_TPU_DISABLE_PREEMPTION", "1")
    assert not preemption_enabled()


# ------------------------------------------------------------------- parity
def test_preempted_resume_f64_identity_across_ladder(x64):
    """Acceptance: preempted-and-resumed greedy requests are f64
    token-identical to an uncontended run, at prompt lengths straddling every
    prefill-ladder rung (1 / bucket / bucket+1 / window), with deterministic
    victim identity across repeat runs and zero new compiled programs per
    preempt/resume cycle."""
    model, params = _make_model(param_dtype=jnp.float64)
    from perceiver_io_tpu.serving.paging import pages_for_request

    for n in (1, LATENTS, LATENTS + 1, WINDOW):
        prompts = [list(range(3, 3 + n)), list(range(20, 20 + n)), list(range(40, 40 + n))]
        expected = _uncontended(model, params, prompts)

        bucket = LATENTS if n <= LATENTS else WINDOW
        need = pages_for_request(bucket, 4, WINDOW, PAGE)

        def run():
            engine = ServingEngine(model, params, num_slots=3,
                                   **_contended_pool_kwargs(need, fits=2))
            bg = [engine.submit(p, max_new_tokens=4) for p in prompts[:2]]
            engine.step()  # both admitted, one token each
            assert all(h.status is RequestStatus.RUNNING for h in bg)
            hi = engine.submit(prompts[2], max_new_tokens=4, priority=1)
            engine.step()  # blocked on pages -> preempts one victim, admits
            assert hi.status is RequestStatus.RUNNING, f"len {n}: no preemptive admit"
            victim = next(h for h in bg if h.preemptions == 1)
            assert victim.status is RequestStatus.PREEMPTED
            # the RESUME must compile NOTHING: the forced-token replay rides
            # the one decode program and the re-prefill rides the warm bucket
            # (every program — release included — compiled by this point)
            compiles_mid = engine.total_compilations
            engine.run_until_drained(max_steps=400)
            assert engine.total_compilations == compiles_mid
            assert engine.decode_compilations == 1
            assert engine._jit_install._cache_size() <= len(engine.prefill_buckets)
            assert engine._pool.pages_in_use == 0
            handles = bg + [hi]
            return ([h.result().tolist() for h in handles],
                    [h.status.value for h in handles],
                    victim.request_id, engine.metrics.preemptions)

        toks1, statuses1, victim1, npreempt1 = run()
        toks2, statuses2, victim2, _ = run()
        assert statuses1 == ["finished"] * 3 == statuses2
        assert toks1 == expected, f"len {n}: preempt/resume diverged from uncontended"
        assert (toks1, victim1) == (toks2, victim2), f"len {n}: not deterministic"
        # the deterministic victim: same class + page count -> youngest
        # admission loses (least replay work)
        assert victim1 == 1
        assert npreempt1 == 1


def test_preempted_resume_f64_identity_sampled(x64):
    """Sampled requests resume identically too: the forced replay re-advances
    the per-slot rng chain exactly, so the post-resume sampled continuation
    matches the uncontended run token for token."""
    model, params = _make_model(param_dtype=jnp.float64)
    prompts = [[3, 4, 5], [20, 21], [40, 41, 42]]
    cfg = GenerationConfig(max_new_tokens=5, do_sample=True, temperature=0.8, top_k=50)
    rngs = [jax.random.PRNGKey(100 + i) for i in range(3)]
    expected = _uncontended(model, params, prompts, configs=[cfg] * 3, rngs=rngs)

    def run():
        engine = ServingEngine(model, params, num_slots=3,
                               **_contended_pool_kwargs(5, fits=2))
        bg = [engine.submit(p, config=cfg, rng=r) for p, r in zip(prompts[:2], rngs[:2])]
        engine.step()
        hi = engine.submit(prompts[2], config=cfg, rng=rngs[2], priority=1)
        engine.step()
        assert hi.status is RequestStatus.RUNNING
        assert sum(h.preemptions for h in bg) == 1
        engine.run_until_drained(max_steps=400)
        return [h.result().tolist() for h in bg + [hi]]

    toks = run()
    assert toks == expected
    assert toks == run()  # deterministic repeat


def test_dense_slot_pressure_preemption(x64):
    """Preemption also covers SLOT pressure on dense (non-paged) engines: a
    higher-class head with no free slot evicts the youngest lower-class
    running slot, and the resumed victim stays token-identical."""
    model, params = _make_model(param_dtype=jnp.float64)
    prompts = [[3, 4, 5], [20, 21], [40, 41, 42]]
    # dense uncontended reference
    ref_engine = ServingEngine(model, params, num_slots=3)
    refs = [ref_engine.submit(p, max_new_tokens=4) for p in prompts]
    ref_engine.run_until_drained(max_steps=200)
    expected = [h.result().tolist() for h in refs]

    engine = ServingEngine(model, params, num_slots=2)
    bg = [engine.submit(p, max_new_tokens=4) for p in prompts[:2]]
    engine.step()
    hi = engine.submit(prompts[2], max_new_tokens=4, priority=1)
    engine.step()
    assert hi.status is RequestStatus.RUNNING
    victim = next(h for h in bg if h.preemptions == 1)
    assert victim is bg[1]  # youngest admission, same class
    engine.run_until_drained(max_steps=300)
    assert [h.result().tolist() for h in bg + [hi]] == expected
    assert engine.decode_compilations == 1


# ------------------------------------------------------------------ bounds
def test_max_preemptions_bounds_then_untouchable(setup):
    """After max_preemptions preemptions a request runs to completion
    untouchable — no livelock: later high-class arrivals wait instead."""
    model, params = setup
    # 6 allocatable pages: exactly one (bucket 6 + 6 new -> 6 page) session
    engine = ServingEngine(model, params, num_slots=2, max_preemptions=1,
                           kv_page_size=PAGE, num_kv_pages=7)
    bg = engine.submit([3, 4, 5], max_new_tokens=6)
    engine.step()
    hi1 = engine.submit([20, 21], max_new_tokens=2, priority=1)
    engine.step()
    assert hi1.status is RequestStatus.RUNNING and bg.preemptions == 1
    # drain hi1; bg resumes (replay) and decodes on
    while not hi1.done:
        engine.step()
    while bg.status is not RequestStatus.RUNNING:
        engine.step()
    # a second high-class arrival finds bg at its preemption budget: it WAITS
    hi2 = engine.submit([40, 41], max_new_tokens=2, priority=1)
    engine.step()
    assert hi2.status is RequestStatus.QUEUED  # no victim available
    assert bg.preemptions == 1
    engine.run_until_drained(max_steps=300)
    assert bg.ok and hi1.ok and hi2.ok
    assert len(bg.output_ids) == 6
    assert engine.metrics.preemptions == 1
    assert engine.metrics.preempted_replays == 1


def test_victim_set_minimized_no_useless_eviction(setup):
    """The cross-class greedy must not evict a victim whose pages a later,
    larger victim makes redundant: a class-0 slot holding a small reservation
    survives when the class-1 slot alone covers the head's need — no replay
    is burned for zero admission benefit."""
    model, params = setup
    # 10 allocatable pages: class-0 small (4 pages) + class-1 large (6 pages)
    engine = ServingEngine(model, params, num_slots=3, kv_page_size=PAGE,
                           num_kv_pages=11)
    small = engine.submit([3, 4, 5], max_new_tokens=2)  # class 0, 4 pages
    big = engine.submit([20, 21], max_new_tokens=6, priority=1)  # class 1, 6 pages
    engine.step()
    assert small.pages_allocated == 4 and big.pages_allocated == 6
    hi = engine.submit([40, 41, 42], max_new_tokens=6, priority=2)  # needs 6
    engine.step()
    assert hi.status is RequestStatus.RUNNING
    # ONLY the class-1 victim was evicted — it alone covers the need; the
    # greedy's class-0 pick was dropped by the minimization pass
    assert big.preemptions == 1 and big.status is RequestStatus.PREEMPTED
    assert small.preemptions == 0 and small.status is not RequestStatus.PREEMPTED
    assert engine.metrics.preemptions == 1
    engine.run_until_drained(max_steps=300)
    assert small.ok and big.ok and hi.ok


def test_equal_class_never_preempts(setup):
    """Preemption needs STRICTLY lower class: same-class pressure is plain
    backpressure (the head waits), exactly the pre-priority contract."""
    model, params = setup
    engine = ServingEngine(model, params, num_slots=2,
                           kv_page_size=PAGE, num_kv_pages=7)
    a = engine.submit([3, 4, 5], max_new_tokens=6, priority=1)
    engine.step()
    b = engine.submit([20, 21], max_new_tokens=2, priority=1)
    engine.step()
    assert b.status is RequestStatus.QUEUED and a.preemptions == 0
    engine.run_until_drained(max_steps=200)
    assert a.ok and b.ok and engine.metrics.preemptions == 0


def test_aging_promotes_starved_request_in_engine(setup):
    """Engine-level anti-starvation: with priority_aging_ticks set, a starved
    class-0 request eventually outranks LATER class-1 submits in queue order
    (aging raises queue rank — it never makes the aged request preempt).
    max_preemptions=0 makes every admitted request untouchable (priorities
    order the queue, nothing is ever evicted), isolating the aging order —
    with preemption on, the class-1 arrival would win the slot back by
    preempting the freshly admitted aged request, which is by design (aging
    protects queue rank, not slot tenure)."""
    model, params = setup
    engine = ServingEngine(model, params, num_slots=1, priority_aging_ticks=1,
                           max_preemptions=0)
    running = engine.submit([3, 4, 5], max_new_tokens=6)
    starved = engine.submit([20, 21], max_new_tokens=2)  # class 0, queued
    for _ in range(3):
        engine.step()  # starved ages 3 classes while the slot is held
    late_hi = engine.submit([40, 41], max_new_tokens=2, priority=1)
    engine.run_until_drained(max_steps=200)
    assert running.ok and starved.ok and late_hi.ok
    # the aged class-0 request admitted BEFORE the late class-1 submit
    assert starved.admitted_at < late_hi.admitted_at
    assert engine.metrics.preemptions == 0  # aging never preempted anything


def test_drain_finishes_preempted_continuations(setup):
    """Drain's "in-flight work is finished, not dropped" contract covers a
    PREEMPTED continuation: it is accepted mid-generation work (tokens may
    already be streamed), so drain re-admits and finishes it instead of
    sweeping it into the rejected backlog; never-admitted queued work is
    still rejected as 'draining'."""
    model, params = setup
    engine = ServingEngine(model, params, num_slots=2,
                           **_contended_pool_kwargs(5, fits=2))
    bg = [engine.submit(p, max_new_tokens=4) for p in ([3, 4, 5], [20, 21])]
    engine.step()
    hi = engine.submit([40, 41, 42], max_new_tokens=4, priority=1)
    engine.step()
    victim = next(h for h in bg if h.preemptions == 1)
    assert victim.status is RequestStatus.PREEMPTED
    backlog = engine.submit([7, 8], max_new_tokens=2)  # never admitted
    drained = engine.drain(max_steps=300)
    # the victim finished its full generation through the drain loop
    assert victim.ok and len(victim.output_ids) == 4
    assert hi.ok and all(h.ok for h in bg)
    assert backlog.status is RequestStatus.REJECTED
    assert backlog.finish_reason == "draining"
    assert {h.request_id for h in drained} == {h.request_id for h in bg + [hi, backlog]}


def test_preempted_deadline_expiry_reports_emitted_tokens(setup, tmp_path):
    """A preempted continuation whose deadline expires while parked held a
    slot and emitted tokens: the terminal event must carry them (the
    never-admitted case stays 0), so the stream's accounting matches the
    handle and the preempt event."""
    model, params = setup
    path = tmp_path / "expiry.jsonl"
    engine = ServingEngine(model, params, num_slots=2, metrics_jsonl=str(path),
                           **_contended_pool_kwargs(5, fits=2))
    bg = [engine.submit(p, max_new_tokens=4, deadline_s=120.0)
          for p in ([3, 4, 5], [20, 21])]
    engine.step()
    hi = engine.submit([40, 41, 42], max_new_tokens=4, priority=1)
    engine.step()
    victim = next(h for h in bg if h.preemptions == 1)
    emitted = len(victim.output_ids)
    assert victim.status is RequestStatus.PREEMPTED and emitted >= 1
    victim.deadline_s = 0.0  # expire it while parked
    engine.step()
    assert victim.status is RequestStatus.TIMED_OUT
    assert len(victim.output_ids) == emitted  # partial output preserved
    engine.run_until_drained(max_steps=200)
    engine.close()
    events = load_metrics_jsonl(str(path))["events"]
    terminal = next(e for e in events if e["event"] == "finish"
                    and e["request_id"] == victim.request_id)
    assert terminal["status"] == "timed_out"
    assert terminal["new_tokens"] == emitted  # decode work not erased
    preempt = next(e for e in events if e["event"] == "preempt")
    assert preempt["emitted_tokens"] == emitted  # the two events agree


# ------------------------------------------------------------- kill-switch
def test_kill_switch_restores_fifo_and_f64_parity(x64, monkeypatch):
    """PERCEIVER_IO_TPU_DISABLE_PREEMPTION=1: priorities are ignored (strict
    FIFO), nothing is preempted, and statuses AND tokens are bit-identical to
    the same workload at all-default priorities on an unswitched engine (the
    pre-priority behavior)."""
    model, params = _make_model(param_dtype=jnp.float64)
    prompts = [[3, 4, 5], [20, 21], [40, 41, 42]]

    def run(disable, priorities):
        if disable:
            monkeypatch.setenv("PERCEIVER_IO_TPU_DISABLE_PREEMPTION", "1")
        else:
            monkeypatch.delenv("PERCEIVER_IO_TPU_DISABLE_PREEMPTION", raising=False)
        engine = ServingEngine(model, params, num_slots=3,
                               **_contended_pool_kwargs(5, fits=2))
        bg = [engine.submit(p, max_new_tokens=4) for p in prompts[:2]]
        engine.step()
        hi = engine.submit(prompts[2], max_new_tokens=4, priority=priorities[2])
        engine.step()
        engine.run_until_drained(max_steps=400)
        handles = bg + [hi]
        return ([h.status.value for h in handles],
                [h.result().tolist() for h in handles],
                engine.metrics.preemptions, engine.priority_preemption)

    sts_off, toks_off, preempts_off, feature_off = run(True, (0, 0, 2))
    sts_base, toks_base, preempts_base, feature_base = run(False, (0, 0, 0))
    assert not feature_off and feature_base
    assert preempts_off == 0 and preempts_base == 0
    # bit-identical to the pre-priority FIFO engine
    assert (sts_off, toks_off) == (sts_base, toks_base)


# ---------------------------------------------------------------- metrics
def test_metrics_v6_preemption_counters_and_reader(setup, tmp_path):
    model, params = setup
    path = tmp_path / "preempt.jsonl"
    engine = ServingEngine(model, params, num_slots=3, metrics_jsonl=str(path),
                           **_contended_pool_kwargs(5, fits=2))
    bg = [engine.submit(p, max_new_tokens=4) for p in ([3, 4, 5], [20, 21])]
    engine.step()
    hi = engine.submit([40, 41, 42], max_new_tokens=4, priority=1)
    engine.step()
    engine.run_until_drained(max_steps=300)
    snap = engine.metrics.write_snapshot()
    engine.close()
    assert all(h.ok for h in bg) and hi.ok

    assert snap["schema"] == "serving-metrics/v13"
    assert snap["preemptions"] == 1
    assert snap["preempted_replays"] == 1
    assert set(snap["queue_wait_by_priority"]) == {"0", "1"}
    assert snap["queue_wait_by_priority"]["1"]["p95"] is not None

    got = load_metrics_jsonl(str(path))
    preempts = [e for e in got["events"] if e["event"] == "preempt"]
    assert len(preempts) == 1
    assert preempts[0]["preempted_by"] == hi.request_id
    assert preempts[0]["pages_freed"] == 5
    assert preempts[0]["priority"] == 0
    resumed = [e for e in got["events"]
               if e["event"] == "admit" and e.get("preempted_replay")]
    assert len(resumed) == 1 and resumed[0]["request_id"] == preempts[0]["request_id"]
    submits = [e for e in got["events"] if e["event"] == "submit"]
    assert [e["priority"] for e in submits] == [0, 0, 1]

    # pre-v6 snapshots normalize the new fields to None; unknown schemas raise
    v5 = tmp_path / "v5.jsonl"
    v5.write_text(json.dumps({
        "event": "snapshot", "ts": 1.0, "schema": "serving-metrics/v5",
        "num_slots": 2, "tokens_generated": 5, "page_pool": None,
    }) + "\n")
    old = load_metrics_jsonl(str(v5))["snapshots"][0]
    assert old["preemptions"] is None
    assert old["preempted_replays"] is None
    assert old["queue_wait_by_priority"] is None
    bad = tmp_path / "bad.jsonl"
    bad.write_text(json.dumps({"event": "snapshot", "schema": "serving-metrics/v99"}) + "\n")
    with pytest.raises(ValueError, match="unknown metrics schema"):
        load_metrics_jsonl(str(bad))


# ------------------------------------------------------------------ router
def test_router_forwards_priority_and_aggregates_preemptions(setup):
    """The router forwards ``priority`` verbatim to its engines, mirrors the
    PREEMPTED status on the routed handle, counts preempted-replay parking in
    dispatch load, and aggregates the v6 counters over replica sections."""
    model, params = setup
    router = ServingRouter(model, params, num_replicas=1, num_slots=3,
                           kv_page_size=PAGE, num_kv_pages=11)
    bg = [router.submit(p, max_new_tokens=4) for p in ([3, 4, 5], [20, 21])]
    router.step()
    engine = router.replicas[0].engine
    load_before = engine.load  # both bg running, queue empty
    hi = router.submit([40, 41, 42], max_new_tokens=4, priority=1)
    assert hi._engine_handle.priority == 1  # forwarded verbatim
    router.step()
    victim = next(h for h in bg if h._engine_handle.preemptions == 1)
    assert victim.status is RequestStatus.PREEMPTED  # mirrored on the handle
    # the preempted continuation parks in the queue: dispatch load sees it
    assert engine.load > load_before
    router.run_until_drained(max_steps=300)
    assert all(h.ok for h in bg) and hi.ok
    snap = router.snapshot()
    assert snap["preemptions"] == 1 and snap["preempted_replays"] == 1
    assert snap["queue_wait_by_priority"] is None  # per-engine stat
    assert snap["replicas"]["r0"]["preemptions"] == 1
    assert set(snap["replicas"]["r0"]["queue_wait_by_priority"]) == {"0", "1"}
    router.close()


# -------------------------------------------------------------- serve_bench
def test_serve_bench_priority_arm_smoke(tmp_path):
    """CI satellite: ``serve_bench --priority-arm`` writes the mixed-priority
    overload block — preemption-on vs kill-switch-off TTFT/deadline-miss —
    into BENCH_serving.json, with identical snapshot schemas across arms."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "serve_bench_priority_under_test",
        os.path.join(os.path.dirname(__file__), "..", "scripts", "serve_bench.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    out = tmp_path / "SERVE_BENCH.json"
    profile_out = tmp_path / "BENCH_serving.json"
    result = mod.main([
        "--preset", "tiny", "--slots", "2", "--requests", "3",
        "--priority-arm", "--priority-repeats", "1", "--no-baseline",
        "--out", str(out), "--profile-out", str(profile_out),
    ])
    block = result["priority_preemption"]
    on, off = block["preemption_on"], block["preemption_off"]
    assert on["preemptions"] > 0  # the contended workload actually preempted
    assert off["preemptions"] == 0  # the kill-switch arm never did
    assert on["hi_ttft_p95_s"] > 0 and off["hi_ttft_p95_s"] > 0
    assert 0 <= on["deadline_miss_rate"] <= 1
    assert block["schema_keys_identical"]  # kill-switch arm: same v6 schema
    on_disk = json.loads(profile_out.read_text())
    assert on_disk["priority_preemption"]["preemption_on"]["preemptions"] > 0
    assert (tmp_path / "BENCH_serving.manifest.json").exists()
