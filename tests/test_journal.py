"""Write-ahead request journal + process-restart recovery (ISSUE 10;
docs/serving.md "Request journal", docs/reliability.md journal kill-point
table).

The durability contract under test: **accepted ⇒ durable** — an engine
"dies" (the object is abandoned without close; the REAL kill -9 version
lives in scripts/journal_crash_harness.py and the ``journal_crash_restart``
chaos scenario) and a fresh engine recovers every accepted, non-terminal
request as a forced replay that is f64 token-identical to an uninterrupted
run (rng chain included, sampled requests too), at original priority and
seniority, compiling zero programs beyond the standard set. Torn tails and
corrupt records truncate deterministically at the first bad record; the
compaction/recovery generation swap survives kills at both stages; the
``PERCEIVER_IO_TPU_DISABLE_JOURNAL`` kill-switch and ``journal=None`` are
bit-identical to the pre-journal engine.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.models.core.config import CausalSequenceModelConfig
from perceiver_io_tpu.models.core.perceiver_ar import CausalSequenceModel
from perceiver_io_tpu.reliability import armed
from perceiver_io_tpu.reliability.faults import KilledMidWrite
from perceiver_io_tpu.serving import (
    JournalCorruptError,
    JournalSession,
    JournalTornWrite,
    RequestJournal,
    RequestStatus,
    ServingEngine,
    load_metrics_jsonl,
    read_journal,
)
from perceiver_io_tpu.serving.journal import decode_record, encode_record
from perceiver_io_tpu.utils import env_override

VOCAB = 60
WINDOW = 12
LATENTS = 6


def _make_model(param_dtype=jnp.float32):
    config = CausalSequenceModelConfig(
        vocab_size=VOCAB, max_seq_len=WINDOW, max_latents=LATENTS,
        num_channels=16, num_heads=2, num_self_attention_layers=1,
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


def _mixed_submit(engine, max_new=5):
    """Greedy + sampled mix with fixed keys — the sampled request pins the
    rng CHAIN across recovery, not just argmax."""
    specs = [([1, 2, 3], False), ([4, 5], True), ([6, 7, 8, 9], False)]
    return [
        engine.submit(p, max_new_tokens=max_new, do_sample=s,
                      temperature=0.9 if s else 1.0, rng=jax.random.PRNGKey(7 + i))
        for i, (p, s) in enumerate(specs)
    ]


def _reference(model, params, max_new=5):
    engine = ServingEngine(model, params, num_slots=2)
    handles = _mixed_submit(engine, max_new=max_new)
    engine.run_until_drained(max_steps=300)
    assert all(h.ok for h in handles)
    return [h.result().tolist() for h in handles]


# ------------------------------------------------------------ record format
def test_record_roundtrip_and_crc():
    record = {"seq": 3, "type": "accept", "rid": 1, "prompt": [1, 2],
              "config": {"max_new_tokens": 4}, "rng": [0, 7]}
    line = encode_record(record)
    assert decode_record(line) == record
    # any single-character corruption of the body fails the CRC
    assert decode_record(line.replace('"rid":1', '"rid":2')) is None
    # garbage and truncation decode to None, never raise
    assert decode_record("not json") is None
    assert decode_record(line[: len(line) // 2]) is None
    assert decode_record(json.dumps({"r": record})) is None  # missing crc


def test_journal_append_read_roundtrip(tmp_path):
    j = RequestJournal(str(tmp_path / "j"))
    j.append_accept(0, [1, 2, 3], {"max_new_tokens": 4}, [0, 7], priority=1)
    j.append_accept(1, [9], {"max_new_tokens": 2}, [0, 8], deadline_s=60.0,
                    replay=[5, 6])
    j.append_tick(admitted=[0], tokens={0: [11, 12]}, terminal=[])
    j.append_tick(admitted=[], tokens={0: [13]}, terminal=[(1, "finished", "eos")])
    j.close()

    state = read_journal(str(tmp_path / "j"))
    assert not state.truncated and state.dropped_records == 0
    assert state.terminal == 1
    assert len(state.sessions) == 1
    s = state.sessions[0]
    assert s.rid == 0 and s.priority == 1 and s.admitted
    assert s.emitted == [11, 12, 13]  # replay prefix empty + journaled tokens
    # the terminal request is gone; its replay-bearing accept resolved too
    # a fresh journal refuses the non-empty directory (recovery source)
    with pytest.raises(JournalCorruptError):
        RequestJournal(str(tmp_path / "j"))


def test_remaining_deadline_counts_through_outage():
    s = JournalSession(rid=0, prompt=[1], config={}, rng=[0, 0],
                      deadline_s=10.0, accepted_ts=1000.0)
    assert s.remaining_deadline(now=1004.0) == pytest.approx(6.0)
    assert s.remaining_deadline(now=1011.0) == 0.0  # died of old age offline
    assert JournalSession(rid=0, prompt=[1], config={}, rng=[0, 0]
                          ).remaining_deadline(now=1.0) is None


# ---------------------------------------------------------- torn / corrupt
def test_read_truncates_at_physically_torn_tail(tmp_path):
    j = RequestJournal(str(tmp_path / "j"))
    for rid in range(3):
        j.append_accept(rid, [rid + 1], {"max_new_tokens": 2}, [0, rid])
    j.close()
    seg = next(p for p in sorted(os.listdir(tmp_path / "j")))
    path = tmp_path / "j" / seg
    data = path.read_bytes()
    path.write_bytes(data[: len(data) - 10])  # power loss mid-final-record

    state = read_journal(str(tmp_path / "j"))
    assert state.truncated and state.dropped_records == 1
    assert [s.rid for s in state.sessions] == [0, 1]  # prefix intact


def test_corrupt_mid_segment_record_truncates_everything_after(tmp_path):
    j = RequestJournal(str(tmp_path / "j"))
    with armed("serving.journal.corrupt_record", after=2, times=1):
        # accepts rid=0/1 are clean (after=2 skips them), accept rid=2 is
        # written with a wrong CRC, and rid=3 follows it byte-intact
        for rid in range(4):
            j.append_accept(rid, [rid + 1], {"max_new_tokens": 2}, [0, rid])
    j.close()
    state = read_journal(str(tmp_path / "j"))
    # the reader must not resynchronize past the hole: the corrupt rid=2 AND
    # the intact rid=3 after it are dropped (a record past a hole may
    # reference state the hole lost)
    assert state.truncated
    assert state.dropped_records == 2
    assert [s.rid for s in state.sessions] == [0, 1]


def test_torn_write_fault_raises_and_recovers_prefix(tmp_path):
    j = RequestJournal(str(tmp_path / "j"))
    j.append_accept(0, [1, 2], {"max_new_tokens": 2}, [0, 0])
    with armed("serving.journal.torn_write", times=1):
        with pytest.raises(JournalTornWrite):
            j.append_accept(1, [3, 4], {"max_new_tokens": 2}, [0, 1])
    # the "process" is dead; the reader sees the half-written record
    state = read_journal(str(tmp_path / "j"))
    assert state.truncated and [s.rid for s in state.sessions] == [0]


# ------------------------------------------------------ rotation/compaction
def test_rotation_compacts_terminal_requests_away(tmp_path):
    j = RequestJournal(str(tmp_path / "j"), segment_max_records=4)
    j.append_accept(0, [1], {"max_new_tokens": 2}, [0, 0])
    j.append_accept(1, [2], {"max_new_tokens": 2}, [0, 1])
    j.append_tick(admitted=[0, 1], tokens={0: [5]}, terminal=[(0, "finished", "eos")])
    # 4 records (meta + 2 accepts + tick) -> rotation fires, and with one
    # terminal request accumulated it COMPACTS into generation 2
    assert j.compactions == 1 and j.stats()["generation"] == 2
    names = sorted(os.listdir(tmp_path / "j"))
    assert names == ["seg-0002-000000.jsonl"]  # gen-1 segments deleted
    state = read_journal(str(tmp_path / "j"))
    assert [s.rid for s in state.sessions] == [1]
    assert state.sessions[0].admitted
    # appends continue in the new generation and stay readable
    j.append_tick(admitted=[], tokens={1: [9]}, terminal=[])
    j.close()
    state = read_journal(str(tmp_path / "j"))
    assert state.sessions[0].emitted == [9]


@pytest.mark.parametrize("stage", [0, 1])
def test_compaction_kill_at_either_stage_loses_nothing(tmp_path, stage):
    def build(path):
        j = RequestJournal(str(path), segment_max_records=4)
        j.append_accept(0, [1], {"max_new_tokens": 2}, [0, 0])
        j.append_accept(1, [2], {"max_new_tokens": 2}, [0, 1])
        return j

    j = build(tmp_path / "j")
    with armed("serving.journal.compact.kill", slot=stage, times=1):
        with pytest.raises(KilledMidWrite):
            j.append_tick(admitted=[0, 1], tokens={0: [5]},
                          terminal=[(0, "finished", "eos")])
    # dead mid-compaction; whichever generation is durable must yield the
    # same LIVE state a never-compacted journal would
    state = read_journal(str(tmp_path / "j"))
    if stage == 0:
        # rename never landed: the old generation (tick record included) is
        # the truth — but the tick that triggered compaction was appended
        # BEFORE the rotation check, so both readings agree on live state
        assert state.generation == 1
    else:
        assert state.generation == 2
    assert [s.rid for s in state.sessions] == [1]
    assert state.sessions[0].admitted and state.sessions[0].emitted == []


# ------------------------------------------------- engine wiring + recovery
def test_journal_off_and_killswitch_bit_identical(x64, tmp_path):
    model, params = _make_model(param_dtype=jnp.float64)
    baseline = _reference(model, params)

    # journal on: tokens identical (pure host-side bookkeeping)
    eng = ServingEngine(model, params, num_slots=2, journal=str(tmp_path / "j"))
    handles = _mixed_submit(eng)
    eng.run_until_drained(max_steps=300)
    assert [h.result().tolist() for h in handles] == baseline
    assert eng.decode_compilations == 1
    eng.close()

    # kill-switch: a configured journal is inert — no directory created,
    # tokens bit-identical, snapshot reports journal None
    with env_override("PERCEIVER_IO_TPU_DISABLE_JOURNAL", "1"):
        eng = ServingEngine(model, params, num_slots=2,
                            journal=str(tmp_path / "off"))
    handles = _mixed_submit(eng)
    eng.run_until_drained(max_steps=300)
    assert [h.result().tolist() for h in handles] == baseline
    assert eng.journal is None
    assert not (tmp_path / "off").exists()
    assert eng.metrics.snapshot()["journal"] is None
    eng.close()


def test_recover_mid_run_f64_identity_greedy_and_sampled(x64, tmp_path):
    model, params = _make_model(param_dtype=jnp.float64)
    expected = _reference(model, params)

    engine = ServingEngine(model, params, num_slots=2,
                           journal=str(tmp_path / "j"))
    _mixed_submit(engine)
    for _ in range(3):
        engine.step()
    # process death: the object is abandoned (no close, buffers unflushed
    # beyond the per-tick writes — exactly what a kill leaves)
    engine2, info = ServingEngine.recover(model, params, str(tmp_path / "j"),
                                          num_slots=2)
    assert info["sessions"] == 3 and info["replayed_tokens"] > 0
    engine2.run_until_drained(max_steps=300)
    handles = info["handles"]
    assert all(h.ok for h in handles)
    assert [h.result().tolist() for h in handles] == expected
    # replay compiles nothing beyond the standard set
    assert engine2.decode_compilations == 1
    assert engine2.prefill_compilations <= len(engine2.prefill_buckets)

    # crash AGAIN mid-replay: double recovery is still identical
    engine3 = ServingEngine(model, params, num_slots=2,
                            journal=str(tmp_path / "j2"))
    _mixed_submit(engine3)
    for _ in range(2):
        engine3.step()
    engine4, _ = ServingEngine.recover(model, params, str(tmp_path / "j2"),
                                       num_slots=2)
    for _ in range(3):
        engine4.step()  # partial replay progress, then dies too
    engine5, info5 = ServingEngine.recover(model, params, str(tmp_path / "j2"),
                                           num_slots=2)
    engine5.run_until_drained(max_steps=300)
    assert [h.result().tolist() for h in info5["handles"]] == expected


def test_recover_preserves_priority_and_seniority(setup, tmp_path):
    model, params = setup
    engine = ServingEngine(model, params, num_slots=1,
                           journal=str(tmp_path / "j"))
    # one running + a queued backlog across priority classes
    engine.submit([1, 2], max_new_tokens=6)
    engine.step()
    lo1 = engine.submit([3, 4], max_new_tokens=2, priority=0)
    hi = engine.submit([5, 6], max_new_tokens=2, priority=2)
    lo2 = engine.submit([7, 8], max_new_tokens=2, priority=0)
    order = [(r.priority, r.request_id) for r, _p, _s in
             engine.scheduler.queue_snapshot()]
    assert [p for p, _ in order] == [2, 0, 0]

    engine2, info = ServingEngine.recover(model, params, str(tmp_path / "j"),
                                          num_slots=1)
    # recovered admission order: same classes, same relative seniority
    # (accept order) on fresh monotone ids. The pre-crash RUNNING request is
    # queued too now — it re-enters as the most-senior class-0 continuation
    snap = engine2.scheduler.queue_snapshot()
    assert [r.priority for r, _p, _s in snap] == [2, 0, 0, 0]
    class0_seqs = [s for r, _p, s in snap if r.priority == 0]
    assert class0_seqs == sorted(class0_seqs)  # FIFO within the class
    recovered_prompts = [r.prompt_ids.tolist() for r, _p, _s in snap]
    assert recovered_prompts == [[5, 6], [1, 2], [3, 4], [7, 8]]
    engine2.run_until_drained(max_steps=300)
    assert all(h.ok for h in info["handles"])


def test_drain_on_recovered_engine_finishes_continuations_rejects_backlog(
        setup, tmp_path):
    """ISSUE 10 satellite: drain × recovery — replayed in-flight work (ever
    admitted before the crash) FINISHES through a post-recovery drain, while
    never-admitted journal-queue entries reject as backlog."""
    model, params = setup
    engine = ServingEngine(model, params, num_slots=2,
                           journal=str(tmp_path / "j"))
    running = [engine.submit([i + 1, i + 2], max_new_tokens=6) for i in range(2)]
    queued = [engine.submit([i + 10], max_new_tokens=2) for i in range(2)]
    for _ in range(2):
        engine.step()
    assert all(r.status is RequestStatus.RUNNING for r in running)
    assert all(q.status is RequestStatus.QUEUED for q in queued)

    engine2, info = ServingEngine.recover(model, params, str(tmp_path / "j"),
                                          num_slots=2)
    handles = info["handles"]
    # in-flight continuations park as PREEMPTED (displaced by process death)
    assert [h.status for h in handles[:2]] == [RequestStatus.PREEMPTED] * 2
    assert [h.status for h in handles[2:]] == [RequestStatus.QUEUED] * 2
    assert info["in_flight"] == 2
    drained = engine2.drain(max_steps=300)
    assert len(drained) == 4
    assert all(h.ok and len(h.output_ids) == 6 for h in handles[:2])
    assert all(h.status is RequestStatus.REJECTED
               and h.finish_reason == "draining" for h in handles[2:])
    # the journal closed out every session: nothing left to recover
    engine2.close()
    assert read_journal(str(tmp_path / "j")).sessions == []


def test_recovered_journal_stays_durable_for_next_crash(setup, tmp_path):
    """The recovery swap is itself journaled state: after recover(), fresh
    submits and recovered sessions share one journal whose next recovery
    sees exactly the still-live set."""
    model, params = setup
    engine = ServingEngine(model, params, num_slots=1,
                           journal=str(tmp_path / "j"))
    engine.submit([1, 2], max_new_tokens=8)
    engine.step()
    engine2, info = ServingEngine.recover(model, params, str(tmp_path / "j"),
                                          num_slots=1)
    fresh = engine2.submit([3, 4], max_new_tokens=2)
    engine2.step()
    # dies again; next recovery must hold BOTH sessions
    engine3, info3 = ServingEngine.recover(model, params, str(tmp_path / "j"),
                                           num_slots=1)
    assert info3["sessions"] == 2
    engine3.run_until_drained(max_steps=300)
    assert all(h.ok for h in info3["handles"])


def test_recover_rejects_dirty_engine_and_accepts_empty_dir(setup, tmp_path):
    model, params = setup
    engine = ServingEngine(model, params, num_slots=1)
    engine.submit([1], max_new_tokens=1)
    with pytest.raises(JournalCorruptError):
        engine._recover_attach(str(tmp_path / "j"))
    # recovering a nonexistent/empty journal is a clean cold start
    engine2, info = ServingEngine.recover(model, params,
                                          str(tmp_path / "empty"),
                                          num_slots=1)
    assert info["sessions"] == 0
    assert engine2.journal is not None  # attached, ready for fresh accepts


# ----------------------------------------------------------- metrics (v7)
def test_metrics_v7_journal_gauges_and_recovery_event(setup, tmp_path):
    model, params = setup
    jsonl = tmp_path / "m.jsonl"
    engine = ServingEngine(model, params, num_slots=2,
                           journal=str(tmp_path / "j"),
                           metrics_jsonl=str(jsonl))
    h = engine.submit([1, 2, 3], max_new_tokens=3)
    engine.run_until_drained(max_steps=100)
    snap = engine.metrics.write_snapshot()
    assert snap["schema"] == "serving-metrics/v13"
    j = snap["journal"]
    assert j["records_appended"] >= 2 and j["bytes_written"] > 0
    assert j["fsyncs"] >= 1  # the accept fsync under the default policy
    assert j["live_sessions"] == 0  # finished -> terminal journaled
    engine.close()

    engine2, _ = ServingEngine.recover(model, params, str(tmp_path / "j"),
                                       num_slots=2,
                                       metrics_jsonl=str(jsonl))
    engine2.close()
    loaded = load_metrics_jsonl(str(jsonl))
    events = {e["event"] for e in loaded["events"]}
    assert "recovery" in events
    rec = next(e for e in loaded["events"] if e["event"] == "recovery")
    assert rec["sessions"] == 0 and rec["truncated"] is False


def test_reader_normalizes_pre_v7_journal_field(tmp_path):
    path = tmp_path / "v6.jsonl"
    snap = {"event": "snapshot", "schema": "serving-metrics/v6",
            "requests_submitted": 1}
    path.write_text(json.dumps(snap) + "\n")
    got = load_metrics_jsonl(str(path))["snapshots"][0]
    assert got["journal"] is None  # not recorded, distinguishable from {}


# ------------------------------------------------------------- bench smoke
def test_serve_bench_journal_arm_smoke(tmp_path):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "serve_bench_journal_smoke",
        os.path.join(os.path.dirname(__file__), "..", "scripts", "serve_bench.py"),
    )
    sb = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sb)
    out = tmp_path / "BENCH_serving.json"
    result = sb.main([
        "--preset", "tiny", "--slots", "2", "--requests", "4",
        "--no-baseline", "--journal", "--journal-repeats", "1",
        "--out", str(tmp_path / "serve.json"), "--profile-out", str(out),
    ])
    block = result["journal"]
    assert block["outputs_identical_across_arms"]
    assert block["journal_writes"]["records_appended"] > 0
    assert block["journal_on"]["tokens_per_s"] > 0
    merged = json.loads(out.read_text())
    assert "journal" in merged and "journal_recorded_at" in merged


def test_recovered_session_ttl_expiry_carries_salvaged_tokens(setup, tmp_path):
    """Code-review fix: a session whose TTL elapsed during the outage still
    surfaces its journaled partial tokens on the handle AND the terminal
    event at the recovered engine's first tick — the parked-deadline salvage
    contract, not a silent drop of work the journal durably holds."""
    import time as _time

    model, params = setup
    engine = ServingEngine(model, params, num_slots=1,
                           journal=str(tmp_path / "j"))
    warm = engine.submit([9, 9], max_new_tokens=1)  # compile outside the TTL
    engine.run_until_drained(max_steps=50)
    assert warm.ok
    doomed = engine.submit([1, 2, 3], max_new_tokens=10, deadline_s=0.5)
    k = 3
    for _ in range(k):
        engine.step()
    assert len(doomed.output_ids) == k
    _time.sleep(0.6)  # the process is "down" past the deadline

    jsonl = tmp_path / "m.jsonl"
    engine2, info = ServingEngine.recover(model, params, str(tmp_path / "j"),
                                          num_slots=1,
                                          metrics_jsonl=str(jsonl))
    handle = info["handles"][0]
    assert handle.output_ids == doomed.output_ids  # salvage on the handle
    engine2.run_until_drained(max_steps=50)
    assert handle.status is RequestStatus.TIMED_OUT
    assert handle.result().tolist() == doomed.output_ids  # partials kept
    got = load_metrics_jsonl(str(jsonl))
    finish = next(e for e in got["events"]
                  if e["event"] == "finish"
                  and e["request_id"] == handle.request_id)
    assert finish["new_tokens"] == k  # the terminal EVENT carries the salvage
    engine2.close()


def test_router_recover_detects_stray_replica_journals(setup, tmp_path):
    """Code-review fix: recovering fewer replicas than the dead fleet ran
    must fail loudly instead of silently never reading the extra replicas'
    accepted sessions."""
    from perceiver_io_tpu.serving import ServingRouter

    model, params = setup
    template = str(tmp_path / "r{i}")
    router = ServingRouter(model, params, num_replicas=3, num_slots=1,
                           journal=template)
    for i in range(3):
        router.submit([i + 1, i + 2], max_new_tokens=6)
    router.step()  # dispatched across replicas; accepts durable
    # process death; the operator recovers with the (wrong) default count
    with pytest.raises(ValueError, match="beyond num_replicas"):
        ServingRouter.recover(model, params, template, num_replicas=2,
                              num_slots=1)
    # the right count recovers everything
    router2, info = ServingRouter.recover(model, params, template,
                                          num_replicas=3, num_slots=1)
    assert info["sessions"] == 3
    router2.run_until_drained(max_steps=300)
    assert all(h.ok for h in info["handles"])


def test_reader_accepts_generations_past_the_pad_width(tmp_path):
    """Code-review fix: segment names zero-pad to 4/6 digits but GROW past
    them; the reader must not silently ignore a gen>=10000 journal (that
    would recover 0 sessions — a silent accepted⇒durable violation)."""
    j = RequestJournal(str(tmp_path / "j"))
    j.append_accept(0, [1, 2], {"max_new_tokens": 2}, [0, 0])
    j.close()
    old = tmp_path / "j" / "seg-0001-000000.jsonl"
    old.rename(tmp_path / "j" / "seg-10000-1000000.jsonl")
    state = read_journal(str(tmp_path / "j"))
    assert state.generation == 10000
    assert [s.rid for s in state.sessions] == [0]
    # and the non-empty-directory guard still fires for such a directory
    with pytest.raises(JournalCorruptError):
        RequestJournal(str(tmp_path / "j"))


def test_failed_append_fail_stops_the_journal(tmp_path):
    """Code-review fix: after an append dies mid-line (torn write, ENOSPC),
    the journal refuses further appends instead of merging the next record
    into the torn tail — the durable prefix stays recoverable."""
    j = RequestJournal(str(tmp_path / "j"))
    j.append_accept(0, [1, 2], {"max_new_tokens": 2}, [0, 0])
    with armed("serving.journal.torn_write", times=1):
        with pytest.raises(JournalTornWrite):
            j.append_accept(1, [3, 4], {"max_new_tokens": 2}, [0, 1])
    assert j.failed
    with pytest.raises(JournalCorruptError, match="fail-stopped"):
        j.append_accept(2, [5, 6], {"max_new_tokens": 2}, [0, 2])
    with pytest.raises(JournalCorruptError, match="fail-stopped"):
        j.append_tick(admitted=[0], tokens={}, terminal=[])
    j.close()  # close still succeeds; recovery reads the durable prefix
    assert [s.rid for s in read_journal(str(tmp_path / "j")).sessions] == [0]


def test_journal_error_submit_closes_accounting(setup, tmp_path):
    """Code-review fix: a journal append failure inside ``submit()`` must
    close the request's accounting (REJECTED/``journal_error``) before
    re-raising — ``record_submit`` and the obs lifecycle span fire before
    the durability point, and an exception alone would leave a permanently
    dangling submitted counter and async span."""
    model, params = setup
    engine = ServingEngine(model, params, num_slots=1,
                           journal=str(tmp_path / "j"))
    ok = engine.submit([1, 2], max_new_tokens=2)
    with armed("serving.journal.torn_write", times=1):
        with pytest.raises(JournalTornWrite):
            engine.submit([3, 4], max_new_tokens=2)
    snap = engine.metrics.snapshot()
    assert snap["requests_submitted"] == 2
    assert snap["rejected"] == 1  # the failed submit is CLOSED, not dangling
    rejected = [h for h in engine.finished
                if h.status is RequestStatus.REJECTED]
    assert len(rejected) == 1
    assert rejected[0].finish_reason == "journal_error"
    # the accepted request is untouched by its sibling's failure
    engine.run_until_drained(max_steps=100)
    assert ok.ok
    engine.close()


def test_failstop_buffers_dropped_each_tick(setup, tmp_path):
    """Code-review fix: after the journal fail-stops, the per-tick journal
    buffers are DROPPED at each flush — a caller that keeps stepping the
    degraded engine must not accumulate one buffered entry per emitted
    token for the rest of the process lifetime."""
    model, params = setup
    engine = ServingEngine(model, params, num_slots=1,
                           journal=str(tmp_path / "j"))
    handle = engine.submit([1, 2], max_new_tokens=8)
    engine.step()  # admitted; accept + admit durably journaled
    with armed("serving.journal.torn_write", times=1):
        with pytest.raises(JournalTornWrite):
            engine.submit([3, 4], max_new_tokens=2)
    assert engine.journal.failed
    for _ in range(5):
        engine.step()  # decode continues in the degraded mode
        assert engine._journal_tokens == {}
        assert engine._journal_admits == []
        assert engine._journal_terminals == []
    assert len(handle.output_ids) >= 5
    engine.close()


def test_router_recover_allows_drained_stray_journals(setup, tmp_path):
    """Code-review fix: the stray-journal probe checks LIVE sessions, not
    raw records — a fully drained extra replica journal has nothing a
    down-sized recovery could drop, and must not block it."""
    from perceiver_io_tpu.serving import ServingRouter

    model, params = setup
    template = str(tmp_path / "r{i}")
    router = ServingRouter(model, params, num_replicas=3, num_slots=1,
                           journal=template)
    for i in range(3):
        router.submit([i + 1, i + 2], max_new_tokens=3)
    router.run_until_drained(max_steps=300)
    router.close()
    # every session terminal in every journal: the down-size is safe, allowed
    router2, info = ServingRouter.recover(model, params, template,
                                          num_replicas=2, num_slots=1)
    assert info["sessions"] == 0
    router2.close()


class _FlakyFlushFile:
    """File proxy whose first flush raises — a real EIO lands at flush/fsync
    time at least as often as at write() time."""

    def __init__(self, f):
        self._f = f
        self.fail_next_flush = True

    def write(self, s):
        return self._f.write(s)

    def flush(self):
        if self.fail_next_flush:
            self.fail_next_flush = False
            raise OSError("injected EIO at flush")
        return self._f.flush()

    def fileno(self):
        return self._f.fileno()

    def close(self):
        return self._f.close()


def test_flush_failure_fail_stops_the_journal(tmp_path):
    """Code-review fix: an I/O failure at FLUSH/FSYNC time (not just inside
    ``write()``) fail-stops the journal — the on-disk tail state is just as
    unknown, and a retried ``append_tick`` would otherwise re-append the
    same buffered tokens, handing recovery a duplicated token stream."""
    j = RequestJournal(str(tmp_path / "j"))
    j.append_accept(0, [1, 2], {"max_new_tokens": 4}, [0, 0])
    j._file = _FlakyFlushFile(j._file)
    with pytest.raises(OSError, match="injected EIO"):
        j.append_tick(admitted=[0], tokens={0: [5]}, terminal=[])
    assert j.failed  # fail-stopped: a retry cannot double-append
    with pytest.raises(JournalCorruptError, match="fail-stopped"):
        j.append_tick(admitted=[0], tokens={0: [5]}, terminal=[])
    j.close()  # close still succeeds
    assert [s.rid for s in read_journal(str(tmp_path / "j")).sessions] == [0]


def test_engine_close_survives_fail_stopped_journal(setup, tmp_path):
    model, params = setup
    engine = ServingEngine(model, params, num_slots=1,
                           journal=str(tmp_path / "j"))
    engine.submit([1, 2], max_new_tokens=4)
    engine.step()
    with armed("serving.journal.torn_write", times=1):
        with pytest.raises(JournalTornWrite):
            engine.submit([3, 4], max_new_tokens=2)
    engine.step()  # buffered tick state hits the fail-stopped journal: no-op
    engine.close()  # must not raise
    # the durable prefix recovers the first request
    engine2, info = ServingEngine.recover(model, params, str(tmp_path / "j"),
                                          num_slots=1)
    assert info["sessions"] == 1
    engine2.run_until_drained(max_steps=100)
    assert info["handles"][0].ok
