"""The serving tick measured from inside the engine (docs/observability.md "The tick,
tiled" / "A request's life"): span parents and self time in the recorder core, the
phases that tile the tick and the host gap under a fake clock (the fused tick at a
small page and on the default-constructed engine), the same spans on a ``jax.profiler`` trace's clock, the engine's own stamps
of a request's life, the documented table of emitted names, and the bitwise inertness
of all of it (served tokens, compile counts) recorder on vs off."""

import os
import re
import threading

import jax
import jax.numpy as jnp
import pytest

from perceiver_io_tpu.models.core.config import CausalSequenceModelConfig
from perceiver_io_tpu.models.core.perceiver_ar import CausalSequenceModel
from perceiver_io_tpu.obs.core import NULL_RECORDER, SUMMARY_SCHEMA, NullRecorder, TelemetryRecorder
from perceiver_io_tpu.serving import ServingEngine
from perceiver_io_tpu.serving.engine import TickRecord

VOCAB = 262
WINDOW = 16
LATENTS = 6
PAGE = 4
DOCS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "docs", "observability.md")

# a one-shot admission (shorter than the latent floor), two split admissions (chunked:
# 11 and 16 tokens over chunks of 4), and a repeat of the 11-token prompt, whose first
# page (the one whole page under its latent boundary) is a prefix hit
PROMPTS = [[5, 6, 7], list(range(3, 14)), [9] * WINDOW, list(range(3, 14)), [41, 40]]
NEW_TOKENS = [6, 5, 4, 3, 5]


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


@pytest.fixture(scope="module")
def setup64(x64):
    return _make_model(param_dtype=jnp.float64)


def _engine(model, params, telemetry, paged=True, **more):
    """The engine at a small page (chunked admission, prefix cache), or default-constructed
    (the ``dense`` id: one page a window, no chunking, no prefix cache): the fused tick both."""
    pool = dict(kv_page_size=PAGE, prefill_chunk_tokens=4, max_prefill_slots=2, prefix_cache=True) if paged else {}
    return ServingEngine(model, params, num_slots=3, telemetry=telemetry, **pool, **more)


def _serve(engine, upfront=True):
    """All prompts submitted before the first tick (the engine never runs empty, so every
    tick but the first closes a host gap), or one more per tick."""
    handles = []
    for prompt, n in zip(PROMPTS, NEW_TOKENS):
        handles.append(engine.submit(prompt, max_new_tokens=n))
        if not upfront:
            engine.step()
    engine.run_until_drained(max_steps=300)
    assert all(h.ok for h in handles)
    return handles


# ------------------------------------------------------------ recorder core


def test_parent_and_self_time_of_nested_spans():
    t = [0.0]
    rec = TelemetryRecorder(clock=lambda: t[0])
    with rec.span("outer", tick=1):
        t[0] += 1.0
        with rec.span("child"):
            t[0] += 2.0
            with rec.span("grandchild"):
                t[0] += 4.0
        t[0] += 0.5
        with rec.span("child"):
            t[0] += 0.25
    rec.observe("measured", 3.0)
    s = rec.summary()
    assert s["schema"] == SUMMARY_SCHEMA
    phases = s["phases"]
    assert phases["outer"]["total_s"] == pytest.approx(7.75)
    assert phases["outer"]["self_total_s"] == pytest.approx(1.5)  # 7.75 - (6.0 + 0.25)
    assert phases["child"]["total_s"] == pytest.approx(6.25)
    assert phases["child"]["self_total_s"] == pytest.approx(2.25)  # the grandchild's 4 s are not the child's own
    assert phases["grandchild"]["self_total_s"] == pytest.approx(4.0)
    assert phases["measured"]["self_total_s"] == pytest.approx(3.0)  # an observed interval has no children
    events = {(e["name"], e["ts"]): e for e in rec.chrome_trace()["traceEvents"] if e["ph"] == "X"}
    parents = {name: e.get("parent") for (name, _), e in events.items()}
    assert parents == {"outer": None, "child": "outer", "grandchild": "child"}
    assert next(e for e in events.values() if e["name"] == "outer")["args"] == {"tick": 1}


def test_begin_end_pairs_tile_and_keep_one_stack_per_thread():
    t = [10.0]
    rec = TelemetryRecorder(clock=lambda: t[0])
    rec.declare_phases(["never"])
    rec.span_begin("tick", at=8.0, tick=7)  # backdated to a reading the caller held
    rec.span_begin("schedule", at=8.0)
    t[0] = 11.0
    edge = rec.span_end("schedule")
    assert edge == 11.0
    rec.span_begin("dispatch", at=edge)
    t[0] = 12.5

    seen = {}

    def other_thread():
        # this thread's stack is its own: "tick" (open on the main thread) is no parent here
        rec.span_begin("worker")
        with rec.span("inner"):
            t[0] += 0.0
        rec.span_end("worker")
        seen["unmatched"] = rec.span_end("tick")  # not open on THIS thread: ignored

    worker = threading.Thread(target=other_thread)
    worker.start()
    worker.join(timeout=30)
    assert not worker.is_alive() and seen["unmatched"] is None

    end = rec.span_end("dispatch")
    rec.span_end("tick", at=end)
    assert rec.span_end("tick") is None  # an unmatched end is ignored
    phases = rec.summary()["phases"]
    assert phases["tick"]["total_s"] == pytest.approx(4.5) and phases["tick"]["self_total_s"] == pytest.approx(0.0)
    assert phases["schedule"]["total_s"] == pytest.approx(3.0) and phases["dispatch"]["total_s"] == pytest.approx(1.5)
    assert phases["never"] == {"count": 0, "total_s": 0.0, "self_total_s": 0.0, "mean_s": 0.0,
                               "p50_s": 0.0, "p95_s": 0.0, "max_s": 0.0}
    by_name = {e["name"]: e for e in rec.chrome_trace()["traceEvents"] if e["ph"] == "X"}
    assert by_name["schedule"]["parent"] == "tick" and by_name["dispatch"]["parent"] == "tick"
    assert "parent" not in by_name["worker"] and by_name["inner"]["parent"] == "worker"
    assert by_name["worker"]["tid"] != by_name["tick"]["tid"]
    assert by_name["tick"]["args"] == {"tick": 7}


def test_interleaved_begin_end_pairs_close_by_name():
    """Router replicas on one thread: r0's tick ends while r1's is still open."""
    t = [0.0]
    rec = TelemetryRecorder(clock=lambda: t[0])
    rec.span_begin("r0.tick")
    t[0] = 1.0
    rec.span_begin("r1.tick")
    t[0] = 3.0
    rec.span_end("r0.tick")
    t[0] = 4.0
    rec.span_end("r1.tick")
    phases = rec.summary()["phases"]
    assert phases["r0.tick"]["total_s"] == pytest.approx(3.0) and phases["r1.tick"]["total_s"] == pytest.approx(3.0)
    # r1's tick outlived its parent: it is credited to no one, and no self time goes negative
    assert phases["r0.tick"]["self_total_s"] == pytest.approx(3.0)


def test_span_stacks_hold_under_thread_contention():
    """More threads than cores nest spans into one recorder under a shortened switch
    interval: every span finds its own thread's parent, and no count or second is lost."""
    import sys

    threads, rounds = 16, 400
    rec = TelemetryRecorder(max_events=4 * threads * rounds)
    errors = []

    def work(k):
        try:
            for i in range(rounds):
                rec.span_begin(f"outer.{k}", tick=i)
                with rec.span("inner", worker=k):
                    with rec.span("leaf", worker=k):
                        pass
                rec.span_end(f"outer.{k}")
        except Exception as exc:  # surfaced below: a worker's failure must fail the test
            errors.append(exc)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        workers = [threading.Thread(target=work, args=(k,)) for k in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(w.is_alive() for w in workers)
    phases = rec.summary()["phases"]
    assert phases["inner"]["count"] == phases["leaf"]["count"] == threads * rounds
    assert all(phases[f"outer.{k}"]["count"] == rounds for k in range(threads))
    for e in rec.chrome_trace()["traceEvents"]:
        if e["ph"] == "X" and e["name"] == "inner":
            assert e["parent"] == f"outer.{e['args']['worker']}"  # never another thread's span
        if e["ph"] == "X" and e["name"] == "leaf":
            assert e["parent"] == "inner"
    for name, p in phases.items():
        assert 0.0 <= p["self_total_s"] <= p["total_s"] + 1e-9, name


# -------------------------------------------------------- the tick, tiled


def _x_events(rec, ns="serving"):
    return [e for e in rec.chrome_trace()["traceEvents"] if e["ph"] == "X" and e["name"].startswith(ns + ".")]


@pytest.mark.parametrize("paged", [True, False], ids=["ragged", "dense"])
def test_phases_tile_the_tick_and_the_host_gap_under_a_fake_clock(setup, paged):
    """Every clock reading advances the fake clock by one second, so any stretch the
    phases do not cover shows up as whole seconds. In microseconds (the trace's unit)
    every number below is an integer, hence exact. The prompts are served twice: the
    first round compiles every program (a gap with a compile in it is not booked), the
    second is the steady state the books are checked on."""
    model, params = setup
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    rec = TelemetryRecorder(clock=clock)
    engine = _engine(model, params, rec, paged)
    _serve(engine, upfront=True)
    warm = rec.summary()["phases"]
    warm_ticks = warm["serving.tick"]["count"]
    compiled = engine.total_compilations
    _serve(engine, upfront=True)
    assert engine.total_compilations == compiled  # round two compiled nothing
    engine.close()
    phases = rec.summary()["phases"]
    grew = lambda name, key="total_s": (phases[name][key] - warm[name][key]) * (1 if key == "count" else 1e6)

    events = _x_events(rec)
    every_tick = sorted((e for e in events if e["name"] == "serving.tick"), key=lambda e: e["ts"])
    assert [e["args"]["tick"] for e in every_tick] == list(range(1, len(every_tick) + 1))
    children = {}
    for e in events:
        if e.get("parent") == "serving.tick":
            children.setdefault(e["args"]["tick"], {})[e["name"]] = e
    end = lambda e: e["ts"] + e["dur"]
    seams = 0.0
    for tick in every_tick:  # both rounds: the spans tile every tick, compile or not
        c = children[tick["args"]["tick"]]
        sched = c["serving.schedule"]
        assert sched["ts"] == tick["ts"]  # schedule begins where the tick does
        # all prompts are queued before a round's first tick, whose one-shot admission
        # decodes at once: every tick dispatches and harvests
        disp, sync, harvest = c["serving.decode_dispatch"], c["serving.sample_sync"], c["serving.harvest"]
        assert set(c) == {"serving.schedule", "serving.decode_dispatch", "serving.sample_sync", "serving.harvest"}
        assert disp["ts"] == end(sched) and harvest["ts"] == end(sync) and end(harvest) == end(tick)
        # the ONE stretch of the tick no child covers: dispatch's return to the sync's start
        seam = sync["ts"] - end(disp)
        assert seam == 1e6  # one clock reading (the sync span's begin)
        assert tick["dur"] == sched["dur"] + disp["dur"] + sync["dur"] + harvest["dur"] + seam
        seams += seam
    assert phases["serving.tick"]["self_total_s"] * 1e6 == pytest.approx(seams)

    # the host gap: sync's return -> next dispatch's return, and it IS its four parts. In
    # round two the engine never ran empty and nothing compiled, so every tick after the
    # round's first closes a gap that began at the previous tick's sync.
    ticks = every_tick[warm_ticks:]
    numbers = [tk["args"]["tick"] for tk in ticks]
    assert len(ticks) >= 8
    parts = ("serving.host_gap.harvest", "serving.between_steps", "serving.host_gap.schedule",
             "serving.host_gap.dispatch")
    for name in ("serving.host_gap",) + parts:
        assert grew(name, "count") == len(ticks) - 1
    assert grew("serving.host_gap") == pytest.approx(sum(grew(name) for name in parts), abs=0.5)
    total = lambda name, ticks_: sum(children[n][name]["dur"] for n in ticks_)
    assert grew("serving.host_gap.harvest") == pytest.approx(total("serving.harvest", numbers[:-1]), abs=0.5)
    assert grew("serving.host_gap.schedule") == pytest.approx(total("serving.schedule", numbers[1:]), abs=0.5)
    assert grew("serving.host_gap.dispatch") == pytest.approx(total("serving.decode_dispatch", numbers[1:]), abs=0.5)
    # between_steps: harvest's end to the next entry, i.e. the space between two tick spans
    assert grew("serving.between_steps") == pytest.approx(
        sum(b["ts"] - end(a) for a, b in zip(ticks, ticks[1:])), abs=0.5)
    # round one booked fewer gaps than it had: those in which a program compiled are left out
    assert 0 < warm["serving.host_gap"]["count"] < warm_ticks - 1
    # each tick's wall time (dispatch's start to sync's return) went to one of the two books
    walls = "serving.tick_wall.decode_only", "serving.tick_wall.with_prefill"
    assert grew(walls[0], "count") + grew(walls[1], "count") == len(ticks)
    # the default engine's lanes are its split admissions' (a prompt of the latent floor or more: its whole tail
    # and its finish in the admitting tick); chunking spreads a prompt over more ticks
    assert grew(walls[0], "count") >= 3 and (grew(walls[1], "count") >= 3 if paged else grew(walls[1], "count") >= 1)
    assert grew(walls[0]) + grew(walls[1]) == pytest.approx(
        sum(end(children[n]["serving.sample_sync"]) - children[n]["serving.decode_dispatch"]["ts"] for n in numbers), abs=0.5)
    # nesting below the tiles, and the ids the per-admission spans carry
    by_parent = {}
    for e in events:
        by_parent.setdefault(e["name"], set()).add(e.get("parent"))
    assert by_parent["serving.admit"] == {"serving.schedule"} and by_parent["serving.evict"] == {"serving.harvest"}
    assert by_parent["serving.prefill_dispatch"] == {"serving.admit"} == by_parent["serving.install"]
    per_admission = ["serving.prefill_dispatch", "serving.install"]
    # a chunked engine's later chunks are advanced by the schedule; unchunked, a prompt's lanes are its admission's
    carried_by = {"serving.admit", "serving.schedule"} if paged else {"serving.admit"}
    assert by_parent["serving.prefill_chunk"] == carried_by
    assert by_parent["serving.prefill_finish"] <= carried_by
    per_admission += ["serving.prefill_chunk", "serving.prefill_finish"]
    for name in per_admission:
        ids = {e["args"]["request_id"] for e in events if e["name"] == name}
        assert ids and ids <= set(range(2 * len(PROMPTS)))


def test_no_gap_is_booked_across_an_empty_engine(setup):
    model, params = setup
    rec = TelemetryRecorder()
    engine = _engine(model, params, rec)
    engine.submit([5, 6, 7], max_new_tokens=3)
    engine.run_until_drained(max_steps=50)  # compiles the programs this prompt uses
    before = rec.summary()["phases"]
    for _ in range(3):  # each request runs alone to its end: the engine is empty in between
        engine.submit([5, 6, 7], max_new_tokens=3)
        engine.run_until_drained(max_steps=50)
    phases = rec.summary()["phases"]
    ticks = phases["serving.tick"]["count"] - before["serving.tick"]["count"]
    # per request: the first tick follows an empty engine (no gap), the others close one
    for name in ("serving.host_gap", "serving.between_steps", "serving.host_gap.harvest"):
        assert phases[name]["count"] - before[name]["count"] == ticks - 3
    assert phases["serving.tick_wall.with_prefill"]["count"] == 0  # declared, never observed
    engine.close()


# ------------------------------------------------ what a tick carried


FIELDS = TickRecord._fields
DISPATCH_FIELDS = ("tick", "chunk_lanes", "finish_lanes", "decoding", "after_empty")


@pytest.fixture(scope="module")
def carried(setup):
    """One run of the paged engine on int8 pages (a split admission resets its private
    pages' scales: the tick's ``resets``) under a fake clock, with what the engine handed
    ``record_tick_dispatch`` and the ticks at which it booked a host gap noted beside the
    recorder's events. The engine runs empty three times: after the round that compiles
    every program, after a one-shot admission served alone (a step with nothing to do
    falls into that stretch), and after a 16-token prompt served alone (whose first ticks
    carry lanes and decode nothing)."""
    model, params = setup
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    rec = TelemetryRecorder(clock=clock)
    engine = _engine(model, params, rec, kv_quant="int8")
    given, gap_ticks, chunk_tokens = [], [], []
    record, book, chunk = engine.metrics.record_tick_dispatch, engine._book_host_gap, engine.metrics.record_chunk

    def spy_record(programs, chunk_items, finish_items, decode_items, build_s, descriptor_transfers=None):
        given.append(dict(programs=programs, chunk_lanes=chunk_items, finish_lanes=finish_items,
                          decoding=decode_items, transfers=descriptor_transfers or 0, chunk_tokens=sum(chunk_tokens)))
        chunk_tokens.clear()
        return record(programs, chunk_items, finish_items, decode_items, build_s, descriptor_transfers)

    def spy_chunk(request_id, slot, tokens, seconds):  # one call a chunk lane, with the prompt tokens it carries
        chunk_tokens.append(tokens)
        return chunk(request_id, slot, tokens, seconds)

    def spy_book(*edges):
        gap_ticks.append(engine._tick_no)
        return book(*edges)

    engine.metrics.record_tick_dispatch, engine._book_host_gap, engine.metrics.record_chunk = spy_record, spy_book, spy_chunk
    _serve(engine, upfront=True)
    engine.submit([5, 6, 7], max_new_tokens=3)
    engine.run_until_drained(max_steps=50)
    engine.step()  # nothing to do: a tick that dispatches nothing
    engine.submit([9] * WINDOW, max_new_tokens=3)
    engine.run_until_drained(max_steps=50)
    _serve(engine, upfront=False)
    walls = {name: rec.summary()["phases"][f"serving.tick_wall.{name}"]["count"] for name in ("with_prefill", "decode_only")}
    engine.close()
    events = sorted(_x_events(rec), key=lambda e: e["ts"])
    named = lambda name: [e for e in events if e["name"] == name]
    ticks = named("serving.tick")
    return {"given": given, "gap_ticks": gap_ticks, "walls": walls, "ticks": ticks,
            "dispatching": [e for e in ticks if len(e["args"]) > 1],
            "syncs": {e["args"]["tick"]: e for e in named("serving.sample_sync")},
            "dispatches": {e["args"]["tick"]: e for e in named("serving.decode_dispatch")},
            "prefill_dispatches": named("serving.prefill_dispatch")}


def _case_ten_fields_on_sync_and_tick(run):  # eleven since ISSUE 46: the record's, whatever their number
    assert len(run["dispatching"]) >= 20 and len(run["dispatching"]) < len(run["ticks"])
    for tick in run["dispatching"]:
        assert tuple(tick["args"]) == FIELDS and all(isinstance(v, int) for v in tick["args"].values())
        sync = run["syncs"].get(tick["args"]["tick"])
        # the sync carries the whole record at its begin; a tick that decodes nothing has none
        assert (sync is None) == (tick["args"]["decoding"] == 0)
        assert sync is None or sync["args"] == tick["args"]
    for tick in run["ticks"]:
        if len(tick["args"]) == 1:  # it dispatched nothing: no record, and no dispatch or sync span
            assert tick["args"]["tick"] not in run["dispatches"] and tick["args"]["tick"] not in run["syncs"]


def _case_dispatch_carries_what_is_known_before_the_pack(run):
    assert len(run["dispatches"]) == len(run["dispatching"])
    for tick in run["dispatching"]:
        dispatch = run["dispatches"][tick["args"]["tick"]]
        assert tuple(dispatch["args"]) == DISPATCH_FIELDS
        assert dispatch["args"] == {f: tick["args"][f] for f in DISPATCH_FIELDS}


def _case_fields_are_what_the_metrics_were_given(run):
    assert len(run["given"]) == len(run["dispatching"])
    for given, tick in zip(run["given"], run["dispatching"]):
        assert given == {f: tick["args"][f] for f in given}
    total = lambda f: sum(e["args"][f] for e in run["dispatching"])
    # the traffic had every kind of work: lanes beside decoding slots and alone, a packed
    # and a resident descriptor, scale resets (int8 pages), more than one program a tick
    assert total("chunk_lanes") >= 10 and total("finish_lanes") >= 6 and total("resets") >= 6
    assert 4 * total("chunk_lanes") >= total("chunk_tokens") > 3 * total("chunk_lanes")  # chunks of 4, and shorter tails
    assert {e["args"]["transfers"] for e in run["dispatching"]} == {0, 1}
    assert any(e["args"]["decoding"] == 0 for e in run["dispatching"]) and max(e["args"]["programs"] for e in run["dispatching"]) >= 3
    for e in run["dispatching"]:
        lanes = e["args"]["chunk_lanes"] or e["args"]["finish_lanes"] or e["args"]["resets"]
        assert e["args"]["transfers"] == (1 if lanes else 0)


def _case_oneshot_admissions_count_the_ticks_prefill_dispatches(run):
    inside = lambda tick: sum(1 for e in run["prefill_dispatches"] if tick["ts"] <= e["ts"] < tick["ts"] + tick["dur"])
    assert [e["args"]["oneshot_admissions"] for e in run["dispatching"]] == [inside(e) for e in run["dispatching"]]
    assert sum(e["args"]["oneshot_admissions"] for e in run["dispatching"]) == len(run["prefill_dispatches"]) == 5
    for e in run["dispatching"]:  # each brings a prefill and an install program ahead of the tick's own
        assert e["args"]["programs"] >= 2 * e["args"]["oneshot_admissions"] + 1


def _case_after_empty_marks_the_ticks_with_no_gap_for_want_of_a_request(run):
    numbers = [e["args"]["tick"] for e in run["dispatching"]]
    after_empty = {e["args"]["tick"] for e in run["dispatching"] if e["args"]["after_empty"]}
    # the engine's first tick, and the first after each of the three times it ran empty
    assert len(after_empty) == 4 and numbers[0] in after_empty
    no_gap = set(numbers) - set(run["gap_ticks"])
    # the one other way to have no gap to close: the tick before synced nothing
    after_lanes_only = {b["args"]["tick"] for a, b in zip(run["dispatching"], run["dispatching"][1:]) if a["args"]["decoding"] == 0}
    assert after_lanes_only and no_gap == after_empty | after_lanes_only
    assert any(e["args"]["after_empty"] and e["args"]["decoding"] == 0 for e in run["dispatching"])  # lanes alone, after an empty engine
    before = {e["args"]["tick"]: a for a, e in zip(run["ticks"], run["ticks"][1:])}
    idle_step = next(e for e in run["ticks"] if len(e["args"]) == 1)
    assert before[idle_step["args"]["tick"] + 1]["args"] == idle_step["args"] and idle_step["args"]["tick"] + 1 in after_empty


def _case_tick_wall_books_count_what_they_counted(run):
    synced = [e for e in run["dispatching"] if e["args"]["decoding"]]
    with_lane = [e for e in synced if e["args"]["chunk_lanes"] or e["args"]["finish_lanes"]]
    assert run["walls"] == {"with_prefill": len(with_lane), "decode_only": len(synced) - len(with_lane)}
    assert len(with_lane) >= 6 and len(synced) - len(with_lane) >= 6


TICK_CASES = {check.__name__[len("_case_"):]: check for check in (
    _case_ten_fields_on_sync_and_tick, _case_dispatch_carries_what_is_known_before_the_pack,
    _case_fields_are_what_the_metrics_were_given, _case_oneshot_admissions_count_the_ticks_prefill_dispatches,
    _case_after_empty_marks_the_ticks_with_no_gap_for_want_of_a_request, _case_tick_wall_books_count_what_they_counted)}


@pytest.mark.parametrize("case", [*TICK_CASES, "telemetry_off_builds_no_record_for_the_spans"])
def test_a_tick_says_what_it_carried(setup, carried, monkeypatch, case):
    if case in TICK_CASES:
        return TICK_CASES[case](carried)
    # telemetry off: the shared null recorder is handed ``tick`` (as before) and nothing of
    # the record, whose dict is never made
    model, params = setup
    seen = []

    def spy(method):
        def call(self, name, at=None, **args):
            seen.append((method, name, set(args)))
        return call

    monkeypatch.setattr(NullRecorder, "span_begin", spy("begin"))
    monkeypatch.setattr(NullRecorder, "span_end", spy("end"))
    monkeypatch.setattr(TickRecord, "_asdict", lambda self: pytest.fail("a record was made into span arguments with telemetry off"))
    engine = _engine(model, params, False)
    assert engine._obs is NULL_RECORDER and engine.watchdog is None
    _serve(engine, upfront=False)
    assert engine.metrics.snapshot()["ragged_tick"]["ticks"] >= 10  # the metrics still get theirs
    engine.close()
    assert {name for _, name, _ in seen} >= {"serving.tick", "serving.sample_sync", "serving.harvest"}
    assert all(args <= {"tick"} for _, _, args in seen)
    assert not any(method == "begin" and name == "serving.decode_dispatch" for method, name, _ in seen)


# ------------------------------------------------- the profiler's clock


def test_spans_reach_a_jax_profiler_trace(setup, tmp_path):
    """A default-constructed recorder handed to the engine puts the program's spans into
    the /host:CPU plane of any jax.profiler trace."""
    profile_data = pytest.importorskip("jax.profiler").__dict__.get("ProfileData")
    if profile_data is None:
        pytest.skip("this jax has no jax.profiler.ProfileData")
    import glob

    model, params = setup
    engine = _engine(model, params, TelemetryRecorder())
    engine.submit([5, 6, 7], max_new_tokens=6)
    engine.step()  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            engine.step()
    finally:
        jax.profiler.stop_trace()
    engine.close()
    files = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    assert files
    host = next(p for p in profile_data.from_file(files[0]).planes if p.name == "/host:CPU")
    spans = {}
    for line in host.lines:
        for event in line.events:
            if event.name.startswith("serving."):
                spans.setdefault(event.name, []).append((event.start_ns, event.duration_ns))
    assert len(spans["serving.tick"]) == 3 and len(spans["serving.harvest"]) == 3
    assert {"serving.schedule", "serving.decode_dispatch", "serving.sample_sync", "serving.evict"} <= set(spans)
    for (t0, d0), (h0, hd) in zip(sorted(spans["serving.tick"]), sorted(spans["serving.harvest"])):
        assert t0 <= h0 and h0 + hd <= t0 + d0 + 1e3  # harvest lies inside its tick, on one clock
    # the tick's record rides the annotations' arguments: the benchmark's reader finds it as
    # the two events' stats (the tick span's annotation was entered with ``tick`` alone)
    from benchmark.trace import ticks

    carriers, _ = ticks.read_profile(files[0])
    assert [name for name, *_ in carriers] == ["serving.decode_dispatch", "serving.sample_sync"] * 3
    assert ticks.carries_records(carriers)
    for (_, d_start, d_dur, dispatch), (_, s_start, _, sync) in zip(carriers[::2], carriers[1::2]):
        # the reader takes its fields by name: the record may carry more (ISSUE 46's riding lanes), after them
        assert tuple(sync) == TickRecord._fields and TickRecord._fields[:len(ticks.FIELDS)] == ticks.FIELDS
        assert tuple(dispatch) == ticks.DISPATCH_FIELDS
        assert dispatch == {f: sync[f] for f in dispatch} and d_start + d_dur <= s_start
        assert ticks.tick_class(ticks.record_of("serving.sample_sync", sync)) == "decode_only"


# ------------------------------------------------------ a request's life


@pytest.mark.parametrize("paged", [True, False], ids=["ragged", "dense"])
def test_the_engine_stamps_a_requests_life(setup, paged):
    model, params = setup
    engine = _engine(model, params, False, paged)
    admits, hits = {}, []
    record_admit, record_hit = engine.metrics.record_admit, engine.metrics.record_prefix_hit

    def spy_admit(request_id, slot, **kw):
        admits[request_id] = kw
        return record_admit(request_id, slot, **kw)

    def spy_hit(request_id, shared_pages, shared_tokens):
        hits.append(shared_tokens)
        return record_hit(request_id, shared_pages, shared_tokens)

    engine.metrics.record_admit, engine.metrics.record_prefix_hit = spy_admit, spy_hit
    handles = _serve(engine, upfront=False)
    assert {len(h.prompt_ids) >= LATENTS for h in handles} == {True, False}  # either side of the latent floor: both admission paths ran
    for h in handles:
        assert h.enqueued_at <= h.slot_claimed_at <= h.admitted_at <= h.first_token_at <= h.finished_at
        assert h.first_token_at <= h.last_token_at <= h.finished_at
        kw = admits[h.request_id]
        assert kw["wait_s"] == h.slot_claimed_at - h.enqueued_at  # the queue wait ends at the slot claim
        assert kw["prefill_s"] == h.admitted_at - h.slot_claimed_at
        assert kw["prompt_tokens"] == len(h.prompt_ids)
    snap = engine.metrics.snapshot()
    assert snap["schema"] == "serving-metrics/v13"
    assert bool(hits) is paged and snap["prefix_hit_tokens"] == sum(hits)  # the repeated 11-token prompt forked a cached page
    assert snap["prompt_tokens_admitted"] == sum(len(p) for p in PROMPTS)
    first = sorted(h.first_token_at - h.slot_claimed_at for h in handles)
    assert snap["first_token_s"]["max"] == pytest.approx(first[-1], abs=1e-6)
    assert snap["ttft_s"]["max"] == pytest.approx(max(h.first_token_at - h.enqueued_at for h in handles), abs=1e-6)
    # one inter-token gap per token after a request's first
    assert len(engine.metrics._inter_token_gaps) == sum(NEW_TOKENS) - len(handles)
    assert len(engine.metrics._first_token_times) == len(handles) == len(engine.metrics._ttfts)
    assert snap["inter_token_s"]["max"] > 0.0
    engine.close()


def test_lifecycle_span_marks_slot_claim_and_first_token(setup):
    model, params = setup
    rec = TelemetryRecorder()
    engine = _engine(model, params, rec)
    _serve(engine)
    engine.close()
    instants = {}
    for e in rec.chrome_trace()["traceEvents"]:
        if e["ph"] == "n":
            instants.setdefault(e["id"], []).append(e["name"])
    assert set(instants) == set(range(len(PROMPTS)))
    for names in instants.values():
        assert names == ["queued", "slot_claimed", "prefill", "first_token"]


# ------------------------------------------------------ the documented table


def _documented(kinds):
    with open(DOCS) as f:
        text = f.read()
    table = text[text.index("## Everything the program emits"):]
    names = set()
    for row in re.findall(r"^\| (.+?) \| (.+?) \|", table, flags=re.M):
        if row[1].split(" ")[0] in kinds:
            names.update(re.findall(r"`([a-z_.0-9]+)`", row[0]))
    return names


def test_emitted_serving_names_are_exactly_the_documented_table(setup):
    model, params = setup
    rec = TelemetryRecorder()
    engine = _engine(model, params, rec)
    _serve(engine, upfront=False)
    engine.close()
    summary = rec.summary()
    declared = {name for name in summary["phases"] if name.startswith("serving.")}
    ran = {name for name, p in summary["phases"].items() if p["count"] and name.startswith("serving.")}
    assert {e["name"] for e in _x_events(rec)} <= declared
    gauges = {g for g in summary["gauges"] if g.startswith("serving.")}
    counters = {c for c in summary["counters"] if c.startswith("serving.")}
    assert declared == ran  # the paged engine under this traffic runs every span it declares
    assert declared == {n for n in _documented({"span", "interval"}) if n.startswith("serving.")}
    assert gauges == {n for n in _documented({"gauge"}) if n.startswith("serving.")}
    assert counters == set()  # the engine's counts live in EngineMetrics, not in the recorder


# ------------------------------------------------------------- inertness


def test_tokens_and_compile_counts_are_the_same_recorder_on_and_off(setup64):
    """f64 bitwise pin over the paged engine and its spans, stamps and named scopes:
    telemetry times host calls and never touches a device value (the default engine's pin is
    tests/test_obs.py::test_engine_disabled_telemetry_is_null_and_token_identical)."""
    model, params = setup64

    def run(telemetry):
        engine = _engine(model, params, telemetry)
        tokens = [h.result().tolist() for h in _serve(engine, upfront=False)]
        counts = (engine.decode_compilations, engine.prefill_compilations, engine.total_compilations)
        engine.close()
        return tokens, counts

    rec = TelemetryRecorder()
    tokens_off, counts_off = run(False)
    tokens_on, counts_on = run(rec)
    assert tokens_on == tokens_off
    assert counts_on == counts_off and counts_on[0] == 1  # the tick program compiles once, recorder on or off
    # the pin covers the tick's record: the recorder-on run made one a dispatching tick
    syncs = [e for e in _x_events(rec) if e["name"] == "serving.sample_sync"]
    assert syncs and all(tuple(e["args"]) == TickRecord._fields for e in syncs)
