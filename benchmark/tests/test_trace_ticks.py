"""``trace/ticks.py``: the join of a tick's record to its own device execution, on a trace
small enough to compute by hand and on a cut of a trace recorded on the chip
(``data/serve_ticks_cut.json.gz``: 0.2 s of the traced ``serve-455m-online`` run of PR 38, TPU
v5 lite, cut by ``python -m benchmark.trace.ticks --cut``); the eight readers that read the
joined table, and what they say of a trace that carries no record."""

import copy
import json
import os

import pytest

from benchmark.harness import layers, manifest
from benchmark.trace import gaps, ticks

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = os.path.join(DATA, "serve_ticks_cut.json.gz")
METRICS = [
    "tick_program.lane_tick_share_pct.online", "tick_program.lane_extra_device_ms.online",
    "tick_program.chunk_lane_device_ms.online", "tick_program.finish_lane_device_ms.online",
    "tick_loop.lane_extra_host_ms.online", "tick_loop.launch_lag_ms.online", "tick_loop.readback_lag_ms.online",
    "device.idle_no_request_pct.online",
]
TICK = "jit(ragged_tick)/"


def record(tick, **fields):
    return dict({"tick": tick, "programs": 1, "oneshot_admissions": 0, "chunk_lanes": 0, "finish_lanes": 0,
                 "chunk_tokens": 0, "resets": 0, "decoding": 2, "transfers": 0, "after_empty": 0}, **fields)


def spans(rec, dispatch, sync=None):
    """The two carriers of one tick: ``(start, duration)`` of its dispatch and of its sync."""
    out = [["serving.decode_dispatch", *dispatch, {f: rec[f] for f in ticks.DISPATCH_FIELDS}]]
    return out + ([["serving.sample_sync", *sync, rec]] if sync else [])


# Four ticks, seconds for milliseconds. Tick 1 follows an empty engine and admits one
# request by the one-shot path: its prefill program runs [0.5, 0.7] AHEAD of the tick
# program [1, 2]. Tick 2 decodes only; the device starts its program [3, 4] BEFORE the
# dispatch returns at 3.1. Tick 3 carries a chunk lane of 256 tokens: program [5, 7]. Tick 4
# carries lanes and decodes nothing: no sync; program [7.5, 8].
HAND = {
    "devices": {"0": {
        "modules": [["jit_prefill_one(1)", 0.5, 0.2], ["jit_ragged_tick(7)", 1.0, 1.0], ["jit_ragged_tick(7)", 3.0, 1.0],
                    ["jit_ragged_tick(7)", 5.0, 2.0], ["jit_ragged_tick(7)", 7.5, 0.5]],
        "ops": [["fusion.0", 0.5, 0.2, "jit(prefill_one)/dot_general"],
                ["fusion.1", 1.0, 0.9, TICK + "tick.decode/M.decode_step_paged/sa/mlp/dot_general"],
                ["fusion.2", 3.0, 1.0, TICK + "tick.decode/M.decode_step_paged/sa/mlp/dot_general"],
                ["while.3", 5.0, 0.8, TICK + "tick.chunk_lanes/while"],  # a container: its body's operation follows
                ["fusion.4", 5.0, 0.8, TICK + "tick.chunk_lanes/while/body/mlp/dot_general"],
                ["fusion.5", 5.8, 1.2, TICK + "tick.decode/M.decode_step_paged/head/dot_general"],
                ["fusion.6", 7.5, 0.3, TICK + "tick.chunk_lanes/while/body/scatter"],
                ["fusion.7", 7.8, 0.2, TICK + "tick.finish_lanes/paged_prefill_attention/dot_general"]],
    }},
    "host": (spans(record(1, after_empty=1, oneshot_admissions=1, programs=3), (0.8, 0.1), (0.95, 1.25))
             + spans(record(2), (2.5, 0.6), (3.1, 1.1))
             + spans(record(3, chunk_lanes=1, chunk_tokens=256, transfers=1), (4.6, 0.3), (4.9, 2.4))
             + spans(record(4, decoding=0, chunk_lanes=1, finish_lanes=1, chunk_tokens=64, transfers=1), (7.4, 0.05))),
}


def test_each_tick_program_joins_the_record_of_its_own_tick():
    joined = ticks.join(HAND, "ragged_tick")
    assert (joined["programs"], joined["joined"], joined["unjoined_programs"], joined["edge_ticks"]) == (4, 4, 0, 0)
    rows = {row["tick"]: row for row in joined["ticks"]}
    assert [rows[n]["cls"] for n in (1, 2, 3, 4)] == ["admission", "decode_only", "lane", "lane_only"]
    assert [rows[n]["run"] for n in (1, 2, 3, 4)] == [0, 1, 2, 3]
    ms = lambda n, column: rows[n][column]
    assert ms(1, "device_ms") == pytest.approx(900.0) and ms(1, "program_ms") == pytest.approx(1000.0)
    assert ms(3, "device_ms") == pytest.approx(2000.0)  # the container is not counted twice
    assert rows[3]["by_scope"] == {"tick.chunk_lanes/mlp": pytest.approx(800.0), "tick.decode/head": pytest.approx(1200.0)}
    assert rows[4]["by_scope"] == {"tick.chunk_lanes": pytest.approx(300.0), "tick.finish_lanes": pytest.approx(200.0)}
    assert [ms(n, "dispatch_ms") for n in (1, 2, 3, 4)] == pytest.approx([100.0, 600.0, 300.0, 50.0])
    # the launch lag is negative where the device started before the dispatch returned
    assert [ms(n, "launch_lag_ms") for n in (1, 2, 3, 4)] == pytest.approx([100.0, -100.0, 100.0, 50.0])
    assert [ms(n, "readback_lag_ms") for n in (1, 2, 3)] == pytest.approx([200.0, 200.0, 300.0]) and ms(4, "readback_lag_ms") is None
    assert [ms(n, "wall_ms") for n in (1, 2, 3)] == pytest.approx([1400.0, 1700.0, 2700.0]) and ms(4, "wall_ms") is None
    # the previous sync's return to this dispatch's return; the first tick has no previous one
    assert ms(1, "sync_to_dispatch_ms") is None
    assert [ms(n, "sync_to_dispatch_ms") for n in (2, 3, 4)] == pytest.approx([900.0, 700.0, 150.0])
    assert ms(1, "idle_before_ms") is None  # no tick program before it in the trace
    assert [ms(n, "idle_before_ms") for n in (2, 3, 4)] == pytest.approx([1000.0, 1000.0, 500.0])
    # between two ticks the three stretches ARE the stretch between the two programs
    for a, b in ((1, 2), (2, 3)):
        assert ms(a, "readback_lag_ms") + ms(b, "sync_to_dispatch_ms") + ms(b, "launch_lag_ms") == pytest.approx(
            ms(b, "idle_before_ms") + ms(b, "other_programs_ms"))


def test_the_device_clocks_offset_is_bounded_by_the_runtimes_events_and_taken_out():
    """The host's clock runs 0.4 s ahead of the device's. The runtime enqueued each tick
    program 0.02 to 0.05 s before it started and ran its completion callbacks 0.02 to 0.08 s
    after it ended, on the host's clock: the offset lies between 0.38 and 0.42."""
    shifted = copy.deepcopy(HAND)
    for event in shifted["host"]:
        event[1] += 0.4
    runs = [m for m in shifted["devices"]["0"]["modules"] if "ragged_tick" in m[0]]
    for run_id, (run, early, late) in enumerate(zip(runs, (0.05, 0.02, 0.03, 0.04), (0.08, 0.05, 0.02, 0.06)), start=70):
        run.append(run_id)
        shifted["host"].append([ticks.ENQUEUE, run[1] - early + 0.4, 0.01, {"run_id": run_id}])
        shifted["host"].append([ticks.COMPLETE, run[1] + run[2] + late + 0.4, 0.01, {"run_id": run_id}])
    shifted["host"].append([ticks.COMPLETE, 0.0, 0.01, {"run_id": 7}])  # another program's, not in the trace
    shifted["host"].sort(key=lambda e: e[1])
    assert ticks.clock_offset(shifted) == {"low_s": pytest.approx(0.38), "high_s": pytest.approx(0.42)}
    assert ticks.clock_offset(HAND) is None  # no runtime events: joined as it stands
    shifted["clock"] = ticks.clock_offset(shifted)
    joined, plain = ticks.join(shifted, "ragged_tick"), ticks.join(HAND, "ragged_tick")
    assert joined["clock_offset_ms"] == {"low": pytest.approx(380.0), "high": pytest.approx(420.0)} and plain["clock_offset_ms"] is None
    assert joined["joined"] == 4 and ticks.metrics(joined) == {k: pytest.approx(v) for k, v in ticks.metrics(plain).items()}
    rows = {row["tick"]: row for row in joined["ticks"]}
    # the host's share of each lag, on the host's clock alone: the dispatch's return to the
    # enqueue (tick 2: 3.0 - 0.02 - 3.1), the completion callbacks to the sync's return
    assert [rows[n]["enqueue_lag_ms"] for n in (1, 2, 3, 4)] == pytest.approx([50.0, -120.0, 70.0, 10.0])
    assert [rows[n]["fetch_ms"] for n in (1, 2, 3)] == pytest.approx([120.0, 150.0, 280.0]) and rows[4]["fetch_ms"] is None
    assert [rows[n]["period_ms"] for n in (2, 3)] == pytest.approx([2000.0, 3100.0])  # one sync's return to the next
    # without the correction tick 2's program, which started before its dispatch returned, would go to nobody
    del shifted["clock"]
    assert ticks.join(shifted, "ragged_tick")["unjoined_programs"] >= 1


def test_another_program_ahead_of_the_tick_is_booked_to_it_and_the_idle_account_adds_up():
    trace = copy.deepcopy(HAND)
    # tick 2 admits one request by the one-shot path too: its prefill runs [2.6, 2.8]
    trace["devices"]["0"]["modules"].append(["jit_prefill_one(1)", 2.6, 0.2])
    trace["devices"]["0"]["ops"].append(["fusion.9", 2.6, 0.2, "jit(prefill_one)/dot_general"])
    trace["host"][2][3]["after_empty"] = trace["host"][3][3]["after_empty"] = 1
    joined = ticks.join(trace, "ragged_tick")
    row = next(r for r in joined["ticks"] if r["tick"] == 2)
    assert row["other_programs_ms"] == pytest.approx(200.0) and row["idle_before_ms"] == pytest.approx(800.0)
    idle = joined["idle"]
    # span [0.5, 8.0]; busy 0.2 + 0.2 + 0.9 + 1.0 + 2.0 + 0.5
    assert idle["span_s"] == pytest.approx(7.5) and idle["idle_s"] == pytest.approx(7.5 - 4.8)
    assert idle["parts_s"] == {"no_request": pytest.approx(0.8), "before_other_ticks": pytest.approx(1.5),
                               "before_unjoined_programs": 0.0, "inside_tick_programs": pytest.approx(0.1),
                               "edges": pytest.approx(0.3)}
    assert sum(idle["parts_s"].values()) == pytest.approx(idle["idle_s"])
    assert ticks.metrics(joined)["device.idle_no_request_pct.online"] == pytest.approx(100 * 0.8 / 7.5)


def test_the_eight_metrics_and_the_two_identities_on_the_hand_trace():
    joined = ticks.join(HAND, "ragged_tick")
    assert ticks.metrics(joined) == {
        "tick_program.lane_tick_share_pct.online": pytest.approx(100 / 3),  # of the three ticks that decode
        "tick_program.lane_extra_device_ms.online": pytest.approx(1000.0),
        "tick_program.chunk_lane_device_ms.online": pytest.approx((800.0 + 300.0) / 2),  # ticks 3 and 4, a lane each
        "tick_program.finish_lane_device_ms.online": pytest.approx(200.0),
        "tick_loop.lane_extra_host_ms.online": pytest.approx((300.0 + 100.0) - (600.0 - 100.0)),
        "tick_loop.launch_lag_ms.online": pytest.approx(-100.0),
        "tick_loop.readback_lag_ms.online": pytest.approx(200.0),
        "device.idle_no_request_pct.online": 0.0,  # the one such tick is the trace's first: its idle time is the edge's
    }
    ids = ticks.identities(joined)
    assert ids["between_ticks"]["host_gap_ms"] == pytest.approx(1000.0)  # median of 1000, 1000, 500
    assert ids["between_ticks"]["unexplained_ms"] == pytest.approx(1000.0 - (200.0 + 900.0 - 100.0))
    lane = ids["lane"]
    assert lane["wall_extra_ms"] == pytest.approx(1000.0) and lane["readback_lag_extra_ms"] == pytest.approx(100.0)
    assert lane["unexplained_ms"] == pytest.approx(lane["readback_lag_extra_ms"] + lane["program_idle_extra_ms"])
    table = ticks.by_class(joined)
    assert {cls: entry["count"] for cls, entry in table.items()} == {"decode_only": 1, "admission": 1, "lane": 1, "lane_only": 1}
    assert table["lane"]["mean_record"]["chunk_tokens"] == 256 and table["lane"]["median"]["device_ms"] == pytest.approx(2000.0)


def test_ticks_cut_by_the_edges_and_runs_nobody_claims_are_counted():
    trace = copy.deepcopy(HAND)
    host = trace["host"]
    del host[0]  # tick 1's dispatch began before the trace did: its sync and its run are left over
    del host[-3]  # tick 3's sync ends after the trace does
    joined = ticks.join(trace, "ragged_tick")
    assert [row["tick"] for row in joined["ticks"]] == [2, 4]
    assert (joined["programs"], joined["joined"], joined["unjoined_programs"], joined["edge_ticks"]) == (4, 2, 2, 2)
    assert joined["idle"]["parts_s"]["before_unjoined_programs"] == pytest.approx(1.0)  # before tick 3's run
    assert sum(joined["idle"]["parts_s"].values()) == pytest.approx(joined["idle"]["idle_s"])
    rows = {row["tick"]: row for row in joined["ticks"]}
    assert rows[4]["sync_to_dispatch_ms"] is None  # the tick before it has no sync in the trace


def test_a_renamed_field_raises_naming_it_and_an_older_trace_reads_as_nothing(monkeypatch):
    trace = copy.deepcopy(HAND)
    trace["host"][3][3]["decoding_slots"] = trace["host"][3][3].pop("decoding")
    with pytest.raises(KeyError, match="serving.sample_sync of tick 2 carries no argument 'decoding'"):
        ticks.join(trace, "ragged_tick")
    trace = copy.deepcopy(HAND)
    del trace["host"][-1][3]["after_empty"]
    with pytest.raises(KeyError, match="serving.decode_dispatch of tick 4 carries no argument 'after_empty'"):
        ticks.join(trace, "ragged_tick")
    older = [[name, start, dur, {"tick": stats["tick"]}] for name, start, dur, stats in HAND["host"]]
    assert ticks.carries_records(HAND["host"]) and not ticks.carries_records(older)
    # the readers: nothing without a trace, with a stub's or a rehearsal's, or with an older program's
    reduced = {"devices": {"0": {"ops": [e[:3] for e in HAND["devices"]["0"]["ops"]], "modules": HAND["devices"]["0"]["modules"]}},
               "host": [], "planes": {}}
    monkeypatch.setattr(ticks, "newest_trace_file", lambda scratch: "some.xplane.pb")
    monkeypatch.setattr(ticks, "read_trace", lambda path: {"devices": HAND["devices"], "host": older, "clock": None})
    for ctx in ({}, {"trace": None}, {"trace": {"devices": {}, "host": [], "planes": {}}},
                {"trace": {k: v for k, v in reduced.items() if k != "planes"}, "program_name": "ragged_tick"},
                {"trace": reduced, "program_name": "ragged_tick"}):
        assert [layers.load_reader(name)(dict(ctx)) for name in METRICS] == [None] * 8


def test_the_readers_read_the_runs_own_trace_file_once(monkeypatch, capsys):
    reads = []
    reduced = {"devices": {"0": {"ops": [e[:3] for e in HAND["devices"]["0"]["ops"]], "modules": HAND["devices"]["0"]["modules"]}},
               "host": [], "planes": {}}
    monkeypatch.setattr(ticks, "newest_trace_file", lambda scratch: "this-run.xplane.pb")
    monkeypatch.setattr(ticks, "read_trace", lambda path: reads.append(path) or HAND)
    ctx = {"trace": reduced, "program_name": "ragged_tick"}
    entries = [m for m in manifest.load_manifest()["per_layer"] if m["name"] in METRICS]
    values = layers.read_all(entries, ctx)
    assert values == {name: pytest.approx(value) for name, value in ticks.metrics(ticks.join(HAND, "ragged_tick")).items()}
    assert reads == ["this-run.xplane.pb"]  # read once, shared by the eight
    note = json.loads(capsys.readouterr().out.splitlines()[0])
    assert note["phase"] == "tick_join" and (note["programs"], note["joined"]) == (4, 4) and note["read_and_join_s"] >= 0.0
    # a file whose programs are not the run's own (another run's trace) is left alone
    other = copy.deepcopy(reduced)
    other["devices"]["0"]["modules"] = other["devices"]["0"]["modules"][:-1]
    assert layers.read_all(entries, {"trace": other, "program_name": "ragged_tick"}) == {}


def test_the_eight_readers_are_listed_for_both_serving_cells():
    entries = {m["name"]: m for m in manifest.load_manifest()["per_layer"]}
    for name in METRICS:
        assert entries[name]["workloads"] == ["serve-455m-online", "serve-falcon-h1-chat"]
        assert entries[name]["moves"] == "serve_gap_p95_ms" and entries[name]["better"] == "lower"
        assert entries[name]["unit"] == ("%" if name.endswith("_pct.online") else "ms")
    assert [m["name"] for m in manifest.load_manifest()["per_layer"]][-8:] == METRICS  # appended, in the issue's order


def test_a_cut_round_trips_and_keeps_whole_events_only(tmp_path):
    kept = ticks.cut(HAND, seconds=3.9, skip=0.4)  # [0.9, 4.8]: ticks 1 (its dispatch is cut off) and 2
    path = tmp_path / "cut.json"
    path.write_text(json.dumps(kept))
    again = ticks.load(str(path))
    assert [e[0] for e in again["devices"]["0"]["modules"]] == ["jit_ragged_tick(7)"] * 2
    assert [(name, stats["tick"]) for name, _, _, stats in again["host"]] == [
        ("serving.sample_sync", 1), ("serving.decode_dispatch", 2), ("serving.sample_sync", 2)]
    assert again["devices"]["0"]["ops"][0] == ["fusion.1", pytest.approx(0.1), pytest.approx(0.9),
                                               TICK + "tick.decode/M.decode_step_paged/sa/mlp/dot_general"]
    joined = ticks.join(again, "ragged_tick")
    assert [row["tick"] for row in joined["ticks"]] == [2] and joined["edge_ticks"] == 1 and joined["unjoined_programs"] == 1


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_cut_of_the_online_cell():
    with open(RECORDED.replace(".json.gz", ".expected.json")) as f:
        expected = json.load(f)
    joined = ticks.join(ticks.load(RECORDED), "ragged_tick")
    counts = {cls: len(ticks.of_class(joined, cls)) for cls in ticks.CLASSES}
    assert counts == expected["classes"] and min(counts[c] for c in ("decode_only", "admission", "lane")) >= 1
    # one tick program a tick; what the cut's edges took is counted, not guessed
    assert (joined["programs"], joined["joined"], joined["unjoined_programs"], joined["edge_ticks"]) == tuple(expected["join"])
    assert joined["joined"] >= 0.95 * joined["programs"]
    assert len({row["run"] for row in joined["ticks"]}) == joined["joined"]
    for row in joined["ticks"]:
        record = row["record"]
        assert row["cls"] == ticks.tick_class(record)
        if row["cls"] == "admission":  # its prefill and install ran on the device ahead of the tick program
            assert row["other_programs_ms"] is None or row["other_programs_ms"] > 0.0
        if row["cls"] == "decode_only" and row["other_programs_ms"] is not None and not record["after_empty"]:
            assert row["other_programs_ms"] < 0.2  # at most a release or two from the harvest before
        lanes = sum(ms for name, ms in row["by_scope"].items() if name.startswith((ticks.CHUNK_SCOPE, ticks.FINISH_SCOPE)))
        assert (lanes > 0.0) == bool(record["chunk_lanes"] or record["finish_lanes"])
        assert 0.97 * row["device_ms"] <= sum(row["by_scope"].values()) <= 1.03 * row["device_ms"]
    for name, value in ticks.metrics(joined).items():
        assert value == pytest.approx(expected["metrics"][name], rel=1e-6, abs=1e-9), name
    ids = ticks.identities(joined)
    assert abs(ids["between_ticks"]["unexplained_ms"]) <= expected["tolerance_ms"]["between_ticks"]
    assert abs(ids["lane"]["unexplained_ms"]) <= expected["tolerance_ms"]["lane"]
    idle = joined["idle"]
    assert sum(idle["parts_s"].values()) == pytest.approx(idle["idle_s"], abs=1e-6)
