"""The readers of the program's own books (its recorder's summary, its metrics snapshot)
against a recorded pair (``data/serve_books.json``: a toy engine on the CPU), their
refusal of a renamed span or key, their silence on a program that predates what they
read, and ``trace/gaps.py`` on a hand trace and on a cut of a trace recorded on the chip
(``data/serve_trace_cut.json``, PR 24)."""

import copy
import json
import os

import pytest

from benchmark.harness import layers, manifest
from benchmark.trace import books, gaps

DATA = os.path.join(os.path.dirname(__file__), "data")
NEW = [
    "tick_loop.sync_to_dispatch_ms.online", "tick_loop.harvest_ms.online", "tick_loop.schedule_ms.online",
    "tick_loop.dispatch_ms.online", "tick_program.prefill_tick_extra_ms.online", "admission.slot_wait_p95_ms",
    "prefill.claim_to_first_token_p95_ms", "prefix_cache.saved_token_pct",
]


@pytest.fixture()
def ctx():
    with open(os.path.join(DATA, "serve_books.json")) as f:
        recorded = json.load(f)
    return {"obs": recorded["obs"], "snapshot": recorded["snapshot"]}


def read(name, ctx):
    return layers.load_reader(name)(ctx)


def test_every_new_metric_is_listed_for_the_online_cell_and_has_a_reader():
    entries = {m["name"]: m for m in manifest.load_manifest()["per_layer"]}
    for name in NEW:
        assert entries[name]["workloads"] == ["serve-455m-online"]
        assert callable(layers.load_reader(name))
    assert [m["name"] for m in manifest.load_manifest()["per_layer"]][-len(NEW):] == NEW  # appended, in the issue's order


def test_readers_on_the_recorded_pair(ctx):
    phases, snap = ctx["obs"]["phases"], ctx["snapshot"]
    mean = lambda name: 1e3 * phases[name]["total_s"] / phases[name]["count"]
    assert read("tick_loop.sync_to_dispatch_ms.online", ctx) == pytest.approx(mean("serving.host_gap"))
    assert read("tick_loop.harvest_ms.online", ctx) == pytest.approx(mean("serving.host_gap.harvest"))
    assert read("tick_loop.schedule_ms.online", ctx) == pytest.approx(mean("serving.host_gap.schedule"))
    assert read("tick_loop.dispatch_ms.online", ctx) == pytest.approx(mean("serving.host_gap.dispatch"))
    # the four parts are booked for the same ticks as the gap, and tile it
    parts = sum(read(n, ctx) for n in ("tick_loop.harvest_ms.online", "tick_loop.schedule_ms.online",
                                       "tick_loop.dispatch_ms.online")) + mean("serving.between_steps")
    assert parts == pytest.approx(read("tick_loop.sync_to_dispatch_ms.online", ctx), abs=1e-3)
    assert len({phases[n]["count"] for n in phases if n.startswith("serving.host_gap") or n == "serving.between_steps"}) == 1
    assert read("tick_program.prefill_tick_extra_ms.online", ctx) == pytest.approx(
        1e3 * (phases["serving.tick_wall.with_prefill"]["p50_s"] - phases["serving.tick_wall.decode_only"]["p50_s"]))
    assert read("admission.slot_wait_p95_ms", ctx) == pytest.approx(1e3 * snap["queue_wait_s"]["p95"])
    assert read("prefill.claim_to_first_token_p95_ms", ctx) == pytest.approx(1e3 * snap["first_token_s"]["p95"])
    assert read("prefix_cache.saved_token_pct", ctx) == pytest.approx(100.0 * 168 / 557)


def test_a_renamed_span_or_key_fails_the_run_instead_of_thinning_the_line(ctx):
    for metric, span in [("tick_loop.sync_to_dispatch_ms.online", "serving.host_gap"),
                         ("tick_loop.harvest_ms.online", "serving.host_gap.harvest"),
                         ("tick_loop.schedule_ms.online", "serving.host_gap.schedule"),
                         ("tick_loop.dispatch_ms.online", "serving.host_gap.dispatch"),
                         ("tick_program.prefill_tick_extra_ms.online", "serving.tick_wall.decode_only")]:
        renamed = copy.deepcopy(ctx)
        renamed["obs"]["phases"][span + "_v2"] = renamed["obs"]["phases"].pop(span)
        with pytest.raises(KeyError, match=span.replace(".", r"\.")):
            read(metric, renamed)
    for metric, key in [("admission.slot_wait_p95_ms", "queue_wait_s"), ("prefill.claim_to_first_token_p95_ms", "first_token_s"),
                        ("prefix_cache.saved_token_pct", "prefix_hit_tokens")]:
        renamed = copy.deepcopy(ctx)
        del renamed["snapshot"][key]
        with pytest.raises(KeyError, match=key):
            read(metric, renamed)


def test_a_phase_that_never_ran_reads_as_nothing_not_as_a_fault(ctx):
    ctx["obs"]["phases"]["serving.tick_wall.with_prefill"] = {
        "count": 0, "total_s": 0.0, "self_total_s": 0.0, "mean_s": 0.0, "p50_s": 0.0, "p95_s": 0.0, "max_s": 0.0}
    assert read("tick_program.prefill_tick_extra_ms.online", ctx) is None
    ctx["snapshot"]["prompt_tokens_admitted"] = 0
    assert read("prefix_cache.saved_token_pct", ctx) is None


def test_an_older_program_or_a_stub_context_gives_nothing_and_does_not_raise(ctx):
    """The driver lays these readers over the parent's checkout too: a recorder summary
    with no ``schema`` (before telemetry-summary/v2) and a snapshot under
    serving-metrics/v13 are a program from before the spans and stamps existed."""
    parent = {"obs": {"phases": {"serving.tick": {"count": 3, "total_s": 1.0}, "serving.decode_dispatch": {"count": 3, "total_s": 0.1}},
                      "counters": {}, "gauges": {}},
              "snapshot": {"schema": "serving-metrics/v12", "queue_wait_s": {"mean": 0.1, "max": 0.3, "p50": 0.08, "p95": 0.25}}}
    for name in NEW:
        value = read(name, parent)
        assert value == (pytest.approx(250.0) if name == "admission.slot_wait_p95_ms" else None)
        assert read(name, {}) is None
        assert read(name, {"obs": None, "snapshot": None}) is None


def test_a_traced_rehearsal_would_print_every_new_metric():
    """The whole path on the CPU at the toy size: the harness's recorder and snapshot reach
    the new readers, and the line a chip run would print holds all eight."""
    import subprocess
    import sys

    done = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "run.py"), "--workload", "serve-455m-online", "--seed",
         str(2**31 + 17), "--seconds", "2", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=manifest.ROOT, timeout=900)
    would = [json.loads(l) for l in done.stdout.splitlines() if '"rehearsal-result"' in l]
    assert done.returncode == 4 and would, done.stderr[-2000:]
    metrics = json.loads(would[-1]["would_print"])["metrics"]
    assert set(NEW) <= set(metrics)
    parts = sum(metrics[f"tick_loop.{part}_ms.online"]["value"] for part in ("harvest", "schedule", "dispatch"))
    assert 0.0 < parts <= metrics["tick_loop.sync_to_dispatch_ms.online"]["value"]  # the rest: between steps


# ------------------------------------------------------------------- gaps.py
HAND = {
    "devices": {"0": {
        "modules": [["jit_ragged_tick(7)", 0.0, 1.0], ["jit_release(3)", 1.05, 0.05], ["jit_ragged_tick(7)", 2.0, 1.0]],
        "ops": [
            ["fusion.1", 0.0, 0.4, "jit(ragged_tick)/tick.chunk_lanes/cond/branch_1_fun/while/body/mlp/dot_general"],
            ["conditional.2", 0.4, 0.6, "jit(ragged_tick)/cond"],  # a container: its body's operations follow
            ["fusion.3", 0.4, 0.3, "jit(ragged_tick)/cond/branch_1_fun/tick.decode/M.decode_step_paged/ca/cache_append/scatter"],
            ["fused_decode_attention.4", 0.7, 0.2, "jit(ragged_tick)/cond/branch_1_fun/tick.decode/M.decode_step_paged/sa/decode_attention/pallas_call"],
            ["fusion.5", 0.9, 0.1, "jit(ragged_tick)/cond/branch_1_fun/tick.decode/M.decode_step_paged/sa/layers/mlp/dense_1/dot_general"],
            ["copy.6", 1.05, 0.05, "jit(release)/scatter"],  # another program's: left out
            ["fusion.7", 2.0, 0.5, "jit(ragged_tick)/cond/branch_1_fun/tick.decode/M.decode_step_paged/q_proj/dot_general"],
            ["fusion.8", 2.5, 0.25, "jit(ragged_tick)/cond/branch_1_fun/tick.sample/sort"],
            ["fusion.9", 2.75, 0.25, "jit(ragged_tick)/convert_element_type"],
        ],
    }},
    # the device idles over [1.0, 1.05] and [1.1, 2.0]: under harvest [1.0, 1.3] (with evict
    # [1.02, 1.2] inside), nothing [1.3, 1.5], the next tick's schedule [1.52, 1.8] and its
    # decode_dispatch [1.8, 2.05]
    "host": [["serving.tick", -0.05, 1.35], ["serving.harvest", 1.0, 0.3], ["serving.evict", 1.02, 0.18],
             ["serving.tick", 1.5, 1.6], ["serving.schedule", 1.52, 0.28], ["serving.decode_dispatch", 1.8, 0.25]],
}


def test_idle_is_shared_among_the_program_spans_over_each_gap():
    idle = gaps.report(HAND, None)["idle"]
    assert idle["idle_s"] == pytest.approx(0.05 + 0.9)
    # [1.0,1.02] harvest; [1.02,1.05] and [1.1,1.2] evict; [1.2,1.3] harvest; [1.3,1.5] between two
    # ticks: the caller's; [1.5,1.52] the tick's own; [1.52,1.8] schedule; [1.8,2.0] dispatch: the
    # innermost span each time
    assert idle["by_span"] == {"serving.schedule": pytest.approx(0.28), "serving.decode_dispatch": pytest.approx(0.2),
                               "serving.between_steps": pytest.approx(0.2), "serving.evict": pytest.approx(0.13),
                               "serving.harvest": pytest.approx(0.12), "serving.tick": pytest.approx(0.02)}
    assert idle["attributed_pct"] == pytest.approx(100.0)
    assert idle["under_recorded_spans_pct"] == pytest.approx(100 * 0.75 / 0.95)
    # with no tick on either side a gap has no name
    lone = dict(HAND, host=[s for s in HAND["host"] if s[1] < 1.4])
    assert gaps.report(lone, None)["idle"]["by_span"]["(unattributed)"] == pytest.approx(0.7)


def test_busy_by_scope_covers_the_programs_operations():
    busy = gaps.report(HAND, "ragged_tick")["program"]["devices"]["0"]
    assert busy["executions"] == 2 and busy["busy_s"] == pytest.approx(2.0)
    assert busy["by_scope"] == {
        "tick.decode/other": pytest.approx(0.5), "tick.chunk_lanes": pytest.approx(0.4),
        "tick.decode/cache_append": pytest.approx(0.3), "tick.sample": pytest.approx(0.25),
        "(unscoped)": pytest.approx(0.25), "tick.decode/decode_attention": pytest.approx(0.2),
        "tick.decode/mlp": pytest.approx(0.1)}
    assert busy["scoped_over_busy_pct"] == pytest.approx(100.0)
    assert gaps.report(HAND, "no_such_program")["program"]["devices"]["0"]["executions"] == 0


def test_a_cut_round_trips(tmp_path):
    kept = gaps.cut(HAND, seconds=1.6, skip=0.5)
    assert kept["devices"]["0"]["ops"][0][:3] == ["conditional.2", 0.0, pytest.approx(0.5)]
    path = tmp_path / "cut.json"
    path.write_text(json.dumps(kept))
    again = gaps.load(str(path))
    assert again["devices"]["0"]["ops"][1][3].endswith("cache_append/scatter")
    assert gaps.report(again, None)["idle"]["idle_s"] == pytest.approx(0.05 + 0.9)


def test_the_wire_reader_finds_what_the_profiler_wrote(tmp_path):
    """A tiny XSpace written field by field: one device plane, one operation whose event
    metadata carries ``tf_op``, one program execution."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    field = lambda number, payload: varint(number << 3 | 2) + varint(len(payload)) + payload
    scalar = lambda number, value: varint(number << 3) + varint(value)
    entry = lambda key, value: scalar(1, key) + field(2, value)
    stat_meta = field(5, entry(9, scalar(1, 9) + field(2, b"tf_op")))
    op_meta = field(4, entry(1, scalar(1, 1) + field(2, b"%fusion.3 = f32[8]{0} fusion(%p), kind=kLoop")
                             + field(5, scalar(1, 9) + field(5, b"jit(ragged_tick)/tick.decode/head/dot_general"))))
    run_meta = field(4, entry(2, scalar(1, 2) + field(2, b"jit_ragged_tick(123)")))
    ops_line = field(3, field(2, b"XLA Ops") + scalar(3, 2_000_000_000)
                     + field(4, scalar(1, 1) + scalar(2, 500_000_000) + scalar(3, 250_000_000)))
    runs_line = field(3, field(2, b"XLA Modules") + scalar(3, 2_000_000_000)
                      + field(4, scalar(1, 2) + scalar(2, 0) + scalar(3, 1_000_000_000)))
    plane = field(1, field(2, b"/device:TPU:0") + ops_line + runs_line + op_meta + run_meta + stat_meta)
    other = field(1, field(2, b"/host:CPU"))
    path = tmp_path / "tiny.xplane.pb"
    path.write_bytes(other + plane)
    device = gaps.read_scoped_ops(str(path))["0"]
    assert device["ops"] == [["fusion.3", pytest.approx(2.0005), pytest.approx(0.00025),
                              "jit(ragged_tick)/tick.decode/head/dot_general"]]
    assert device["modules"] == [["jit_ragged_tick(123)", pytest.approx(2.0), pytest.approx(0.001)]]
    assert gaps.busy_by_scope(device["ops"], device["modules"], "ragged_tick")["by_scope"] == {
        "tick.decode/head": pytest.approx(0.00025)}


RECORDED = os.path.join(DATA, "serve_trace_cut.json")


def test_recorded_serving_trace_cut():
    """A cut of the traced ``serve-455m-online`` run of PR 24 (TPU v5 lite): nearly all of
    the device's idle time lies under one of the engine's spans, and the tick program's
    operations, summed by scope, cover its busy time."""
    with open(os.path.join(DATA, "serve_trace_cut.expected.json")) as f:
        expected = json.load(f)
    out = gaps.report(gaps.load(RECORDED), "ragged_tick")
    assert out["idle"]["idle_s"] == pytest.approx(expected["idle_s"], rel=1e-6)
    assert out["idle"]["attributed_pct"] >= 95.0 and out["idle"]["under_recorded_spans_pct"] >= 90.0
    assert set(out["idle"]["by_span"]) - {gaps.UNATTRIBUTED} <= {
        "serving.tick", "serving.harvest", "serving.evict", "serving.schedule", "serving.admit", "serving.prefill_dispatch",
        "serving.install", "serving.prefill_chunk", "serving.prefill_finish", "serving.decode_dispatch", "serving.sample_sync",
        "serving.between_steps"}
    busy = out["program"]["devices"]["0"]
    assert busy["busy_s"] == pytest.approx(expected["busy_s"], rel=1e-6)
    assert 98.0 <= busy["scoped_over_busy_pct"] <= 102.0
    assert busy["by_scope"].keys() >= {"tick.decode/cache_append", "tick.decode/decode_attention", "tick.decode/mlp",
                                       "tick.decode/head", "tick.sample"}
    for scope, seconds in expected["by_scope"].items():
        assert busy["by_scope"][scope] == pytest.approx(seconds, rel=1e-6)
