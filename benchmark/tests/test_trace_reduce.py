"""The reduction, on a trace small enough to compute by hand, and on a cut of a trace
recorded on the chip (``data/train_trace_cut.json``, PR 23)."""

import json
import os

import pytest

from benchmark.trace import reduce, serving, training

HAND = {
    "devices": {"0": {
        # two executions of the program "jit_step": [0, 1.0] and [1.5, 2.5]
        "modules": [["jit_step(1)", 0.0, 1.0], ["jit_step(1)", 1.5, 1.0], ["jit_other(2)", 2.6, 0.1]],
        "ops": [
            ["fusion.1", 0.0, 0.4], ["splash_mha_fwd.3", 0.4, 0.2], ["all-gather-done.7", 0.7, 0.1],
            ["fusion.1", 1.5, 0.5], ["splash_mha_fwd.3", 2.0, 0.25], ["fusion.9", 2.2, 0.3],  # overlaps the kernel
            ["copy.2", 2.6, 0.1],
        ],
    }},
    "host": [["bench.step", 0.0, 1.2], ["bench.submit", 1.2, 0.25], ["bench.step", 1.45, 1.3]],
}


def test_busy_is_the_union_of_operation_intervals():
    ops = HAND["devices"]["0"]["ops"]
    # [0,.6] + [.7,.8] + [1.5,2.5] + [2.6,2.7]
    assert reduce.busy_seconds(ops) == pytest.approx(0.6 + 0.1 + 1.0 + 0.1)
    assert reduce.busy_intervals(ops) == [pytest.approx([0.0, 0.6]), pytest.approx([0.7, 0.8]),
                                          pytest.approx([1.5, 2.5]), pytest.approx([2.6, 2.7])]


def test_kernel_time_is_the_sum_of_its_events_by_instruction_name():
    ops = HAND["devices"]["0"]["ops"]
    assert reduce.kernel_seconds(ops, "splash_mha") == pytest.approx(0.45)
    assert reduce.seconds_by_name(ops)["fusion"] == pytest.approx(0.4 + 0.5 + 0.3)
    assert reduce.base_name("%fused_decode_attention.12.3") == "fused_decode_attention"


def test_idle_gaps_are_named_by_the_host_span_over_their_middle():
    ops = HAND["devices"]["0"]["ops"]
    gaps = reduce.idle_gaps(ops, 0.0, 2.7)
    assert gaps == [pytest.approx([0.6, 0.7]), pytest.approx([0.8, 1.5]), pytest.approx([2.5, 2.6])]
    named = reduce.name_gaps(gaps, HAND["host"])
    # [.6,.7] mid .65 and [.8,1.5] mid 1.15 -> bench.step; [2.5,2.6] mid 2.55 -> bench.step
    assert named == {"bench.step": pytest.approx(0.1 + 0.7 + 0.1)}
    assert reduce.name_gaps([[1.25, 1.35]], HAND["host"]) == {"bench.submit": pytest.approx(0.1)}
    assert reduce.name_gaps([[5.0, 6.0]], HAND["host"]) == {"(no harness span)": pytest.approx(1.0)}


def test_summary_gives_busy_span_and_a_breakdown():
    s = reduce.summarize(HAND)
    assert s["busy_s"] == pytest.approx(1.8) and s["span_s"] == pytest.approx(2.7)
    assert s["breakdown"]["device_ops"][0] == ["fusion", pytest.approx(1.2)]
    assert len(s["breakdown"]["device_ops"]) <= 10


def test_per_step_metrics_on_the_hand_trace():
    ctx = {"trace": HAND, "program_name": "jit_step", "summary": reduce.summarize(HAND)}
    # execution 1: ops in [0,1.0]: busy .6 + .1 = .7; execution 2: [1.5,2.5] fully busy: 1.0
    assert training.step_busy_seconds(ctx) == pytest.approx(0.85)
    assert training.kernel_seconds_per_step(ctx, "splash_mha") == pytest.approx(0.225)
    assert reduce.idle_pct(ctx) == pytest.approx(100 * (1 - 1.8 / 2.7))
    # serving view of the same trace: the gap between the two executions, [1.0,1.5], is idle
    assert serving.tick_host_gap_ms(ctx) == pytest.approx(500.0)
    assert serving.tick_device_ms(ctx) == pytest.approx(850.0)


def test_a_trace_with_no_device_plane_is_refused():
    with pytest.raises(ValueError):
        reduce.summarize({"devices": {}, "host": []})


RECORDED = os.path.join(os.path.dirname(__file__), "data", "train_trace_cut.json")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_trace_cut():
    with open(RECORDED) as f:
        trace = json.load(f)
    with open(RECORDED.replace(".json", ".expected.json")) as f:
        expected = json.load(f)
    s = reduce.summarize(trace)
    assert s["busy_s"] == pytest.approx(expected["busy_s"], rel=1e-9)
    assert s["span_s"] == pytest.approx(expected["span_s"], rel=1e-9)
    ops = next(iter(trace["devices"].values()))["ops"]
    # busy can never exceed the span, nor the plain sum of durations
    assert s["busy_s"] <= s["span_s"] and s["busy_s"] <= sum(e[2] for e in ops) + 1e-12
    assert reduce.kernel_seconds(ops, "splash_mha") == pytest.approx(expected["splash_s"], rel=1e-9)
