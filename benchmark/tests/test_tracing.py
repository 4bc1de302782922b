"""A traced run's line says how busy the device was, or there is no line: a trace that
holds no operation of a device (a profiler that came up after the work had ended leaves
no device plane at all; my chip run, PR 23) ends the run with an error. And the tracer
counts its seconds from when the profiler was up, not from when it was asked for."""

import json
import os
import time

import pytest

from benchmark.harness import check, device, loops, manifest, tracing

RECORDED = os.path.join(os.path.dirname(__file__), "data", "train_trace_cut.json")
NO_DEVICE = {"devices": {}, "host": [], "planes": {"/host:CPU": {}, "#Chip0 Misc": {}}}


def _run_traced(trace, monkeypatch, capsys):
    from benchmark import run as entry

    class Driver:
        @staticmethod
        def run(cell, env):
            env["window_opened"](time.time())
            return {"end_to_end": {}, "attempted": 3, "failed": 0, "checks": check.Checks(), "memory_peak_bytes": 7,
                    "excluded_from_setup_s": 0.0,
                    "context": {"kind": "train", "trace": trace, "obs": None, "window_s": 1.0, "chips": 1,
                                "rows_per_step": 16, "sizes": cell["config"]["sizes"], "program_name": "train_step"}}

    monkeypatch.setattr(device, "describe_devices", lambda chips, rehearse: {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    monkeypatch.setattr(loops, "driver_for", lambda kind: Driver)
    cell = next(w["name"] for w in manifest.load_manifest()["workloads"]
                if manifest.load_traffic(w["traffic"])["kind"] == "train")
    code = entry.main(["--workload", cell, "--seed", "5", "--seconds", "1", "--trace", "1"])
    captured = capsys.readouterr()
    return code, [l for l in captured.out.splitlines() if l.strip()], captured.err


def test_a_trace_without_device_operations_gives_no_result(monkeypatch, capsys):
    code, lines, err = _run_traced(NO_DEVICE, monkeypatch, capsys)
    assert code != 0 and "#Chip0 Misc" in err
    assert not any("correct" in json.loads(l) for l in lines)


def test_a_traced_line_has_busy_and_window_seconds(monkeypatch, capsys):
    with open(RECORDED) as f:
        trace = json.load(f)
    code, lines, _ = _run_traced(trace, monkeypatch, capsys)
    line = json.loads(lines[-1])
    assert code == 0 and line["correct"] is True
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert line["breakdown"]["device_ops"]


@pytest.mark.parametrize("start_takes", [0.0, 0.4])
def test_the_tracer_counts_from_when_the_profiler_is_up(start_takes, monkeypatch, tmp_path):
    import jax.profiler

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: (time.sleep(start_takes), calls.append("start")))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: calls.append("stop"))
    tracer = tracing.WindowTrace(str(tmp_path / "trace"), start_after=1.0, seconds=0.2)
    tracer.poll(0.5)
    assert calls == []
    tracer.poll(1.0)
    assert calls == ["start"] and tracer.start_s >= start_takes
    tracer.poll(1.0 + start_takes + 0.05)  # the profiler has been up for less than ``seconds``
    assert calls == ["start"]
    time.sleep(0.2)
    tracer.poll(1.0 + start_takes + 0.25)
    assert calls == ["start", "stop"] and tracer.stopped_at - tracer.started_at >= 0.2
