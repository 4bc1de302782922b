"""The knee on record is what ``sweep.judge`` reads off the recorded ladders, and the
cell's rate is its stated share of it."""

import pytest

from benchmark import sweep
from benchmark.harness import manifest, traffic

OPEN_LOOP = [w["name"] for w in manifest.load_manifest()["workloads"]
             if manifest.load_traffic(w["traffic"])["kind"] == "open_loop"]


@pytest.mark.parametrize("cell", OPEN_LOOP)
def test_recorded_sweeps_give_the_recorded_knee_and_rate(cell):
    settings = manifest.load_workload_settings(cell)
    mean_answer = float(traffic.length_set(manifest.resolve_cell(cell)["traffic"]["new_tokens"], 1000).mean())
    for recorded in settings["sweep"]["recorded"]:
        judged = sweep.judge(recorded["table"], recorded["slots"], mean_answer)
        assert judged["knee_rps"] == settings["knee_rps"]
        assert judged["capacity_rps"] > settings["knee_rps"]
        assert not judged["table"][-1]["sustained"]  # the ladder went on into overload
    assert settings["rate_rps"] == pytest.approx(settings["rate_share"] * settings["knee_rps"])


def test_the_knee_is_the_last_rate_before_the_tail_rises():
    row = lambda rate, p95, close: {"rate_rps": rate, "failed": 0, "ttft": {"p95_ms": p95}, "tokens_per_s": 100.0 * rate,
                                    "in_flight_at_open": 4, "in_flight_at_close": close}
    judged = sweep.judge([row(1, 100, 4), row(2, 120, 6), row(3, 126, 8), row(4, 110, 9), row(5, 400, 40)], 8, 50.0)
    # 3/s is over 1.25 x the floor, so 4/s, though under it again, is past the knee
    assert judged["knee_rps"] == 2 and [r["steady"] for r in judged["table"]] == [True, True, False, True, False]
    assert [r["sustained"] for r in judged["table"]] == [True, True, True, True, False]
    assert judged["capacity_rps"] == pytest.approx(10.0)
