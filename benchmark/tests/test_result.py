"""The last line of a run names every number ``correct`` was decided by beside its limit,
under a key that comes last, and standard error ends with the same."""

import json

from benchmark.harness import check, result


def _rows():
    checks = check.Checks()
    checks.at_most("served_token_deficit_max", 0.02, 0.08)
    checks.at_most("failed_requests", 1, 0)
    return checks


def test_compared_numbers_come_last_in_the_result_line():
    checks = _rows()
    line = json.loads(result.result_line(checks.ok, 10, 1, {"setup_s": 3.0}, {"setup_s": "s"}, {"platform": "tpu"},
                                         {"device_ops": [], "idle_gaps": []}, checks.rows))
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "breakdown", "compared"]
    assert line["correct"] is False
    assert line["compared"] == {"served_token_deficit_max": {"value": 0.02, "limit": 0.08},
                                "failed_requests": {"value": 1.0, "limit": 0}}


def test_compared_numbers_are_said_on_standard_error(capsys):
    result.say_compared(_rows().rows)
    err = capsys.readouterr().err.splitlines()
    assert err == ["compared served_token_deficit_max: value 0.02 limit 0.08 ok",
                   "compared failed_requests: value 1.0 limit 0 NOT OK"]
