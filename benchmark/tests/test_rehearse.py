"""Every cell runs end to end at its toy size on the CPU and then refuses to print a
result; without ``--rehearse`` a machine with no TPU gets no result either."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import manifest

CELLS = [w["name"] for w in manifest.load_manifest()["workloads"]]


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, os.path.join(manifest.BENCH_DIR, "run.py"), *args],
                          capture_output=True, text=True, env=env, cwd=manifest.ROOT, timeout=900)


def _is_result(line: str) -> bool:
    try:
        record = json.loads(line)
    except ValueError:
        return False
    return {"correct", "attempted", "failed", "metrics", "device"} <= set(record)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_and_prints_no_result(cell, trace):
    done = _run("--workload", cell, "--seed", str(2**31 + 11), "--seconds", "2", "--trace", str(trace), "--rehearse")
    lines = [l for l in done.stdout.splitlines() if l.strip()]
    assert done.returncode == 4, done.stderr[-2000:]
    assert not any(_is_result(l) for l in lines)
    would = [json.loads(l) for l in lines if '"rehearsal-result"' in l]
    assert would and json.loads(would[-1]["would_print"])["correct"] is True
    checks = [json.loads(l) for l in lines if l.startswith('{"check"')]
    assert checks and all({"check", "value", "limit", "ok"} <= set(c) for c in checks)


def test_no_tpu_no_result():
    done = _run("--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0")
    assert done.returncode != 0 and not any(_is_result(l) for l in done.stdout.splitlines())
