"""What the chip bring-up must hold on the CPU: ``chip_smoke.py`` refuses to run
without a TPU, the compile cache can be placed from outside, an unknown device
has no peak FLOP/s, and a process that holds the chip cannot spawn replica
workers that need it."""

import json
import os

import jax
import pytest

import chip_smoke
from perceiver_io_tpu import compile_cache
from perceiver_io_tpu.training.flops import TPU_PEAK_FLOPS, detect_peak_flops

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]], ids=["one-chip", "four-chips"])
def test_chip_smoke_refuses_without_a_tpu(argv, capsys):
    """No CPU fallback: on this backend the script runs no phase, says why,
    and exits non-zero — a CPU run can never pass for a chip result."""
    assert chip_smoke.main(argv) != 0
    out = capsys.readouterr().out.strip().splitlines()
    last = json.loads(out[-1])
    assert last["ok"] is False and "no TPU" in last["error"]
    assert last["device"]["platform"] == "cpu"
    assert not any('"phase"' in line for line in out)


@pytest.mark.slow
def test_chip_smoke_rehearsal_runs_every_phase_and_is_never_a_result(capsys):
    assert chip_smoke.main(["--rehearse"]) != 0
    lines = [json.loads(line) for line in capsys.readouterr().out.strip().splitlines()]
    assert lines[-1]["ok"] is False and "rehearsal" in lines[-1]["error"]
    phases = [line["phase"] for line in lines if "phase" in line]
    assert phases == ["start", "train", "kernel_vs_xla", "serve", "serve", "done"]


def test_compile_cache_is_placed_by_the_environment(monkeypatch, tmp_path):
    configured = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == configured  # no other path set in code


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compile_cache()
    assert first == compile_cache.enable_compile_cache() == jax.config.jax_compilation_cache_dir
    # held to the CPU, the suite keeps its per-machine subdirectory under it
    assert os.path.dirname(first) == os.path.join(_REPO, ".jax_cache")
    assert os.path.basename(first).startswith("cpu-")


@pytest.mark.parametrize("kind", ["cpu", "TPU v9 imaginary"])
def test_unknown_device_kind_has_no_peak(kind):
    with pytest.raises(ValueError, match="no peak FLOP/s on record"):
        detect_peak_flops(kind)


def test_v5e_peak_is_the_tables():
    assert detect_peak_flops("TPU v5 lite") == TPU_PEAK_FLOPS["v5 lite"] == 197e12


def test_process_replicas_refused_where_the_parent_holds_the_chip(monkeypatch):
    """A parent whose backend is the TPU holds the chip its workers would need:
    the router refuses before it spawns one (backend faked here)."""
    from perceiver_io_tpu.serving import ServingRouter
    from perceiver_io_tpu.serving import transport
    from tests.test_transport import _make_model

    model, params = _make_model()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(transport.subprocess, "Popen",
                        lambda *a, **k: pytest.fail("a worker process was spawned"))
    with pytest.raises(RuntimeError, match="holds the chip"):
        ServingRouter(model, params, num_replicas=1, num_slots=2, replica_mode="process")
