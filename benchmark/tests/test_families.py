"""The seam between the harness and "which model this is": a configuration names its
``family``, and ``benchmark/families/<family>/`` gives the builder, the weights, the
reference and the check. A second family enters by files alone: the fixture under
``data/fixture/`` (other key names, a list-valued key, a ``mixture`` length law) runs both
drivers to ``correct`` without any file of the harness knowing it."""

import json
import os
import re
import sys

import numpy as np
import pytest

from benchmark import families
from benchmark.harness import device, manifest, traffic
from benchmark.harness.loops import driver_for

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "fixture")
FAMILY_WORDS = re.compile(r"perceiver_ar|max_latents|CausalSequenceModel|perceiver_io_tpu\.models")


def _python_files(*parts):
    top = os.path.join(manifest.BENCH_DIR, *parts)
    if os.path.isfile(top):
        return [top]
    return [os.path.join(root, f) for root, _, files in os.walk(top) for f in files if f.endswith(".py")]


HARNESS = sorted(_python_files("harness") + _python_files("trace") + _python_files("run.py")
                 + _python_files("sweep.py") + _python_files("control.py"))


@pytest.mark.parametrize("path", HARNESS, ids=lambda p: os.path.relpath(p, manifest.BENCH_DIR))
def test_the_harness_names_no_family_and_imports_no_model(path):
    with open(path) as f:
        found = FAMILY_WORDS.findall(f.read())
    assert not found, f"{path} knows a model family: {sorted(set(found))}"


def test_only_a_family_imports_the_programs_models():
    for path in _python_files(""):
        inside = os.path.relpath(path, manifest.BENCH_DIR).split(os.sep)[0]
        if inside in ("families", "tests"):
            continue
        with open(path) as f:
            assert "perceiver_io_tpu.models" not in f.read(), path


SERVING = ("warm_up_prompt_lengths", "live_cache_entries", "TICK_PROGRAM", "check_served")
TRAINING = ("make_program_train_step", "row_tokens", "make_train_step", "leaf_norms")
EVERY = ("SIZE_KEYS", "build_model", "to_program_params", "from_program_params", "check_param_tree",
         "seed_key", "build_weights", "make_weights")


@pytest.mark.parametrize("config", manifest.load_manifest()["configs"], ids=lambda c: c["name"])
def test_a_configurations_family_gives_every_name_its_cells_drivers_ask_for(config):
    M = manifest.load_manifest()
    family = manifest.load_family(manifest.load_config(config)["family"])
    kinds = {manifest.load_traffic(w["traffic"])["kind"] for w in M["workloads"] if w["config"] == config["name"]}
    wanted = EVERY + (TRAINING if "train" in kinds else ()) + (SERVING if kinds - {"train"} else ())
    assert [name for name in wanted if not hasattr(family, name)] == []
    assert all(name in families.__doc__ for name in wanted)  # the list a new family is written from


# ------------------------------------------------------------------ a second family
@pytest.fixture()
def fixture_benchmark(monkeypatch):
    """The fixture's tree in place of ``benchmark/``'s data files, and its family findable
    beside the real ones; what it imported is forgotten afterwards."""
    monkeypatch.setattr(manifest, "BENCH_DIR", FIXTURE)
    monkeypatch.setattr(families, "__path__", [*families.__path__, os.path.join(FIXTURE, "families")])
    with open(os.path.join(FIXTURE, "BENCHMARK.json")) as f:
        yield json.load(f)
    for name in [n for n in sys.modules if n.startswith("benchmark.families.toy_")]:
        del sys.modules[name]


def _drive(cell: dict, tmp_path, seconds: float = 1.5, seed: int = 2**31 + 29):
    device.enable_caches()
    env = {"seed": seed, "seconds": seconds, "trace": False, "rehearse": True,
           "monitor": device.CompileMonitor(), "scratch": str(tmp_path), "device": {},
           "memory_peak_bytes": lambda: 0, "window_opened": lambda t: None}
    return driver_for(cell["traffic"]["kind"]).run(cell, env)


@pytest.mark.parametrize("cell_name", ["toy-train", "toy-serve"])
def test_a_family_the_harness_has_never_heard_of_runs_to_correct(cell_name, fixture_benchmark, tmp_path):
    cell = manifest.resolve_cell(cell_name, fixture_benchmark)
    assert cell["config"]["sizes"]["layer_kinds"] == ["rotary", "plain"] and "hidden_size" in cell["config"]["sizes"]
    assert "num_channels" not in cell["config"]["sizes"]
    outcome = _drive(cell, tmp_path)
    assert outcome["checks"].ok, outcome["checks"].rows
    assert outcome["attempted"] > 0 and outcome["failed"] == 0
    compared = {row["check"] for row in outcome["checks"].rows}
    assert compared >= ({"served_token_deficit_mean"} if cell_name == "toy-serve" else {"update_norm_gap_worst_leaf"})


def test_the_fixture_family_is_not_findable_without_its_directory():
    with pytest.raises(ValueError, match="toy_decoder"):
        manifest.load_family("toy_decoder")


# ------------------------------------------------------- a configuration and its family
def _manifest_with(tmp_path, config: dict) -> dict:
    path = tmp_path / "config.json"
    path.write_text(json.dumps(config))
    M = manifest.load_manifest()
    entry = {**M["configs"][0], "file": str(path)}
    return {**M, "configs": [entry]}


def _real_config() -> dict:
    M = manifest.load_manifest()
    with open(os.path.join(manifest.ROOT, M["configs"][0]["file"])) as f:
        return json.load(f)


@pytest.mark.parametrize("family, says", [(None, 'has no "family"'), ("no_such_family", "names the family 'no_such_family'")])
def test_a_configuration_without_a_family_fails_in_resolve_cell(family, says, tmp_path):
    config = {k: v for k, v in _real_config().items() if k != "family"}
    if family:
        config["family"] = family
    M = _manifest_with(tmp_path, config)
    with pytest.raises(ValueError) as e:
        manifest.resolve_cell(M["workloads"][0]["name"], M)
    message = str(e.value)
    assert says in message and str(tmp_path / "config.json") in message
    assert os.path.join(manifest.ROOT, "benchmark", "families") in message  # the directory looked in


def test_a_configuration_that_lacks_a_size_key_says_which_and_whose(tmp_path):
    config = _real_config()
    del config["num_heads"]
    M = _manifest_with(tmp_path, config)
    with pytest.raises(ValueError, match=r"lacks \['num_heads'\].*perceiver_ar"):
        manifest.resolve_cell(M["workloads"][0]["name"], M)


def test_run_exits_2_and_prints_no_result_for_a_cell_whose_family_is_missing(tmp_path, monkeypatch, capsys):
    from benchmark import run

    config = {**_real_config(), "family": "no_such_family"}
    M = _manifest_with(tmp_path, config)
    monkeypatch.setattr(manifest, "load_manifest", lambda: M)
    assert run.main(["--workload", M["workloads"][0]["name"], "--rehearse"]) == 2
    out = capsys.readouterr()
    assert out.out == "" and "no_such_family" in out.err


# ------------------------------------------------------------------ the mixture law
MIXTURE = {"law": "mixture", "parts": [
    {"share": 0.7, "law": "lognormal", "median": 100, "sigma": 0.8, "min": 16, "max": 400},
    {"share": 0.3, "law": "uniform", "min": 2000, "max": 8000},
]}


@pytest.mark.parametrize("n", [10, 11, 333])
def test_mixture_honours_its_shares_to_the_rounding_of_counts(n):
    lengths = traffic.length_set(MIXTURE, n)
    short = int((lengths < 1000).sum())
    assert len(lengths) == n and abs(short - 0.7 * n) < 1 and short + int((lengths >= 2000).sum()) == n


def test_mixture_clips_each_part_to_its_own_bounds():
    lengths = traffic.length_set(MIXTURE, 400)
    short, long = lengths[lengths < 1000], lengths[lengths >= 1000]
    assert short.min() == 16 and short.max() == 400  # sigma 0.8 reaches both bounds of the first part
    assert long.min() >= 2000 and long.max() <= 8000
    assert (np.sort(short) == np.sort(traffic.length_set(MIXTURE["parts"][0], len(short)))).all()
    assert traffic.length_bounds(MIXTURE) == (16, 8000)


def test_mixture_gives_every_seed_the_same_multiset_in_another_order():
    mix = {"kind": "open_loop", "prompt_tokens": MIXTURE,
           "new_tokens": {"law": "mixture", "parts": [{"share": 0.5, "law": "fixed", "value": 8},
                                                      {"share": 0.5, "law": "uniform", "min": 100, "max": 200}]}}
    a, b = (traffic.make_requests(mix, {"vocab_size": 1000}, seed, 101, rate_rps=10.0) for seed in (2**31 + 1, 7))
    for key in (lambda r: len(r["prompt"]), lambda r: r["new_tokens"]):
        assert sorted(map(key, a)) == sorted(map(key, b)) and list(map(key, a)) != list(map(key, b))
    assert sum(r["new_tokens"] == 8 for r in a) in (50, 51)


@pytest.mark.parametrize("shares", [[0.5, 0.4], [1.2, -0.2]])
def test_mixture_refuses_shares_that_do_not_sum_to_one(shares):
    law = {"law": "mixture", "parts": [{"share": s, "law": "fixed", "value": 8} for s in shares]}
    with pytest.raises(ValueError, match="shares"):
        traffic.length_set(law, 10)
