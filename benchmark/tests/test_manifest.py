"""BENCHMARK.json against the contract's letter: characters, files, readers, arrows."""

import os
import re

import pytest

from benchmark.harness import layers, manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

M = manifest.load_manifest()


def test_top_level_keys_and_limits():
    assert set(M) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    assert M["paths"] == ["benchmark"] and len(M["command"]) <= 32
    assert os.path.getsize(os.path.join(manifest.ROOT, "BENCHMARK.json")) <= 64 * 1024


@pytest.mark.parametrize("entry", M["configs"] + M["workloads"] + M["end_to_end"] + M["per_layer"],
                         ids=lambda e: e["name"])
def test_names_units_and_lines(entry):
    assert NAME.match(entry["name"])
    for key in ("config", "traffic"):
        if key in entry:
            assert NAME.match(entry[key])
    if "unit" in entry:
        assert UNIT.match(entry["unit"]) and entry["better"] in ("lower", "higher") and entry["source"] in SOURCES
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] and "\t" not in entry[key]


def test_entries_have_just_the_contracts_keys():
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and w["chips"] in (1, 4)
    for m in M["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace") and 0 < m["bound"] <= 0.1
    for m in M["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}


def test_names_are_unique_and_cells_pair_once():
    for group in (M["configs"], M["workloads"], M["end_to_end"] + M["per_layer"]):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert sum(w["chips"] == 4 for w in M["workloads"]) <= max(1, len(M["workloads"]) // 4)
    assert {c["name"] for c in M["configs"]} == {w["config"] for w in M["workloads"]}


@pytest.mark.parametrize("cell", [w["name"] for w in M["workloads"]])
def test_every_cell_resolves_and_reports(cell):
    resolved = manifest.resolve_cell(cell)
    names = {m["name"] for m in resolved["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2 and resolved["per_layer"]
    assert resolved["traffic"]["kind"] in ("train", "open_loop")
    size_keys = manifest.load_family(resolved["config"]["family"]).SIZE_KEYS
    assert "vocab_size" in resolved["config"]["sizes"] and set(size_keys) <= set(resolved["config"]["sizes"])
    for key in size_keys:
        assert resolved["config"]["sizes"][key] == resolved["config"][key]


@pytest.mark.parametrize("metric", M["per_layer"], ids=lambda m: m["name"])
def test_every_per_layer_metric_has_a_reader_and_an_arrow(metric):
    assert callable(layers.load_reader(metric["name"]))
    cells = {w["name"] for w in M["workloads"]}
    moved = {m["name"]: m for m in M["end_to_end"]}[metric["moves"]]
    for cell in metric.get("workloads", cells):
        assert cell in cells and cell in moved.get("workloads", cells)
    if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
        assert metric["unit"] == "%"
    assert metric["name"] != "setup_s"


def test_files_exist_and_lie_under_paths():
    for c in M["configs"]:
        assert c["file"].startswith("benchmark/") and os.path.exists(os.path.join(manifest.ROOT, c["file"]))
    for w in M["workloads"]:
        assert os.path.exists(os.path.join(manifest.BENCH_DIR, "traffic", w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(manifest.BENCH_DIR, "workloads", w["name"] + ".json"))
    for root, _, files in os.walk(manifest.BENCH_DIR):
        if "__pycache__" in root:
            continue
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), os.path.join(root, f)
