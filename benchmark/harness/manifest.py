"""Reading ``BENCHMARK.json`` and the data files it names. Every cell, configuration,
traffic mix and per-layer metric is found by its name; nothing here knows one of them."""

from __future__ import annotations

import copy
import importlib
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")

FAMILIES = "benchmark.families"


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest() -> dict:
    return _load(os.path.join(ROOT, "BENCHMARK.json"))


def _family_dirs() -> list:
    return list(importlib.import_module(FAMILIES).__path__)


def load_family(name: str, named_by: str = "the configuration"):
    """The package ``benchmark/families/<name>/``: a model family's glue to the program,
    its weights, its reference and what the drivers ask of it (``families/__init__.py``
    lists the names). Imported once; every later call finds it."""
    try:
        return importlib.import_module(f"{FAMILIES}.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"{FAMILIES}.{name}":
            raise  # the family is there, and something it imports is not
        raise ValueError(f"{named_by} names the family {name!r}, and there is no {name}/__init__.py "
                         f"under {_family_dirs()}") from e


def load_config(config_entry: dict) -> dict:
    """A configuration file as the harness uses it: ``sizes`` gathered from the top level
    under the keys its ``family`` sizes a model by (``vocab_size`` is every family's: the
    traffic generator draws token ids from it)."""
    file = config_entry["file"]
    raw = _load(os.path.join(ROOT, file))
    if not isinstance(raw.get("family"), str):
        raise ValueError(f'{file} has no "family": it has to name a directory under {_family_dirs()}')
    size_keys = dict.fromkeys(("vocab_size", *load_family(raw["family"], file).SIZE_KEYS))
    missing = [k for k in size_keys if k not in raw]
    if missing:
        raise ValueError(f"{file} lacks {missing}, which the family {raw['family']!r} sizes its model by")
    return {**raw, "sizes": {k: raw[k] for k in size_keys}}


def load_traffic(name: str) -> dict:
    return _load(os.path.join(BENCH_DIR, "traffic", f"{name}.json"))


def load_workload_settings(name: str) -> dict:
    return _load(os.path.join(BENCH_DIR, "workloads", f"{name}.json"))


def resolve_cell(name: str, manifest: dict | None = None) -> dict:
    """Everything one cell runs with: its manifest entry, configuration, traffic mix and
    settings, and the metrics it reports."""
    manifest = manifest or load_manifest()
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (has: {sorted(cells)})")
    cell = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    in_cell = lambda m: name in m.get("workloads", [name])
    end_to_end = [m for m in manifest["end_to_end"] if in_cell(m)]
    reported = {m["name"] for m in end_to_end}
    per_layer = [m for m in manifest["per_layer"] if in_cell(m) and m["moves"] in reported]
    return {
        "name": name, "chips": cell["chips"],
        "config": load_config(configs[cell["config"]]),
        "traffic": load_traffic(cell["traffic"]),
        "settings": load_workload_settings(name),
        "end_to_end": end_to_end, "per_layer": per_layer,
        "run_seconds": manifest["run_seconds"],
    }


def rehearsal_cell(cell: dict) -> dict:
    """The cell at its toy size: the overrides its settings file gives under ``rehearse``."""
    cell = copy.deepcopy(cell)
    over = cell["settings"].get("rehearse", {})
    cell["config"]["sizes"].update(over.get("sizes", {}))
    cell["config"]["execution"] = {**cell["config"].get("execution", {}), **over.get("execution", {})}
    cell["config"]["compute_dtype"] = over.get("compute_dtype", cell["config"]["compute_dtype"])
    cell["traffic"].update(over.get("traffic", {}))
    cell["settings"].update(over.get("settings", {}))
    return cell
