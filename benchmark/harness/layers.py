"""Per-layer metrics: one small reader per metric, a file of its own under
``benchmark/layer_metrics/`` named after the metric, found by that name. A reader is
``read(ctx) -> float | None``; one that finds nothing to read returns ``None`` and the
metric is left out of the line."""

from __future__ import annotations

import importlib.util
import os

from benchmark.harness.manifest import BENCH_DIR


def load_reader(metric_name: str):
    path = os.path.join(BENCH_DIR, "layer_metrics", f"{metric_name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"per-layer metric {metric_name!r} has no reader at {path}")
    spec = importlib.util.spec_from_file_location(f"benchmark_layer_metric_{abs(hash(metric_name))}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def read_all(per_layer: list, ctx: dict) -> dict:
    values = {}
    for metric in per_layer:
        value = load_reader(metric["name"])(ctx)
        if value is not None:
            values[metric["name"]] = float(value)
    return values
