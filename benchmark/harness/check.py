"""The comparisons that decide ``correct``. Each number compared is printed beside its
limit; the limits live in the cell's settings file with the readings they were set from
(PERF.md)."""

from __future__ import annotations

import math

import numpy as np


class Checks:
    """Collects ``name: value <= limit`` comparisons; ``ok`` is their conjunction."""

    def __init__(self):
        self.rows: list = []

    def at_most(self, name: str, value: float, limit: float) -> None:
        value = float(value)
        ok = math.isfinite(value) and value <= limit
        self.rows.append({"check": name, "value": value, "limit": limit, "ok": bool(ok)})

    @property
    def ok(self) -> bool:
        return all(row["ok"] for row in self.rows)


def leaf_gaps(program: dict, reference: dict) -> tuple:
    """(names, gaps): for every leaf the gap between the program's and the reference's
    norm, against the reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero)."""
    if set(program) != set(reference):
        raise ValueError(f"leaves differ: {sorted(set(program) ^ set(reference))}")
    names, got, want = [], [], []
    for name in sorted(reference):
        g, w = np.atleast_1d(np.asarray(program[name], np.float64)), np.atleast_1d(np.asarray(reference[name], np.float64))
        for i in range(len(w)):
            names.append(name if len(w) == 1 else f"{name}[{i}]")
            got.append(g[i])
            want.append(w[i])
    got, want = np.array(got), np.array(want)
    scale = np.maximum(want, np.median(want))
    gaps = np.abs(got - want) / scale
    return names, np.where(np.isfinite(gaps), gaps, np.inf)


def worst_leaf_gap(program: dict, reference: dict) -> tuple:
    """The largest of ``leaf_gaps`` and its leaf's name."""
    names, gaps = leaf_gaps(program, reference)
    worst = int(np.argmax(gaps))
    return float(gaps[worst]), names[worst]


def token_deficits(logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far each token's logit lies below the best logit at its position (>= 0)."""
    logits = np.asarray(logits, np.float64)
    return logits.max(axis=-1) - logits[np.arange(len(tokens)), np.asarray(tokens)]
