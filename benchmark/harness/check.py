"""The comparisons that decide ``correct``. Each number compared is printed beside its
limit; the limits live in the cell's settings file with the readings they were set from
(PERF.md)."""

from __future__ import annotations

import math
import time

import numpy as np

from benchmark.harness.result import note


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


def served_token_deficits(score_served, weights, sizes: dict, served: list, limits: dict, checks: Checks,
                          controls=()) -> dict:
    """The usual comparison of served output, for greedy tokens: the reference
    (``score_served(weights, sizes, prompt, tokens[, precision])`` -> logits) runs once
    over each ``(prompt, tokens)`` of ``served``, and the widest and the mean gap by which
    a served token's logit lies below the reference's best are held to ``limits``. With
    ``controls`` (lower precisions) the first one named is scored in the program's place:
    at each position, the token that precision puts first."""
    deficits, control = [], {p: [] for p in controls}
    t0 = time.perf_counter()
    for prompt, tokens in served:
        logits = np.asarray(score_served(weights, sizes, prompt, tokens))
        deficits.append(token_deficits(logits, tokens))
        for precision in controls:
            # a control: at each position, the token the lower precision puts first
            low = np.asarray(score_served(weights, sizes, prompt, tokens, precision))
            control[precision].append(token_deficits(logits, low.argmax(axis=-1)))
    deficits = np.concatenate(deficits)
    control = {p: np.concatenate(d) for p, d in control.items()}
    scored = control[controls[0]] if controls else deficits
    checks.at_most("served_token_deficit_max", scored.max(), limits["served_token_deficit_max"])
    checks.at_most("served_token_deficit_mean", scored.mean(), limits["served_token_deficit_mean"])
    report = {"phase": "reference", "seconds": time.perf_counter() - t0, "requests": len(served),
              "tokens": int(len(deficits)), "scored": controls[0] if controls else "program",
              "program_deficit_max": float(deficits.max()), "program_deficit_mean": float(deficits.mean()),
              "tokens_off_reference_argmax": int((deficits > 0).sum()),
              "controls": {p: {"deficit_max": float(d.max()), "deficit_mean": float(d.mean()),
                               "tokens_moved": int((d > 0).sum())} for p, d in control.items()}}
    note(report)
    return report
