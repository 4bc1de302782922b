"""The last line of a run, and the lines before it."""

from __future__ import annotations

import json
import sys
import time

_T0 = time.time()


def note(record: dict) -> None:
    """An earlier line of the output: anything worth reading that is not the result."""
    print(json.dumps({**record, "at_s": round(time.time() - _T0, 2)}), flush=True)


def fail(message: str, code: int = 1) -> int:
    """No result line: the reason goes to standard error."""
    print(f"benchmark: {message}", file=sys.stderr, flush=True)
    return code


def result_line(correct: bool, attempted: int, failed: int, metrics: dict, units: dict,
                device: dict, breakdown: dict | None = None) -> str:
    line = {
        "correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
        "metrics": {name: {"value": float(value), "unit": units[name]} for name, value in metrics.items()},
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    return json.dumps(line)
