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


def say_compared(rows: list) -> None:
    """Each number ``correct`` was decided by, beside its limit: the last lines of
    standard error (a record of a run that is not correct keeps only the end of it)."""
    for row in rows:
        print(f"compared {row['check']}: value {row['value']!r} limit {row['limit']!r} {'ok' if row['ok'] else 'NOT OK'}",
              file=sys.stderr, flush=True)


def result_line(correct: bool, attempted: int, failed: int, metrics: dict, units: dict,
                device: dict, breakdown: dict | None = None, compared: list = ()) -> str:
    """The one JSON object a run prints last; ``compared`` (the checks' rows) comes last
    in it, each number under its short name with its limit."""
    line = {
        "correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
        "metrics": {name: {"value": float(value), "unit": units[name]} for name, value in metrics.items()},
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = {row["check"]: {"value": row["value"], "limit": row["limit"]} for row in compared}
    return json.dumps(line)
