"""Look at one trace by hand: ``python -m benchmark.trace.describe <dir or .xplane.pb>``
prints its planes, lines and commonest events; ``--cut out.json --seconds s`` also writes
the first ``s`` seconds (after the first device operation) as the plain dict that
``reduce.py`` works on, small enough to keep as a test's recorded trace."""

from __future__ import annotations

import argparse
import glob
import json
import os

from benchmark.trace import reduce


def newest_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return max(files, key=os.path.getmtime)


def cut(trace: dict, seconds: float) -> dict:
    starts = [e[1] for d in trace["devices"].values() for e in d["ops"]]
    t0 = min(starts)
    t1 = t0 + seconds
    shift = lambda events: [[n, round(s - t0, 9), round(d, 9)] for n, s, d in reduce.clip(events, t0, t1)]
    return {"devices": {k: {"ops": shift(d["ops"]), "modules": shift(d["modules"])} for k, d in trace["devices"].items()},
            "host": shift(trace["host"])}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("path")
    parser.add_argument("--cut")
    parser.add_argument("--seconds", type=float, default=0.5)
    args = parser.parse_args(argv)
    path = newest_xplane(args.path)
    print(json.dumps({"file": path, "bytes": os.path.getsize(path), "planes": reduce.describe_xplane(path)}, indent=1))
    if args.cut:
        with open(args.cut, "w") as f:
            json.dump(cut(reduce.read_xplane(path), args.seconds), f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
