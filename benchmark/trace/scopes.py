"""The tick program's device time by named scope, for a model whose parts
``benchmark/trace/gaps.py`` does not know by name.

    python -m benchmark.trace.scopes <trace dir or .xplane.pb> [--program ragged_tick]

``gaps.py`` splits ``tick.decode`` into Perceiver AR's four parts and books everything
else there under ``other``. This reads the same trace the same way and names an operation
by its tick phase plus the first of ``PARTS`` found further down its ``op_name`` path
(``jit(ragged_tick)/.../tick.decode/.../ssm_update/...`` -> ``tick.decode/ssm_update``), in
any phase: a chunk lane's ``ssd_scan`` and ``attention`` are split too."""

from __future__ import annotations

import argparse
from collections import defaultdict

from benchmark.trace import describe, gaps, reduce

PARTS = ("ssm_update", "ssd_scan", "attention", "decode_attention", "cache_append", "mlp", "head")


def scope_of(op_name: str) -> str:
    parts = op_name.split("/")
    at = next((i for i, p in enumerate(parts) if p.startswith(gaps.TICK_SCOPE_PREFIX)), None)
    if at is None:
        return gaps.UNSCOPED
    inner = next((p for p in parts[at + 1:] if p in PARTS), None)
    return f"{parts[at]}/{inner}" if inner else parts[at]


def busy_by_scope(scoped_ops: list, modules: list, program: str) -> dict:
    """Device seconds inside the program's executions by ``scope_of``, and by kernel name
    for the custom calls (Pallas kernels)."""
    runs = sorted(reduce.program_events(modules, program), key=lambda e: e[1])
    edges = [(start, start + dur) for _, start, dur in runs]
    by_scope, inside, k = defaultdict(float), [], 0
    for name, start, dur, op_name in sorted(scoped_ops, key=lambda e: e[1]):
        while k < len(edges) and edges[k][1] <= start:
            k += 1
        if k == len(edges) or start < edges[k][0]:
            continue
        inside.append([name, start, dur])
        if reduce.base_name(name) not in reduce.CONTAINERS:
            by_scope[scope_of(op_name)] += dur
    busy = reduce.busy_seconds(inside)
    return {"executions": len(runs), "busy_s": busy,
            "by_scope": dict(sorted(by_scope.items(), key=lambda kv: -kv[1])),
            "by_name": dict(sorted(reduce.seconds_by_name(inside).items(), key=lambda kv: -kv[1])[:12])}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("path")
    parser.add_argument("--program", default="ragged_tick")
    args = parser.parse_args(argv)
    path = args.path if args.path.endswith(".json") else describe.newest_xplane(args.path)
    for device, d in gaps.load(path)["devices"].items():
        out = busy_by_scope(d["ops"], d["modules"], args.program)
        print(f"device {device}: {out['executions']} executions of {args.program}, busy {out['busy_s']:.4f} s")
        for name, seconds in out["by_scope"].items():
            print(f"  busy {seconds:9.4f} s  {100 * seconds / out['busy_s'] if out['busy_s'] else 0.0:5.1f}%  {name}")
        for name, seconds in out["by_name"].items():
            print(f"  op   {seconds:9.4f} s  {1e3 * seconds / max(out['executions'], 1):8.3f} ms/tick  {name}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
