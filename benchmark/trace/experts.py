"""Per-tick reductions for a served model of short-convolution, attention and routed expert
layers: the tick programs' device time under its named scopes (``moe/route``,
``moe/experts``, ``short_conv``), and the experts' grouped products against the work the
tick's own COUNTERS say they had. Each returns None where there is nothing to read (a
program that lacks the scope or the counters; a run that was not traced).

    python -m benchmark.trace.experts <trace dir or .xplane.pb> [--program ragged_tick]

prints the tick program's device time by these scopes (``trace/scopes.py`` knows the
scopes of the models before this one).

The counters: the engine's harvest span (``serving.<ns>.harvest``) of a tick whose model
counts its experts' assignments carries ``expert_assignments`` and ``experts_touched``
(the mean number of experts a layer that received a row in the tick's DECODE step), read
back with the tick's tokens. The roofline is taken over the decode phase: a decode step
is one call a layer, so the experts the counters name are the matrices it had to read;
a chunk lane is a call of its own whose counters the tick sums with the lanes before it."""

from __future__ import annotations

import argparse
import os
from collections import defaultdict

from benchmark.rooflines import lfm2_paged_gqa_decode, moe_grouped_matmul
from benchmark.trace import describe, gaps, recurrent, reduce, ticks

DECODE_SCOPE = "tick.decode"
HARVEST = ".harvest"
# innermost first: an operation goes to the first of these found in its ``op_name`` path
SCOPES = ("moe/route", "moe/experts", "moe", "short_conv", "attention", "mlp", "head")


def scope_of(op_name: str) -> str:
    """``<tick phase>/<scope>`` of an operation, by ``SCOPES``; the phase alone where none is found."""
    parts = op_name.split("/")
    at = next((i for i, p in enumerate(parts) if p.startswith(gaps.TICK_SCOPE_PREFIX)), None)
    if at is None:
        return gaps.UNSCOPED
    path = "/" + "/".join(parts[at + 1:]) + "/"
    inner = next((s for s in SCOPES if f"/{s}/" in path), None)
    return f"{parts[at]}/{inner}" if inner else parts[at]


def _under(scoped: str, scope: str) -> bool:
    """``<phase>/moe/experts`` is under ``moe`` and under ``moe/experts``."""
    inner = scoped.partition("/")[2]
    return inner == scope or inner.startswith(scope + "/")


def _inside(ops, programs) -> list:
    """``(program index, operation)`` for the operations inside the tick programs'
    executions, containers left out."""
    edges = [(start, start + dur) for _, start, dur, *_ in programs]
    out, k = [], 0
    for op in sorted(ops, key=lambda e: e[1]):
        while k < len(edges) and edges[k][1] <= op[1]:
            k += 1
        if k == len(edges):
            break
        if op[1] >= edges[k][0] and reduce.base_name(op[0]) not in reduce.CONTAINERS:
            out.append((k, op))
    return out


def read_harvests(path: str) -> dict:
    """``{tick: (expert_assignments, experts_touched)}`` from the harvest spans of an
    ``.xplane.pb`` that carry the counters; empty where the program's spans do not."""
    import jax.profiler

    out = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != reduce.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serving.") and e.name.endswith(HARVEST):
                    stats = dict(e.stats)
                    if "experts_touched" in stats and "tick" in stats:
                        out[int(stats["tick"])] = (int(stats["expert_assignments"]), float(stats["experts_touched"]))
    return out


def scoped_ticks(ctx):
    """(this run's device with every operation's ``op_name``, its tick programs, the
    harvests' counters by tick), read once from the trace file the harness has just written
    and shared by the readers (``ctx["experts_scoped"]``); None where there is nothing to
    read: no trace, no device plane (a rehearsal on the CPU), or a file that is not this
    run's. The harness's own reduction (``ctx["trace"]``) keeps an operation's name and
    times only."""
    if "experts_scoped" in ctx:
        return ctx["experts_scoped"]
    ctx["experts_scoped"] = None
    trace = ctx.get("trace")
    if not trace or not trace.get("devices") or "planes" not in trace:
        return None
    from benchmark.harness import manifest

    path = ticks.newest_trace_file(os.path.join(manifest.ROOT, "benchmark_out"))
    if path is None:
        return None
    devices = gaps.read_scoped_ops(path)
    if {k: len(d["modules"]) for k, d in devices.items()} != {k: len(d["modules"]) for k, d in trace["devices"].items()}:
        return None  # another run's file
    device = next(iter(devices.values()))  # a serving cell drives one chip
    programs = sorted(reduce.program_events(device["modules"], ctx["program_name"]), key=lambda e: e[1])
    if len(programs) >= 2:
        ctx["experts_scoped"] = (device, programs, read_harvests(path))
    return ctx["experts_scoped"]


def scope_share_pct(ctx, scope: str):
    """Time of the tick programs' operations under ``scope`` (any phase) over the
    programs' busy time."""
    found = scoped_ticks(ctx)
    if found is None:
        return None
    device, programs, _ = found
    seconds = sum(op[2] for _, op in _inside(device["ops"], programs) if _under(scope_of(op[3]), scope))
    busy = sum(reduce.per_program_busy([op[:3] for op in device["ops"]], programs))
    return 100.0 * seconds / busy if seconds > 0 and busy > 0 else None


def decode_roofline_pct(ops, programs, rows, harvests: dict, sizes: dict, peaks: dict):
    """``ops`` with their ``op_name``, the tick ``programs``, the joined ticks' ``rows``
    (``ticks.join``) and the harvests' counters by tick: per tick that decodes, what its
    decode step's grouped products had to move and compute by the tick's own counters
    (``rooflines/moe_grouped_matmul.py``) at the chip's peaks, over the time of the
    operations under ``tick.decode/.../moe/experts`` in that tick's program; summed over
    the ticks both are known for."""
    layers, top_k = moe_grouped_matmul.expert_layers(sizes), sizes["num_experts_per_tok"]
    under = defaultdict(float)  # program start -> seconds under the decode step's moe/experts
    for k, op in _inside(ops, programs):
        scoped = scope_of(op[3])
        if scoped.startswith(DECODE_SCOPE + "/") and _under(scoped, "moe/experts"):
            under[programs[k][1]] += op[2]
    need = spent = 0.0
    for row in rows:
        counted = harvests.get(row["tick"])
        seconds = under.get(row["program_start_s"], 0.0)
        if counted is None or not row["record"]["decoding"] or seconds <= 0:
            continue
        need += moe_grouped_matmul.seconds_at_roofline(
            sizes, peaks, touched=counted[1] * layers, assignments=row["record"]["decoding"] * top_k * layers)
        spent += seconds
    return 100.0 * need / spent if spent > 0 else None


def moe_grouped_matmul_roofline_pct(ctx):
    found, joined, peaks = scoped_ticks(ctx), ticks.joined_ticks(ctx), ctx.get("peaks")
    if found is None or not joined or not joined["ticks"] or not peaks:
        return None
    device, programs, harvests = found
    if not harvests:
        return None
    return decode_roofline_pct(device["ops"], programs, joined["ticks"], harvests, ctx["sizes"], peaks)


def paged_gqa_roofline_pct(ctx):
    """Live key and value bytes of the slots each traced tick decoded, in the attention
    layers alone, over the chip's memory bandwidth, over the grouped-query paged kernel's
    time."""
    return recurrent._roofline_pct(ctx, recurrent.GQA_KERNEL,
                                   lambda t: lfm2_paged_gqa_decode.bytes_per_tick(ctx["sizes"], t[2]))


def snapshot_experts(ctx, *path: str):
    """A value of the ``experts`` block of the engine's snapshot; None where the served
    model counts nothing."""
    value = (ctx.get("snapshot") or {}).get("experts")
    for key in path:
        if not isinstance(value, dict) or key not in value:
            return None
        value = value[key]
    return value


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("path")
    parser.add_argument("--program", default="ragged_tick")
    args = parser.parse_args(argv)
    path = args.path if args.path.endswith(".json") else describe.newest_xplane(args.path)
    for name, d in gaps.load(path)["devices"].items():
        programs = sorted(reduce.program_events(d["modules"], args.program), key=lambda e: e[1])
        by_scope, by_name = defaultdict(float), defaultdict(float)
        for _, op in _inside(d["ops"], programs):
            by_scope[scope_of(op[3])] += op[2]
            by_name[reduce.base_name(op[0])] += op[2]
        busy = sum(reduce.per_program_busy([op[:3] for op in d["ops"]], programs))
        print(f"device {name}: {len(programs)} executions of {args.program}, busy {busy:.4f} s")
        for scope, seconds in sorted(by_scope.items(), key=lambda kv: -kv[1]):
            print(f"  busy {seconds:9.4f} s  {100 * seconds / busy if busy else 0.0:5.1f}%  "
                  f"{1e3 * seconds / max(len(programs), 1):8.3f} ms/tick  {scope}")
        for op, seconds in sorted(by_name.items(), key=lambda kv: -kv[1])[:14]:
            print(f"  op   {seconds:9.4f} s  {1e3 * seconds / max(len(programs), 1):8.3f} ms/tick  {op}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
