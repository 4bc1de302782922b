"""Per-tick reductions for a served model whose layers are Mamba-2, expert and attention
layers alone in one stack, on one chip's SHARE of its experts: the tick programs' device
time under the scope ``moe/shared``, the experts' grouped products against the work the
tick's own counters say the HELD experts had, and the two decode kernels it shares with
the models before it against this model's bytes. Each returns None where there is nothing
to read (a program that lacks the scope or the counter; a run that was not traced).

The counters: the engine's harvest span of a tick whose model counts its experts'
assignments carries ``experts_touched`` (the mean number of HELD experts a layer that
received a row in the tick's decode step: the matrices it had to read) and
``experts_held_assignments`` (the decode step's assignments that fell to held experts, over
the layers: the rows it had to compute; the others are another chip's).

    python -m benchmark.trace.nemotron <trace dir or .xplane.pb> [--program ragged_tick]

prints the tick program's device time by this model's scopes (``trace/experts.py`` books
``ssm`` and ``moe/shared`` under their phase)."""

from __future__ import annotations

import argparse
import os
from collections import defaultdict

from benchmark.rooflines import nemotron_moe_grouped_matmul, nemotron_paged_gqa_decode, nemotron_ssm_decode_update
from benchmark.trace import describe, experts, gaps, recurrent, reduce, ticks

# innermost first: an operation goes to the first of these found in its ``op_name`` path
SCOPES = ("moe/route", "moe/experts", "moe/shared", "moe", "ssm", "attention", "head")
HELD = "experts_held_assignments"


def scope_of(op_name: str) -> str:
    """``<tick phase>/<scope>`` of an operation, by ``SCOPES``; the phase alone where none is found."""
    parts = op_name.split("/")
    at = next((i for i, p in enumerate(parts) if p.startswith(gaps.TICK_SCOPE_PREFIX)), None)
    if at is None:
        return gaps.UNSCOPED
    path = "/" + "/".join(parts[at + 1:]) + "/"
    inner = next((s for s in SCOPES if f"/{s}/" in path), None)
    return f"{parts[at]}/{inner}" if inner else parts[at]


def read_held_harvests(path: str) -> dict:
    """``{tick: (experts_touched, experts_held_assignments)}`` from the harvest spans of an
    ``.xplane.pb``; empty where the program's spans carry no such counter."""
    import jax.profiler

    out = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != reduce.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serving.") and e.name.endswith(experts.HARVEST):
                    stats = dict(e.stats)
                    if HELD in stats and "experts_touched" in stats and "tick" in stats:
                        out[int(stats["tick"])] = (float(stats["experts_touched"]), int(stats[HELD]))
    return out


def scope_share_pct(ctx, scope: str):
    """Time of the tick programs' operations under ``scope`` (any phase), by this model's
    ``SCOPES``, over the programs' busy time."""
    found = experts.scoped_ticks(ctx)
    if found is None:
        return None
    device, programs, _ = found
    seconds = sum(op[2] for _, op in experts._inside(device["ops"], programs) if experts._under(scope_of(op[3]), scope))
    busy = sum(reduce.per_program_busy([op[:3] for op in device["ops"]], programs))
    return 100.0 * seconds / busy if seconds > 0 and busy > 0 else None


def decode_roofline_pct(ops, programs, rows, harvests: dict, sizes: dict, peaks: dict):
    """As ``experts.decode_roofline_pct``, for a share: per tick that decodes, the two
    matrices of every HELD expert that received a row and the rows of the assignments that
    fell to held experts (``rooflines/nemotron_moe_grouped_matmul.py``), over the time
    under ``tick.decode/.../moe/experts`` in that tick's program."""
    layers = nemotron_moe_grouped_matmul.expert_layers(sizes)
    under = defaultdict(float)
    for k, op in experts._inside(ops, programs):
        scoped = scope_of(op[3])
        if scoped.startswith(experts.DECODE_SCOPE + "/") and experts._under(scoped, "moe/experts"):
            under[programs[k][1]] += op[2]
    need = spent = 0.0
    for row in rows:
        counted = harvests.get(row["tick"])
        seconds = under.get(row["program_start_s"], 0.0)
        if counted is None or not row["record"]["decoding"] or seconds <= 0:
            continue
        need += nemotron_moe_grouped_matmul.seconds_at_roofline(
            sizes, peaks, touched=counted[0] * layers, assignments=counted[1])
        spent += seconds
    return 100.0 * need / spent if spent > 0 else None


def moe_grouped_matmul_roofline_pct(ctx):
    found, joined, peaks = experts.scoped_ticks(ctx), ticks.joined_ticks(ctx), ctx.get("peaks")
    if found is None or not joined or not joined["ticks"] or not peaks:
        return None
    from benchmark.harness import manifest

    path = ticks.newest_trace_file(os.path.join(manifest.ROOT, "benchmark_out"))
    harvests = read_held_harvests(path) if path else {}
    if not harvests:
        return None
    device, programs, _ = found
    return decode_roofline_pct(device["ops"], programs, joined["ticks"], harvests, ctx["sizes"], peaks)


def ssm_update_roofline_pct(ctx):
    """State bytes of the slots each traced tick decoded, read and written, in the ``M``
    layers, over the chip's memory bandwidth, over the state-update kernel's time."""
    if "mamba_num_heads" not in ctx["sizes"]:
        return None
    return recurrent._roofline_pct(ctx, recurrent.SSM_KERNEL,
                                   lambda t: nemotron_ssm_decode_update.bytes_per_tick(ctx["sizes"], t[1]))


def paged_gqa_roofline_pct(ctx):
    """Live key and value bytes of the slots each traced tick decoded, in the ``*`` layers
    alone, over the chip's memory bandwidth, over the grouped-query paged kernel's time."""
    if "hybrid_override_pattern" not in ctx["sizes"]:
        return None
    return recurrent._roofline_pct(ctx, recurrent.GQA_KERNEL,
                                   lambda t: nemotron_paged_gqa_decode.bytes_per_tick(ctx["sizes"], t[2]))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("path")
    parser.add_argument("--program", default="ragged_tick")
    args = parser.parse_args(argv)
    path = args.path if args.path.endswith(".json") else describe.newest_xplane(args.path)
    for name, d in gaps.load(path)["devices"].items():
        programs = sorted(reduce.program_events(d["modules"], args.program), key=lambda e: e[1])
        by_scope = defaultdict(float)
        for _, op in experts._inside(d["ops"], programs):
            by_scope[scope_of(op[3])] += op[2]
        busy = sum(reduce.per_program_busy([op[:3] for op in d["ops"]], programs))
        print(f"device {name}: {len(programs)} executions of {args.program}, busy {busy:.4f} s")
        for scope, seconds in sorted(by_scope.items(), key=lambda kv: -kv[1]):
            print(f"  busy {seconds:9.4f} s  {100 * seconds / busy if busy else 0.0:5.1f}%  "
                  f"{1e3 * seconds / max(len(programs), 1):8.3f} ms/tick  {scope}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
