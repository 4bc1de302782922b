"""Whose time is the device's idle time, and whose the tick program's busy time:

    python -m benchmark.trace.gaps <dir or .xplane.pb> [--program ragged_tick] [--json out.json]
                                   [--cut cut.json --seconds 0.25 --skip 1.0]

prints (1) the device's idle seconds by the innermost PROGRAM span over each instant of
each gap — the program's own recorder spans (``serving.*``, ``train.*``), which an enabled
``TelemetryRecorder`` also enters as ``jax.profiler.TraceAnnotation``s, so that they sit
in the trace's ``/host:CPU`` plane on the clock the device's operations are on — and (2)
the device seconds of the tick program by the named scope of each operation
(``tick.resets``, ``tick.chunk_lanes``, ``tick.finish_lanes``, ``tick.sample``, and inside
``tick.decode`` the model's ``cache_append`` / ``decode_attention`` / ``mlp`` / ``head``).

The idle half works on ``reduce.read_xplane``'s dict with ``reduce.idle_gaps`` and
``reduce.name_gaps`` as they stand; a gap is first cut at the program spans' edges, so
that one device gap over harvest, the caller's time, schedule and dispatch is shared among
them instead of going whole to the span over its middle; the stretch between two
``serving.tick`` spans is named ``serving.between_steps`` (the caller's time, which the
engine books as an interval and enters no annotation for). The scope half needs each
operation's ``op_name`` (the ``tf_op`` stat of its EVENT METADATA), which
``jax.profiler.ProfileData`` does not expose (it gives an event's own stats only): the
few fields needed are read from the file's protobuf wire format directly, with no
dependency beyond the standard library.
"""

from __future__ import annotations

import argparse
import json
import struct
from collections import defaultdict

from benchmark.trace import describe, reduce

PROGRAM_PREFIXES = ("serving.", "train.")
UNATTRIBUTED = "(unattributed)"
BETWEEN_STEPS = "between_steps"
UNSCOPED = "(unscoped)"
TICK_SCOPE_PREFIX = "tick."
DECODE_SCOPE = "tick.decode"
# inside tick.decode, the first of these found in an operation's scope path
DECODE_PARTS = ("cache_append", "decode_attention", "mlp", "head")


# ------------------------------------------------------------- idle by program span
def cut_at_span_edges(gaps, host_spans) -> list:
    """Every gap cut where a host span starts or ends inside it."""
    edges = sorted({t for _, start, dur in host_spans for t in (start, start + dur)})
    pieces = []
    for start, end in gaps:
        cuts = [start] + [t for t in edges if start < t < end] + [end]
        pieces.extend([a, b] for a, b in zip(cuts, cuts[1:]))
    return pieces


def between_ticks(host_spans) -> list:
    """The stretch between one ``<ns>.tick`` span's end and the next one's start, as a
    span ``<ns>.between_steps``: the caller's time between two ``step()`` calls, which
    the engine books as an interval of that name and for which it enters no annotation
    (docs/observability.md)."""
    ticks = defaultdict(list)
    for name, start, dur in host_spans:
        if name.startswith("serving.") and name.endswith(".tick"):
            ticks[name[:-len("tick")]].append((start, start + dur))
    return [[ns + BETWEEN_STEPS, a_end, b_start - a_end]
            for ns, spans in ticks.items()
            for (_, a_end), (b_start, _) in zip(sorted(spans), sorted(spans)[1:]) if b_start > a_end]


def idle_by_program_span(trace: dict) -> dict:
    """``{"idle_s", "by_span": {name: seconds}, "attributed_pct", "under_recorded_spans_pct"}``,
    averaged over the devices, over the span from the first to the last device operation.
    ``attributed_pct`` counts the stretches between two ticks (``serving.between_steps``,
    the caller's); ``under_recorded_spans_pct`` leaves them out."""
    devices = [d for d in trace["devices"].values() if d["ops"]]
    if not devices:
        raise ValueError("no operation ran on the device in this trace")
    t0 = min(e[1] for d in devices for e in d["ops"])
    t1 = max(e[1] + e[2] for d in devices for e in d["ops"])
    host = sorted(trace["host"] + between_ticks(trace["host"]), key=lambda e: e[1])
    by_span = defaultdict(float)
    for d in devices:
        gaps = cut_at_span_edges(reduce.idle_gaps(d["ops"], t0, t1), host)
        for name, seconds in reduce.name_gaps(gaps, host, unnamed=UNATTRIBUTED).items():
            by_span[name] += seconds / len(devices)
    idle = sum(by_span.values())
    attributed = idle - by_span.get(UNATTRIBUTED, 0.0)
    recorded = attributed - sum(s for name, s in by_span.items() if name.endswith(BETWEEN_STEPS))
    share = lambda seconds: 100.0 * seconds / idle if idle > 0 else None
    return {"span_s": t1 - t0, "idle_s": idle, "by_span": dict(sorted(by_span.items(), key=lambda kv: -kv[1])),
            "attributed_pct": share(attributed), "under_recorded_spans_pct": share(recorded)}


# ---------------------------------------------------------- busy by named scope
def scope_of(op_name: str) -> str:
    """The tick phase an operation belongs to, from its ``op_name`` path
    (``jit(ragged_tick)/.../tick.decode/.../cache_append/...``)."""
    parts = op_name.split("/")
    phase = next((p for p in parts if p.startswith(TICK_SCOPE_PREFIX)), None)
    if phase is None:
        return UNSCOPED
    if phase != DECODE_SCOPE:
        return phase
    inner = next((p for p in parts if p in DECODE_PARTS), "other")
    return f"{DECODE_SCOPE}/{inner}"


def busy_by_scope(scoped_ops: list, modules: list, program: str) -> dict:
    """Device seconds of the program's executions by scope. ``scoped_ops`` are
    ``[name, start_s, duration_s, op_name]``; containers are left out (their bodies'
    operations are events of their own). The sum is compared with the union of the
    operations' intervals inside the program's executions."""
    runs = sorted(reduce.program_events(modules, program), key=lambda e: e[1])
    if not runs:
        return {"executions": 0, "busy_s": 0.0, "by_scope": {}, "scoped_over_busy_pct": None}
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
    total = sum(by_scope.values())
    return {"executions": len(runs), "busy_s": busy, "by_scope": dict(sorted(by_scope.items(), key=lambda kv: -kv[1])),
            "scoped_over_busy_pct": 100.0 * total / busy if busy > 0 else None}


# ------------------------------------------------ the protobuf fields that are needed
def _varint(buf, i):
    value, shift = 0, 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, wire type, value)`` of one message; length-delimited values are
    memoryviews into ``buf``."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = struct.unpack_from("<Q", buf, i)[0], i + 8
        elif wire == 5:
            value, i = struct.unpack_from("<I", buf, i)[0], i + 4
        else:
            raise ValueError(f"wire type {wire} is not in an XSpace")
        yield number, wire, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(buf):
    key = value = None
    for number, _, v in _fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def read_scoped_ops(path: str) -> dict:
    """``{device: {"ops": [[name, start_s, duration_s, op_name], ...], "modules": [...]}}``
    for every ``/device:TPU:<n>`` plane of an ``.xplane.pb``. Field numbers are those of
    ``tsl/profiler/protobuf/xplane.proto`` (XSpace.planes=1; XPlane.name=2, lines=3,
    event_metadata=4, stat_metadata=5; XLine.name=2, timestamp_ns=3, events=4;
    XEvent.metadata_id=1, offset_ps=2, duration_ps=3; XEventMetadata.name=2, stats=5;
    XStat.metadata_id=1, str_value=5, ref_value=7; XStatMetadata.name=2)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for number, _, plane in _fields(space):
        if number != 1:
            continue
        name, lines, event_meta, stat_meta = None, [], [], []
        for n, _, v in _fields(plane):
            if n == 2:
                name = _text(v)
            elif n == 3:
                lines.append(v)
            elif n == 4:
                event_meta.append(v)
            elif n == 5:
                stat_meta.append(v)
        match = reduce.DEVICE_PLANE.match(name or "")
        if not match:
            continue
        stat_names = {}
        for entry in stat_meta:
            key, value = _map_entry(entry)
            stat_names[key] = next((_text(v) for n, _, v in _fields(value) if n == 2), "")
        names, op_names = {}, {}
        for entry in event_meta:
            key, value = _map_entry(entry)
            for n, _, v in _fields(value):
                if n == 2:
                    names[key] = reduce.short_name(_text(v))
                elif n == 5:
                    stat = {sn: sv for sn, _, sv in _fields(v)}
                    if stat_names.get(stat.get(1)) == "tf_op":
                        op_names[key] = _text(stat[5]) if 5 in stat else stat_names.get(stat.get(7), "")
        device = {"ops": [], "modules": []}
        for line in lines:
            line_name, t0_ns, events = None, 0, []
            for n, _, v in _fields(line):
                if n == 2:
                    line_name = _text(v)
                elif n == 3:
                    t0_ns = v
                elif n == 4:
                    events.append(v)
            key = {reduce.OPS_LINE: "ops", reduce.MODULES_LINE: "modules"}.get(line_name)
            if not key:
                continue
            for event in events:
                f = {n: v for n, _, v in _fields(event)}
                row = [names.get(f.get(1), ""), t0_ns * 1e-9 + f.get(2, 0) * 1e-12, f.get(3, 0) * 1e-12]
                device[key].append(row + [op_names.get(f.get(1), "")] if key == "ops" else row)
        out[match.group(1)] = device
    return out


# ------------------------------------------------------------------------ report
def load(path: str) -> dict:
    """``{"devices": {n: {"ops": [[name, start_s, duration_s, op_name], ...], "modules":
    [...]}}, "host": [...]}`` from an ``.xplane.pb``, or from a cut of one kept as JSON
    (``--cut``: a test's recorded trace)."""
    if path.endswith(".json"):
        with open(path) as f:
            kept = json.load(f)
        table = kept.pop("op_names")  # a cut keeps each distinct op_name once
        for d in kept["devices"].values():
            d["ops"] = [[name, start, dur, table[i]] for name, start, dur, i in d["ops"]]
        return kept
    trace = reduce.read_xplane(path, host_prefix=PROGRAM_PREFIXES)
    return {"devices": read_scoped_ops(path), "host": trace["host"]}


def cut(trace: dict, seconds: float, skip: float = 0.0) -> dict:
    """``seconds`` of the trace from ``skip`` seconds after its first device operation,
    times shifted to start at 0: small enough to keep as a test's recorded trace."""
    t0 = min(e[1] for d in trace["devices"].values() for e in d["ops"]) + skip
    t1 = t0 + seconds

    def clip(events):
        out = []
        for name, start, dur, *rest in events:
            lo, hi = max(start, t0), min(start + dur, t1)
            if hi > lo:
                out.append([name, round(lo - t0, 9), round(hi - lo, 9), *rest])
        return out

    table = {}
    intern = lambda ops: [[name, start, dur, table.setdefault(op_name, len(table))] for name, start, dur, op_name in ops]
    devices = {k: {"ops": intern(clip(d["ops"])), "modules": clip(d["modules"])} for k, d in trace["devices"].items()}
    return {"op_names": list(table), "devices": devices, "host": clip(trace["host"])}


def report(trace: dict, program: str | None) -> dict:
    plain = {"devices": {k: {"ops": [e[:3] for e in d["ops"]], "modules": d["modules"]}
                         for k, d in trace["devices"].items()}, "host": trace["host"]}
    out = {"idle": idle_by_program_span(plain),
           "host_spans": dict(sorted(reduce.seconds_by_name(trace["host"]).items(), key=lambda kv: -kv[1]))}
    if program:
        out["program"] = {"name": program, "devices": {
            d: busy_by_scope(v["ops"], v["modules"], program) for d, v in trace["devices"].items()}}
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("path", help="a directory holding a trace, an .xplane.pb, or a --cut .json")
    parser.add_argument("--program", default=None,
                        help="also split this program's device time by named scope (e.g. ragged_tick)")
    parser.add_argument("--json", help="write the report there as well")
    parser.add_argument("--cut", help="write --seconds of the trace there as JSON (a test's recorded trace)")
    parser.add_argument("--seconds", type=float, default=0.25)
    parser.add_argument("--skip", type=float, default=0.0)
    args = parser.parse_args(argv)
    path = args.path if args.path.endswith(".json") else describe.newest_xplane(args.path)
    trace = load(path)
    out = {"file": path, **report(trace, args.program)}
    idle = out["idle"]
    print(f"{path}\ntraced {idle['span_s']:.4f} s, device idle {idle['idle_s']:.4f} s, "
          f"{idle['attributed_pct'] or 0.0:.1f}% of it under a program span "
          f"({idle['under_recorded_spans_pct'] or 0.0:.1f}% without the stretches between two ticks)")
    for name, seconds in idle["by_span"].items():
        print(f"  idle {seconds:9.4f} s  {100 * seconds / idle['idle_s'] if idle['idle_s'] else 0.0:5.1f}%  {name}")
    for device, busy in out.get("program", {}).get("devices", {}).items():
        print(f"device {device}: {busy['executions']} executions of {args.program}, busy {busy['busy_s']:.4f} s, "
              f"operations by scope sum to {busy['scoped_over_busy_pct'] or 0.0:.1f}% of it")
        for name, seconds in busy["by_scope"].items():
            print(f"  busy {seconds:9.4f} s  {100 * seconds / busy['busy_s']:5.1f}%  {name}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
    if args.cut:
        with open(args.cut, "w") as f:
            json.dump(cut(trace, args.seconds, args.skip), f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
