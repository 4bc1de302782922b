"""One row a tick: what the tick carried, joined to the tick's own device execution.

    python -m benchmark.trace.ticks <dir, .xplane.pb or a cut> [--program ragged_tick] [--json out.json]
                                    [--cut cut.json.gz --seconds 0.2 --skip 1.0]

The engine makes one record a dispatching tick (``serving/engine.py`` ``TickRecord``: tick,
programs, oneshot_admissions, chunk_lanes, finish_lanes, chunk_tokens, resets, decoding,
transfers, after_empty) and its spans carry it: ``<ns>.sample_sync`` all ten fields at its
begin, ``<ns>.decode_dispatch`` those known before the pack (tick, chunk_lanes, finish_lanes,
decoding, after_empty). An enabled recorder enters every span as a
``jax.profiler.TraceAnnotation`` with its arguments, so in a profiler trace the record is
the ``stats`` of two ``/host:CPU`` events on the clock the device's ``XLA Modules`` and
``XLA Ops`` are on (docs/observability.md "The tick, tiled"), up to one offset a profiler
session: the profiler aligns the device's clock with the host's once, to within a
millisecond or so, which is as much as the lags below. ``clock_offset`` bounds that offset
from both sides with the runtime's own host events (a program cannot start before the
host enqueued it, ``DoEnqueueProgram``; the host cannot run its completion callbacks before
it ended, ``CompleteCallbacks``; paired with their program by ``run_id``), and the join takes
its middle; half the bounds' distance is what every number that crosses the two clocks is
uncertain by. A trace without those events is joined as it stands.

**The join.** The device runs tick programs in the order the host dispatched them, one a
dispatching tick. Tick n's execution is the first run of the program not yet given to an
earlier tick that starts after ``decode_dispatch(n)`` starts and before ``sample_sync(n)``
ends (a tick that decodes nothing has no sync: before the next sync's end). A tick whose
spans the trace's edges cut, or that finds no run, is dropped and counted; so is a run no
tick claims. Per joined tick:

* ``cls``: ``lane`` (a chunk or finish lane beside decoding slots), ``admission`` (no lane,
  a one-shot prefill + install queued ahead of the tick program), ``decode_only`` (neither),
  ``lane_only`` (lanes and no decoding slot: the usual first ticks after an empty engine;
  no token waits on them);
* ``device_ms``: device busy inside the tick program, and ``by_scope``: its operations'
  time by named scope (``scopes.scope_of``: tick phase / model part);
* ``dispatch_ms`` (the ``decode_dispatch`` span: pack, transfer, jit call), ``launch_lag_ms``
  (the program's start minus the dispatch's return; negative when the device started
  first), of which ``enqueue_lag_ms`` (the dispatch's return to the runtime's enqueue, on
  the host's clock alone: the jit call returns before its program is enqueued);
  ``readback_lag_ms`` (the sync's return minus the program's end), of which ``fetch_ms`` (the
  runtime's completion callbacks to the sync's return, on the host's clock alone: the
  device-to-host copies of the tick's two outputs); ``wall_ms``
  (dispatch's start to sync's return, what ``serving.tick_wall.*`` books),
  ``sync_to_dispatch_ms`` (the previous tick's sync's return to this dispatch's return, what
  ``serving.host_gap`` books), ``period_ms`` (the previous sync's return to this one's: the gap
  between two tokens of every slot that decodes in both ticks);
* ``idle_before_ms`` and ``other_programs_ms``: from the previous tick program's end to this
  one's start, the time the device ran nothing, and the time it ran other programs (one-shot
  prefill and install of this tick's admissions, releases of the harvest before).

Two identities tie the rows to what the benchmark already reports. Between two ticks,
``readback_lag(n) + sync_to_dispatch(n+1) + launch_lag(n+1)`` is exactly the stretch between
the two tick programs, which ``tick_loop.host_gap_ms.online`` reads as a median over all
pairs. On a lane tick, ``wall = dispatch + launch_lag + program + readback_lag``, so the
lane ticks' extra wall is the extra device time plus the extra host time plus what the
lags and the program's own idle time differ by. Medians do not add, so each is printed
with its remainder as ``unexplained``.
"""

from __future__ import annotations

import argparse
import bisect
import glob
import gzip
import json
import os
import time
from collections import defaultdict

from benchmark.trace import describe, gaps, reduce, scopes

FIELDS = ("tick", "programs", "oneshot_admissions", "chunk_lanes", "finish_lanes", "chunk_tokens",
          "resets", "decoding", "transfers", "after_empty")
DISPATCH_FIELDS = ("tick", "chunk_lanes", "finish_lanes", "decoding", "after_empty")
DISPATCH, SYNC = "decode_dispatch", "sample_sync"
CLASSES = ("decode_only", "admission", "lane", "lane_only")
COLUMNS = ("device_ms", "program_ms", "dispatch_ms", "launch_lag_ms", "enqueue_lag_ms", "readback_lag_ms", "fetch_ms",
           "wall_ms", "sync_to_dispatch_ms", "period_ms", "idle_before_ms", "other_programs_ms")
CHUNK_SCOPE, FINISH_SCOPE = "tick.chunk_lanes", "tick.finish_lanes"
# the TPU runtime's own events on the host's clock, each carrying the ``run_id`` of one
# execution of a program: when the host enqueued it, and when it ran its completion callbacks
ENQUEUE, COMPLETE = "DoEnqueueProgram", "CompleteCallbacks"
RUNTIME_EVENTS = (ENQUEUE, COMPLETE)
CUT_SUFFIXES = (".json", ".json.gz")  # a cut of some hundred milliseconds is megabytes of operations: kept gzipped


# ------------------------------------------------------------------------ reading
def read_profile(path: str):
    """From an ``.xplane.pb``, with ``jax.profiler.ProfileData``: the ``/host:CPU`` events the
    join needs, ``[name, start_s, duration_s, {stat: value}]`` (every
    ``serving.*.decode_dispatch`` and ``serving.*.sample_sync`` with the tick's record, and
    the runtime's ``DoEnqueueProgram`` / ``CompleteCallbacks`` with their ``run_id``), and for
    every device plane the ``run_id`` of each ``XLA Modules`` event in order of start."""
    import jax.profiler

    wanted = ("." + DISPATCH, "." + SYNC)
    events, run_ids = [], {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        match = reduce.DEVICE_PLANE.match(plane.name)
        if match:
            for line in plane.lines:
                if line.name == reduce.MODULES_LINE:
                    runs = sorted((e.start_ns, dict(e.stats).get("run_id")) for e in line.events)
                    run_ids[match.group(1)] = [run_id for _, run_id in runs]
        elif plane.name == reduce.HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    name = e.name
                    if name in RUNTIME_EVENTS or (name.startswith("serving.") and name.endswith(wanted)):
                        events.append([name, e.start_ns * 1e-9, e.duration_ns * 1e-9, dict(e.stats)])
    return sorted(events, key=lambda s: s[1]), run_ids


def read_trace(path: str) -> dict:
    """An ``.xplane.pb`` as the dict the join works on: the device's operations with their
    ``op_name`` and its programs (``gaps.read_scoped_ops``), each program with its ``run_id``
    as a fourth element; the host's events (``read_profile``); the device clock's offset."""
    host, run_ids = read_profile(path)
    devices = gaps.read_scoped_ops(path)
    for key, d in devices.items():
        d["modules"].sort(key=lambda e: e[1])
        ids = run_ids.get(key, [])
        d["modules"] = [[*run, ids[i] if len(ids) == len(d["modules"]) else None] for i, run in enumerate(d["modules"])]
    trace = {"devices": devices, "host": host}
    trace["clock"] = clock_offset(trace)
    return trace


def load(path: str) -> dict:
    """``{"devices": {n: {"ops": [[name, start_s, duration_s, op_name], ...], "modules":
    [[name, start_s, duration_s, run_id], ...]}}, "host": [[name, start_s, duration_s, stats],
    ...], "clock": {"low_s", "high_s"} or None}`` from an ``.xplane.pb``, or from a cut of one
    kept as JSON (``--cut``: a test's recorded trace)."""
    if path.endswith(CUT_SUFFIXES):
        with (gzip.open(path, "rt") if path.endswith(".gz") else open(path)) as f:
            kept = json.load(f)
        table = kept.pop("op_names")  # a cut keeps each distinct op_name once
        for d in kept["devices"].values():
            d["ops"] = [[name, start, dur, table[i]] for name, start, dur, i in d["ops"]]
        return kept
    return read_trace(path)


def clock_offset(trace: dict):
    """How far the device's clock runs behind the host's in this trace, bounded from both
    sides by the runtime's own host events: a program cannot start on the device before
    the host enqueued it (``DoEnqueueProgram``), nor can the host run its completion
    callbacks (``CompleteCallbacks``) before it ended; each is paired with its program by
    ``run_id``. ``{"low_s", "high_s"}`` (device time + offset = host time), or ``None`` where
    the trace has no such events or they contradict each other. The profiler aligns the two
    clocks once a session, to within a millisecond or so: PERF.md section 6, PR 38."""
    starts, ends = {}, {}
    for d in trace["devices"].values():
        for _, start, dur, *run_id in d["modules"]:
            if run_id and run_id[0] is not None:
                starts[run_id[0]], ends[run_id[0]] = start, start + dur
    paired = lambda name, edge: [t - edge[stats["run_id"]] for n, t, _, stats in trace["host"]
                                 if n == name and stats.get("run_id") in edge]
    low, high = paired(ENQUEUE, starts), paired(COMPLETE, ends)
    if not low or not high or max(low) > min(high):
        return None
    return {"low_s": max(low), "high_s": min(high)}


def cut(trace: dict, seconds: float, skip: float = 0.0) -> dict:
    """``seconds`` of the trace from ``skip`` seconds after its first device operation, times
    shifted to start at 0. Unlike ``gaps.cut`` an event the edge would cut is left out
    whole (a span cut short would read as a shorter span; the join counts what is missing)."""
    t0 = min(e[1] for d in trace["devices"].values() for e in d["ops"]) + skip
    t1 = t0 + seconds
    inside = lambda events: [[e[0], round(e[1] - t0, 9), round(e[2], 9), *e[3:]] for e in events
                             if t0 <= e[1] and e[1] + e[2] <= t1]
    table = {}
    intern = lambda ops: [[name, start, dur, table.setdefault(op_name, len(table))] for name, start, dur, op_name in ops]
    devices = {k: {"ops": intern(inside(d["ops"])), "modules": inside(d["modules"])} for k, d in trace["devices"].items()}
    return {"op_names": list(table), "devices": devices, "host": inside(trace["host"]), "clock": trace.get("clock")}


# --------------------------------------------------------------------- the record
def carries_records(host_spans) -> bool:
    """Whether the program that wrote the trace is new enough to carry the tick's record: a
    span of an older one has ``tick`` and nothing else."""
    return any(set(stats) - {"tick"} for name, _, _, stats in host_spans if name not in RUNTIME_EVENTS)


def record_of(name: str, stats: dict, fields=FIELDS) -> dict:
    """The span's record; a field it lacks was renamed, which raises, naming it."""
    for field in fields:
        if field not in stats:
            raise KeyError(f"{name} of tick {stats.get('tick')} carries no argument {field!r} (renamed?); "
                           f"it carries {sorted(stats)}")
    return {field: int(stats[field]) for field in fields}


def tick_class(record: dict) -> str:
    if not record["decoding"]:
        return "lane_only"
    if record["chunk_lanes"] or record["finish_lanes"]:
        return "lane"
    return "admission" if record.get("oneshot_admissions") else "decode_only"


# ----------------------------------------------------------------------- the join
class _Ops:
    """The device's operations by start time: busy seconds and scoped seconds of a stretch."""

    def __init__(self, ops):
        self.ops = sorted(ops, key=lambda e: e[1])
        self.starts = [e[1] for e in self.ops]

    def within(self, t0: float, t1: float) -> list:
        return self.ops[bisect.bisect_left(self.starts, t0):bisect.bisect_left(self.starts, t1)]

    def busy(self, t0: float, t1: float) -> float:
        return reduce.busy_seconds([e[:3] for e in self.within(t0, t1)]) if t1 > t0 else 0.0


def join(trace: dict, program: str) -> dict:
    """Rows of the joined ticks (module docstring) and the account of what did not join.
    Host times are brought onto the device's clock first (``clock_offset``, its middle)."""
    device = next(iter(trace["devices"].values()))  # a serving cell drives one chip
    ops = _Ops(device["ops"])
    runs = sorted(reduce.program_events(device["modules"], program), key=lambda e: e[1])
    clock = trace.get("clock")
    offset = 0.5 * (clock["low_s"] + clock["high_s"]) if clock else 0.0
    dispatches, syncs, enqueued, completed = [], {}, {}, {}
    for name, start, dur, stats in trace["host"]:
        start -= offset
        if name == ENQUEUE:
            enqueued[stats.get("run_id")] = start
        elif name == COMPLETE:
            completed[stats.get("run_id")] = start
        elif name.endswith("." + DISPATCH):
            dispatches.append((name[:-len(DISPATCH) - 1], start, dur, stats))
        elif name.endswith("." + SYNC):
            syncs[name[:-len(SYNC) - 1], stats.get("tick")] = (start, dur, stats)
    sync_ends = sorted(start + dur for start, dur, _ in syncs.values())
    rows, taken, k, edge_ticks = [], set(), 0, 0
    previous = None  # (dispatching tick before this one, its sync's end or None)
    for ns, d_start, d_dur, d_stats in dispatches:
        sync = syncs.pop((ns, d_stats.get("tick")), None)
        if sync is not None:
            record = record_of(f"{ns}.{SYNC}", sync[2])
            limit = sync[0] + sync[1]
        else:
            record = record_of(f"{ns}.{DISPATCH}", d_stats, DISPATCH_FIELDS)
            after = bisect.bisect_right(sync_ends, d_start)
            limit = sync_ends[after] if after < len(sync_ends) else float("inf")
            if record["decoding"]:  # it did sync, after the trace's end
                previous, edge_ticks = (record["tick"], None), edge_ticks + 1
                continue
        while k < len(runs) and runs[k][1] < d_start:
            k += 1  # a run from before this dispatch is an earlier tick's, or nobody's
        if k == len(runs) or runs[k][1] >= limit:
            previous, edge_ticks = (record["tick"], None), edge_ticks + 1
            continue
        _, p_start, p_dur, *run_id = runs[k]
        run_id = run_id[0] if run_id else None
        taken.add(k)
        p_end, d_end = p_start + p_dur, d_start + d_dur
        inside = ops.within(p_start, p_end)
        by_scope = defaultdict(float)
        for name, _, dur, op_name in inside:
            if reduce.base_name(name) not in reduce.CONTAINERS:
                by_scope[scopes.scope_of(op_name)] += 1e3 * dur
        row = {"tick": record["tick"], "cls": tick_class(record), "record": record, "run": k,
               "device_ms": 1e3 * reduce.busy_seconds([e[:3] for e in inside]), "program_ms": 1e3 * p_dur,
               "by_scope": dict(by_scope), "dispatch_ms": 1e3 * d_dur, "launch_lag_ms": 1e3 * (p_start - d_end),
               "enqueue_lag_ms": None, "readback_lag_ms": None, "fetch_ms": None, "wall_ms": None,
               "sync_to_dispatch_ms": None, "period_ms": None, "idle_before_ms": None, "other_programs_ms": None,
               "program_start_s": p_start}
        if run_id in enqueued:  # on the host's clock alone: the dispatch's return to the runtime's enqueue
            row["enqueue_lag_ms"] = 1e3 * (enqueued[run_id] - d_end)
        if sync is not None:
            row["readback_lag_ms"], row["wall_ms"] = 1e3 * (limit - p_end), 1e3 * (limit - d_start)
            if run_id in completed:  # on the host's clock alone: the completion callbacks to the sync's return
                row["fetch_ms"] = 1e3 * (limit - completed[run_id])
        if previous is not None and previous[1] is not None and previous[0] == record["tick"] - 1:
            row["sync_to_dispatch_ms"] = 1e3 * (d_end - previous[1])
            if sync is not None:  # what every decoding slot's next token waited: one sync's return to the next
                row["period_ms"] = 1e3 * (limit - previous[1])
        if k > 0:
            before = runs[k - 1][1] + runs[k - 1][2]
            other = ops.busy(before, p_start)
            row["idle_before_ms"], row["other_programs_ms"] = 1e3 * (p_start - before - other), 1e3 * other
        rows.append(row)
        previous = (record["tick"], limit if sync is not None else None)
        k += 1
    edge_ticks += len(syncs)  # a sync whose dispatch began before the trace did
    return {"program": program, "programs": len(runs), "joined": len(rows), "edge_ticks": edge_ticks,
            "unjoined_programs": len(runs) - len(taken), "ticks": rows,
            "clock_offset_ms": {k[:-2]: 1e3 * v for k, v in clock.items()} if clock else None,
            "idle": idle_account(ops, runs, rows), "host_gap_ms": _host_gap_ms(ops, runs)}


def _host_gap_ms(ops: _Ops, runs: list):
    """``serving.tick_host_gap_ms``'s number on this trace: median device-idle time between
    consecutive tick programs, whatever their class."""
    if len(runs) < 2:
        return None
    return 1e3 * reduce.median([max(b[1] - a[1] - a[2], 0.0) - ops.busy(a[1] + a[2], b[1]) for a, b in zip(runs, runs[1:])])


def idle_account(ops: _Ops, runs: list, rows: list) -> dict:
    """The device's idle seconds over the traced span (first to last operation), each
    stretch once: before a tick that followed an empty engine (no request: not the loop's
    cost), before every other joined tick, before a run no tick claimed, inside the tick
    programs, and at the trace's two edges. The parts add up to ``idle_s`` exactly."""
    t0, t1 = ops.starts[0], max(e[1] + e[2] for e in ops.ops)
    by_run = {row["run"]: row for row in rows}
    parts = dict.fromkeys(("no_request", "before_other_ticks", "before_unjoined_programs", "inside_tick_programs", "edges"), 0.0)
    for i, (_, start, dur, *_) in enumerate(runs):
        parts["inside_tick_programs"] += dur - ops.busy(start, start + dur)
        if i == 0:
            continue
        before = runs[i - 1][1] + runs[i - 1][2]
        idle = max(start - before, 0.0) - ops.busy(before, start)
        row = by_run.get(i)
        key = "before_unjoined_programs" if row is None else "no_request" if row["record"]["after_empty"] else "before_other_ticks"
        parts[key] += idle
    if runs:
        last = runs[-1][1] + runs[-1][2]
        parts["edges"] = (runs[0][1] - t0 - ops.busy(t0, runs[0][1])) + (t1 - last - ops.busy(last, t1))
    idle = (t1 - t0) - ops.busy(t0, t1)
    return {"span_s": t1 - t0, "idle_s": idle, "parts_s": parts, "idle_pct": 100.0 * idle / (t1 - t0),
            "parts_pct": {k: 100.0 * v / (t1 - t0) for k, v in parts.items()}}


# --------------------------------------------------------------------- reductions
def of_class(joined: dict, *classes: str) -> list:
    return [row for row in joined["ticks"] if row["cls"] in classes]


def median_of(rows: list, column) -> float | None:
    """Median of a column (or of ``column(row)``) over the rows that have it."""
    get = column if callable(column) else (lambda row: row[column])
    values = [v for v in map(get, rows) if v is not None]
    return reduce.median(values) if values else None


def _minus(a, b):
    return None if a is None or b is None else a - b


def host_ms(row: dict) -> float:
    """The host's part of a tick's wall before the device starts it: pack, transfer, jit
    call, and the launch after the call returned."""
    return row["dispatch_ms"] + row["launch_lag_ms"]


def lane_scope_ms(joined: dict, scope: str, lanes: str):
    """Device milliseconds under ``scope`` a lane, over the ticks that carry such a lane."""
    rows = [row for row in joined["ticks"] if row["record"][lanes]]
    count = sum(row["record"][lanes] for row in rows)
    if not count:
        return None
    return sum(ms for row in rows for name, ms in row["by_scope"].items() if name.startswith(scope)) / count


def metrics(joined: dict) -> dict:
    """The eight per-layer metrics this file reads (``benchmark/layer_metrics/``), by name."""
    decode, lane = of_class(joined, "decode_only"), of_class(joined, "lane")
    decoding = of_class(joined, "decode_only", "admission", "lane")
    idle = joined["idle"]
    return {
        "tick_program.lane_tick_share_pct.online": 100.0 * len(lane) / len(decoding) if decoding else None,
        "tick_program.lane_extra_device_ms.online": _minus(median_of(lane, "device_ms"), median_of(decode, "device_ms")),
        "tick_program.chunk_lane_device_ms.online": lane_scope_ms(joined, CHUNK_SCOPE, "chunk_lanes"),
        "tick_program.finish_lane_device_ms.online": lane_scope_ms(joined, FINISH_SCOPE, "finish_lanes"),
        "tick_loop.lane_extra_host_ms.online": _minus(median_of(lane, host_ms), median_of(decode, host_ms)),
        "tick_loop.launch_lag_ms.online": median_of(decode, "launch_lag_ms"),
        "tick_loop.readback_lag_ms.online": median_of(decode, "readback_lag_ms"),
        "device.idle_no_request_pct.online": idle["parts_pct"]["no_request"] if joined["joined"] else None,
    }


def identities(joined: dict) -> dict:
    """The two identities of the module docstring, each side in milliseconds, and what the
    medians leave over."""
    decode, lane = of_class(joined, "decode_only"), of_class(joined, "lane")
    steady = [row for row in decode if not row["record"]["after_empty"]]
    parts = {"readback_lag_ms": median_of(decode, "readback_lag_ms"),
             "sync_to_dispatch_ms": median_of(steady, "sync_to_dispatch_ms"),
             "launch_lag_ms": median_of(decode, "launch_lag_ms")}
    between = {"parts": parts, "host_gap_ms": joined["host_gap_ms"], "unexplained_ms": None}
    if None not in parts.values() and joined["host_gap_ms"] is not None:
        between["unexplained_ms"] = joined["host_gap_ms"] - sum(parts.values())
    extra = lambda column: _minus(median_of(lane, column), median_of(decode, column))
    own_idle = lambda row: row["program_ms"] - row["device_ms"]
    lane_id = {"lane_extra_device_ms": extra("device_ms"), "lane_extra_host_ms": extra(host_ms),
               "wall_extra_ms": extra("wall_ms"), "readback_lag_extra_ms": extra("readback_lag_ms"),
               "program_idle_extra_ms": extra(own_idle), "unexplained_ms": None}
    if None not in (lane_id["lane_extra_device_ms"], lane_id["lane_extra_host_ms"], lane_id["wall_extra_ms"]):
        lane_id["unexplained_ms"] = lane_id["wall_extra_ms"] - lane_id["lane_extra_device_ms"] - lane_id["lane_extra_host_ms"]
    return {"between_ticks": between, "lane": lane_id}


def by_class(joined: dict) -> dict:
    """The table the CLI prints: per class the count, the median of each column, the mean
    record (lanes, tokens, decoding slots a tick) and the mean device milliseconds a tick by
    scope (means add up; medians do not)."""
    out = {}
    for cls in CLASSES:
        rows = of_class(joined, cls)
        if not rows:
            continue
        scopes_ms = defaultdict(float)
        for row in rows:
            for name, ms in row["by_scope"].items():
                scopes_ms[name] += ms / len(rows)
        mean = lambda field: sum(row["record"].get(field) or 0 for row in rows) / len(rows)
        out[cls] = {"count": len(rows), "median": {c: median_of(rows, c) for c in COLUMNS},
                    "mean_record": {f: mean(f) for f in FIELDS[1:]},
                    "mean_scope_ms": dict(sorted(scopes_ms.items(), key=lambda kv: -kv[1]))}
    return out


# ---------------------------------------------------------------- the readers' side
def newest_trace_file(scratch: str):
    """The trace the harness has just written (``Bench.start_tracer``: ``<scratch>/trace-<cell>``)."""
    files = [f for d in glob.glob(os.path.join(scratch, "trace-*"))
             for f in glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)]
    return max(files, key=os.path.getmtime) if files else None


def joined_ticks(ctx: dict):
    """The run's joined ticks, read once and shared by the readers; ``None`` where there is
    nothing to read: no trace, no device plane (a rehearsal on the CPU), a trace file that
    is not this run's, or a program whose spans carry ``tick`` and nothing else."""
    if "joined_ticks" in ctx:
        return ctx["joined_ticks"]
    ctx["joined_ticks"] = None
    trace = ctx.get("trace")
    if not trace or not trace.get("devices") or "planes" not in trace:
        return None
    from benchmark.harness import manifest, result

    path = newest_trace_file(os.path.join(manifest.ROOT, "benchmark_out"))
    if path is None:
        return None
    t0 = time.perf_counter()
    own = read_trace(path)
    if not carries_records(own["host"]):
        return None
    if {k: len(d["modules"]) for k, d in own["devices"].items()} != {k: len(d["modules"]) for k, d in trace["devices"].items()}:
        return None  # another run's file
    ctx["joined_ticks"] = joined = join(own, ctx["program_name"])
    # how well the join went and what this second read of the file cost, for whoever reads the run's log
    result.note({"phase": "tick_join", **{k: joined[k] for k in ("programs", "joined", "unjoined_programs", "edge_ticks", "clock_offset_ms")},
                 "classes": {cls: len(of_class(joined, cls)) for cls in CLASSES}, "identities": identities(joined),
                 "idle_parts_pct": joined["idle"]["parts_pct"], "read_and_join_s": time.perf_counter() - t0})
    return joined


def metric(ctx: dict, name: str):
    joined = joined_ticks(ctx)
    return None if joined is None else metrics(joined)[name]


# ------------------------------------------------------------------------- report
def _ms(value) -> str:
    return "-" if value is None else f"{value:.3f}"


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("path", help="a directory holding a trace, an .xplane.pb, or a --cut .json / .json.gz")
    parser.add_argument("--program", default="ragged_tick")
    parser.add_argument("--json", help="write the joined rows, the table and the identities there as well")
    parser.add_argument("--cut", help="write --seconds of the trace there as JSON (a test's recorded trace)")
    parser.add_argument("--seconds", type=float, default=0.2)
    parser.add_argument("--skip", type=float, default=0.0)
    args = parser.parse_args(argv)
    path = args.path if args.path.endswith(CUT_SUFFIXES) else describe.newest_xplane(args.path)
    trace = load(path)
    if args.cut:
        with (gzip.open(args.cut, "wt") if args.cut.endswith(".gz") else open(args.cut, "w")) as f:
            json.dump(cut(trace, args.seconds, args.skip), f, separators=(",", ":"))
    if not carries_records(trace["host"]):
        print(f"{path}\nthe spans of this trace carry no tick record (a program from before it, or telemetry off)")
        return 1
    joined = join(trace, args.program)
    table, ids, idle = by_class(joined), identities(joined), joined["idle"]
    print(f"{path}\n{joined['programs']} executions of {args.program}: {joined['joined']} joined to one tick record each, "
          f"{joined['unjoined_programs']} claimed by no tick, {joined['edge_ticks']} ticks cut by the trace's edges")
    clock = joined["clock_offset_ms"]
    print("the device's clock runs behind the host's by " + (
        f"{clock['low']:.3f} to {clock['high']:.3f} ms (the middle is taken; what crosses the two clocks is uncertain by "
        f"{0.5 * (clock['high'] - clock['low']):.3f} ms)" if clock else "an unknown offset (no runtime events): taken as 0"))
    print(f"{'class':12s}{'count':>6s}" + "".join(f"{c[:-3]:>14s}" for c in COLUMNS) + "   (medians, ms)")
    for cls, entry in table.items():
        print(f"{cls:12s}{entry['count']:6d}" + "".join(f"{_ms(entry['median'][c]):>14s}" for c in COLUMNS))
    for cls, entry in table.items():
        record = ", ".join(f"{f} {v:.2f}" for f, v in entry["mean_record"].items() if v)
        print(f"{cls}: a tick carries on average {record or 'nothing'}; device ms a tick by scope (means):")
        for name, ms in entry["mean_scope_ms"].items():
            print(f"    {ms:8.3f}  {name}")
    for name, value in metrics(joined).items():
        print(f"metric {name} = {'-' if value is None else round(value, 4)}")
    between, lane = ids["between_ticks"], ids["lane"]
    print("between two ticks (decode_only, medians): " + " + ".join(f"{k[:-3]} {_ms(v)}" for k, v in between["parts"].items())
          + f" against tick_loop.host_gap_ms.online {_ms(between['host_gap_ms'])}: unexplained {_ms(between['unexplained_ms'])} ms")
    print(f"a lane tick's extra (medians, lane - decode_only): device {_ms(lane['lane_extra_device_ms'])} + host "
          f"{_ms(lane['lane_extra_host_ms'])} against wall {_ms(lane['wall_extra_ms'])}: unexplained "
          f"{_ms(lane['unexplained_ms'])} ms (readback lag differs by {_ms(lane['readback_lag_extra_ms'])}, "
          f"the program's own idle time by {_ms(lane['program_idle_extra_ms'])})")
    print(f"idle account: traced {idle['span_s']:.4f} s, idle {idle['idle_s']:.4f} s = {idle['idle_pct']:.2f}% = "
          + " + ".join(f"{k} {v:.2f}" for k, v in idle["parts_pct"].items()))
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"file": path, **joined, "by_class": table, "identities": ids, "metrics": metrics(joined)}, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
