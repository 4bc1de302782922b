"""Reduction of a profiler trace to busy time, kernel time, gaps and their names.

Two stages, so that the arithmetic can be checked on a small recorded trace
(``tests/data/``) without the profiler:

* ``read_xplane(path)`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData`` into a
  plain dict: for every device plane its operations and its programs, and the host's
  annotated spans, each as ``[name, start_s, duration_s]`` on the trace's own clock;
* everything else works on that dict.

What the planes and lines of a TPU trace are called was read off a trace by hand
(PERF.md, Findings): device planes ``/device:TPU:<n>``, with the lines ``XLA Ops`` (one
event per executed HLO instruction, serial on the core) and ``XLA Modules`` (one event
per executed program); the host's spans are on ``/host:CPU``.
"""

from __future__ import annotations

import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
# instructions that only hold others (their bodies' instructions are events of their own)
CONTAINERS = ("conditional", "while", "call")


def short_name(name: str) -> str:
    """A device event is named by its whole HLO instruction, ``%fusion.7 = (f32[...]...)
    fusion(...)``; keep the instruction's own name."""
    return name.split(" = ", 1)[0].lstrip("%")


def read_xplane(path: str, host_prefix: str = "bench.") -> dict:
    """The trace as a plain dict. Host spans are kept where their name starts with
    ``host_prefix`` (the harness's own ``TraceAnnotation``s)."""
    import jax.profiler

    data = jax.profiler.ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        match = DEVICE_PLANE.match(plane.name)
        if match:
            entry = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    entry[key] = [[short_name(e.name), e.start_ns * 1e-9, e.duration_ns * 1e-9]
                                  for e in line.events]
            devices[match.group(1)] = entry
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend([e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9]
                            for e in line.events if e.name.startswith(host_prefix))
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1]),
            "planes": {plane.name: {line.name: len(list(line.events)) for line in plane.lines} for plane in data.planes}}


def describe_xplane(path: str, top: int = 12) -> dict:
    """Planes, lines and the commonest event names of a trace: for looking at one by hand."""
    import jax.profiler

    data = jax.profiler.ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            seconds, count = defaultdict(float), 0
            for e in line.events:
                seconds[short_name(e.name)] += e.duration_ns * 1e-9
                count += 1
            best = sorted(seconds.items(), key=lambda kv: -kv[1])[:top]
            lines[line.name] = {"events": count, "top": [[n, round(s, 6)] for n, s in best]}
        out[plane.name] = lines
    return out


# ------------------------------------------------------------------ intervals
def union(intervals) -> list:
    """Sorted, merged ``[start, end]`` intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def clip(events, t0: float, t1: float) -> list:
    """Events cut to the window ``[t0, t1]``; those outside are dropped."""
    out = []
    for name, start, dur in events:
        lo, hi = max(start, t0), min(start + dur, t1)
        if hi > lo:
            out.append([name, lo, hi - lo])
    return out


def busy_intervals(ops) -> list:
    return union([start, start + dur] for _, start, dur in ops)


def busy_seconds(ops) -> float:
    return sum(end - start for start, end in busy_intervals(ops))


def base_name(name: str) -> str:
    """An HLO instruction's name without its ``%`` and its numeric suffix."""
    return re.sub(r"(\.\d+)+$", "", name.lstrip("%"))


def seconds_by_name(ops) -> dict:
    out = defaultdict(float)
    for name, _, dur in ops:
        out[base_name(name)] += dur
    return dict(out)


def kernel_seconds(ops, prefix: str) -> float:
    """Summed duration of the events whose instruction name starts with ``prefix``."""
    return sum(dur for name, _, dur in ops if base_name(name).startswith(prefix))


def idle_gaps(ops, t0: float, t1: float) -> list:
    """``[start, end]`` of every stretch of ``[t0, t1]`` in which no operation ran."""
    gaps, cursor = [], t0
    for start, end in busy_intervals(clip(ops, t0, t1)):
        if start > cursor:
            gaps.append([cursor, start])
        cursor = max(cursor, end)
    if t1 > cursor:
        gaps.append([cursor, t1])
    return gaps


def name_gaps(gaps, host_spans, unnamed: str = "(no harness span)") -> dict:
    """Idle seconds by what the host was doing: each gap goes to the innermost (latest
    started) harness span that covers its middle."""
    out = defaultdict(float)
    for start, end in gaps:
        mid = 0.5 * (start + end)
        cover = [s for s in host_spans if s[1] <= mid <= s[1] + s[2]]
        out[max(cover, key=lambda s: s[1])[0] if cover else unnamed] += end - start
    return dict(out)


def program_events(modules, contains: str) -> list:
    """The executions of the program whose name contains ``contains``."""
    return [e for e in modules if contains in e[0]]


def median(values) -> float:
    xs = sorted(values)
    if not xs:
        raise ValueError("median of nothing")
    mid = len(xs) // 2
    return xs[mid] if len(xs) % 2 else 0.5 * (xs[mid - 1] + xs[mid])


def per_program_busy(ops, programs) -> list:
    """Device busy seconds inside each execution of a program (its operations' union)."""
    return [busy_seconds(clip(ops, start, start + dur)) for _, start, dur in programs]


def idle_pct(ctx):
    """Share of the traced span in which no operation ran on the device (mean over devices)."""
    summary = ctx.get("summary")
    if not summary or summary["span_s"] <= 0:
        return None
    return 100.0 * (1.0 - summary["busy_s"] / summary["span_s"])


# -------------------------------------------------------------------- summary
def summarize(trace: dict, top: int = 10) -> dict:
    """What every traced run reports: busy seconds averaged over the devices, the traced
    span, and the breakdown (operations that took most time; idle gaps by host span)."""
    devices = trace["devices"]
    if not devices:
        raise ValueError("the trace holds no device plane")
    spans = [(min(e[1] for e in d["ops"]), max(e[1] + e[2] for e in d["ops"]))
             for d in devices.values() if d["ops"]]
    if not spans:
        raise ValueError("no operation ran on the device in the traced window")
    t0, t1 = min(s[0] for s in spans), max(s[1] for s in spans)
    busy = [busy_seconds(d["ops"]) for d in devices.values()]
    by_name, gaps = defaultdict(float), defaultdict(float)
    for d in devices.values():
        for name, seconds in seconds_by_name(d["ops"]).items():
            if name not in CONTAINERS:
                by_name[name] += seconds / len(devices)
        for name, seconds in name_gaps(idle_gaps(d["ops"], t0, t1), trace["host"]).items():
            gaps[name] += seconds / len(devices)
    rank = lambda table: [[n, s] for n, s in sorted(table.items(), key=lambda kv: -kv[1])[:top]]
    return {"busy_s": sum(busy) / len(busy), "span_s": t1 - t0, "t0": t0, "t1": t1,
            "breakdown": {"device_ops": rank(by_name), "idle_gaps": rank(gaps)}}
