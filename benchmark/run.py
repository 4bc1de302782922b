"""Run one cell of the benchmark once.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: loads, warms up every shape the cell uses (set-up), measures for
``--seconds``, checks what the timed path produced against the plain reference, and
prints one JSON line last. Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result: there is no CPU fallback on the measurement path.
``--rehearse`` runs the same code at a toy size on whatever backend there is, to find
wrong paths and arguments without the chip; it never prints a result.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

T_PROCESS_START = time.time()  # the clock of the trainer's log lines and of every window

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _override(cell: dict, assignment: str) -> None:
    """``settings.engine.num_slots=16`` or ``traffic.tail_s=30``: set one value of the
    cell's settings or traffic mix for a trial run."""
    import json

    path, value = assignment.split("=", 1)
    *parents, leaf = path.split(".")
    node = cell
    for key in parents:
        node = node[key]
    node[leaf] = json.loads(value)


def run(args) -> int:
    from benchmark.harness import device, layers, manifest, stall
    from benchmark.harness.loops import driver_for
    from benchmark.harness.result import fail, note, result_line, say_compared

    try:
        cell = manifest.resolve_cell(args.workload)
    except (OSError, KeyError, ValueError) as e:
        return fail(str(e), 2)
    try:
        cache_dir = device.enable_caches()
    except ImportError as e:
        return fail(f"the system under test is not importable from {ROOT}: {e}", 2)
    try:
        info = device.describe_devices(cell["chips"], args.rehearse)
    except device.NoAccelerator as e:
        return fail(str(e), 3)
    if args.rehearse:
        cell = manifest.rehearsal_cell(cell)
    for assignment in args.set or []:
        _override(cell, assignment)
    scratch = os.path.join(ROOT, "benchmark_out")
    os.makedirs(scratch, exist_ok=True)
    monitor = device.CompileMonitor()
    opened = {}
    env = {
        "seed": args.seed, "seconds": args.seconds, "trace": bool(args.trace), "rehearse": args.rehearse,
        "monitor": monitor, "scratch": scratch, "device": info,
        "memory_peak_bytes": device.memory_peak_bytes,
        "window_opened": lambda t: opened.setdefault("t", t),
    }
    note({"phase": "start", "workload": cell["name"], "seed": args.seed, "seconds": args.seconds,
          "trace": args.trace, "device": info, "compile_cache_dir": cache_dir, "rehearsal": args.rehearse})
    heartbeat, pressure0 = stall.Heartbeat(), stall.pressure_us()
    heartbeat.start()
    try:
        outcome = driver_for(cell["traffic"]["kind"]).run(cell, env)
    finally:
        heartbeat.stop()
    setup_s = opened["t"] - T_PROCESS_START - outcome["excluded_from_setup_s"]
    for row in outcome["checks"].rows:
        note(row)
    note({"phase": "host", **heartbeat.report(opened["t"], args.seconds),
          "pressure_ms": {k: (v - pressure0[k]) / 1e3 for k, v in stall.pressure_us().items() if k in pressure0}})
    note({"phase": "done", "setup_s": setup_s, "wall_s": time.time() - T_PROCESS_START,
          **monitor.since(), "slowest_compilations": monitor.slowest()})

    units = {m["name"]: m["unit"] for m in cell["end_to_end"] + cell["per_layer"]}
    dev = {**info, "memory_peak_bytes": outcome["memory_peak_bytes"]}
    breakdown = None
    if args.trace:
        from benchmark.trace import reduce

        ctx = outcome["context"]
        ctx["peaks"] = None if args.rehearse else device.peaks_for(info["kind"])
        try:
            ctx["summary"] = summary = reduce.summarize(ctx["trace"])
        except ValueError as e:
            # a traced run has to say how busy the device was: without that, no result
            if not args.rehearse:
                return fail(f"{e}; the trace's planes and their lines: {ctx['trace'].get('planes')}", 5)
            ctx["summary"] = summary = None
        metrics = layers.read_all(cell["per_layer"], ctx)
        if summary is not None:
            dev.update(busy_s=summary["busy_s"], window_s=summary["span_s"])
            breakdown = summary["breakdown"]
    else:
        metrics = {**outcome["end_to_end"], "setup_s": setup_s}
        metrics = {m["name"]: metrics[m["name"]] for m in cell["end_to_end"]}
    rows = outcome["checks"].rows
    line = result_line(outcome["checks"].ok, outcome["attempted"], outcome["failed"], metrics, units, dev, breakdown, rows)
    say_compared(rows)
    if args.rehearse:
        note({"phase": "rehearsal-result", "would_print": line})
        return fail("rehearsal: every phase ran at a toy size; not a result", 4)
    print(line, flush=True)
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0, help="inputs and weights are made from it")
    parser.add_argument("--seconds", type=float, default=10.0, help="length of the measured window")
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0,
                        help="1: profile a few seconds of the window and report the per-layer metrics")
    parser.add_argument("--rehearse", action="store_true",
                        help="toy size on any backend; never prints a result")
    parser.add_argument("--set", action="append", metavar="PATH=JSON",
                        help="a trial run's override of one value of the cell's settings (never the driver's)")
    return run(parser.parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
