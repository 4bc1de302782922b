"""Read the numbers ``correct`` is decided by, for the program and for its control, over
several seeds in one process (one set-up of the compiled programs).

    python benchmark/control.py --workload <name> --seeds 11,12,13 [--precision int8] [--seconds 4]

With ``--precision float32`` (the default) each seed is a sound run: the program against
the reference. With a lower precision the CONTROL is read beside it: the reference
computed in that precision put in the program's place (training: its first steps against
the float32 reference; serving: at each position of the same prompts and served tokens,
the gap of the token the lower precision puts first). A control has to come out not
correct. Not part of a benchmark run; PERF.md records what it read and the limits set."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--precision", default="float32",
                        help="float32, bfloat16, int8 or float8; several, comma-separated, are read in turn")
    parser.add_argument("--seconds", type=float, default=4.0)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)

    from benchmark.harness import device, manifest
    from benchmark.harness.loops import driver_for

    cell = manifest.resolve_cell(args.workload)
    device.enable_caches()
    info = device.describe_devices(cell["chips"], args.rehearse)
    if args.rehearse:
        cell = manifest.rehearsal_cell(cell)
    scratch = os.path.join(ROOT, "benchmark_out")
    os.makedirs(scratch, exist_ok=True)
    monitor = device.CompileMonitor()
    rows = []
    precision = args.precision  # several, comma-separated, are read in one run; checks use the first
    for seed in [int(s) for s in args.seeds.split(",")]:
        env = {"seed": seed, "seconds": args.seconds, "trace": False, "rehearse": args.rehearse,
               "monitor": monitor, "scratch": scratch, "device": info,
               "memory_peak_bytes": device.memory_peak_bytes, "window_opened": lambda t: None,
               "reference_precision": precision}
        t0 = time.time()
        outcome = driver_for(cell["traffic"]["kind"]).run(cell, env)
        row = {"seed": seed, "precision": precision, "correct": outcome["checks"].ok,
               "checks": {r["check"]: r["value"] for r in outcome["checks"].rows}, "seconds": time.time() - t0}
        rows.append(row)
        print(json.dumps({"control_row": row}), flush=True)
    print(json.dumps({"workload": args.workload, "device": info, "precision": args.precision, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
