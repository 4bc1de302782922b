"""Find an open-loop cell's knee, once, on the chip: one process, one set-up, a ladder of
offered rates of ``--seconds`` each.

    python benchmark/sweep.py --workload serve-455m-online --rates 3,4,5,6,7,8 [--slots 128,64]

Two numbers come out (``judge``). ``capacity_rps`` is what the engine completes when
saturated: the most tokens per second any rate of the ladder completed, over the mix's
mean answer length. ``knee_rps`` is the highest rate at which the tail a user feels is
still the unloaded one: every rate up to it has no failure and a 95th-percentile time to
first token within ``TTFT_RISE`` of the ladder's lowest rate (so the ladder starts well
below). Between the two the engine still completes what is offered, but requests queue
for a slot in bursts and the tail swings from window to window: a cell at four fifths
of the capacity read a TTFT p95 of 400 or of 780 ms by the seed (PERF.md, PR 23). The
ladder goes on past the knee until the backlog beyond the slots grows by a second's
arrivals or more (``sustained`` false), which gives the capacity. With ``--slots`` it
first takes the largest slot count whose engine builds, warms up and drains here. The
result goes to standard output; the builder copies it into the cell's settings file with
``rate_rps`` = rate_share x knee (``tests/test_sweep.py`` holds the two to each other).
Not part of a benchmark run."""

from __future__ import annotations

import argparse
import copy
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


TTFT_RISE = 1.25  # a tail a quarter above the unloaded one is queueing, not noise (bounds are <= 10%)


def judge(table: list, slots: int, mean_answer_tokens: float) -> dict:
    """The knee and the capacity of a swept ladder (rows as ``open_loop.summary`` gives
    them plus ``rate_rps``, ascending), and each row's ``steady`` and ``sustained``."""
    floor = table[0]["ttft"]["p95_ms"]
    rows, knee, below = [], None, True
    for row in table:
        waiting = [max(row[k] - slots, 0) for k in ("in_flight_at_open", "in_flight_at_close")]
        steady = row["failed"] == 0 and row["ttft"]["p95_ms"] <= TTFT_RISE * floor
        sustained = row["failed"] == 0 and waiting[1] - waiting[0] < row["rate_rps"] * 1.0
        below = below and steady
        if below:
            knee = row["rate_rps"]
        rows.append({**row, "waiting_at_open": waiting[0], "waiting_at_close": waiting[1],
                     "steady": steady, "sustained": sustained})
    return {"knee_rps": knee, "capacity_rps": max(r["tokens_per_s"] for r in table) / mean_answer_tokens,
            "ttft_p95_floor_ms": floor, "table": rows}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True, help="offered rates, requests/s, comma-separated, ascending")
    parser.add_argument("--slots", default=None, help="slot counts to try, largest first")
    parser.add_argument("--seconds", type=float, default=15.0)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)

    from benchmark.harness import device, manifest, traffic
    from benchmark.harness.loops import open_loop
    from benchmark.harness.loops._serving import Bench
    from benchmark.harness.result import note

    cell = manifest.resolve_cell(args.workload)
    device.enable_caches()
    info = device.describe_devices(cell["chips"], args.rehearse)
    if args.rehearse:
        cell = manifest.rehearsal_cell(cell)
    scratch = os.path.join(ROOT, "benchmark_out")
    os.makedirs(scratch, exist_ok=True)
    env = {"seed": args.seed, "seconds": args.seconds, "trace": False, "rehearse": args.rehearse,
           "monitor": device.CompileMonitor(), "scratch": scratch, "device": info}
    bench = None
    ladder = [int(s) for s in args.slots.split(",")] if args.slots else [cell["settings"]["engine"]["num_slots"]]
    for slots in ladder:
        trial = copy.deepcopy(cell)
        trial["settings"]["engine"]["num_slots"] = slots
        try:
            bench = Bench(trial, env)
            bench.warm_up()
        except Exception as e:  # a slot count the chip refuses: say why, try the next
            note({"slots": slots, "refused": f"{type(e).__name__}: {str(e)[:400]}"})
            bench = None
            continue
        note({"slots": slots, "built": True, "memory_peak_bytes": device.memory_peak_bytes()})
        break
    if bench is None:
        return 1
    mix = bench.mix
    mean_answer = float(traffic.length_set(mix["new_tokens"], 1000).mean())
    table = []
    for rate in [float(r) for r in args.rates.split(",")]:
        n = math.ceil(rate * (mix["ramp_s"] + args.seconds + mix["tail_s"]))
        requests = traffic.make_requests(mix, bench.sizes, args.seed, n, rate_rps=rate, page_size=bench.page)
        books = open_loop.drive(bench, requests, mix["ramp_s"], args.seconds, mix["tail_s"])
        table.append({"rate_rps": rate, **open_loop.summary(books)})
        judged = judge(table, bench.slots, mean_answer)["table"][-1]
        note(judged)
        if not judged["sustained"]:
            break  # higher rates will not be either, and an overload takes long to drain
        bench.engine.run_until_drained()  # whatever the tail left behind
        bench.engine.finished.clear()
    print(json.dumps({"workload": args.workload, "slots": bench.slots, "device": info,
                      "memory_peak_bytes": device.memory_peak_bytes(), "mean_answer_tokens": mean_answer,
                      **judge(table, bench.slots, mean_answer)}))
    bench.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
