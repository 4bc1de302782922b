"""Open-loop serving cells: requests arrive on a schedule drawn from the seed, whether or
not earlier ones have finished, at the fixed rate the cell's settings give. Each request
is timed from when it was DUE. Arrivals start ``ramp_s`` before the window opens (set-up:
the slots are at steady occupancy when it does) and go on after it closes until every
request that was due inside it has finished (at most ``tail_s``)."""

from __future__ import annotations

import math
import time

from benchmark.harness import check, stats, traffic
from benchmark.harness.loops._serving import Bench
from benchmark.harness.result import note


def drive(bench: Bench, requests: list, ramp: float, seconds: float, tail: float,
          on_open=None, on_close=None) -> dict:
    """Offer ``requests`` (sorted by ``due``) on their schedule and tick the engine until
    every request due inside ``[ramp, ramp + seconds)`` has finished, or ``tail`` has
    passed after the window. Returns the raw books of the window."""
    n = len(requests)
    bench.live.clear()
    bench.done.clear()
    bench.start_clock()
    t_open, t_close, horizon = ramp, ramp + seconds, ramp + seconds + tail
    opened = closed = False
    nxt, late, in_flight_at_open, in_flight_at_close = 0, [], None, None
    while True:
        now = bench.now()
        if not opened and now >= t_open:
            opened, in_flight_at_open = True, len(bench.live)
            if on_open:
                on_open()
        if not closed and now >= t_close:
            closed, in_flight_at_close = True, len(bench.live)
            if on_close:
                on_close()
        while nxt < n and requests[nxt]["due"] <= now:
            due = requests[nxt]["due"]
            in_window = t_open <= due < t_close
            served = bench.submit(requests[nxt], due, in_window)
            if in_window:
                late.append(served.submitted - due)
            nxt += 1
        if bench.live:
            bench.step()
        elif nxt < n:
            time.sleep(min(max(requests[nxt]["due"] - bench.now(), 0.0), 0.002))
        if bench.tracer is not None and opened:
            bench.tracer.poll(bench.now() - t_open)
        now = bench.now()
        if closed:
            waiting = any(s.in_window for s in bench.live.values()) or (nxt < n and requests[nxt]["due"] < t_close)
            if not waiting or now >= horizon or (nxt >= n and not bench.live):
                break
    window = [s for s in bench.done if s.in_window]
    unfinished = [s for s in bench.live.values() if s.in_window]
    good = [s for s in window if s.ok]
    everyone = bench.done + list(bench.live.values())
    return {
        "attempted": len(window) + len(unfinished),
        "failed": len(unfinished) + sum(1 for s in window if not s.ok),
        "good": good, "late": late,
        "ttft": [s.times[0] - s.due for s in good],
        "gaps": stats.token_gaps([s.times for s in good]),
        "queue_wait": [s.handle.admitted_at - s.handle.enqueued_at for s in good if s.handle.admitted_at is not None],
        "tokens_per_s": stats.tokens_in_window([s.times for s in everyone], t_open, t_close) / seconds,
        "prompt_tokens_admitted": sum(len(s.request["prompt"]) for s in everyone if s.handle.admitted_at is not None),
        "in_flight_at_open": in_flight_at_open, "in_flight_at_close": in_flight_at_close,
        "longest_tick": {"ms": 1e3 * bench.longest_tick[0], "at_window_s": bench.longest_tick[1] - t_open},
    }


def summary(books: dict) -> dict:
    """The window's books as one line of numbers (medians, tails and sample counts)."""
    return {
        "attempted": books["attempted"], "failed": books["failed"],
        "ttft": stats.latency_summary(books["ttft"]) if books["ttft"] else None,
        "gap": stats.latency_summary(books["gaps"]) if books["gaps"] else None,
        "late": stats.latency_summary(books["late"]) if books["late"] else None,
        "tokens_per_s": books["tokens_per_s"],
        "in_flight_at_open": books["in_flight_at_open"], "in_flight_at_close": books["in_flight_at_close"],
        "longest_tick": books["longest_tick"],
    }


def run(cell: dict, env: dict) -> dict:
    bench = Bench(cell, env)
    mix, settings, seconds = bench.mix, bench.settings, env["seconds"]
    rate, ramp, tail = settings["rate_rps"], mix["ramp_s"], mix["tail_s"]
    n = math.ceil(rate * (ramp + seconds + tail))
    requests = traffic.make_requests(mix, bench.sizes, env["seed"], n, rate_rps=rate, page_size=bench.page)
    bench.warm_up()
    trace_from = 0.65 * seconds
    if env["trace"]:
        # stopping the profiler stalls the loop for seconds (it writes the trace out): the
        # trace is taken late in the window, and the host-clock per-layer metrics are taken
        # over the requests due before it
        bench.start_tracer(start_after=trace_from, seconds=min(3.0, 0.25 * seconds))
    stamps = {}

    def on_open():
        stamps["open"] = time.time()
        env["window_opened"](stamps["open"])

    def on_close():
        stamps["close"] = time.time()
        stamps["memory_peak"] = env["memory_peak_bytes"]()

    books = drive(bench, requests, ramp, seconds, tail, on_open, on_close)
    trace = bench.tracer.finish() if bench.tracer else None
    compiled_in_window = env["monitor"].between(stamps["open"], stamps["close"])
    note({"phase": "window", "rate_rps": rate, **summary(books), "compilations_in_window": compiled_in_window})
    if trace is not None:
        note({"phase": "traced", "span_on_load_clock_s": bench.trace_span(), "profiler_start_s": bench.tracer.start_s,
              "profiler_stop_s": bench.tracer.stop_s, "device_planes": {k: len(d["ops"]) for k, d in trace["devices"].items()}})
    hit_tokens = bench.prefix_hit_tokens
    trace_span = bench.trace_span()
    engine_books = bench.close()

    checks = check.Checks()
    bench.check_served(books["good"], checks)
    checks.at_most("failed_requests", books["failed"], 0)
    checks.at_most("compilations_in_window", compiled_in_window, 0)
    end_to_end = {}
    if books["ttft"] and books["gaps"]:
        end_to_end = {"serve_ttft_p95_ms": stats.percentile(books["ttft"], 95) * 1e3,
                      "serve_gap_p95_ms": stats.percentile(books["gaps"], 95) * 1e3,
                      "serve_tokens_per_s": books["tokens_per_s"]}
    return {
        "end_to_end": end_to_end, "attempted": books["attempted"], "failed": books["failed"], "checks": checks,
        "memory_peak_bytes": stamps["memory_peak"], "excluded_from_setup_s": 0.0,
        "context": {
            "kind": "open_loop", "trace": trace, "obs": engine_books["obs"], "snapshot": engine_books["snapshot"],
            "late_s": [s.submitted - s.due for s in books["good"] if s.due < ramp + 0.5 * seconds],
            "queue_wait_s": [s.handle.admitted_at - s.handle.enqueued_at for s in books["good"]
                             if s.due < ramp + 0.5 * seconds and s.handle.admitted_at is not None],
            "prefix_hit_tokens": hit_tokens,
            "prompt_tokens_admitted": books["prompt_tokens_admitted"], "ticks": bench.ticks, "slots": bench.slots,
            "sizes": bench.sizes, "program_name": bench.family.TICK_PROGRAM, "window_s": seconds, "chips": cell["chips"],
            "trace_span": trace_span,
        },
    }
