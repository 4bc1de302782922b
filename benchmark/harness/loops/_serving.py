"""What every serving loop driver shares (today the open loop): the engine under test, its
warm-up, the per-tick bookkeeping, and the check of served tokens against the reference.

The harness drives the engine from one thread: it calls ``submit`` for whatever is due
and then ``step()``, which ends in the tick's one device sync. The engine reports no time
of a token, so the harness stamps each token after the ``step()`` that produced it."""

from __future__ import annotations

import gc
import os
import time

import numpy as np

from benchmark.harness import check, program
from benchmark.harness.result import note
from benchmark.harness.tracing import WindowTrace
from benchmark.reference import perceiver_ar as ref
from benchmark.reference import weights as ref_weights


class Served:
    """One request under way: its handle, when it was due and submitted, and the time of
    each of its tokens (seconds since the load started)."""

    __slots__ = ("request", "handle", "due", "submitted", "times", "in_window")

    def __init__(self, request: dict, handle, due: float, submitted: float, in_window: bool):
        self.request, self.handle = request, handle
        self.due, self.submitted, self.in_window = due, submitted, in_window
        self.times: list = []

    @property
    def ok(self) -> bool:
        return self.handle.ok and len(self.handle.output_ids) == self.request["new_tokens"]


class Bench:
    """The engine, the clock and the books of one serving run."""

    def __init__(self, cell: dict, env: dict):
        import jax

        from perceiver_io_tpu.serving import ServingEngine

        self.cell, self.env = cell, env
        self.config, self.mix, self.settings = cell["config"], cell["traffic"], cell["settings"]
        self.sizes = self.config["sizes"]
        engine_cfg = self.settings["engine"]
        self.slots, self.page = engine_cfg["num_slots"], engine_cfg["kv_page_size"]
        self.dtype = program.DTYPES[self.config["compute_dtype"]]
        self.model = program.build_model(self.config, deterministic=True)
        # weights in the type they are served in, made on the device in one call
        self.weights = ref_weights.make_weights(self.sizes, env["seed"], self.dtype)
        params = program.to_program_params(self.weights)
        program.check_param_tree(self.model, params)
        self.recorder = None
        if env["trace"]:
            from perceiver_io_tpu.obs.core import TelemetryRecorder

            self.recorder = TelemetryRecorder()
        self.engine = ServingEngine(
            self.model, params, num_slots=self.slots, kv_page_size=self.page,
            prefill_chunk_tokens=engine_cfg["prefill_chunk_tokens"], prefix_cache=engine_cfg["prefix_cache"],
            num_kv_pages=engine_cfg.get("num_kv_pages"), kv_quant=engine_cfg.get("kv_quant"),
            weight_dtype=engine_cfg.get("weight_dtype"), telemetry=self.recorder or False)
        if not self.engine.ragged and not env["rehearse"]:
            raise RuntimeError("the engine did not take the fused ragged tick")
        # EngineMetrics keeps no count of the prompt tokens a prefix hit saved: count the
        # program's own calls of its recording hook (a work-around, PERF.md section 7)
        self.prefix_hit_tokens = 0
        record = self.engine.metrics.record_prefix_hit

        def counted(request_id, shared_pages, shared_tokens):
            self.prefix_hit_tokens += shared_tokens
            return record(request_id, shared_pages, shared_tokens)

        self.engine.metrics.record_prefix_hit = counted
        self.live: dict = {}  # the engine's request id -> Served
        self.done: list = []
        self.ticks: list = []  # per tick of a traced run: (time, occupied slots, live entries)
        self.t0 = None
        self.tracer = None
        self._annotate = jax.profiler.TraceAnnotation

    # ---------------------------------------------------------------- clock
    def start_clock(self) -> None:
        self.t0 = time.perf_counter()
        self.longest_tick = (0.0, 0.0)  # (seconds, when on the load's clock): where a stall shows

    def now(self) -> float:
        return time.perf_counter() - self.t0

    # --------------------------------------------------------------- warm-up
    def warm_up(self) -> None:
        """Compile every program the mix's traffic uses, and nothing else: one prompt
        under the latent count where the mix has such (prefill + install), one over it
        (chunks and finish inside the tick), both run to their end."""
        lengths = self.mix["prompt_tokens"]
        latents = self.sizes["max_latents"]
        lo = lengths.get("min", lengths.get("value"))
        hi = lengths.get("max", lengths.get("value"))
        if self.mix.get("shared_prefix"):
            hi = max(hi, self.mix["shared_prefix"]["preamble_tokens"] + lo)
        probe = [n for n in (min(lo, latents - 1) if lo < latents else None, max(hi, latents)) if n]
        rng = np.random.default_rng(0)
        handles = [self.engine.submit(rng.integers(1, self.sizes["vocab_size"], size=n).astype(np.int32),
                                      max_new_tokens=4) for n in probe]
        self.engine.run_until_drained()
        for h in handles:
            if not h.ok:
                raise RuntimeError(f"warm-up request failed: {h.status.value}/{h.finish_reason}")
        self.prefix_hit_tokens = 0

    # ------------------------------------------------------------- the loop
    def submit(self, request: dict, due: float, in_window: bool) -> "Served":
        with self._annotate("bench.submit"):
            handle = self.engine.submit(request["prompt"], max_new_tokens=request["new_tokens"])
        served = Served(request, handle, due, self.now(), in_window)
        self.live[handle.request_id] = served
        return served

    def step(self) -> int:
        """One tick and its bookkeeping; returns how many requests it finished. A traced
        run also counts occupied slots and live cache entries per tick."""
        before = self.now()
        with self._annotate("bench.step"):
            self.engine.step()
        t = self.now()
        if t - before > self.longest_tick[0]:
            self.longest_tick = (t - before, t)
        occupied = entries = 0
        window = self.sizes["max_seq_len"]
        for served in self.live.values():
            n = len(served.handle.output_ids)
            if n > len(served.times):
                served.times.extend([t] * (n - len(served.times)))
            if not served.handle.done and served.handle.admitted_at is not None:
                occupied += 1
                entries += min(len(served.request["prompt"]) + n, window)
        if self.tracer is not None:
            self.ticks.append((t, occupied, entries))
        finished = self.engine.finished
        for handle in finished:
            served = self.live.pop(handle.request_id, None)
            if served is not None:
                self.done.append(served)
        n_finished = len(finished)
        finished.clear()
        return n_finished

    def start_tracer(self, start_after: float, seconds: float) -> None:
        directory = os.path.join(self.env["scratch"], f"trace-{self.cell['name']}")
        self.tracer = WindowTrace(directory, start_after, seconds)

    def trace_span(self):
        """(start, stop) of the trace on the load's clock, or None."""
        if self.tracer is None or self.tracer.started_at is None or self.tracer.stopped_at is None:
            return None
        return (self.tracer.started_at - self.t0, self.tracer.stopped_at - self.t0)

    # --------------------------------------------------------------- ending
    def close(self) -> dict:
        """Stop the engine and free the device for the reference; returns what the
        program's own books say."""
        snapshot = self.engine.metrics.snapshot()
        obs = self.recorder.summary() if self.recorder else None
        compile_summary = self.engine.watchdog.summary() if self.engine.watchdog is not None else None
        self.engine.close()
        self.engine = None
        gc.collect()
        return {"snapshot": snapshot, "obs": obs, "watchdog": compile_summary}

    def check_tokens(self, candidates: list, checks: check.Checks) -> dict:
        """Score a seeded sample of the finished requests, the longest in it, with the
        reference: the widest and the mean gap by which a served token's logit lies
        below the reference's best."""
        limits, n = self.settings["limits"], self.mix["check_requests"]
        rng = np.random.default_rng([self.env["seed"], 3])
        pool = [s for s in candidates if s.ok]
        if not pool:
            checks.at_most("requests_to_score", 1, 0)
            return {}
        longest = max(pool, key=lambda s: len(s.request["prompt"]) + s.request["new_tokens"])
        rest = [s for s in pool if s is not longest]
        picks = [longest] + [rest[i] for i in rng.permutation(len(rest))[: n - 1]]
        controls = [p for p in self.env.get("reference_precision", "float32").split(",") if p != "float32"]
        deficits, control = [], {p: [] for p in controls}
        t0 = time.perf_counter()
        for served in picks:
            tokens = np.asarray(served.handle.output_ids, np.int32)
            logits = np.asarray(ref.score_served(self.weights, self.sizes, served.request["prompt"], tokens))
            deficits.append(check.token_deficits(logits, tokens))
            for precision in controls:
                # a control: at each position, the token the lower precision puts first
                low = np.asarray(ref.score_served(self.weights, self.sizes, served.request["prompt"], tokens, precision))
                control[precision].append(check.token_deficits(logits, low.argmax(axis=-1)))
        deficits = np.concatenate(deficits)
        control = {p: np.concatenate(d) for p, d in control.items()}
        scored = control[controls[0]] if controls else deficits
        checks.at_most("served_token_deficit_max", scored.max(), limits["served_token_deficit_max"])
        checks.at_most("served_token_deficit_mean", scored.mean(), limits["served_token_deficit_mean"])
        report = {"phase": "reference", "seconds": time.perf_counter() - t0, "requests": len(picks),
                  "tokens": int(len(deficits)), "scored": controls[0] if controls else "program",
                  "program_deficit_max": float(deficits.max()), "program_deficit_mean": float(deficits.mean()),
                  "tokens_off_reference_argmax": int((deficits > 0).sum()),
                  "controls": {p: {"deficit_max": float(d.max()), "deficit_mean": float(d.mean()),
                                   "tokens_moved": int((d > 0).sum())} for p, d in control.items()}}
        note(report)
        return report
