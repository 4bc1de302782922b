"""What every serving loop driver shares (today the open loop): the engine under test, its
warm-up, the per-tick bookkeeping, and the sample of served requests that the family's
check compares with its reference. What depends on the model (its builder, its weights,
which prompts warm up every program, how many cache entries a request holds) comes from
the configuration's family (``benchmark/families/<family>/``).

The harness drives the engine from one thread: it calls ``submit`` for whatever is due
and then ``step()``, which ends in the tick's one device sync. The engine reports no time
of a token, so the harness stamps each token after the ``step()`` that produced it."""

from __future__ import annotations

import gc
import os
import time

import numpy as np

from benchmark.harness import check, manifest, traffic
from benchmark.harness.tracing import WindowTrace


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
        import jax.numpy as jnp

        from perceiver_io_tpu.serving import ServingEngine

        self.cell, self.env = cell, env
        self.config, self.mix, self.settings = cell["config"], cell["traffic"], cell["settings"]
        self.family = family = manifest.load_family(self.config["family"])
        self.sizes = self.config["sizes"]
        engine_cfg = self.settings["engine"]
        self.slots, self.page = engine_cfg["num_slots"], engine_cfg["kv_page_size"]
        self.model = family.build_model(self.config, deterministic=True)
        # weights in the type they are served in, made on the device in one call
        self.weights = family.make_weights(self.sizes, env["seed"], jnp.dtype(self.config["compute_dtype"]))
        params = family.to_program_params(self.weights)
        family.check_param_tree(self.model, params)
        self.recorder = None
        if env["trace"]:
            from perceiver_io_tpu.obs.core import TelemetryRecorder

            self.recorder = TelemetryRecorder()
        # the settings file's keys are the engine's argument names: an option is data
        self.engine = ServingEngine(self.model, params, **engine_cfg, telemetry=self.recorder or False)
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
        """Compile every program the mix's traffic uses, and nothing else: requests of
        the prompt lengths the family names for the mix's shortest and longest prompt,
        run to their end."""
        lo, hi = traffic.length_bounds(self.mix["prompt_tokens"])
        if self.mix.get("shared_prefix"):
            hi = max(hi, self.mix["shared_prefix"]["preamble_tokens"] + lo)
        probe = self.family.warm_up_prompt_lengths(self.sizes, lo, hi)
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
        sizes, live_entries = self.sizes, self.family.live_cache_entries
        for served in self.live.values():
            n = len(served.handle.output_ids)
            if n > len(served.times):
                served.times.extend([t] * (n - len(served.times)))
            if not served.handle.done and served.handle.admitted_at is not None:
                occupied += 1
                entries += live_entries(sizes, len(served.request["prompt"]), n)
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

    def check_served(self, candidates: list, checks: check.Checks) -> dict:
        """Hand a seeded sample of the finished requests, the longest in it, to the
        family's comparison with its reference, under the cell's limits."""
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
        served = [(s.request["prompt"], np.asarray(s.handle.output_ids, np.int32)) for s in picks]
        return self.family.check_served(self.weights, self.sizes, served, limits, checks, controls)
