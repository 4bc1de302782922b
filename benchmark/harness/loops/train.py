"""Training cells: ``run_fit`` -> ``Trainer`` on seeded rows, the prefetcher running.

Set-up builds ONE train state and drives it through its first steps by the program's own
call (``run_fit``) and feed (the program's ``DataLoader`` + ``DevicePrefetcher``), reads
off what the reference is compared with, calibrates the step time, and hands the same
state to the window. The window is one more fit, whose step count is worked out from the warm-up's
step time (``TrainerConfig`` has no time limit): it opens at that fit's first log boundary
and closes at its last, each of which ends in a device sync, and tokens/s is all the
steps between the two over all the time between. A traced run then profiles a few
seconds' steps of a further fit of the same state. The model, its step, its weights and
the reference's step come from the configuration's family (``benchmark/families/<family>/``).
"""

from __future__ import annotations

import gc
import math
import os
import shutil
import tempfile
import time

import numpy as np

from benchmark.harness import check, manifest, traffic
from benchmark.harness.result import note
from benchmark.harness.tracing import WindowTrace


class Rows:
    """The seeded rows as the program's loader wants them: a sequence of examples."""

    def __init__(self, rows: np.ndarray):
        self.rows = rows

    def __len__(self) -> int:
        return len(self.rows)

    def __getitem__(self, i: int) -> dict:
        row = self.rows[i]
        return {"input_ids": row[:-1], "labels": row[1:]}


class RowsModule:
    """What ``run_fit`` asks of a data module. Each fit continues where the last ended:
    ``advance`` moves the offset by the steps that fit trained on."""

    def __init__(self, rows: np.ndarray, rows_per_step: int):
        self.rows, self.rows_per_step, self.offset = rows, rows_per_step, 0

    @staticmethod
    def _collate(examples):
        return {k: np.stack([e[k] for e in examples]) for k in ("input_ids", "labels")}

    def train_dataloader(self):
        from perceiver_io_tpu.data.loader import DataLoader

        return DataLoader(Rows(self.rows[self.offset:]), self.rows_per_step,
                          collate_fn=self._collate, shuffle=False)

    def val_dataloader(self):
        raise NotImplementedError("the benchmark evaluates nothing")

    def advance(self, steps: int) -> None:
        self.offset += steps * self.rows_per_step

    def batches(self, first_step: int, steps: int) -> list:
        """The batches of steps ``first_step..first_step+steps`` (0-based), as fed."""
        r = self.rows_per_step
        return [self._collate([Rows(self.rows)[i] for i in range((first_step + s) * r, (first_step + s + 1) * r)])
                for s in range(steps)]


def _adam_mu(opt_state):
    """The first moment of the optimizer's one Adam state."""
    import jax

    has_mu = lambda x: hasattr(x, "mu") and hasattr(x, "nu")
    found = [s for s in jax.tree.leaves(opt_state, is_leaf=has_mu) if has_mu(s)]
    if len(found) != 1:
        raise ValueError(f"expected one Adam state in the optimizer state, found {len(found)}")
    return found[0].mu


def run(cell: dict, env: dict) -> dict:
    import jax
    import jax.numpy as jnp

    from perceiver_io_tpu.obs.core import TelemetryRecorder
    from perceiver_io_tpu.scripts.common import run_fit
    from perceiver_io_tpu.training.fit import TrainerConfig
    from perceiver_io_tpu.training.metrics import load_metrics_jsonl
    from perceiver_io_tpu.training.trainer import TrainState, build_optimizer

    config, mix, settings = cell["config"], cell["traffic"], cell["settings"]
    family = manifest.load_family(config["family"])
    sizes, seed, seconds = config["sizes"], env["seed"], env["seconds"]
    opt, limits = settings["optimizer"], settings["limits"]
    rows_per_step, log_every = mix["rows_per_step"], mix["log_every"]
    seq_len, trained_tokens = family.row_tokens(sizes)
    ref_steps = mix["reference_steps"]
    mesh_axes = settings.get("mesh_axes")
    monitor = env["monitor"]

    # ------------------------------------------------------------ set-up
    warm_to = log_every * math.ceil((ref_steps + 2 * log_every) / log_every)
    max_window_steps = log_every * (1 + math.ceil(seconds / settings["step_s_floor"] / log_every))
    traced_s = min(3.0, 0.25 * seconds) if env["trace"] else 0.0
    max_traced_steps = log_every * math.ceil(traced_s / settings["step_s_floor"] / log_every)
    data = RowsModule(
        traffic.markov_rows(mix["stream"], sizes, seed,
                            (warm_to + log_every + max_window_steps + max_traced_steps) * rows_per_step, seq_len),
        rows_per_step)
    model = family.build_model(config, deterministic=False)
    tx = build_optimizer(opt["learning_rate"], weight_decay=opt["weight_decay"],
                         max_grad_norm=opt["max_grad_norm"], b1=opt["b1"], b2=opt["b2"])
    train_step = family.make_program_train_step(model, tx, sizes)
    key = family.seed_key(seed)

    # the seed's key is an ARGUMENT of every program that uses it: closed over, it would
    # be a constant of the program, and every new seed would miss the compile cache
    def make_state(key):
        params = family.to_program_params(family.build_weights(sizes, key, jnp.float32))
        return TrainState.create(params, tx, rng=key)

    family.check_param_tree(model, jax.eval_shape(make_state, key).params)
    tokens_per_step = rows_per_step * trained_tokens
    recorder = TelemetryRecorder() if env["trace"] else False
    tmp = tempfile.mkdtemp(prefix="bench-train-", dir=env["scratch"])
    jsonl = os.path.join(tmp, "train.jsonl")

    def fit(state, max_steps: int, every: int):
        cfg = TrainerConfig(
            max_steps=max_steps, log_every=every, eval_every=10**9, mesh_axes=mesh_axes,
            tokens_per_batch=tokens_per_step, prefetch_depth=mix["prefetch_depth"],
            handle_preemption=False, telemetry=recorder, metrics_jsonl=jsonl)
        before = int(state.step)
        state = run_fit(cfg, state, train_step, data)
        data.advance(max_steps - before)
        return state

    def logs() -> list:
        return load_metrics_jsonl(jsonl)["by_kind"]["train_log"]

    leaf_norms = jax.jit(lambda tree: family.leaf_norms(family.from_program_params(tree)))
    update_norms = jax.jit(lambda weights, key: family.leaf_norms(jax.tree.map(
        lambda a, b: a - b, weights, family.build_weights(sizes, key, jnp.float32))))

    state = fit(jax.jit(make_state)(key), 1, 1)
    got_grad = jax.device_get(leaf_norms(jax.tree.map(lambda m: m / (1 - opt["b1"]), _adam_mu(state.opt_state))))
    state = fit(state, ref_steps, 1)
    got_update = jax.device_get(update_norms(family.from_program_params(state.params), key))
    got_losses = [line["loss"] for line in logs()][:ref_steps]
    # warm-up: a fit of its own up to the log boundary ``warm_to``; the shortest of its
    # whole log intervals gives the step time that sizes the window's fit (the shortest:
    # an interval in which the host stalled would make the window as much too short)
    n_before = len(logs())
    state = fit(state, warm_to, log_every)
    lines = logs()
    step_s = min(b["ts"] - a["ts"] for a, b in zip(lines[n_before:], lines[n_before + 1:])
                 if b["step"] - a["step"] == log_every) / log_every
    n_window = min(log_every * max(2, round(seconds / step_s / log_every)), max_window_steps)
    note({"phase": "set-up", "warm_step_s": step_s, "window_steps": n_window,
          "first_losses": got_losses, **monitor.since()})

    # ------------------------------------------------------------ window
    # one more fit: ``log_every`` lead-in steps (its loop starts and the metric fold is
    # found in the cache there), then the window, from that log boundary to the last
    lead = log_every
    n_before = len(lines)
    state = fit(state, warm_to + lead + n_window, log_every)
    jax.block_until_ready(state.params)
    window_lines = [line for line in logs()[n_before:] if line["step"] >= warm_to + lead]
    memory_peak = env["memory_peak_bytes"]()
    first, last = window_lines[0], window_lines[-1]
    env["window_opened"](first["ts"])
    counted_steps = last["step"] - first["step"]
    counted_s = last["ts"] - first["ts"]
    tokens_per_s = counted_steps * tokens_per_step / counted_s
    losses = [line["loss"] for line in window_lines[1:]]
    compiled_in_window = monitor.between(first["ts"], last["ts"])
    intervals = [b["ts"] - a["ts"] for a, b in zip(window_lines, window_lines[1:])]
    note({"phase": "window", "steps": counted_steps, "seconds": counted_s,
          "log_interval_s": {"median": sorted(intervals)[len(intervals) // 2], "longest": max(intervals),
                             "longest_at_window_s": window_lines[intervals.index(max(intervals))]["ts"] - first["ts"]},
          "loss_first_last": [losses[0], losses[-1]], "compilations_in_window": compiled_in_window})
    obs_summary = recorder.summary() if recorder else None
    trace = None
    if env["trace"]:
        # the traced steps are a fit of their own after the window, by the same call on the
        # same state and compiled step: ``run_fit`` gives no hook inside a fit, and a
        # profiler started by a timer beside the window's fit can miss it (a trace in which
        # nothing ran has no device plane at all). Here the steps begin once it is up.
        n_traced = min(log_every * max(1, round(traced_s / step_s / log_every)), max_traced_steps)
        tracer = WindowTrace(os.path.join(tmp, "trace"))
        tracer.start()
        state = fit(state, warm_to + lead + n_window + n_traced, log_every)
        jax.block_until_ready(state.params)
        trace = tracer.finish()
        note({"phase": "traced", "steps": n_traced, "profiler_start_s": tracer.start_s, "profiler_stop_s": tracer.stop_s,
              "device_planes": {k: len(d["ops"]) for k, d in trace["devices"].items()}})
    shutil.rmtree(tmp, ignore_errors=True)  # the log and the trace have been read

    # --------------------------------------------------------- reference
    # after the window, once the program's state is freed: the float32 pass needs the room
    t_ref = time.perf_counter()
    del state
    jax.clear_caches()
    gc.collect()
    batches = [jax.tree.map(jnp.asarray, b) for b in data.batches(0, ref_steps)]

    def reference(precision: str):
        """(losses, first clipped gradient's leaf norms, update's leaf norms) of the
        reference's first steps from the seed's weights."""
        weights = family.make_weights(sizes, seed, jnp.float32)
        mu = jax.tree.map(jnp.zeros_like, weights)
        nu = jax.tree.map(jnp.zeros_like, weights)
        step = family.make_train_step(sizes, opt, mix["reference_rows_per_block"], precision)
        losses, first_grad = [], None
        for t, batch in enumerate(batches, start=1):
            weights, mu, nu, loss, grad_norms = step(weights, mu, nu, batch, t)
            losses.append(float(loss))
            if first_grad is None:
                first_grad = jax.device_get(grad_norms)
        del mu, nu
        return losses, first_grad, jax.device_get(update_norms(weights, key))

    want_losses, want_grad, want_update = reference("float32")
    reference_s = time.perf_counter() - t_ref
    program_readings = {
        "loss_gap_first_steps": max(abs(g - w) for g, w in zip(got_losses, want_losses)),
        "first_gradient_norm_gap_worst_leaf": check.worst_leaf_gap(got_grad, want_grad),
        "update_norm_gap_worst_leaf": check.worst_leaf_gap(got_update, want_update),
    }
    controls = [p for p in env.get("reference_precision", "float32").split(",") if p != "float32"]
    if controls:
        note({"phase": "program", "readings": {k: v if isinstance(v, float) else v[0] for k, v in program_readings.items()}})
    for precision in reversed(controls):
        # a control: the reference in a lower precision, in the program's place; the
        # checks below are made on the first one named
        got_losses, got_grad, got_update = reference(precision)
        note({"phase": "control", "precision": precision, "readings": {
            "loss_gap_first_steps": max(abs(g - w) for g, w in zip(got_losses, want_losses)),
            "first_gradient_norm_gap_worst_leaf": check.worst_leaf_gap(got_grad, want_grad)[0],
            "update_norm_gap_worst_leaf": check.worst_leaf_gap(got_update, want_update)[0]}})

    checks = check.Checks()
    first_loss = got_losses[0]
    loss_gap = max(abs(g - w) for g, w in zip(got_losses, want_losses))
    grad_gap, grad_leaf = check.worst_leaf_gap(got_grad, want_grad)
    update_gap, update_leaf = check.worst_leaf_gap(got_update, want_update)
    checks.at_most("loss_gap_first_steps", loss_gap, limits["loss_gap_first_steps"])
    checks.at_most("first_gradient_norm_gap_worst_leaf", grad_gap, limits["first_gradient_norm_gap_worst_leaf"])
    checks.at_most("update_norm_gap_worst_leaf", update_gap, limits["update_norm_gap_worst_leaf"])
    checks.at_most("loss_last_over_first", losses[-1] / first_loss if all(map(math.isfinite, losses)) else math.inf,
                   limits["loss_last_over_first"])
    checks.at_most("compilations_in_window", compiled_in_window, 0)
    note({"phase": "reference", "seconds": reference_s, "losses_program": got_losses, "losses_reference": want_losses,
          "worst_gradient_leaf": grad_leaf, "worst_update_leaf": update_leaf})

    return {
        "end_to_end": {"train_tokens_per_s": tokens_per_s},
        "attempted": counted_steps, "failed": 0 if all(map(math.isfinite, losses)) else 1,
        "checks": checks, "memory_peak_bytes": memory_peak, "excluded_from_setup_s": 0.0,
        "context": {
            "kind": "train", "trace": trace, "obs": obs_summary, "window_s": counted_s, "chips": cell["chips"], "rows_per_step": rows_per_step,
            "sizes": sizes, "program_name": "train_step",
        },
    }
