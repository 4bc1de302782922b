"""Chip smoke: the system's main path, once, on the accelerator.

    python chip_smoke.py [--seed N]      one chip: trainer, then server
    python chip_smoke.py --chips 4       four chips: the FSDP train step only

One process (a chip belongs to one process at a time). With no arguments it
drives, at the full width of the 455M flagship (``flagship_455m_config()``,
bf16, nothing cut, weights and data made from ``--seed``):

  1. **trainer** — ``run_fit`` -> ``Trainer`` with ``make_causal_lm_train_step``,
     batch 16 x 1024, a few steps on a synthetic Markov token stream. Checks:
     finite loss, lower at the last step than at the first; no compilation
     after step 1 (``obs/watchdog.py``); the compiled step holds the splash
     kernel (``tpu_custom_call``), not the XLA attention.
  2. **server** — ``ServingEngine`` over the same model (paged pool, chunked
     prefill, prefix cache, the fused ragged tick): sixteen greedy requests,
     prompts of 64..1024 tokens, 64 new tokens each, ``run_until_drained()``;
     on fp pages, then on an int8 pool of >= 2,048 pages. Checks: every request
     finishes with the asked number of tokens; the compiled tick holds the
     Pallas paged kernel; the kernel agrees with the attention module's own
     XLA gather-and-mask branch on the same pool (fp, int8, int4); for two
     prompts the engine's tokens equal ``generate()``'s up to the first
     position whose top-2 logit margin is inside the tolerance, and every
     engine token is within that tolerance of the best logit of ``generate()``'s
     own decode loop fed the engine's tokens.

``--chips 4`` runs only the same train step under ``mesh_axes={"fsdp": 4}``
and the one-chip step it is compared with, on the same seeded batches.

Every phase prints one JSON line; the LAST line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU (or on any failed check) the script prints ``"ok": false`` and
exits non-zero: there is no CPU fallback and no interpret mode. ``--rehearse``
runs the same code at a toy size on whatever backend there is, to find wrong
paths and arguments without the chip; it always ends ``"ok": false``.
Timings are smoke readings around a blocking host fetch, not a benchmark.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import re
import sys
import tempfile
import time
import traceback

# agreement bounds at bf16 (8 mantissa bits), eight units in the last place
# each: the kernel against the XLA branch relative to the reference's largest
# magnitude, and greedy choices in logits (2**-5 is one bf16 step at the
# flagship's top-logit magnitude of 4..8; two correct bf16 paths measured up
# to 0.16 apart on the chip, a wrong mask or page would be whole units off)
KERNEL_REL_TOL = 2.0 ** -5
LOGIT_TOL = 0.25
# dropping the trainer's state may leave this much behind before the server is built
RESIDENT_SLACK_BYTES = 2**30

_COLLECTIVES = ("all-gather", "reduce-scatter", "all-reduce", "all-to-all", "collective-permute")


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


class Monitor:
    """Counts backend compilations and persistent-cache hits as JAX reports them."""

    def __init__(self):
        import jax.monitoring

        self.compile_s: list = []
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name: str, duration: float, **_) -> None:
        if name.endswith("backend_compile_duration"):
            self.compile_s.append(duration)

    def _on_event(self, name: str, **_) -> None:
        if name.endswith("/cache_hits"):
            self.cache_hits += 1
        elif name.endswith("/cache_misses"):
            self.cache_misses += 1

    def mark(self) -> tuple:
        return len(self.compile_s), self.cache_hits, self.cache_misses

    def since(self, mark: tuple) -> dict:
        n, hits, misses = mark
        return {
            "compilations": len(self.compile_s) - n,
            "compile_s": round(sum(self.compile_s[n:]), 2),
            "longest_compile_s": round(max(self.compile_s[n:], default=0.0), 2),
            "cache_hits": self.cache_hits - hits,
            "cache_misses": self.cache_misses - misses,
        }


def memory(device) -> dict:
    stats = device.memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use")}


def check(cond: bool, message: str) -> None:
    if not cond:
        raise AssertionError(message)


def program_facts(compiled) -> dict:
    """Which Pallas kernels (``tpu_custom_call``s, by the name of the kernel's
    instruction) and which collectives a compiled program holds."""
    text = compiled.as_text()
    kernels: dict = {}
    for name in re.findall(r'%([A-Za-z_]\w*?)[.\d]* = [^\n]*custom_call_target="tpu_custom_call"', text):
        kernels[name] = kernels.get(name, 0) + 1
    collectives = {name: len(re.findall(rf"\s{name}(?:-start)?\(", text)) for name in _COLLECTIVES}
    return {"kernels": kernels, "collectives": {k: v for k, v in collectives.items() if v}}


def has_kernel(facts: dict, prefix: str) -> bool:
    return any(name.startswith(prefix) for name in facts["kernels"])


# --------------------------------------------------------------------- sizes
def sizes(rehearse: bool) -> dict:
    import jax.numpy as jnp

    from perceiver_io_tpu.models.core.config import CausalSequenceModelConfig, flagship_455m_config

    if not rehearse:
        return dict(
            name="flagship_455m", config=flagship_455m_config(), dtype=jnp.bfloat16,
            batch=16, steps=8, fsdp_steps=3,
            slots=8, page=64, chunk=256, requests=16, new_tokens=64, min_prompt=64,
            int8_pages=2048, kernel_rows=16,
        )
    config = CausalSequenceModelConfig(
        vocab_size=512, max_seq_len=256, max_latents=64, num_channels=64, num_heads=2,
        num_self_attention_layers=2, cross_attention_dropout=0.0, abs_pos_emb=False,
        output_norm=True, output_bias=False,
    )
    return dict(
        name="rehearsal-toy", config=config, dtype=jnp.float32, batch=4, steps=4, fsdp_steps=2,
        slots=2, page=32, chunk=32, requests=6, new_tokens=6, min_prompt=8,
        int8_pages=40, kernel_rows=3,
    )


# ------------------------------------------------------------------- trainer
def train_setup(sz: dict, seed: int, steps: int):
    import jax
    import jax.numpy as jnp

    from perceiver_io_tpu.data.text.synthetic import SyntheticTextDataModule
    from perceiver_io_tpu.models.core.perceiver_ar import CausalSequenceModel
    from perceiver_io_tpu.training.trainer import TrainState, build_optimizer, make_causal_lm_train_step

    config, batch = sz["config"], sz["batch"]
    seq = config.max_seq_len
    model = CausalSequenceModel(config=config, deterministic=False, dtype=sz["dtype"])
    tx = build_optimizer(1e-3, max_grad_norm=1.0)
    rng = jax.random.PRNGKey(seed)
    sample = jnp.zeros((batch, seq), jnp.int32)

    def make_state():
        params = model.init({"params": rng, "dropout": rng}, sample, prefix_len=seq - config.max_latents)
        return TrainState.create(params, tx, rng=rng)

    # a low-entropy stream over 64 of the model's token ids: a few steps are
    # enough for the loss to fall well below log(vocab)
    data = SyntheticTextDataModule(
        source="markov", seq_len=seq, batch_size=batch, n_train_tokens=steps * batch * seq,
        n_val_tokens=seq, vocab_size=64, seed=seed,
    )
    data.setup()
    train_step = make_causal_lm_train_step(model, tx, max_latents=config.max_latents)
    return make_state, train_step, data


def fit(sz: dict, seed: int, steps: int, mesh_axes, on_tpu: bool):
    """``steps`` trainer steps through run_fit on a fresh seeded state; returns
    (state, train_step, data module, train_log lines, recorder)."""
    import math

    from perceiver_io_tpu.obs.core import TelemetryRecorder
    from perceiver_io_tpu.scripts.common import run_fit
    from perceiver_io_tpu.training.fit import TrainerConfig
    from perceiver_io_tpu.training.flops import PerceiverARFlops, detect_peak_flops
    from perceiver_io_tpu.training.metrics import load_metrics_jsonl

    config, batch = sz["config"], sz["batch"]
    make_state, train_step, data = train_setup(sz, seed, steps)
    flops = PerceiverARFlops(config, config.max_seq_len, config.cross_attention_dropout)
    recorder = TelemetryRecorder()
    with tempfile.TemporaryDirectory() as tmp:
        jsonl = os.path.join(tmp, "train.jsonl")
        trainer_cfg = TrainerConfig(
            max_steps=steps, log_every=1, eval_every=10**9, mesh_axes=mesh_axes,
            tokens_per_batch=flops.tokens_per_step(batch),
            flops_per_step=flops.train_flops_per_step(batch),
            peak_flops=detect_peak_flops() * math.prod((mesh_axes or {}).values()) if on_tpu else None,
            handle_preemption=False, telemetry=recorder, metrics_jsonl=jsonl,
        )
        state = run_fit(trainer_cfg, make_state, train_step, data)
        logs = load_metrics_jsonl(jsonl)["by_kind"]["train_log"]
    return state, train_step, data, logs, recorder


def check_training(logs: list, recorder, steps: int) -> dict:
    import math

    losses = [line["loss"] for line in logs]
    check(len(losses) == steps, f"trainer logged {len(losses)} steps, asked {steps}")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    unexpected = recorder.counters.get("compile.unexpected", 0)
    check(unexpected == 0, f"{unexpected} compilation(s) after step 1")
    return {"losses": losses, "compilations_after_step_1": int(unexpected)}


def phase_train(sz: dict, seed: int, monitor: Monitor, device, on_tpu: bool) -> None:
    import jax

    steps = sz["steps"]
    mark = monitor.mark()
    t0 = time.perf_counter()
    state, train_step, data, logs, recorder = fit(sz, seed, steps, None, on_tpu)
    wall = time.perf_counter() - t0
    record = {"phase": "train", "model": sz["name"],
              "params": sum(int(p.size) for p in jax.tree.leaves(state.params)),
              "batch": sz["batch"], "seq_len": sz["config"].max_seq_len, "steps": steps}
    record.update(check_training(logs, recorder, steps))
    # steps 2.. are steady; step 1 holds the compile
    record["step_ms_smoke_reading"] = [
        round(1e3 * sz["batch"] * sz["config"].max_latents / line["tokens_per_sec"], 1)
        for line in logs[1:]
    ]
    if on_tpu:
        record["mfu_smoke_reading"] = [line["mfu"] for line in logs[1:]]
    record["fit_wall_s"] = round(wall, 1)
    record.update(monitor.since(mark))
    record["memory"] = memory(device)

    # the program Trainer.fit compiled (same function, shapes and donation):
    # with the persistent cache on this is a cache read, not a second compile
    mark = monitor.mark()
    batch = next(iter(data.train_dataloader()))
    compiled = jax.jit(train_step, donate_argnums=(0,)).lower(state, batch).compile()
    record["program"] = {**program_facts(compiled), **monitor.since(mark)}
    record["attention_path"] = "splash (Pallas)" if has_kernel(record["program"], "splash") else "xla"
    if on_tpu:
        check(has_kernel(record["program"], "splash"),
              "the compiled train step holds no splash kernel: attention fell back to XLA")
    emit(record)


# -------------------------------------------------------------------- server
def make_requests(sz: dict, seed: int):
    """Prompt lengths drawn from the seed in [min_prompt, window]. Requests 0/1
    and the last two share a page-aligned preamble long enough to be cacheable
    (prefix-cache traffic across the two admission waves); request 2 is short
    (classic prefill + install) and request 3 long (chunked through the tick):
    the two that are compared with generate()."""
    import numpy as np

    config = sz["config"]
    window, latents, n = config.max_seq_len, config.max_latents, sz["requests"]
    rng = np.random.default_rng(seed)
    lengths = rng.integers(sz["min_prompt"], window + 1, size=n)
    lengths[2] = rng.integers(sz["min_prompt"], latents)
    lengths[3] = rng.integers(latents + sz["page"], window - sz["new_tokens"])
    shared = [0, 1, n - 2, n - 1]
    preamble = rng.integers(0, config.vocab_size, size=2 * sz["page"])
    prompts = []
    for i, length in enumerate(lengths):
        if i in shared:
            # a prompt takes part in the prefix cache only while prompt plus
            # generation fit the window (its ring never wraps into shared pages)
            length = int(rng.integers(latents + len(preamble), window - sz["new_tokens"] + 1))
        ids = rng.integers(0, config.vocab_size, size=int(length))
        if i in shared:
            ids[: len(preamble)] = preamble
        prompts.append(ids.astype(np.int32))
    return prompts


def serve(model, params, prompts, sz: dict, recorder, **engine_kwargs) -> tuple:
    from perceiver_io_tpu.serving import ServingEngine

    engine = ServingEngine(
        model, params, num_slots=sz["slots"], kv_page_size=sz["page"],
        prefill_chunk_tokens=sz["chunk"], prefix_cache=True, telemetry=recorder,
        **engine_kwargs,
    )
    t0 = time.perf_counter()
    handles = [engine.submit(p, max_new_tokens=sz["new_tokens"]) for p in prompts]
    engine.run_until_drained()
    wall = time.perf_counter() - t0
    return engine, handles, wall


def check_served(engine, handles, sz: dict, on_tpu: bool, monitor: Monitor) -> dict:
    import jax

    for h in handles:
        check(h.ok and len(h.output_ids) == sz["new_tokens"],
              f"request {h.request_id}: status {h.status.value}/{h.finish_reason}, "
              f"{len(h.output_ids)} of {sz['new_tokens']} tokens")
    check(engine.ragged, "the engine did not take the fused ragged tick")
    compile_summary = engine.watchdog.summary()
    check(not compile_summary["unexpected"], f"unexpected compilations: {compile_summary['unexpected']}")
    mark = monitor.mark()
    program = {**program_facts(engine.lower_tick().compile()), **monitor.since(mark)}
    paged_kernel = has_kernel(program, "fused_paged_decode_attention")
    if on_tpu:
        check(paged_kernel, "the compiled tick holds no paged decode kernel: "
                            "paged attention fell back to XLA")
    snap = engine.metrics.snapshot()
    ca = engine._cache.ca
    pool_bytes = sum(int(x.nbytes) for x in jax.tree.leaves((ca.kp, ca.vp, ca.k_scale, ca.v_scale)))
    return {
        "requests": len(handles), "tokens_generated": sum(len(h.output_ids) for h in handles),
        "ticks": snap["ragged_tick"]["ticks"], "prefix_hits": snap["prefix_cache"]["hits"],
        "pool_pages": int(ca.num_pages), "page_size": int(ca.page_size), "pool_bytes": pool_bytes,
        "programs": {k: v["compilations"] for k, v in compile_summary["per_function"].items()},
        "tick_program": program,
        "attention_path": "paged decode kernel (Pallas)" if paged_kernel else "xla gather-and-mask",
    }


def kernel_vs_xla(sz: dict, seed: int, kv_quant) -> dict:
    """One decode-attention call of the model's cross-attention geometry on a
    seeded paged pool: the module's automatic path against its own XLA
    gather-and-mask branch (``use_flash=False``), same parameters, same pool."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perceiver_io_tpu.models.core.perceiver_ar import CausalSequenceModel
    from perceiver_io_tpu.ops.attention import MultiHeadAttention
    from perceiver_io_tpu.ops.position import frequency_position_encoding

    config, dtype = sz["config"], sz["dtype"]
    window, page, rows = config.max_seq_len, sz["page"], sz["kernel_rows"]
    channels, heads = config.num_channels, config.num_heads
    pages_per_slot = -(-window // page)
    num_pages = rows * pages_per_slot + 1
    host = np.random.default_rng(seed + 17)
    table = 1 + host.permutation(num_pages - 1).reshape(rows, pages_per_slot)
    start = host.integers(0, window, size=rows)
    live = host.integers(1, window + 1, size=rows)
    live[0], live[-1] = window, 1  # a full window and a single live token
    rot = channels // heads // 2  # rotary on the first half of each head
    keys = jax.random.split(jax.random.PRNGKey(seed + 17), 4)

    @jax.jit
    def build(table, start, live):
        ca = CausalSequenceModel(config=config, dtype=dtype).init_paged_cache(
            rows, num_pages, page, dtype=dtype, kv_quant=kv_quant).ca
        blocks = lambda k: jax.random.normal(k, (num_pages, page, channels), jnp.float32).astype(dtype)
        ca = ca.write_pages(jnp.arange(num_pages), blocks(keys[0]), blocks(keys[1]))
        ca = ca.replace(page_table=table, start=start)
        ring = jnp.arange(pages_per_slot * page)[None, :]
        rope_k = frequency_position_encoding(jnp.mod(ring - start[:, None], window), rot)
        rope_q = frequency_position_encoding(jnp.full((rows, 1), window - 1), rot)
        x = jax.random.normal(keys[2], (rows, 1, channels), jnp.float32).astype(dtype)
        return x, dict(rope_q=rope_q, rope_k=rope_k, kv_cache=ca, kv_live=live)

    x, call = build(*(jnp.asarray(a, jnp.int32) for a in (table, start, live)))
    auto = MultiHeadAttention(num_heads=heads, num_q_input_channels=channels,
                              num_kv_input_channels=channels, causal_attention=True, dtype=dtype)
    xla = auto.clone(use_flash=False)
    params = jax.jit(lambda x, call: xla.init(keys[3], x, x, **call))(x, call)
    run = lambda module: jax.jit(lambda p, x, call: module.apply(p, x, x, **call)[0])
    run_auto = run(auto)
    lowered = run_auto.lower(params, x, call)
    got = run_auto(params, x, call).astype(jnp.float32)
    want = run(xla)(params, x, call).astype(jnp.float32)
    diff = float(jnp.max(jnp.abs(got - want)))
    scale = float(jnp.max(jnp.abs(want)))
    return {
        "pool": kv_quant or "fp", "rows": rows, "window": window, "page": page,
        "auto_path": "pallas" if "tpu_custom_call" in lowered.as_text() else "xla",
        "max_abs_diff": diff, "ref_max_abs": scale, "rel_diff": diff / scale,
        "finite": bool(jnp.all(jnp.isfinite(got))),
    }


def canonical(prompts, window: int):
    """generate()'s canonical form of a served prompt: left-padded to the window."""
    import jax.numpy as jnp
    import numpy as np

    ids = np.zeros((len(prompts), window), np.int32)
    pad = np.ones((len(prompts), window), bool)
    for i, p in enumerate(prompts):
        ids[i, window - len(p):] = p
        pad[i, window - len(p):] = False
    return jnp.asarray(ids), jnp.asarray(pad)


class GenerateReference:
    """``generate()`` on the probe prompts, and its own loop — prefill, then
    ``decode_step`` over the dense cache — as a scorer of token streams: per
    step, the margin between the two best logits and how far the fed token's
    logit lies below the best. Independent of the paged engine path."""

    PROBES = (2, 3)  # a short prompt (classic prefill + install) and a long one (chunked)

    def __init__(self, model, params, prompts, new_tokens: int):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from perceiver_io_tpu.generation.generate import GenerationConfig, _cache_dtype, generate

        window, latents = model.max_seq_len, model.max_latents
        self.params, self.new_tokens = params, new_tokens
        self.prompts = [prompts[i] for i in self.PROBES]
        ids, pad = canonical(self.prompts, window)
        out = generate(model, params, ids, num_latents=latents, pad_mask=pad,
                       config=GenerationConfig(max_new_tokens=new_tokens))
        self.tokens = np.asarray(out)[:, window:]
        # both probes twice: generate()'s own stream beside the stream under test
        self.inputs = canonical(self.prompts * 2, window)

        @jax.jit
        def score(params, ids, pad, forced):
            cache = model.init_cache(batch_size=ids.shape[0], dtype=_cache_dtype(model))
            logits, cache = model.apply(params, ids, window - latents, cache, pad_mask=pad,
                                        method=type(model).prefill)

            def body(carry, tok):
                cache, next_logits = carry
                next_logits = next_logits.astype(jnp.float32)
                top2 = jax.lax.top_k(next_logits, 2)[0]
                chosen = jnp.take_along_axis(next_logits, tok[:, None], axis=1)[:, 0]
                logits_t, cache = model.apply(params, tok[:, None], cache, method=type(model).decode_step)
                return (cache, logits_t[:, -1]), (top2[:, 0] - top2[:, 1], top2[:, 0] - chosen)

            _, (margin, deficit) = jax.lax.scan(body, (cache, logits[:, -1]), forced.T)
            return margin.T, deficit.T

        self._score = score

    def compare(self, handles) -> tuple:
        """(record, failed checks) for the engine's tokens on the probe requests."""
        import jax.numpy as jnp
        import numpy as np

        n, new = len(self.PROBES), self.new_tokens
        engine = np.stack([handles[i].result() for i in self.PROBES])
        forced = jnp.asarray(np.concatenate([self.tokens, engine]), jnp.int32)
        margin, deficit = (np.asarray(x) for x in self._score(self.params, *self.inputs, forced))
        record, failures = [], []
        for row, i in enumerate(self.PROBES):
            differ = np.flatnonzero(self.tokens[row] != engine[row])
            first = int(differ[0]) if differ.size else None
            off = deficit[n + row]
            record.append({
                "request": i, "prompt_len": int(len(self.prompts[row])),
                "tokens_equal_before_first_difference": first if first is not None else new,
                "margin_at_first_difference": None if first is None else float(margin[row, first]),
                "engine_token_max_logit_deficit": float(off.max()),
                "engine_tokens_off_the_reference_argmax": int((off > 0).sum()),
            })
            if first is not None and margin[row, first] > LOGIT_TOL:
                failures.append(f"request {i}: engine and generate() part at token {first} where "
                                f"the top-2 margin is {margin[row, first]:.4f} > {LOGIT_TOL}")
            if off.max() > LOGIT_TOL:
                failures.append(f"request {i}: an engine token lies {off.max():.4f} below the "
                                f"reference's best logit (> {LOGIT_TOL})")
            if (off > 0).sum() > new // 4:
                failures.append(f"request {i}: {(off > 0).sum()} of {new} engine tokens are not "
                                "the reference's greedy choice")
            if deficit[row].max() != 0.0:
                failures.append(f"request {i}: the scoring loop does not reproduce generate()")
        return record, failures


def phase_serve(sz: dict, seed: int, monitor: Monitor, device, on_tpu: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perceiver_io_tpu.models.core.perceiver_ar import CausalSequenceModel
    from perceiver_io_tpu.obs.core import TelemetryRecorder

    config = sz["config"]
    model = CausalSequenceModel(config=config, dtype=sz["dtype"])
    sample = jnp.zeros((1, config.max_seq_len), jnp.int32)
    params = jax.jit(model.init, static_argnames="prefix_len")(
        jax.random.PRNGKey(seed), sample, prefix_len=config.max_seq_len - config.max_latents)
    prompts = make_requests(sz, seed)

    # the kernels alone, against the module's XLA branch on one seeded pool
    # (int4 has no engine run here: it is checked at the kernel)
    mark = monitor.mark()
    kernel = {"phase": "kernel_vs_xla", "tolerance_rel": KERNEL_REL_TOL, "pools": []}
    for kv_quant in (None, "int8", "int4"):
        entry = kernel_vs_xla(sz, seed, kv_quant)
        kernel["pools"].append(entry)
        check(entry["finite"] and entry["rel_diff"] <= KERNEL_REL_TOL,
              f"paged attention on a {entry['pool']} pool is off its XLA branch by "
              f"{entry['rel_diff']:.4f} of the reference scale (> {KERNEL_REL_TOL})")
        if on_tpu:
            check(entry["auto_path"] == "pallas",
                  f"{entry['pool']} pool: the module's automatic path is not the Pallas kernel")
    kernel.update(monitor.since(mark))
    emit(kernel)

    mark = monitor.mark()
    engine, handles, wall = serve(model, params, prompts, sz, TelemetryRecorder())
    record = {"phase": "serve", "pool": "fp", "slots": sz["slots"],
              "prompt_lens": [int(len(p)) for p in prompts], "new_tokens": sz["new_tokens"],
              "drain_wall_s_smoke_reading": round(wall, 2)}
    record.update(check_served(engine, handles, sz, on_tpu, monitor))
    fp_tokens = np.stack([h.result() for h in handles])
    engine.close()
    del engine
    reference = GenerateReference(model, params, prompts, sz["new_tokens"])
    agreement, failures = reference.compare(handles)
    record["generate_agreement"] = {"logit_tolerance": LOGIT_TOL, "probes": agreement}
    record.update(monitor.since(mark))
    record["memory"] = memory(device)
    emit(record)
    check(not failures, "; ".join(failures))

    mark = monitor.mark()
    engine, handles, wall = serve(model, params, prompts, sz, TelemetryRecorder(),
                                  kv_quant="int8", num_kv_pages=sz["int8_pages"] + 1)
    record = {"phase": "serve", "pool": "int8", "slots": sz["slots"],
              "drain_wall_s_smoke_reading": round(wall, 2)}
    record.update(check_served(engine, handles, sz, on_tpu, monitor))
    check(record["pool_pages"] > sz["int8_pages"], "the int8 pool is smaller than asked")
    int8_tokens = np.stack([h.result() for h in handles])
    # int8 pages are lossy by design, and on random weights near-ties abound:
    # reported beside the pool size, bounded only by the run finishing
    record["greedy_token_agreement_with_fp"] = round(float((int8_tokens == fp_tokens).mean()), 4)
    record["generate_agreement"] = {"probes": reference.compare(handles)[0]}
    engine.close()
    del engine
    record.update(monitor.since(mark))
    record["memory"] = memory(device)
    emit(record)


# ---------------------------------------------------------------- four chips
def phase_fsdp(sz: dict, seed: int, monitor: Monitor, on_tpu: bool) -> None:
    import jax

    from perceiver_io_tpu.parallel.api import make_sharded_train_step
    from perceiver_io_tpu.parallel.mesh import make_mesh

    steps, axes = sz["fsdp_steps"], {"fsdp": 4}
    devices = jax.devices()

    mark = monitor.mark()
    state, _, _, logs, recorder = fit(sz, seed, steps, None, on_tpu)
    one = {"phase": "train_one_chip", "steps": steps, **check_training(logs, recorder, steps)}
    one["per_device_bytes_in_use"] = [memory(d)["bytes_in_use"] for d in devices]
    one.update(monitor.since(mark))
    emit(one)
    del state
    jax.clear_caches()
    gc.collect()

    mark = monitor.mark()
    state, train_step, data, logs, recorder = fit(sz, seed, steps, axes, on_tpu)
    record = {"phase": "train_fsdp", "mesh_axes": axes, "steps": steps,
              **check_training(logs, recorder, steps)}
    per_device = [memory(d)["bytes_in_use"] for d in devices]
    record["per_device_bytes_in_use"] = per_device
    record.update(monitor.since(mark))
    state_bytes = sum(int(x.nbytes) for x in jax.tree.leaves(state))
    record["state_bytes"] = state_bytes
    if per_device[0] is not None:
        check(max(per_device) < state_bytes / 2,
              f"the state is not sharded: {per_device} bytes in use against {state_bytes} of state")
        check(min(per_device) > state_bytes / 8, f"a device holds no share of the state: {per_device}")
    tol = 2e-2 if on_tpu else 1e-4
    first = abs(one["losses"][0] - record["losses"][0])
    record["first_step_loss_difference"] = first
    check(first <= tol * abs(one["losses"][0]),
          f"first-step loss differs: one chip {one['losses'][0]} vs fsdp {record['losses'][0]}")

    mark = monitor.mark()
    mesh = make_mesh(axes)
    state_sh = jax.tree.map(lambda x: x.sharding, state)
    step = make_sharded_train_step(train_step, mesh, state_sh)
    batch = next(iter(data.train_dataloader()))
    record["program"] = {**program_facts(step.lower(state, batch).compile()), **monitor.since(mark)}
    if on_tpu:
        check(has_kernel(record["program"], "splash"),
              "the sharded step holds no splash kernel: attention fell back to XLA")
    check(record["program"]["collectives"], "the sharded step holds no collective")
    emit(record)


# ---------------------------------------------------------------------- main
def fail(error: str, device=None) -> int:
    emit({"ok": False, "error": error, **({"device": device} if device else {})})
    return 1


def run(args) -> int:
    import jax

    from perceiver_io_tpu.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not args.rehearse:
        return fail(f"no TPU: JAX found {device['count']} {device['platform']} device(s); "
                    "chip_smoke.py has no CPU fallback", device)
    if len(devices) < args.chips:
        return fail(f"--chips {args.chips} needs {args.chips} devices, JAX found {len(devices)}", device)
    emit({"phase": "start", "device": device, "seed": args.seed, "chips": args.chips,
          "rehearsal": args.rehearse, "compile_cache_dir": cache_dir, "jax": jax.__version__})
    monitor = Monitor()
    sz = sizes(args.rehearse)
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_fsdp(sz, args.seed, monitor, on_tpu)
    else:
        phase_train(sz, args.seed, monitor, devices[0], on_tpu)
        # the 5.4 GB train state and the engine's pools do not share the chip
        jax.clear_caches()
        gc.collect()
        resident = memory(devices[0])["bytes_in_use"]
        check(resident is None or resident < RESIDENT_SLACK_BYTES,
              f"{resident} bytes still on the device after the trainer's state was dropped")
        phase_serve(sz, args.seed, monitor, devices[0], on_tpu)
    check("perceiver_io_tpu.native" not in sys.modules, "the smoke loaded the native library")
    emit({"phase": "done", "wall_s": round(time.perf_counter() - t0, 1), **monitor.since((0, 0, 0)),
          "memory": memory(devices[0])})
    if args.rehearse:
        return fail("rehearsal: every phase ran, at a toy size; not a chip result", device)
    emit({"ok": True, "device": device})
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0, help="weights, data and prompts are made from it")
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: only the FSDP train step and its one-chip comparison")
    parser.add_argument("--rehearse", action="store_true",
                        help="toy size on any backend; never a result (always ends ok: false)")
    args = parser.parse_args(argv)
    try:
        return run(args)
    except Exception:  # any failed phase fails the smoke, with its trace on stderr
        traceback.print_exc()
        return fail(traceback.format_exc(limit=1).strip().splitlines()[-1])


if __name__ == "__main__":
    sys.exit(main())
