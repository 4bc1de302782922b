"""Serving-engine benchmark: continuous batching vs single-request generate().

Replays a synthetic mixed-length workload (random prompt lengths, a small set
of max_new_tokens values, staggered arrivals) through ``ServingEngine`` and
through the per-request ``generate()`` baseline, and emits one JSON artifact
with the engine's metrics snapshot (docs/serving.md schema) plus the
head-to-head throughput comparison.

``--profile`` additionally runs the bucketed-prefill A/B: short-prompt and
full-window workloads through a bucketed-ladder engine and through a
full-window-prefill baseline engine (``prefill_buckets=[window]``), reporting
ADMISSION (prefill) token throughput and DECODE token throughput separately,
and writes the machine-readable ``BENCH_serving.json`` tracked per PR. The
admission arms drain ``max_new_tokens=1`` workloads (wall time is
prefill-dominated); the decode arms drain long generations and report the
metrics snapshot's ``decode_tokens_per_s``.

``--page-size N`` runs the paged-KV capacity arm (ROADMAP item 1,
docs/serving.md "Paged KV cache"): concurrent sessions per fixed KV-token
budget and admission tokens/s, paged pool vs dense pool, interleaved
median-of-``--page-repeats``; the block is merged into the ``--profile-out``
artifact (BENCH_serving.json) with its run manifest.

``--kv-quant N`` runs the quantized-KV capacity arm (ROADMAP item 3,
docs/serving.md "Quantized KV pages & weight serving"): concurrent sessions
per fixed pool BYTE budget, int8 pages (+ per-page-per-head scale sidecars,
counted inside the budget) vs full-precision pages at page size N —
interleaved median-of-``--kv-quant-repeats`` — with greedy-token agreement
between the arms, a ``kv_quant=None`` pre-quant byte-identity pin, and
measured bf16/int8 weight-serving bytes + teacher-forced CE deltas; the
block is merged into the ``--profile-out`` artifact (BENCH_serving.json).

``--priority-arm`` runs the mixed-priority overload arm (docs/serving.md
"Priority classes & preemption"): a saturating low-priority background plus
high-priority foreground through a page-constrained engine, preemption ON vs
the ``PERCEIVER_IO_TPU_DISABLE_PREEMPTION`` kill-switch arm — high-priority
p95 time-to-first-token and deadline-miss rate at equal total throughput;
the block is merged into ``BENCH_serving.json``.

``--journal`` runs the write-ahead journal overhead arm (docs/serving.md
"Request journal"): the main staggered workload journal-on (accept-fsync
policy) vs journal-off, interleaved median-of-``--journal-repeats`` —
acceptance is admission tokens/s within 10% of journal-off and greedy
outputs byte-identical across arms; the block is merged into
``BENCH_serving.json``.

``--replicas N`` runs the replica-scaling arm (ROADMAP item 2): a burst
workload through a 1-replica and an N-replica ``ServingRouter`` (interleaved,
median-of-``--replica-repeats``), reporting aggregate admission tokens/s
(time until the burst's last admission — the capacity dimension replicas
add) and drain tokens/s, with the v4 shed/failover counters; the block is
merged into the ``--profile-out`` artifact (BENCH_serving.json) with its run
manifest. ``--proc`` runs the N-replica arm with OUT-OF-PROCESS workers
(``replica_mode="process"``, serving/transport.py) against the same
in-process 1-replica baseline — greedy tokens asserted identical across
arms, RPC p50/p95 reported next to the throughput — and lands under
``replica_scaling_proc``.

Runs anywhere: ``JAX_PLATFORMS=cpu python scripts/serve_bench.py --preset tiny``
finishes in under a minute and is what tests/test_serving.py smoke-drives.
The ``bench`` preset uses the shared 30M-class decode shape (bench.py's
``decode_bench_config``) for on-chip numbers.

Fairness notes baked into the harness:
  * both sides are timed AFTER a warmup pass so compile time is excluded from
    the throughput comparison (compile counts are reported separately);
  * the baseline serves requests back-to-back on the engine's canonical
    padded shape (one prefill compile, like the engine) — per-request scan
    programs still recompile per distinct max_new_tokens, which is itself
    part of the single-request story and is reported as
    ``baseline_compile_shapes``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Optional

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import jax
import jax.numpy as jnp
import numpy as np


def _median(xs):
    """Median as the middle element of the sorted sample — the one
    convention every interleaved-arm section of this bench ranks on (a
    per-arm drift in median/percentile handling would silently skew the
    acceptance ratios)."""
    xs = sorted(xs)
    return xs[len(xs) // 2]


def _pct(sorted_xs, q):
    """Index-based percentile over an already-sorted sample (same idiom as
    obs_report's lifetime stats)."""
    return sorted_xs[min(int(len(sorted_xs) * q), len(sorted_xs) - 1)]


def build_model(preset: str):
    from perceiver_io_tpu.models.core.config import CausalSequenceModelConfig
    from perceiver_io_tpu.models.core.perceiver_ar import CausalSequenceModel

    if preset == "tiny":
        config = CausalSequenceModelConfig(
            vocab_size=262, max_seq_len=64, max_latents=16, num_channels=32,
            num_heads=4, num_self_attention_layers=2, cross_attention_dropout=0.0,
        )
        return CausalSequenceModel(config=config), config
    if preset == "profile":
        # wide window, small latent count: the shape class where bucketed
        # prefill pays (prefill cost ~ O(bucket) k/v projections + embedding,
        # window >> latent-stack cost; full-window prefill ~51 ms vs ~4 ms at
        # bucket 256 on CPU). Kept CPU-runnable for the per-PR perf artifact.
        config = CausalSequenceModelConfig(
            vocab_size=262, max_seq_len=2048, max_latents=16, num_channels=256,
            num_heads=8, num_self_attention_layers=1, cross_attention_dropout=0.0,
        )
        return CausalSequenceModel(config=config), config
    if preset == "bench":
        from bench import decode_bench_config

        config = decode_bench_config()
        return CausalSequenceModel(config=config, dtype=jnp.bfloat16), config
    raise SystemExit(f"unknown preset {preset!r} (tiny | profile | bench)")


def synth_workload(config, num_requests: int, seed: int):
    """Mixed-length synthetic requests: prompt lengths across [4, window/2],
    max_new from a small fixed menu (so the baseline compiles O(3) scan
    programs, not O(n)), arrival staggered one submit per decode step."""
    rng = np.random.RandomState(seed)
    menu = (8, 16, 24)
    requests = []
    for i in range(num_requests):
        plen = int(rng.randint(4, max(config.max_seq_len // 2, 5)))
        requests.append({
            "prompt": rng.randint(1, config.vocab_size, size=plen).tolist(),
            "max_new_tokens": int(menu[i % len(menu)]),
        })
    return requests


def run_engine(model, params, requests, num_slots: int, jsonl_path, warmup: bool,
               trace_path=None):
    from perceiver_io_tpu.serving import ServingEngine

    # False (not None) when no --trace: the ambient env must not switch
    # recording on inside this TIMED flow (same discipline as the A/B arms)
    engine = ServingEngine(model, params, num_slots=num_slots, metrics_jsonl=jsonl_path,
                           telemetry=trace_path if trace_path else False)
    if warmup:
        # one admission + one decode step compiles all three programs
        h = engine.submit(requests[0]["prompt"], max_new_tokens=1)
        engine.run_until_drained()  # drains engine.finished for the timed window
        assert h.done
        # fresh metrics: the timed window must not include warmup events
        from perceiver_io_tpu.serving import EngineMetrics

        engine.metrics.close()
        engine.metrics = EngineMetrics(num_slots=num_slots, jsonl_path=jsonl_path)

    t0 = time.perf_counter()
    pending = list(requests)
    step = 0
    # staggered arrivals: one new request per tick until the backlog is in
    for i, r in enumerate(pending):
        engine.submit(r["prompt"], max_new_tokens=r["max_new_tokens"],
                      rng=jax.random.PRNGKey(i))
        engine.step()
        step += 1
    while engine.step():
        step += 1
    wall = time.perf_counter() - t0
    snap = engine.metrics.write_snapshot()
    new_tokens = sum(len(h.output_ids) for h in engine.finished)
    prompt_tokens = sum(len(r["prompt"]) for r in requests)
    result = {
        "wall_seconds": round(wall, 4),
        "new_tokens": new_tokens,
        "tokens_per_s": round(new_tokens / wall, 2) if wall > 0 else 0.0,
        # prefill vs decode split: decode rate from the compiled-step timer,
        # admission rate over the whole drain (prefill dispatch is
        # non-blocking, so its device cost lands inside decode-step syncs —
        # wall is the honest denominator for admission throughput)
        "decode_tokens_per_s": snap["decode_tokens_per_s"],
        "prompt_tokens": prompt_tokens,
        "admission_prompt_tokens_per_s": round(prompt_tokens / wall, 2) if wall > 0 else 0.0,
        "decode_compilations": engine.decode_compilations,
        "prefill_compilations": engine.prefill_compilations,
        "prefill_buckets": list(engine.prefill_buckets),
        # admission-control outcomes (serving-metrics/v3, docs/reliability.md):
        # all zero on this unbounded/undeadlined workload, reported so a
        # bounded/deadlined bench run surfaces drops next to its throughput
        "rejected": snap["rejected"],
        "timed_out": snap["timed_out"],
        "failed": snap["failed"],
        "queue_depth": snap["queue_depth"],
        "metrics": snap,
    }
    telemetry = engine.telemetry_summary()
    if telemetry is not None:
        # per-phase tick breakdown + compile counts (docs/observability.md);
        # close() writes the Chrome trace when --trace gave a path
        result["telemetry"] = telemetry
    engine.close()
    return result


def run_replica_scaling(model, params, requests, num_replicas: int,
                        num_slots: int, repeats: int = 3,
                        replica_mode: str = "inproc") -> dict:
    """ROADMAP item 2's bench target: aggregate ADMISSION tokens/s scaling
    with replica count. A burst of ``len(requests)`` requests (sized ~6x one
    replica's slots) hits a 1-replica router and an N-replica router
    (``num_slots`` each); the admission wall is the time until the LAST
    request reaches a slot. One replica admits ``num_slots`` immediately and
    the rest wait whole generation waves for slots to free; N replicas hold
    N x slots in flight, so the burst admits in a fraction of the waves —
    the capacity dimension replicas actually add. Honesty note: on one CPU
    the DRAIN tokens/s stays ~flat (XLA's intra-op pool already uses every
    core, so N in-process engines add no FLOPs — it is reported anyway,
    un-gamed); on real multi-chip serving each replica owns its own chip and
    both rates scale. Arms are INTERLEAVED A/B/A/B with the wall kept
    per arm as the MEDIAN of the interleaved passes (back-to-back arms pick
    up allocator warm-up drift; minima flip under shared-CPU noise). Admission-control counters ride along so a
    shedding/failing fleet can't pass as a fast one.

    ``replica_mode="process"`` runs the N-replica arm with OUT-OF-PROCESS
    workers (serving/transport.py) against the same in-process 1-replica
    baseline — each worker owns its own interpreter and XLA pool, so on a
    host with >= N free cores the drain rate measures the near-linear
    scaling process isolation unlocks (in-process replicas contend on one
    GIL + one XLA pool). The same honesty discipline as above applies in
    reverse on a SINGLE-core host: there the workers time-slice one core
    and every RPC costs two context switches, so the process arm reads
    SLOWER than in-process — the block records ``cores`` so the ratio is
    interpretable, and the number is reported un-gamed either way. Greedy
    tokens are asserted identical between the arms on every pass: the
    process boundary must be invisible to outputs."""
    from perceiver_io_tpu.serving import ServingRouter

    # telemetry=False: ambient PERCEIVER_IO_TPU_TELEMETRY must not switch
    # recording on inside a TIMED arm (same discipline as the profile arms)
    routers = {
        1: ServingRouter(model, params, num_replicas=1, num_slots=num_slots,
                         telemetry=False),
        num_replicas: ServingRouter(model, params, num_replicas=num_replicas,
                                    num_slots=num_slots, telemetry=False,
                                    replica_mode=replica_mode),
    }

    def one_pass(router):
        t0 = time.perf_counter()
        handles = [
            router.submit(r["prompt"], max_new_tokens=r["max_new_tokens"],
                          rng=jax.random.PRNGKey(i))
            for i, r in enumerate(requests)
        ]
        router.run_until_drained(max_steps=10_000)
        drain_wall = time.perf_counter() - t0
        assert all(h.ok for h in handles)  # a degraded pass must not be timed
        admit_wall = max(h.admitted_at for h in handles) - t0
        return admit_wall, drain_wall, [h.result().tolist() for h in handles]

    tokens_by_arm = {}
    for n, router in routers.items():  # warmup: compiles every covering bucket
        _, _, tokens_by_arm[n] = one_pass(router)
    # the cross-arm identity pin: replica count AND the process boundary are
    # invisible to greedy outputs (a diverging timed arm must not be scored)
    assert tokens_by_arm[num_replicas] == tokens_by_arm[1], \
        "replica arms diverged on greedy tokens"
    admit_walls = {n: [] for n in routers}
    drain_walls = {n: [] for n in routers}
    for _ in range(repeats):
        for n, router in routers.items():  # interleaved A/B
            a, d, toks = one_pass(router)
            assert toks == tokens_by_arm[1], "greedy tokens drifted across passes"
            admit_walls[n].append(a)
            drain_walls[n].append(d)

    new_tokens = sum(r["max_new_tokens"] for r in requests)
    prompt_tokens = sum(len(r["prompt"]) for r in requests)
    arms = {}
    for n, router in routers.items():
        # MEDIAN, not best-of: the arm ratio is the acceptance number, and on
        # a shared CPU the median of interleaved passes is far more stable
        # than the minimum (measured: best-of flips across runs, median
        # holds within a few percent)
        admit, drain = _median(admit_walls[n]), _median(drain_walls[n])
        snap = router.snapshot()
        arms[f"replicas_{n}"] = {
            "replicas": n,
            "replica_mode": "inproc" if n == 1 else replica_mode,
            "slots_per_replica": num_slots,
            "admission_wall_seconds": round(admit, 4),
            "admission_wall_all_repeats": [round(w, 4) for w in admit_walls[n]],
            "admission_prompt_tokens_per_s": round(prompt_tokens / admit, 2)
            if admit > 0 else 0.0,
            "drain_wall_seconds": round(drain, 4),
            "tokens_per_s": round(new_tokens / drain, 2) if drain > 0 else 0.0,
            # admission-control outcomes: all zero on this healthy workload,
            # reported so a degraded run surfaces next to its throughput
            "failovers": snap["failovers"],
            "shed_infeasible": snap["shed_infeasible"],
            "rejected": snap["rejected"],
            "timed_out": snap["timed_out"],
            "failed": snap["failed"],
            "breaker_transitions": snap["breaker_transitions"],
        }
        if snap.get("transport") is not None:
            # process-mode arm: the RPC tax rides next to the throughput it
            # bought (rpc p50/p95, retries, respawns — serving-metrics/v12)
            arms[f"replicas_{n}"]["transport"] = {
                k: snap["transport"][k]
                for k in ("rpcs", "rpc_p50_ms", "rpc_p95_ms", "retries",
                          "timeouts", "worker_respawns")
                if k in snap["transport"]
            }
        router.close()
    single = arms["replicas_1"]
    multi = arms[f"replicas_{num_replicas}"]
    return {
        "requests": len(requests),
        "new_tokens_per_pass": new_tokens,
        "prompt_tokens_per_pass": prompt_tokens,
        "replica_mode": replica_mode,
        "cores": os.cpu_count(),  # the scaling ceiling: N replicas need N cores
        "tokens_identical_across_arms": True,  # asserted on every pass above
        **arms,
        "throughput_speedup": round(multi["tokens_per_s"] / single["tokens_per_s"], 3)
        if single["tokens_per_s"] > 0 else 0.0,
        "admission_speedup": round(
            multi["admission_prompt_tokens_per_s"]
            / single["admission_prompt_tokens_per_s"], 3,
        ) if single["admission_prompt_tokens_per_s"] > 0 else 0.0,
    }


def run_rolling_restart(model, config, params, num_replicas: int,
                        num_slots: int, seed: int, max_new: int = 24,
                        rollout_fraction: float = 0.5) -> dict:
    """Fleet-operations arm (ROADMAP item 4 / docs/serving.md "Fleet
    operations"): the cost of a zero-downtime rolling restart, measured as
    the RUNNING sessions' inter-token latency blip. A sustained streamed
    workload runs twice through an ``num_replicas``-replica router —
    steady-state, then with a rolling restart triggered mid-stream — and
    each pass records every running session's tick-to-tick inter-token gaps,
    tagged by whether the restart was in progress. Acceptance: sessions
    lost = 0 in both passes (every submit FINISHED — a restart drops
    nothing), and the during-restart p95 inter-token gap is a bounded blip,
    reported as ``blip_p95_ratio`` against the steady-state p95. A third
    pass deploys a second param version at ``rollout_fraction`` mid-stream
    and reports the v10 per-version throughput table (the rollout arm)."""
    from perceiver_io_tpu.serving import ServingRouter

    requests = synth_workload(config, 6 * num_slots, seed)
    for r in requests:
        r["max_new_tokens"] = max_new  # uniform: gaps compare apples to apples

    def streamed_pass(router, restart_after: Optional[int] = None,
                      deploy_after: Optional[int] = None, deploy_params=None):
        """Submit one request per tick until the workload drains; returns
        (gaps_steady, gaps_during_restart, handles, steps)."""
        handles, last_len, last_t = [], {}, {}
        gaps_steady, gaps_restart = [], []
        i = step = 0
        more = True
        while more or i < len(requests):
            if i < len(requests):
                h = router.submit(requests[i]["prompt"],
                                  max_new_tokens=requests[i]["max_new_tokens"],
                                  rng=jax.random.PRNGKey(i))
                handles.append(h)
                i += 1
            if restart_after is not None and step == restart_after:
                assert router.begin_rolling_restart()
            if deploy_after is not None and step == deploy_after:
                router.deploy(deploy_params, fraction=rollout_fraction)
            more = router.step()
            now = time.perf_counter()
            in_restart = router.restart_in_progress
            for h in handles:
                n = len(h.output_ids)
                if n > last_len.get(h.request_id, 0):
                    prev = last_t.get(h.request_id)
                    if prev is not None:
                        (gaps_restart if in_restart else gaps_steady).append(
                            now - prev)
                    last_t[h.request_id] = now
                    last_len[h.request_id] = n
            step += 1
            if step > 20_000:
                raise RuntimeError("fleet-ops arm failed to drain")
        return gaps_steady, gaps_restart, handles, step

    def gap_stats(gaps):
        if not gaps:
            return {"n": 0, "p50_ms": None, "p95_ms": None}
        s = sorted(gaps)
        return {"n": len(s), "p50_ms": round(_pct(s, 0.50) * 1e3, 3),
                "p95_ms": round(_pct(s, 0.95) * 1e3, 3)}

    # warmup compiles every covering bucket on a throwaway fleet
    warm = ServingRouter(model, params, num_replicas=num_replicas,
                         num_slots=num_slots, telemetry=False)
    streamed_pass(warm)
    warm.close()

    # steady-state pass
    router = ServingRouter(model, params, num_replicas=num_replicas,
                           num_slots=num_slots, telemetry=False)
    steady, _, handles_a, steps_a = streamed_pass(router)
    snap_a = router.snapshot()
    router.close()
    # restart pass: the rolling restart begins once the fleet is saturated
    router = ServingRouter(model, params, num_replicas=num_replicas,
                           num_slots=num_slots, telemetry=False)
    base, during, handles_b, steps_b = streamed_pass(
        router, restart_after=2 * num_slots)
    snap_b = router.snapshot()
    recycles = snap_b["fleet_ops"]["recycles"]
    router.close()
    # rollout pass: deploy a second version mid-stream, report per-version
    # throughput (params_v2 = a fresh copy of the same tree — the arm
    # measures accounting and steady service, not model quality)
    params_v2 = jax.tree_util.tree_map(lambda x: x, params)
    router = ServingRouter(model, params, num_replicas=num_replicas,
                           num_slots=num_slots, telemetry=False)
    t0 = time.perf_counter()
    _, _, handles_c, _ = streamed_pass(router, deploy_after=2 * num_slots,
                                       deploy_params=params_v2)
    rollout_wall = time.perf_counter() - t0
    snap_c = router.snapshot()
    rollout = snap_c["fleet_ops"]["rollout"]
    router.close()

    steady_stats = gap_stats(steady)
    during_stats = gap_stats(during)
    lost = {
        "steady": sum(1 for h in handles_a if not h.ok),
        "restart": sum(1 for h in handles_b if not h.ok),
        "rollout": sum(1 for h in handles_c if not h.ok),
    }
    blip = (round(during_stats["p95_ms"] / steady_stats["p95_ms"], 3)
            if during_stats["p95_ms"] and steady_stats["p95_ms"] else None)
    per_version = {
        v: {**row, "tokens_per_s": round(row["tokens_generated"] / rollout_wall, 2)
            if rollout_wall > 0 else 0.0}
        for v, row in (rollout or {}).get("versions", {}).items()
    }
    return {
        "replicas": num_replicas,
        "slots_per_replica": num_slots,
        "requests": len(requests),
        "max_new_tokens": max_new,
        "steady_inter_token": steady_stats,
        "restart_baseline_inter_token": gap_stats(base),
        "during_restart_inter_token": during_stats,
        "blip_p95_ratio": blip,
        "recycles": recycles,
        "sessions_lost": lost,
        "sessions_lost_total": sum(lost.values()),
        "steady_steps": steps_a,
        "restart_steps": steps_b,
        "rollout": {
            "fraction": rollout_fraction,
            "per_version": per_version,
            "migrations": snap_c["fleet_ops"]["migrations"],
        },
        "breaker_transitions_during_restart": snap_b["breaker_transitions"],
    }


def run_paging_capacity(model, config, params, page_size: int, num_slots: int,
                        seed: int, repeats: int = 7, max_new: int = 8) -> dict:
    """Acceptance arm (ROADMAP item 1 / docs/serving.md "Paged KV cache"):
    CONCURRENT SESSIONS PER FIXED KV BUDGET, paged vs dense. The budget is
    the dense pool's cross-attention KV backing — ``num_slots`` full windows
    of tokens. The paged arm spends the exact same token budget on a page
    pool (reserved trash page included, honestly inside the budget) and
    raises its slot count to what the pool holds resident for this workload's
    worst-case reservation; the dense arm cannot go past ``num_slots`` without
    more HBM. Short-prompt workload (the ROADMAP's short-heavy traffic),
    uniform ``max_new`` so reservations are uniform and waves are crisp.

    Measured per arm, interleaved median-of-``repeats``: peak concurrent
    RUNNING sessions, admission prompt tokens/s (wall to the LAST admission —
    the burst-capacity dimension), and drain tokens/s. Fairness notes: the
    paged arm's extra slots do cost self-attention cache and slot state
    outside the CA-KV budget (max_latents rows per slot — reported, ~1/128th
    of a window at the profile shape); greedy token identity across the arms
    is pinned in float64 by tests/test_paging.py (this f32 bench records the
    observed identity informationally)."""
    from perceiver_io_tpu.serving import ServingEngine, pages_for_request
    from perceiver_io_tpu.serving.engine import default_prefill_buckets

    window = config.max_seq_len
    budget_tokens = num_slots * window
    num_pages = budget_tokens // page_size
    rng = np.random.RandomState(seed)
    short_hi = max(window // 8, 2)
    buckets = default_prefill_buckets(window, config.max_latents)
    covering = next(b for b in buckets if b >= short_hi)
    need = pages_for_request(covering, max_new, window, page_size)
    paged_slots = max((num_pages - 1) // need, 1)

    k = 2 * max(paged_slots, num_slots)
    prompts = [rng.randint(1, config.vocab_size, size=int(n)).tolist()
               for n in rng.randint(2, short_hi + 1, size=k)]

    # telemetry=False: ambient env must not record inside a TIMED arm
    engines = {
        "dense": ServingEngine(model, params, num_slots=num_slots, telemetry=False),
        "paged": ServingEngine(model, params, num_slots=paged_slots,
                               kv_page_size=page_size, num_kv_pages=num_pages,
                               telemetry=False),
    }

    def one_pass(engine):
        t0 = time.perf_counter()
        handles = [engine.submit(p, max_new_tokens=max_new, rng=jax.random.PRNGKey(i))
                   for i, p in enumerate(prompts)]
        peak = 0
        while engine.step():
            peak = max(peak, engine.scheduler.active_slots)
        drain_wall = time.perf_counter() - t0
        assert all(h.ok for h in handles)  # a degraded pass must not be timed
        admit_wall = max(h.admitted_at for h in handles) - t0
        engine.finished.clear()
        return peak, admit_wall, drain_wall, [h.result().tolist() for h in handles]

    for engine in engines.values():  # warmup compiles every covering bucket
        one_pass(engine)
    peaks = {n: [] for n in engines}
    admit_walls = {n: [] for n in engines}
    drain_walls = {n: [] for n in engines}
    tokens_by_arm = {}
    for _ in range(repeats):
        for name, engine in engines.items():  # interleaved A/B
            peak, admit, drain, toks = one_pass(engine)
            peaks[name].append(peak)
            admit_walls[name].append(admit)
            drain_walls[name].append(drain)
            tokens_by_arm[name] = toks

    prompt_tokens = sum(len(p) for p in prompts)
    new_tokens = max_new * len(prompts)
    arms = {}
    for name, engine in engines.items():
        admit, drain = _median(admit_walls[name]), _median(drain_walls[name])
        arms[name] = {
            "slots": engine.num_slots,
            "kv_budget_tokens": budget_tokens,
            "peak_concurrent_sessions": _median(peaks[name]),
            "admission_wall_seconds": round(admit, 4),
            "admission_prompt_tokens_per_s": round(prompt_tokens / admit, 2)
            if admit > 0 else 0.0,
            "drain_wall_seconds": round(drain, 4),
            "tokens_per_s": round(new_tokens / drain, 2) if drain > 0 else 0.0,
            "decode_compilations": engine.decode_compilations,
        }
        if name == "paged":  # the arm given a page size and a page budget
            snap = engine.metrics.snapshot()
            arms[name]["num_kv_pages"] = num_pages
            arms[name]["pages_per_request"] = snap["page_pool"]["pages_per_request"]
            arms[name]["alloc_failures"] = snap["page_pool"]["alloc_failures"]
        engine.close()
    dense, paged = arms["dense"], arms["paged"]
    return {
        "page_size": page_size,
        "window": window,
        "kv_budget_tokens": budget_tokens,
        "requests": len(prompts),
        "max_new_tokens": max_new,
        "prompt_tokens_per_pass": prompt_tokens,
        # self-attention state the paged arm's extra slots cost OUTSIDE the
        # CA-KV budget (honesty: the budget covers the dominant CA term only)
        "sa_rows_per_slot": config.max_latents,
        **{f"{n}_pool": a for n, a in arms.items()},
        "concurrent_sessions_ratio": round(
            paged["peak_concurrent_sessions"] / dense["peak_concurrent_sessions"], 3
        ) if dense["peak_concurrent_sessions"] else 0.0,
        "admission_speedup": round(
            paged["admission_prompt_tokens_per_s"] / dense["admission_prompt_tokens_per_s"], 3
        ) if dense["admission_prompt_tokens_per_s"] > 0 else 0.0,
        # f64 identity is the pinned contract (tests/test_paging.py); this is
        # the f32 observation on the LAST interleaved pass
        "greedy_tokens_identical_f32": tokens_by_arm["dense"] == tokens_by_arm["paged"],
    }


def run_kv_quant_capacity(model, config, params, page_size: int, num_slots: int,
                          seed: int, repeats: int = 7, max_new: int = 8) -> dict:
    """Acceptance arm (ROADMAP item 3 / docs/serving.md "Quantized KV pages
    & weight serving"): CONCURRENT SESSIONS PER FIXED POOL BYTE BUDGET,
    int8-quantized pages vs full-precision pages — both PAGED, so the ratio
    isolates what quantization alone buys on top of PR 8's paging win. The
    budget is the fp arm's pool bytes (``num_slots`` worth of default paged
    reservations, trash page included); the int8 arm spends the exact same
    bytes on int8 pages + their per-page-per-head f32 scale sidecars
    (honestly counted inside the budget) and raises its slot count to what
    the bigger pool holds resident for this workload's worst-case
    reservation.

    Measured per arm, interleaved median-of-``repeats``: peak concurrent
    RUNNING sessions, admission prompt tokens/s (wall to the LAST admission),
    TTFT p95, and drain tokens/s. Quality is NOT silently dropped: the block
    reports greedy token agreement between the arms (token-level rate, exact
    sequence match fraction, recorded into the quant engine's v9 snapshot
    via ``record_quant_agreement``) and a weight-serving section with
    measured param bytes + teacher-forced CE deltas for bf16/int8 weights vs
    fp32 on a synthetic batch (the cheap stand-in for the convergence/CE
    harness gate — methodology in docs/serving.md). A ``kv_quant=None``
    engine is additionally pinned byte-identical to one constructed with the
    pre-quantization signature."""
    from perceiver_io_tpu.serving import ServingEngine, pages_for_request
    from perceiver_io_tpu.serving.engine import default_prefill_buckets
    from perceiver_io_tpu.serving.quant import dequantize_params, serve_params

    window = config.max_seq_len
    pages_per_slot = -(-window // page_size)
    num_pages_fp = num_slots * pages_per_slot + 1
    fp_itemsize = 4  # the engines below run f32 pools (the serving default)
    page_bytes_fp = 2 * page_size * config.num_channels * fp_itemsize
    page_bytes_q = (2 * page_size * config.num_channels  # int8 KV bytes
                    + 2 * config.num_heads * 4)  # f32 scale sidecars
    budget_bytes = num_pages_fp * page_bytes_fp
    num_pages_q = budget_bytes // page_bytes_q

    rng = np.random.RandomState(seed)
    short_hi = max(window // 8, 2)
    buckets = default_prefill_buckets(window, config.max_latents)
    covering = next(b for b in buckets if b >= short_hi)
    need = pages_for_request(covering, max_new, window, page_size)
    # BOTH arms raise their slot count to what their own pool holds resident
    # for this workload's worst-case reservation — the ratio then isolates
    # what the BYTES buy, not slot-count generosity (each extra slot still
    # costs max_latents SA rows outside the pool budget, reported below —
    # the same honesty note as the paging arm)
    slots_fp = max((num_pages_fp - 1) // need, 1)
    slots_q = max((num_pages_q - 1) // need, 1)

    k = 2 * max(slots_q, slots_fp)
    prompts = [rng.randint(1, config.vocab_size, size=int(n)).tolist()
               for n in rng.randint(2, short_hi + 1, size=k)]

    # telemetry=False: ambient env must not record inside a TIMED arm
    engines = {
        "fp": ServingEngine(model, params, num_slots=slots_fp,
                            kv_page_size=page_size, num_kv_pages=num_pages_fp,
                            telemetry=False),
        "int8": ServingEngine(model, params, num_slots=slots_q,
                              kv_page_size=page_size, num_kv_pages=num_pages_q,
                              kv_quant="int8", telemetry=False),
    }

    def one_pass(engine):
        t0 = time.perf_counter()
        handles = [engine.submit(p, max_new_tokens=max_new, rng=jax.random.PRNGKey(i))
                   for i, p in enumerate(prompts)]
        peak = 0
        while engine.step():
            peak = max(peak, engine.scheduler.active_slots)
        drain_wall = time.perf_counter() - t0
        assert all(h.ok for h in handles)  # a degraded pass must not be timed
        admit_wall = max(h.admitted_at for h in handles) - t0
        ttft = sorted(h.admitted_at - h.submitted_at for h in handles)
        engine.finished.clear()
        return peak, admit_wall, drain_wall, ttft, [h.result().tolist() for h in handles]

    for engine in engines.values():  # warmup compiles every covering bucket
        one_pass(engine)
    peaks = {n: [] for n in engines}
    admit_walls = {n: [] for n in engines}
    drain_walls = {n: [] for n in engines}
    ttft_p95s = {n: [] for n in engines}
    tokens_by_arm = {}
    for _ in range(repeats):
        for name, engine in engines.items():  # interleaved A/B
            peak, admit, drain, ttft, toks = one_pass(engine)
            peaks[name].append(peak)
            admit_walls[name].append(admit)
            drain_walls[name].append(drain)
            ttft_p95s[name].append(_pct(ttft, 0.95))
            tokens_by_arm[name] = toks

    # greedy-token agreement, int8 arm vs fp arm (identical prompts/rngs):
    # the serving-relevant quality number — recorded into the quant engine's
    # v9 snapshot so the agreement rate rides serving-metrics, not only this
    # artifact
    total = matched = exact = diverge_steps = 0
    for a, b in zip(tokens_by_arm["fp"], tokens_by_arm["int8"]):
        total += max(len(a), len(b))
        matched += sum(1 for x, y in zip(a, b) if x == y)
        exact += a == b
        first_div = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                         min(len(a), len(b)))
        diverge_steps += first_div
    engines["int8"].metrics.record_quant_agreement(matched, total)

    prompt_tokens = sum(len(p) for p in prompts)
    new_tokens = max_new * len(prompts)
    arms = {}
    for name, engine in engines.items():
        admit, drain = _median(admit_walls[name]), _median(drain_walls[name])
        snap = engine.metrics.snapshot()
        arms[name] = {
            "slots": engine.num_slots,
            "num_kv_pages": engine._pool.num_pages,
            "pool_bytes": (num_pages_fp * page_bytes_fp if name == "fp"
                           else num_pages_q * page_bytes_q),
            "peak_concurrent_sessions": _median(peaks[name]),
            "admission_wall_seconds": round(admit, 4),
            "admission_prompt_tokens_per_s": round(prompt_tokens / admit, 2)
            if admit > 0 else 0.0,
            "ttft_p95_seconds": round(_median(ttft_p95s[name]), 4),
            "drain_wall_seconds": round(drain, 4),
            "tokens_per_s": round(new_tokens / drain, 2) if drain > 0 else 0.0,
            "decode_compilations": engine.decode_compilations,
            "kv_quant": snap["kv_quant"],
        }
        engine.close()

    # kv_quant=None byte-identity: an engine with the knob explicitly None
    # produces exactly the tokens of one constructed with the PRE-quant
    # signature (no quant kwargs at all) — the off-path really is the old
    # engine (acceptance criterion; the f64 pin lives in tests/test_kv_quant)
    def _identity_tokens(**kw):
        eng = ServingEngine(model, params, num_slots=num_slots,
                            kv_page_size=page_size,
                            num_kv_pages=num_pages_fp, telemetry=False, **kw)
        hs = [eng.submit(p, max_new_tokens=max_new, rng=jax.random.PRNGKey(i))
              for i, p in enumerate(prompts[: 2 * num_slots])]
        eng.run_until_drained(max_steps=20_000)
        eng.close()
        return [h.result().tolist() for h in hs]

    none_identical = (_identity_tokens(kv_quant=None, weight_dtype=None)
                      == _identity_tokens())

    # weight-serving quality/bytes: teacher-forced CE on one synthetic batch,
    # computed through the SAME transform the engine applies (int8 leaves
    # dequantized exactly as the engine's jits do on entry)
    eval_rng = np.random.RandomState(seed + 1)
    ids = jnp.asarray(eval_rng.randint(1, config.vocab_size,
                                       size=(2, window)), jnp.int32)
    prefix_len = window - config.max_latents

    def _ce(tree):
        logits = model.apply(tree, ids, prefix_len)
        targets = ids[:, prefix_len + 1:]
        lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(lp, targets[..., None], axis=-1)
        return float(jnp.mean(nll))

    ce_fp = _ce(params)
    weight_arms = {"fp32": {"param_bytes": serve_params(params, None)[2],
                            "ce": round(ce_fp, 6), "ce_delta": 0.0}}
    for wd in ("bf16", "int8"):
        served, _dq, served_bytes, _fp_bytes = serve_params(params, wd)
        tree = dequantize_params(served) if wd == "int8" else served
        ce = _ce(tree)
        weight_arms[wd] = {
            "param_bytes": served_bytes,
            "ce": round(ce, 6),
            "ce_delta": round(ce - ce_fp, 6),
        }

    fp, q = arms["fp"], arms["int8"]
    return {
        "page_size": page_size,
        "window": window,
        "pool_byte_budget": budget_bytes,
        "page_bytes_fp": page_bytes_fp,
        "page_bytes_int8": page_bytes_q,
        "requests": len(prompts),
        "max_new_tokens": max_new,
        "prompt_tokens_per_pass": prompt_tokens,
        # self-attention state each slot costs OUTSIDE the pool budget (the
        # paging arm's honesty note: the budget covers the dominant CA term)
        "sa_rows_per_slot": config.max_latents,
        **{f"{n}_arm": a for n, a in arms.items()},
        "concurrent_sessions_ratio": round(
            q["peak_concurrent_sessions"] / fp["peak_concurrent_sessions"], 3
        ) if fp["peak_concurrent_sessions"] else 0.0,
        "admission_speedup": round(
            q["admission_prompt_tokens_per_s"] / fp["admission_prompt_tokens_per_s"], 3
        ) if fp["admission_prompt_tokens_per_s"] > 0 else 0.0,
        "quality": {
            "greedy_token_agreement": round(matched / total, 4) if total else None,
            "exact_sequence_match": round(exact / len(prompts), 4),
            "mean_first_divergence_step": round(diverge_steps / len(prompts), 2),
            "compared_tokens": total,
        },
        "kv_quant_none_identical_to_pre_quant": none_identical,
        "weight_serving": weight_arms,
    }


def run_priority_preemption(model, config, params, num_slots: int, seed: int,
                            repeats: int = 3) -> dict:
    """Mixed-priority overload arm (docs/serving.md "Priority classes &
    preemption"): a saturating LOW-priority background (long generations, a
    page pool sized to hold exactly the background's reservations) plus
    periodic HIGH-priority short requests, preemption ON vs the
    PERCEIVER_IO_TPU_DISABLE_PREEMPTION kill-switch arm. Headline numbers per
    arm: high-priority p50/p95 time-to-first-token (submit -> slot) and the
    deadline-miss rate against a derived SLO target (half a background
    generation wave, calibrated from this machine's measured tick time — the
    blocked path waits whole waves for pages, the preemptive path admits in
    ~one tick), plus total throughput so an arm cannot win by starving the
    background. Honesty notes: on CPU both arms share every core, so total
    tokens/s is ~equal by construction and the win is LATENCY, not
    throughput (on real TPU serving the same holds per chip); the SLO target
    is derived (requests carry no engine-enforced deadline) so both arms
    complete identical work and the miss rate is a pure function of the
    measured TTFTs. Arms are INTERLEAVED with per-arm medians, and the
    kill-switch arm's snapshot must carry the identical v6 schema keys."""
    from perceiver_io_tpu.serving import ServingEngine
    from perceiver_io_tpu.serving.engine import default_prefill_buckets
    from perceiver_io_tpu.serving.paging import pages_for_request, pages_for_tokens

    window = config.max_seq_len
    rng = np.random.RandomState(seed)
    short_hi = max(window // 8, 2)
    page_size = max(window // 16, 2)
    bg_max_new, fg_max_new = 16, 4
    buckets = default_prefill_buckets(window, config.max_latents)
    covering = next(b for b in buckets if b >= short_hi)
    bg_need = pages_for_request(covering, bg_max_new, window, page_size)
    # pool holds exactly num_slots background reservations (+ trash page):
    # a foreground arrival is always page-blocked behind the background
    num_pages = max(num_slots * bg_need + 1,
                    pages_for_tokens(window, page_size) + 1)
    bg_prompts = [rng.randint(1, config.vocab_size, size=int(n)).tolist()
                  for n in rng.randint(2, short_hi + 1, size=3 * num_slots)]
    fg_prompts = [rng.randint(1, config.vocab_size, size=int(n)).tolist()
                  for n in rng.randint(2, short_hi + 1, size=num_slots)]
    fg_every = max(bg_max_new // 2, 1)  # one hi-prio arrival per half-wave

    def build(disable: bool) -> ServingEngine:
        from perceiver_io_tpu.utils import env_override

        with env_override("PERCEIVER_IO_TPU_DISABLE_PREEMPTION",
                          "1" if disable else None):
            # telemetry=False: ambient env must not record inside a TIMED arm
            return ServingEngine(model, params, num_slots=num_slots,
                                 kv_page_size=page_size, num_kv_pages=num_pages,
                                 telemetry=False)

    def one_pass(engine):
        t0 = time.perf_counter()
        bg = [engine.submit(p, max_new_tokens=bg_max_new, rng=jax.random.PRNGKey(i))
              for i, p in enumerate(bg_prompts)]
        fg, ticks, fg_iter = [], 0, iter(enumerate(fg_prompts))
        pending_fg = next(fg_iter, None)
        while True:
            has_work = engine.step()
            ticks += 1
            if pending_fg is not None and ticks % fg_every == 0:
                i, p = pending_fg
                fg.append(engine.submit(p, max_new_tokens=fg_max_new, priority=1,
                                        rng=jax.random.PRNGKey(1000 + i)))
                pending_fg = next(fg_iter, None)
            if not has_work and pending_fg is None and not engine.scheduler.has_work:
                break
        wall = time.perf_counter() - t0
        assert all(h.ok for h in bg + fg)  # a degraded pass must not be timed
        ttfts = sorted(h.admitted_at - h.submitted_at for h in fg)
        new_tokens = sum(len(h.output_ids) for h in bg + fg)
        engine.finished.clear()
        return ttfts, wall, new_tokens, ticks

    # pass 1 per arm: warmup (compiles everything — NOT used for timing);
    # pass 2 per arm: warm calibration, whose ON-arm tick time derives the
    # SLO target (half a background generation wave) applied identically to
    # both arms' miss rates. Deriving from the compile pass would inflate
    # the target past even the blocked arm's waits and zero out both rates.
    engines = {"preemption_on": build(False), "preemption_off": build(True)}
    calib = {}
    for name, engine in engines.items():
        one_pass(engine)  # warmup
        _, wall, _, ticks = one_pass(engine)  # warm calibration
        calib[name] = wall / max(ticks, 1)
    tick_s = calib["preemption_on"]
    deadline_target_s = tick_s * bg_max_new * 0.5

    ttfts_by_arm = {n: [] for n in engines}
    walls = {n: [] for n in engines}
    tokens = {n: 0 for n in engines}
    for _ in range(repeats):
        for name, engine in engines.items():  # interleaved A/B
            ttfts, wall, new_tokens, _ = one_pass(engine)
            ttfts_by_arm[name].append(ttfts)
            walls[name].append(wall)
            tokens[name] = new_tokens

    arms = {}
    for name, engine in engines.items():
        per_pass = ttfts_by_arm[name]
        p50 = _median([_pct(t, 0.5) for t in per_pass])
        p95 = _median([_pct(t, 0.95) for t in per_pass])
        misses = _median([sum(1 for x in t if x > deadline_target_s) / len(t)
                          for t in per_pass])
        wall = _median(walls[name])
        snap = engine.metrics.snapshot()
        arms[name] = {
            "hi_ttft_p50_s": round(p50, 5),
            "hi_ttft_p95_s": round(p95, 5),
            "deadline_miss_rate": round(misses, 4),
            "wall_seconds": round(wall, 4),
            "tokens_per_s": round(tokens[name] / wall, 2) if wall > 0 else 0.0,
            "preemptions": snap["preemptions"],
            "preempted_replays": snap["preempted_replays"],
            "queue_wait_by_priority": snap["queue_wait_by_priority"],
            "alloc_failures": snap["page_pool"]["alloc_failures"],
            "snapshot_keys": sorted(snap.keys()),
        }
    on, off = arms["preemption_on"], arms["preemption_off"]
    schema_identical = on.pop("snapshot_keys") == off.pop("snapshot_keys")
    for engine in engines.values():
        engine.close()
    new_tokens_per_pass = (len(bg_prompts) * bg_max_new
                           + len(fg_prompts) * fg_max_new)
    return {
        "page_size": page_size,
        "num_kv_pages": num_pages,
        "slots": num_slots,
        "background_requests": len(bg_prompts),
        "background_max_new": bg_max_new,
        "foreground_requests": len(fg_prompts),
        "foreground_max_new": fg_max_new,
        "deadline_target_s": round(deadline_target_s, 5),
        "new_tokens_per_pass": new_tokens_per_pass,  # identical work, both arms
        "preemption_on": on,
        "preemption_off": off,
        "ttft_p95_improvement": round(off["hi_ttft_p95_s"] / on["hi_ttft_p95_s"], 3)
        if on["hi_ttft_p95_s"] > 0 else 0.0,
        "deadline_miss_improvement": round(
            off["deadline_miss_rate"] - on["deadline_miss_rate"], 4
        ),
        "schema_keys_identical": schema_identical,
        "note": "both arms complete identical useful work "
                "(new_tokens_per_pass); the preemption arm's wall includes "
                "the victims' forced-replay redo ticks, so its tokens/s "
                "reads slightly lower — the deliverable is the hi-class "
                "TTFT/deadline win, honestly priced. CPU: arms share every "
                "core; the SLO target derives from measured warm tick time "
                "(see docstring)",
    }


def run_journal_overhead(model, config, params, num_slots: int, seed: int,
                         repeats: int = 5) -> dict:
    """``--journal`` acceptance arm (docs/serving.md "Request journal"): the
    same staggered mixed workload through a journal-off and a journal-on
    engine (default ``fsync="accept"`` policy — one fsync per ACCEPT, one
    buffered write per tick), interleaved median-of-``repeats``. The
    accepted⇒durable guarantee must ride almost free on the decode hot loop:
    the acceptance bound is admission tokens/s within 10% of journal-off.
    Greedy outputs are asserted byte-identical across arms (the journal is
    pure host-side bookkeeping), and the journal-on arm reports its own
    write/fsync counters so the overhead has an explanation attached."""
    import shutil
    import tempfile

    from perceiver_io_tpu.serving import ServingEngine

    requests = synth_workload(config, 4 * num_slots, seed)

    def one_pass(journal_dir):
        engine = ServingEngine(model, params, num_slots=num_slots,
                               telemetry=False, journal=journal_dir)
        t0 = time.perf_counter()
        handles = []
        for i, r in enumerate(requests):
            handles.append(engine.submit(
                r["prompt"], max_new_tokens=r["max_new_tokens"],
                rng=jax.random.PRNGKey(i)))
            engine.step()
        while engine.step():
            pass
        drain_wall = time.perf_counter() - t0
        assert all(h.ok for h in handles)  # a degraded pass must not be timed
        admit_wall = max(h.admitted_at for h in handles) - t0
        snap = engine.metrics.snapshot()
        tokens = [h.result().tolist() for h in handles]
        engine.close()
        return admit_wall, drain_wall, snap, tokens

    one_pass(None)  # warmup: compiles every covering bucket + the decode step
    walls = {"journal_off": [], "journal_on": []}
    snaps, outputs = {}, {}
    for _ in range(repeats):
        for arm in walls:  # interleaved A/B: shared-CPU drift hits both arms
            tmp = tempfile.mkdtemp(prefix="serve-bench-journal-") \
                if arm == "journal_on" else None
            try:
                admit, drain, snap, tokens = one_pass(
                    os.path.join(tmp, "j") if tmp else None)
            finally:
                if tmp:
                    shutil.rmtree(tmp, ignore_errors=True)
            walls[arm].append((admit, drain))
            snaps[arm] = snap
            outputs.setdefault(arm, tokens)
            assert tokens == outputs[arm], "journal arm changed tokens"

    prompt_tokens = sum(len(r["prompt"]) for r in requests)
    new_tokens = sum(r["max_new_tokens"] for r in requests)
    out = {"requests": len(requests), "slots": num_slots,
           "fsync_policy": "accept",
           "prompt_tokens_per_pass": prompt_tokens,
           "new_tokens_per_pass": new_tokens}
    for arm, samples in walls.items():
        admit = _median([s[0] for s in samples])
        drain = _median([s[1] for s in samples])
        out[arm] = {
            "admission_wall_seconds": round(admit, 4),
            "admission_wall_all_repeats": [round(s[0], 4) for s in samples],
            "admission_prompt_tokens_per_s": round(prompt_tokens / admit, 2)
            if admit > 0 else 0.0,
            "drain_wall_seconds": round(drain, 4),
            "tokens_per_s": round(new_tokens / drain, 2) if drain > 0 else 0.0,
        }
    jstats = snaps["journal_on"]["journal"] or {}
    out["journal_writes"] = {
        k: jstats.get(k)
        for k in ("bytes_written", "records_appended", "fsyncs", "compactions")
    }
    out["outputs_identical_across_arms"] = (
        outputs["journal_off"] == outputs["journal_on"]
    )
    off = out["journal_off"]["admission_prompt_tokens_per_s"]
    on = out["journal_on"]["admission_prompt_tokens_per_s"]
    out["admission_overhead_ratio"] = round(off / on, 3) if on > 0 else 0.0
    out["admission_within_10pct"] = bool(on > 0 and off / on <= 1.10)
    return out


def run_prefix_cache(model, config, params, num_slots: int, seed: int,
                     repeats: int = 7, max_new: int = 8) -> dict:
    """``--prefix-cache`` acceptance arm (docs/serving.md "Prefix cache"):
    an 80%-SHARED-PREFIX multi-tenant workload — one shared system prompt +
    few-shot preamble (~60% of the window) with short distinct tails, plus
    20% fully distinct prompts — through a cache-on vs a cache-off engine at
    EQUAL pool budget (a pool deliberately sized to ~3 dense reservations,
    so admission is page-gated the way multi-tenant serving is HBM-gated).
    Cache-on, a request extending the cached preamble retains those pages
    and prefills only its tail: less prefill compute AND a smaller private
    reservation, so more sessions fit the same pool and the burst admits in
    fewer decode-gated waves. Reported per arm, interleaved
    median-of-``repeats`` on live engines (the cache stays warm across
    passes — the multi-tenant steady state; the warmup pass's cold stats
    ride along): admission prompt tokens/s (wall to last admission), TTFT
    p50/p95 (submit -> slot), peak concurrent sessions at the fixed budget,
    and the cache hit rate. Greedy outputs asserted identical across arms
    (f64 identity is pinned in tests/test_prefix_cache.py; this f32 run
    records the observation)."""
    from perceiver_io_tpu.serving import ServingEngine, pages_for_request
    from perceiver_io_tpu.serving.engine import default_prefill_buckets

    window = config.max_seq_len
    page_size = max(window // 16, 2)
    buckets = default_prefill_buckets(window, config.max_latents)
    dense_need = pages_for_request(window, max_new, window, page_size)
    num_pages = 3 * dense_need + 1  # ~3 dense reservations + trash page
    # slots must NOT be the binding constraint in a page-gated arm (the
    # multi-tenant scenario is HBM-gated): both arms get the same generous
    # slot count and the fixed pool budget decides concurrency — cache-off
    # fits ~3 dense reservations, cache-on fits what page sharing frees
    num_slots = 2 * num_slots
    rng = np.random.RandomState(seed)
    # the shared system prompt + few-shot preamble dominates the prompt
    # (the multi-tenant shape: a ~1.5k-token preamble, a short user tail)
    preamble = rng.randint(1, config.vocab_size,
                           size=int(window * 0.75)).tolist()
    tail_hi = max(window // 8, 2)
    k = 2 * num_slots  # same burst size as before the slot doubling above
    prompts = []
    for i in range(k):
        tail = rng.randint(1, config.vocab_size,
                           size=int(rng.randint(2, tail_hi))).tolist()
        if i % 5 == 4:  # 20%: distinct prompt, same length population
            prompts.append(rng.randint(
                1, config.vocab_size, size=len(preamble) + len(tail)).tolist())
        else:  # 80%: shared preamble + distinct tail
            prompts.append(preamble + tail)

    # telemetry=False: ambient env must not record inside a TIMED arm
    engines = {
        "cache_off": ServingEngine(model, params, num_slots=num_slots,
                                   kv_page_size=page_size,
                                   num_kv_pages=num_pages, telemetry=False),
        "cache_on": ServingEngine(model, params, num_slots=num_slots,
                                  kv_page_size=page_size,
                                  num_kv_pages=num_pages, prefix_cache=True,
                                  telemetry=False),
    }

    def one_pass(engine):
        t0 = time.perf_counter()
        handles = [engine.submit(p, max_new_tokens=max_new,
                                 rng=jax.random.PRNGKey(i))
                   for i, p in enumerate(prompts)]
        peak = 0
        while engine.step():
            peak = max(peak, engine.scheduler.active_slots)
        wall = time.perf_counter() - t0
        assert all(h.ok for h in handles)  # a degraded pass must not be timed
        admit_wall = max(h.admitted_at for h in handles) - t0
        ttfts = sorted(h.admitted_at - h.submitted_at for h in handles)
        engine.finished.clear()
        return (peak, admit_wall, wall, ttfts,
                [h.result().tolist() for h in handles])

    cold_stats = None
    for name, engine in engines.items():  # warmup: compiles + warms the cache
        one_pass(engine)
        if name == "cache_on":
            cold_stats = dict(engine._prefix_cache.stats())  # the COLD pass
    samples = {n: [] for n in engines}
    tokens_by_arm = {}
    for _ in range(repeats):
        for name, engine in engines.items():  # interleaved A/B
            peak, admit, wall, ttfts, toks = one_pass(engine)
            samples[name].append((peak, admit, wall, ttfts))
            tokens_by_arm[name] = toks

    prompt_tokens = sum(len(p) for p in prompts)
    arms = {}
    for name, engine in engines.items():
        peaks = [s[0] for s in samples[name]]
        admit = _median([s[1] for s in samples[name]])
        wall = _median([s[2] for s in samples[name]])
        p50s = [_pct(s[3], 0.50) for s in samples[name]]
        p95s = [_pct(s[3], 0.95) for s in samples[name]]
        arms[name] = {
            "slots": num_slots,
            "num_kv_pages": num_pages,
            "page_size": page_size,
            "peak_concurrent_sessions": _median(peaks),
            "admission_wall_seconds": round(admit, 4),
            "admission_prompt_tokens_per_s": round(prompt_tokens / admit, 2)
            if admit > 0 else 0.0,
            "ttft_p50_s": round(_median(p50s), 4),
            "ttft_p95_s": round(_median(p95s), 4),
            "drain_wall_seconds": round(wall, 4),
            "decode_compilations": engine.decode_compilations,
        }
        snap = engine.metrics.snapshot()
        if name == "cache_on":
            arms[name]["prefix_cache_warm"] = snap["prefix_cache"]
            arms[name]["prefix_cache_cold_pass"] = cold_stats
        engine.close()
    on, off = arms["cache_on"], arms["cache_off"]
    speedup = (round(on["admission_prompt_tokens_per_s"]
                     / off["admission_prompt_tokens_per_s"], 3)
               if off["admission_prompt_tokens_per_s"] > 0 else 0.0)
    return {
        "workload": {
            "requests": k, "shared_fraction": 0.8,
            "preamble_tokens": len(preamble), "tail_hi": tail_hi,
            "max_new_tokens": max_new,
            "prompt_tokens_per_pass": prompt_tokens,
        },
        "kv_budget_tokens": num_pages * page_size,
        **arms,
        "admission_speedup": speedup,
        "admission_speedup_ok": bool(speedup >= 2.0),  # acceptance: >= 2x
        "ttft_p95_ratio": round(off["ttft_p95_s"] / on["ttft_p95_s"], 3)
        if on["ttft_p95_s"] > 0 else 0.0,
        "sessions_at_fixed_hbm_ratio": round(
            on["peak_concurrent_sessions"] / off["peak_concurrent_sessions"], 3
        ) if off["peak_concurrent_sessions"] else 0.0,
        # f64 identity is the pinned contract (tests/test_prefix_cache.py)
        "greedy_tokens_identical_f32":
            tokens_by_arm["cache_on"] == tokens_by_arm["cache_off"],
    }


def run_chunked_interference(model, config, params, num_slots: int, seed: int,
                             repeats: int = 5) -> dict:
    """``--chunked`` interference arm (docs/serving.md "Chunked prefill"):
    running-slot INTER-TOKEN latency under sustained mixed traffic —
    recurring bursts of window-length prompts admitted mid-stream, chunked
    vs unchunked. Background decode sessions stream tokens; a burst of long
    prompts arrives every ``burst_every`` ticks; unchunked, admission fills
    every free slot THAT TICK — each burst's one-shot O(window) prefills
    all land inside a single tick and every running slot's next token waits
    behind the whole pile, often enough that the bystanders' p95 IS the
    stall — chunked (``max_prefill_slots`` bounding concurrent chunk
    streams), admission spreads the same work at most (budget x chunk)
    tokens per tick, bounding both the worst gap and the p95 regardless of
    burst size. Reported per arm, interleaved median-of-``repeats``: the
    background slots' p50/p95/max tick-to-tick token gap from the first
    burst to background completion, plus the last burst admission span (the
    honest price: chunked trades long-prompt TTFT for everyone else's
    p95)."""
    from perceiver_io_tpu.serving import ServingEngine, pages_for_request

    window = config.max_seq_len
    page_size = max(window // 16, 2)
    chunk = max(window // 8, 1)
    n_bg = max(num_slots - 1, 1)
    burst_size = 4
    burst_every = 12  # ticks between bursts (sustained arrival, not one-off)
    n_bursts = 4
    # slots stay SMALL: the compiled decode step's batch dim is num_slots,
    # so oversizing the pool of slots inflates every steady tick and drowns
    # the very stall the arm measures. One spare beyond bg + one burst;
    # chunked streams that outlast a burst interval queue (bounded below)
    # and admit later — the honest TTFT price the arm reports.
    slots = n_bg + burst_size + 1
    dense_need = pages_for_request(window, 8, window, page_size)
    num_pages = (slots + 1) * dense_need + 1
    rng = np.random.RandomState(seed)
    bg_prompts = [rng.randint(1, config.vocab_size,
                              size=int(rng.randint(4, max(window // 8, 5)))).tolist()
                  for _ in range(n_bg)]
    bg_max_new = 48
    long_prompts = [rng.randint(1, config.vocab_size, size=window).tolist()
                    for _ in range(burst_size * n_bursts)]

    def build(chunked: bool) -> ServingEngine:
        # telemetry=False: ambient env must not record inside a TIMED arm
        return ServingEngine(
            model, params, num_slots=slots, kv_page_size=page_size,
            num_kv_pages=num_pages,
            # chunked streams outlasting a burst interval park later bursts
            # in the queue: the bound must cover the whole arrival schedule
            max_queue_depth=4 * len(long_prompts),
            prefill_chunk_tokens=chunk if chunked else None,
            # the per-tick prefill budget: at most 2 concurrent chunk
            # streams, so a tick's added prefill work is <= 2 x chunk
            # tokens no matter how many long prompts queue up
            max_prefill_slots=2 if chunked else None, telemetry=False,
        )

    def one_pass(engine):
        bg = [engine.submit(p, max_new_tokens=bg_max_new,
                            rng=jax.random.PRNGKey(i))
              for i, p in enumerate(bg_prompts)]
        for _ in range(4):  # background admitted and decoding
            engine.step()
        assert all(h.status.value == "running" for h in bg)
        t_long = time.perf_counter()
        lhs = []
        gaps, last, tick = [], t_long, 0
        while any(not h.done for h in bg):
            if tick % burst_every == 0 and len(lhs) < len(long_prompts):
                base = len(lhs)  # captured: extend() would read it lazily
                burst = long_prompts[base:base + burst_size]
                lhs.extend([engine.submit(p, max_new_tokens=4,
                                          rng=jax.random.PRNGKey(99 + base + i))
                            for i, p in enumerate(burst)])
            engine.step()
            tick += 1
            now = time.perf_counter()
            gaps.append(now - last)
            last = now
        while engine.step():
            pass
        assert all(h.ok for h in lhs) and all(h.ok for h in bg)
        long_admit = max(h.admitted_at for h in lhs) - t_long
        engine.finished.clear()
        return sorted(gaps), long_admit, [h.result().tolist() for h in bg + lhs]

    engines = {"unchunked": build(False), "chunked": build(True)}
    for engine in engines.values():  # warmup compiles every program
        one_pass(engine)
    samples = {n: [] for n in engines}
    tokens_by_arm = {}
    for _ in range(repeats):
        for name, engine in engines.items():  # interleaved A/B
            gaps, long_admit, toks = one_pass(engine)
            samples[name].append((gaps, long_admit))
            tokens_by_arm[name] = toks

    arms = {}
    for name, engine in engines.items():
        p50 = _median([_pct(s[0], 0.50) for s in samples[name]])
        p95 = _median([_pct(s[0], 0.95) for s in samples[name]])
        mx = _median([s[0][-1] for s in samples[name]])
        arms[name] = {
            "inter_token_p50_s": round(p50, 4),
            "inter_token_p95_s": round(p95, 4),
            "inter_token_max_s": round(mx, 4),
            "long_prompt_admission_s": round(
                _median([s[1] for s in samples[name]]), 4),
            "decode_compilations": engine.decode_compilations,
        }
        if name == "chunked":
            snap = engine.metrics.snapshot()
            arms[name]["chunked_prefill"] = snap["chunked_prefill"]
        engine.close()
    ch, un = arms["chunked"], arms["unchunked"]
    return {
        "workload": {
            "background_sessions": len(bg_prompts),
            "background_max_new": bg_max_new,
            "long_prompt_tokens": window,
            "burst_size": burst_size,
            "burst_every_ticks": burst_every,
            "bursts": n_bursts,
            "chunk_tokens": chunk,
            "max_prefill_slots_chunked": 2,
            "page_size": page_size,
        },
        **arms,
        "inter_token_p95_ratio": round(
            un["inter_token_p95_s"] / ch["inter_token_p95_s"], 3)
        if ch["inter_token_p95_s"] > 0 else 0.0,
        "inter_token_max_ratio": round(
            un["inter_token_max_s"] / ch["inter_token_max_s"], 3)
        if ch["inter_token_max_s"] > 0 else 0.0,
        # the bounded-stall contract: the chunked arm's WORST gap stays
        # under the unchunked arm's full-prompt stall
        "stall_bounded": bool(ch["inter_token_max_s"] < un["inter_token_max_s"]),
        "greedy_tokens_identical_f32":
            tokens_by_arm["chunked"] == tokens_by_arm["unchunked"],
    }


def run_ragged_tick_bench(model, config, params, num_slots: int, seed: int,
                          repeats: int = 7) -> dict:
    """``--ragged`` arm (docs/serving.md "Unified ragged tick"): the fused
    ONE-program tick on a sustained MIXED workload — background decode
    streams plus recurring window-length prompt bursts admitted through
    chunked prefill, so steady ticks genuinely carry chunk lanes, latent
    finishes AND batched decode at once (the shape class the ragged tick
    exists for; a decode-only workload would show nothing). Reported,
    median-of-``repeats``: decode tokens/s, running-slot inter-token
    p50/p95, and the counts — programs per dispatching tick from the v11
    ``ragged_tick`` metrics block, descriptor build time (the host-side
    cost the single dispatch buys), compilations of the tick program.

    A second section prices the new int4 pages: CONCURRENT SESSIONS PER
    FIXED POOL BYTE BUDGET, int4 vs int8 vs full-precision pages — the same
    budget discipline as the --kv-quant arm (per-page-per-head f32 scale
    sidecars honestly counted inside the budget; int4 packs two offset
    codes per byte so its KV term is half int8's), with greedy token
    agreement vs the fp arm so quality is not silently dropped.
    Acceptance: the int4 arm holds >= 1.8x the fp arm's sessions."""
    from perceiver_io_tpu.serving import ServingEngine, pages_for_request
    from perceiver_io_tpu.serving.engine import default_prefill_buckets

    window = config.max_seq_len
    page_size = max(window // 16, 2)
    chunk = max(window // 8, 1)
    n_bg = max(num_slots - 1, 2)
    burst_size = 2
    burst_every = 8  # ticks between bursts: sustained mixing, not one-off
    n_bursts = 3
    slots = n_bg + burst_size + 1
    need = pages_for_request(window, 8, window, page_size)
    num_pages = (slots + 1) * need + 1
    rng = np.random.RandomState(seed)
    bg_prompts = [rng.randint(1, config.vocab_size,
                              size=int(rng.randint(4, max(window // 8, 5)))).tolist()
                  for _ in range(n_bg)]
    bg_max_new = 32
    burst_max_new = 4
    burst_prompts = [rng.randint(1, config.vocab_size, size=window).tolist()
                     for _ in range(burst_size * n_bursts)]

    # telemetry=False: ambient env must not record inside a TIMED arm
    engine = ServingEngine(
        model, params, num_slots=slots, kv_page_size=page_size,
        num_kv_pages=num_pages,
        max_queue_depth=4 * len(burst_prompts),
        prefill_chunk_tokens=chunk, max_prefill_slots=2,
        telemetry=False)

    def one_pass():
        bg = [engine.submit(p, max_new_tokens=bg_max_new,
                            rng=jax.random.PRNGKey(i))
              for i, p in enumerate(bg_prompts)]
        for _ in range(4):  # background admitted and decoding
            engine.step()
        t0 = time.perf_counter()
        lhs, gaps, last, tick = [], [], t0, 0
        while any(not h.done for h in bg):
            if tick % burst_every == 0 and len(lhs) < len(burst_prompts):
                base = len(lhs)  # captured: extend() would read it lazily
                lhs.extend([engine.submit(p, max_new_tokens=burst_max_new,
                                          rng=jax.random.PRNGKey(99 + base + i))
                            for i, p in enumerate(
                                burst_prompts[base:base + burst_size])])
            engine.step()
            tick += 1
            now = time.perf_counter()
            gaps.append(now - last)
            last = now
        while engine.step():
            pass
        drain = time.perf_counter() - t0
        assert all(h.ok for h in bg) and all(h.ok for h in lhs)
        engine.finished.clear()
        return sorted(gaps), drain

    one_pass()  # warmup compiles every program
    samples = [one_pass() for _ in range(repeats)]

    new_tokens = bg_max_new * len(bg_prompts) + burst_max_new * len(burst_prompts)
    drain = _median([d for _, d in samples])
    rt = engine.metrics.snapshot()["ragged_tick"]
    ragged = {
        "tokens_per_s": round(new_tokens / drain, 2) if drain > 0 else 0.0,
        "drain_wall_seconds": round(drain, 4),
        "inter_token_p50_s": round(_median([_pct(g, 0.50) for g, _ in samples]), 4),
        "inter_token_p95_s": round(_median([_pct(g, 0.95) for g, _ in samples]), 4),
        "dispatching_ticks": rt["ticks"],
        "programs_per_tick": rt["programs_per_tick"],
        "descriptor_build_s": rt["descriptor_build_s"],
        "tick_compilations": engine.decode_compilations,
    }
    engine.close()

    # --- int4 capacity: sessions per fixed pool BYTE budget, three arms.
    # The budget is the fp arm's pool bytes; every arm spends the same
    # bytes on its own page format + sidecars and raises its slot count to
    # what its pool holds resident (the --kv-quant arm's discipline).
    pages_per_slot = -(-window // page_size)
    num_pages_fp = num_slots * pages_per_slot + 1
    page_bytes = {
        "fp": 2 * page_size * config.num_channels * 4,
        "int8": (2 * page_size * config.num_channels
                 + 2 * config.num_heads * 4),
        "int4": (page_size * config.num_channels  # two codes per byte
                 + 2 * config.num_heads * 4),
    }
    budget_bytes = num_pages_fp * page_bytes["fp"]
    short_hi = max(window // 8, 2)
    buckets = default_prefill_buckets(window, config.max_latents)
    covering = next(b for b in buckets if b >= short_hi)
    cap_need = pages_for_request(covering, 8, window, page_size)
    cap_engines, cap_meta = {}, {}
    for name, pb in page_bytes.items():
        n_pages = budget_bytes // pb
        n_slots = max((n_pages - 1) // cap_need, 1)
        cap_engines[name] = ServingEngine(
            model, params, num_slots=n_slots, kv_page_size=page_size,
            num_kv_pages=n_pages, kv_quant=None if name == "fp" else name,
            telemetry=False)
        cap_meta[name] = {"slots": int(n_slots), "num_kv_pages": int(n_pages),
                          "pool_bytes": int(n_pages * pb)}
    k = 2 * max(e.num_slots for e in cap_engines.values())
    cap_prompts = [rng.randint(1, config.vocab_size, size=int(n)).tolist()
                   for n in rng.randint(2, short_hi + 1, size=k)]

    def cap_pass(engine):
        t0 = time.perf_counter()
        hs = [engine.submit(p, max_new_tokens=8, rng=jax.random.PRNGKey(i))
              for i, p in enumerate(cap_prompts)]
        peak = 0
        while engine.step():
            peak = max(peak, engine.scheduler.active_slots)
        wall = time.perf_counter() - t0
        assert all(h.ok for h in hs)  # a degraded pass must not be timed
        engine.finished.clear()
        return peak, wall, [h.result().tolist() for h in hs]

    for engine in cap_engines.values():  # warmup
        cap_pass(engine)
    peaks = {n: [] for n in cap_engines}
    cap_walls = {n: [] for n in cap_engines}
    cap_tokens = {}
    for _ in range(repeats):
        for name, engine in cap_engines.items():  # interleaved
            peak, wall, toks = cap_pass(engine)
            peaks[name].append(peak)
            cap_walls[name].append(wall)
            cap_tokens[name] = toks

    # greedy agreement, int4 arm vs fp arm (identical prompts and rngs)
    total = matched = exact = 0
    for a, b in zip(cap_tokens["fp"], cap_tokens["int4"]):
        total += max(len(a), len(b))
        matched += sum(1 for x, y in zip(a, b) if x == y)
        exact += a == b
    cap_arms = {}
    for name, engine in cap_engines.items():
        cap_arms[name] = {
            **cap_meta[name],
            "peak_concurrent_sessions": _median(peaks[name]),
            "drain_wall_seconds": round(_median(cap_walls[name]), 4),
            "kv_quant": engine.metrics.snapshot()["kv_quant"],
        }
        engine.close()
    fp_peak = cap_arms["fp"]["peak_concurrent_sessions"]
    i8_peak = cap_arms["int8"]["peak_concurrent_sessions"]
    i4_peak = cap_arms["int4"]["peak_concurrent_sessions"]
    int4_vs_fp = round(i4_peak / fp_peak, 3) if fp_peak else 0.0

    return {
        "workload": {
            "background_sessions": len(bg_prompts),
            "background_max_new": bg_max_new,
            "burst_prompt_tokens": window,
            "burst_size": burst_size,
            "burst_every_ticks": burst_every,
            "bursts": n_bursts,
            "chunk_tokens": chunk,
            "max_prefill_slots": 2,
            "page_size": page_size,
            "slots": slots,
        },
        # programs_per_tick p50 is the structural count the arm exists to
        # record: one program a tick
        "ragged_arm": ragged,
        "int4_capacity": {
            "pool_byte_budget": budget_bytes,
            "page_bytes": page_bytes,
            "requests": len(cap_prompts),
            **{f"{n}_arm": a for n, a in cap_arms.items()},
            "int8_vs_fp_sessions_ratio": round(i8_peak / fp_peak, 3)
            if fp_peak else 0.0,
            "int4_vs_int8_sessions_ratio": round(i4_peak / i8_peak, 3)
            if i8_peak else 0.0,
            "int4_vs_fp_sessions_ratio": int4_vs_fp,
            "meets_1p8x_fp": bool(int4_vs_fp >= 1.8),
            "quality": {
                "greedy_token_agreement_vs_fp":
                    round(matched / total, 4) if total else None,
                "exact_sequence_match":
                    round(exact / len(cap_prompts), 4),
                "compared_tokens": total,
            },
        },
    }


def run_baseline(model, params, requests, warmup: bool):
    """Single-request serving: generate() per request, back-to-back, on the
    canonical padded shape (prompt left-padded to the full window)."""
    from perceiver_io_tpu.generation.generate import GenerationConfig, generate

    window = model.max_seq_len
    num_latents = model.max_latents

    def one(r, i):
        n = len(r["prompt"])
        ids = np.zeros((1, window), np.int32)
        pad = np.ones((1, window), bool)
        ids[0, window - n:] = r["prompt"]
        pad[0, window - n:] = False
        out = generate(model, params, jnp.asarray(ids), num_latents=num_latents,
                       pad_mask=jnp.asarray(pad), rng=jax.random.PRNGKey(i),
                       config=GenerationConfig(max_new_tokens=r["max_new_tokens"]))
        return jax.block_until_ready(out)

    shapes = sorted({r["max_new_tokens"] for r in requests})
    if warmup:
        for m in shapes:  # compile each distinct scan length once
            one({"prompt": requests[0]["prompt"], "max_new_tokens": m}, 0)

    t0 = time.perf_counter()
    for i, r in enumerate(requests):
        one(r, i)
    wall = time.perf_counter() - t0
    new_tokens = sum(r["max_new_tokens"] for r in requests)
    return {
        "wall_seconds": round(wall, 4),
        "new_tokens": new_tokens,
        "tokens_per_s": round(new_tokens / wall, 2) if wall > 0 else 0.0,
        "baseline_compile_shapes": shapes,
    }


def profile_workloads(config, num_requests: int, seed: int):
    """Short-prompt (<= window/8, the ROADMAP's short-heavy traffic) and
    full-window (>= 3/4 window) prompt populations."""
    rng = np.random.RandomState(seed)
    w = config.max_seq_len
    short_hi = max(w // 8, 2)
    return {
        "short": [rng.randint(1, config.vocab_size, size=int(n)).tolist()
                  for n in rng.randint(2, short_hi + 1, size=num_requests)],
        "fullwindow": [rng.randint(1, config.vocab_size, size=int(n)).tolist()
                       for n in rng.randint(w * 3 // 4, w + 1, size=num_requests)],
    }


def _admission_engine(model, params, prompts, buckets):
    """Engine with one slot per request, every covering bucket's programs
    compiled (in-vocab warmup ids — range(b) would exceed the tiny benchmark
    vocab), ready for back-to-back admission timing."""
    from perceiver_io_tpu.serving import ServingEngine

    # telemetry=False, not None: an ambient PERCEIVER_IO_TPU_TELEMETRY must
    # not switch recording on inside a TIMED arm and distort the A/B numbers
    engine = ServingEngine(model, params, num_slots=len(prompts), prefill_buckets=buckets,
                           telemetry=False)
    for b in sorted({engine._bucket_for(len(p)) for p in prompts}):
        engine.submit([1] * b, max_new_tokens=1)
    for slot, req in engine.scheduler.pop_admissible():
        engine._admit(slot, req)
        engine._evict(slot, req, "warmup")
    jax.block_until_ready(engine._state.next_hidden)
    return engine


def _measure_admission(engine, prompts) -> float:
    """One timed pass: K prefill+install dispatches back-to-back (the
    non-blocking admission path) with ONE device sync at the end — no decode
    step runs inside the window, so the wall isolates what the bucket ladder
    changes. Slots are evicted afterwards (untimed) for the next pass."""
    for i, p in enumerate(prompts):
        engine.submit(p, max_new_tokens=1, rng=jax.random.PRNGKey(i))
    t0 = time.perf_counter()
    for slot, req in engine.scheduler.pop_admissible():
        engine._admit(slot, req)
    jax.block_until_ready(engine._state.next_hidden)
    wall = time.perf_counter() - t0
    for slot, req in list(engine.scheduler.occupied()):
        engine._evict(slot, req, "measured")
    return wall


def _admission_result(prompts, walls) -> dict:
    admit_wall = min(walls)
    prompt_tokens = sum(len(p) for p in prompts)
    return {
        "requests": len(prompts),
        "prompt_tokens": prompt_tokens,
        "wall_seconds": round(admit_wall, 4),
        "wall_seconds_all_repeats": [round(w, 4) for w in walls],
        "prompt_tokens_per_s": round(prompt_tokens / admit_wall, 2) if admit_wall > 0 else 0.0,
        "admissions_per_s": round(len(prompts) / admit_wall, 2) if admit_wall > 0 else 0.0,
    }


def _run_decode_arm(model, params, prompts, num_slots: int, buckets, decode_tokens: int):
    """Decode throughput: a normal num_slots engine draining full generations;
    decode_tokens_per_s comes from the metrics snapshot (device-step timers,
    insensitive to arm ordering)."""
    from perceiver_io_tpu.serving import ServingEngine

    # telemetry=False: same timed-arm discipline as _admission_engine
    engine = ServingEngine(model, params, num_slots=num_slots, prefill_buckets=buckets,
                           telemetry=False)
    for i, p in enumerate(prompts):  # first drain warms prefill+decode programs
        engine.submit(p, max_new_tokens=1, rng=jax.random.PRNGKey(i))
    engine.run_until_drained()
    engine.metrics.close()
    engine.metrics = type(engine.metrics)(num_slots=num_slots)
    t0 = time.perf_counter()
    for i, p in enumerate(prompts):
        engine.submit(p, max_new_tokens=decode_tokens, rng=jax.random.PRNGKey(i))
    engine.run_until_drained()
    decode_wall = time.perf_counter() - t0
    snap = engine.metrics.snapshot()
    return {
        "decode_compilations": engine.decode_compilations,
        "new_tokens": snap["tokens_generated"],
        "decode_seconds": snap["decode_seconds"],
        "decode_tokens_per_s": snap["decode_tokens_per_s"],
        "wall_tokens_per_s": round(snap["tokens_generated"] / decode_wall, 2)
        if decode_wall > 0 else 0.0,
    }


def run_profile(model, config, num_slots: int, num_requests: int, seed: int,
                decode_tokens: int = 32, repeats: int = 5, params=None) -> dict:
    """Bucketed-ladder engine vs full-window-prefill engine on the short and
    full-window workloads; the short-workload ``admission_speedup`` is the
    acceptance number (target >= 2x on CPU). Admission passes are INTERLEAVED
    A/B/A/B and the best wall kept per arm: back-to-back arms pick up a
    systematic first-arm penalty (allocator/cache warm-up drift) large enough
    to invert the comparison, and single passes on a shared CPU are noisy.
    Even so the throughput view favors the baseline — CPU intra-op
    parallelism compresses the wall ratio well below the O(window/bucket)
    FLOP ratio (a synced per-admission latency probe shows the full gap).
    ``params`` lets the caller share one initialized model across arms (the
    --replicas flow would otherwise pay the init jit twice)."""
    if params is None:
        rng = jax.random.PRNGKey(seed)
        init_ids = jnp.zeros((1, config.max_seq_len), jnp.int32)
        params = jax.jit(model.init, static_argnames="prefix_len")(
            rng, init_ids, prefix_len=model.max_prefix_len
        )
    workloads = profile_workloads(config, num_requests, seed)
    out = {
        "model": {
            "window": config.max_seq_len, "max_latents": config.max_latents,
            "num_channels": config.num_channels,
            "num_self_attention_layers": config.num_self_attention_layers,
            "num_slots": num_slots,
        },
        "workloads": {},
    }
    for name, prompts in workloads.items():
        eng_bucketed = _admission_engine(model, params, prompts, None)
        eng_fullwin = _admission_engine(model, params, prompts, [config.max_seq_len])
        walls_b, walls_f = [], []
        for _ in range(repeats):
            walls_b.append(_measure_admission(eng_bucketed, prompts))
            walls_f.append(_measure_admission(eng_fullwin, prompts))
        bucketed = {
            "prefill_buckets": list(eng_bucketed.prefill_buckets),
            "prefill_compilations": eng_bucketed.prefill_compilations,
            "admission": _admission_result(prompts, walls_b),
            "decode": _run_decode_arm(model, params, prompts, num_slots, None, decode_tokens),
        }
        fullwin = {
            "prefill_buckets": list(eng_fullwin.prefill_buckets),
            "prefill_compilations": eng_fullwin.prefill_compilations,
            "admission": _admission_result(prompts, walls_f),
            "decode": _run_decode_arm(
                model, params, prompts, num_slots, [config.max_seq_len], decode_tokens
            ),
        }
        speedup = (
            round(bucketed["admission"]["prompt_tokens_per_s"]
                  / fullwin["admission"]["prompt_tokens_per_s"], 3)
            if fullwin["admission"]["prompt_tokens_per_s"] > 0 else 0.0
        )
        out["workloads"][name] = {
            "prompt_lens": [len(p) for p in prompts],
            "bucketed": bucketed,
            "fullwindow_baseline": fullwin,
            "admission_speedup": speedup,
        }
    # telemetry pass (docs/observability.md): one drain of the short workload
    # on a telemetry-enabled engine — per-phase tick breakdown (admit /
    # prefill dispatch / install / decode dispatch / sample-sync / evict) and
    # runtime compile counts land in the artifact. Separate from the timed
    # arms above so recording overhead never touches the A/B numbers.
    out["telemetry"] = _telemetry_pass(model, params, workloads["short"], num_slots)
    return out


def _telemetry_pass(model, params, prompts, num_slots: int, decode_tokens: int = 8) -> dict:
    from perceiver_io_tpu.serving import ServingEngine

    engine = ServingEngine(model, params, num_slots=num_slots, telemetry=True)
    for i, p in enumerate(prompts):
        engine.submit(p, max_new_tokens=decode_tokens, rng=jax.random.PRNGKey(i))
    engine.run_until_drained()
    summary = engine.telemetry_summary()
    engine.close()
    return summary


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--preset", default="tiny", choices=("tiny", "profile", "bench"))
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(_REPO, "SERVE_BENCH.json"))
    ap.add_argument("--metrics-jsonl", default=None,
                    help="optional per-event engine log (docs/serving.md schema)")
    ap.add_argument("--no-warmup", action="store_true",
                    help="include compile time in both timings (debug only)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="skip the single-request generate() comparison")
    ap.add_argument("--profile", action="store_true",
                    help="run the bucketed-vs-fullwindow prefill A/B on short "
                         "and full-window workloads; writes --profile-out")
    ap.add_argument("--profile-out", default=os.path.join(_REPO, "BENCH_serving.json"))
    ap.add_argument("--trace", default=None,
                    help="enable engine telemetry on the main workload and write "
                         "a Chrome trace (Perfetto-viewable) to this path")
    ap.add_argument("--page-size", type=int, default=0,
                    help="run the paged-KV capacity arm: concurrent sessions "
                         "per fixed KV budget and admission tokens/s, paged "
                         "(this page size) vs dense, interleaved median-of-7; "
                         "the block lands in the --profile-out artifact "
                         "(BENCH_serving.json)")
    ap.add_argument("--page-repeats", type=int, default=7)
    ap.add_argument("--kv-quant", type=int, default=0, metavar="PAGE_SIZE",
                    help="run the quantized-KV capacity arm: concurrent "
                         "sessions per fixed pool BYTE budget, int8 pages "
                         "(+ scale sidecars) vs full-precision pages at this "
                         "page size, interleaved median-of --kv-quant-repeats, "
                         "with greedy-token agreement + weight-serving CE "
                         "deltas reported; the block lands in the "
                         "--profile-out artifact (BENCH_serving.json)")
    ap.add_argument("--kv-quant-repeats", type=int, default=7)
    ap.add_argument("--priority-arm", action="store_true",
                    help="run the mixed-priority overload arm: saturating "
                         "low-priority background + high-priority foreground, "
                         "preemption on vs the DISABLE_PREEMPTION kill-switch "
                         "arm (hi-prio TTFT p95 + deadline-miss rate); the "
                         "block lands in the --profile-out artifact")
    ap.add_argument("--priority-repeats", type=int, default=3)
    ap.add_argument("--journal", action="store_true",
                    help="run the write-ahead journal overhead arm: the main "
                         "workload journal-on (accept-fsync policy) vs "
                         "journal-off, interleaved median-of "
                         "--journal-repeats (acceptance: admission tokens/s "
                         "within 10%%); the block lands in the --profile-out "
                         "artifact (BENCH_serving.json)")
    ap.add_argument("--journal-repeats", type=int, default=5)
    ap.add_argument("--prefix-cache", action="store_true",
                    help="run the radix prefix-cache arm: 80%%-shared-prefix "
                         "multi-tenant workload, cache-on vs cache-off at "
                         "equal pool budget, interleaved median-of "
                         "--prefix-repeats (acceptance: >= 2x admission "
                         "tokens/s + better TTFT p95); the block lands in "
                         "the --profile-out artifact (BENCH_serving.json)")
    ap.add_argument("--prefix-repeats", type=int, default=7)
    ap.add_argument("--chunked", action="store_true",
                    help="run the chunked-prefill interference arm: "
                         "running-slot inter-token p50/p95/max with a "
                         "window-length prompt admitted mid-stream, chunked "
                         "vs unchunked, interleaved median-of "
                         "--chunked-repeats; the block lands in the "
                         "--profile-out artifact (BENCH_serving.json)")
    ap.add_argument("--chunked-repeats", type=int, default=5)
    ap.add_argument("--ragged", action="store_true",
                    help="run the unified-ragged-tick arm: the fused "
                         "one-program tick on a mixed prefill+decode "
                         "workload (tokens/s, inter-token p95, programs per "
                         "tick), median-of --ragged-repeats, plus the int4-page "
                         "capacity section (sessions at fixed HBM vs "
                         "int8/fp, greedy agreement); the block lands in "
                         "the --profile-out artifact (BENCH_serving.json)")
    ap.add_argument("--ragged-repeats", type=int, default=7)
    ap.add_argument("--replicas", type=int, default=0,
                    help="run the replica-scaling arm: a burst workload through "
                         "a 1-replica vs N-replica ServingRouter (interleaved, "
                         "median-of --replica-repeats); the block lands in the "
                         "--profile-out artifact (BENCH_serving.json)")
    ap.add_argument("--replica-repeats", type=int, default=7)
    ap.add_argument("--proc", action="store_true",
                    help="run the replica-scaling arm's N-replica router with "
                         "OUT-OF-PROCESS workers (replica_mode='process', "
                         "serving/transport.py) against the in-process "
                         "1-replica baseline; the block lands under "
                         "replica_scaling_proc in the --profile-out artifact")
    ap.add_argument("--rolling-restart", action="store_true",
                    help="run the fleet-operations arm (docs/serving.md "
                         "'Fleet operations'): a streamed workload through a "
                         "--restart-replicas-replica router, steady-state vs "
                         "with a rolling restart triggered mid-stream — "
                         "running-session inter-token p50/p95 and the "
                         "during-restart p95 blip ratio, sessions lost "
                         "(acceptance: 0), plus a live-rollout pass with "
                         "per-version throughput; the block lands in the "
                         "--profile-out artifact (BENCH_serving.json)")
    ap.add_argument("--restart-replicas", type=int, default=2)
    args = ap.parse_args(argv)
    if args.replicas == 1:
        ap.error("--replicas needs N >= 2 (the arm compares N replicas against 1)")

    from perceiver_io_tpu.obs import write_run_manifest

    def paging_arm(model, config, params):
        block = run_paging_capacity(model, config, params, args.page_size,
                                    args.slots, args.seed, repeats=args.page_repeats)
        block["preset"] = args.preset
        return block

    def kv_quant_arm(model, config, params):
        block = run_kv_quant_capacity(model, config, params, args.kv_quant,
                                      args.slots, args.seed,
                                      repeats=args.kv_quant_repeats)
        block["preset"] = args.preset
        return block

    def priority_arm(model, config, params):
        block = run_priority_preemption(model, config, params, args.slots,
                                        args.seed, repeats=args.priority_repeats)
        block["preset"] = args.preset
        return block

    def journal_arm(model, config, params):
        block = run_journal_overhead(model, config, params, args.slots,
                                     args.seed, repeats=args.journal_repeats)
        block["preset"] = args.preset
        return block

    def prefix_cache_arm(model, config, params):
        block = run_prefix_cache(model, config, params, args.slots,
                                 args.seed, repeats=args.prefix_repeats)
        block["preset"] = args.preset
        return block

    def chunked_arm(model, config, params):
        block = run_chunked_interference(model, config, params, args.slots,
                                         args.seed, repeats=args.chunked_repeats)
        block["preset"] = args.preset
        return block

    def ragged_arm(model, config, params):
        block = run_ragged_tick_bench(model, config, params, args.slots,
                                      args.seed, repeats=args.ragged_repeats)
        block["preset"] = args.preset
        return block

    def merge_section(key, block, recorded_at):
        """Merge one bench section into the tracked BENCH_serving.json
        (other sections preserved) — the --replicas merge pattern."""
        existing = {}
        if os.path.exists(args.profile_out):
            try:
                with open(args.profile_out) as f:
                    existing = json.load(f)
            except (OSError, ValueError):
                existing = {}  # unreadable artifact: rebuild around the new arm
        existing[key] = block
        existing[f"{key}_recorded_at"] = recorded_at
        existing.setdefault("backend", jax.default_backend())
        tmp = args.profile_out + ".tmp"
        with open(tmp, "w") as f:
            json.dump(existing, f, indent=1)
            f.write("\n")
        os.replace(tmp, args.profile_out)
        manifest = write_run_manifest(args.profile_out, config=vars(args))
        print(f"merged {key} into {args.profile_out} (+ {manifest})", file=sys.stderr)

    def fleet_ops_arm(model, config, params):
        block = run_rolling_restart(model, config, params,
                                    args.restart_replicas, args.slots,
                                    args.seed)
        block["preset"] = args.preset
        return block

    def replica_arm(model, config, params):
        # burst workload ~6x one replica's capacity with UNIFORM generation
        # length: slots free in crisp waves, so the admission wall measures
        # exactly what extra replicas change (mixed lengths are the main
        # bench's job, not this arm's)
        workload = synth_workload(config, 6 * args.slots, args.seed)
        for r in workload:
            r["max_new_tokens"] = 24
        scaling = run_replica_scaling(
            model, params, workload, args.replicas, args.slots,
            repeats=args.replica_repeats,
            replica_mode="process" if args.proc else "inproc",
        )
        scaling["preset"] = args.preset  # the merged artifact may mix presets
        return scaling

    if args.profile:
        model, config = build_model(args.preset)
        # one init shared by the profile arms and the optional replica arm
        profile_params = jax.jit(model.init, static_argnames="prefix_len")(
            jax.random.PRNGKey(args.seed),
            jnp.zeros((1, config.max_seq_len), jnp.int32),
            prefix_len=model.max_prefix_len,
        )
        result = {
            "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "backend": jax.default_backend(),
            "preset": args.preset,
            **run_profile(model, config, args.slots, args.requests, args.seed,
                          params=profile_params),
        }
        if args.replicas >= 2:
            key = "replica_scaling_proc" if args.proc else "replica_scaling"
            result[key] = replica_arm(model, config, profile_params)
        if args.page_size > 0:
            result["paging"] = paging_arm(model, config, profile_params)
        if args.kv_quant > 0:
            result["kv_quant"] = kv_quant_arm(model, config, profile_params)
        if args.priority_arm:
            result["priority_preemption"] = priority_arm(model, config, profile_params)
        if args.journal:
            result["journal"] = journal_arm(model, config, profile_params)
        if args.prefix_cache:
            result["prefix_cache"] = prefix_cache_arm(model, config, profile_params)
        if args.chunked:
            result["chunked_prefill"] = chunked_arm(model, config, profile_params)
        if args.ragged:
            result["ragged_tick"] = ragged_arm(model, config, profile_params)
        if args.rolling_restart:
            result["fleet_ops"] = fleet_ops_arm(model, config, profile_params)
        tmp = args.profile_out + ".tmp"
        with open(tmp, "w") as f:
            json.dump(result, f, indent=1)
            f.write("\n")
        os.replace(tmp, args.profile_out)
        manifest = write_run_manifest(args.profile_out, config=vars(args))
        print(json.dumps(result))
        print(f"wrote {args.profile_out} (+ {manifest})", file=sys.stderr)
        return result

    model, config = build_model(args.preset)
    rng = jax.random.PRNGKey(args.seed)
    init_ids = jnp.zeros((1, config.max_seq_len), jnp.int32)
    params = jax.jit(model.init, static_argnames="prefix_len")(
        rng, init_ids, prefix_len=model.max_prefix_len
    )
    requests = synth_workload(config, args.requests, args.seed)

    engine_res = run_engine(model, params, requests, args.slots,
                            args.metrics_jsonl, warmup=not args.no_warmup,
                            trace_path=args.trace)
    result = {
        "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "backend": jax.default_backend(),
        "preset": args.preset,
        "workload": {
            "requests": len(requests),
            "slots": args.slots,
            "prompt_lens": [len(r["prompt"]) for r in requests],
            "max_new_tokens": [r["max_new_tokens"] for r in requests],
        },
        "engine": engine_res,
    }
    if not args.no_baseline:
        base_res = run_baseline(model, params, requests, warmup=not args.no_warmup)
        result["baseline_single_request"] = base_res
        if base_res["tokens_per_s"] > 0:
            result["engine_vs_baseline"] = round(
                engine_res["tokens_per_s"] / base_res["tokens_per_s"], 3
            )

    if args.replicas >= 2:
        scaling = replica_arm(model, config, params)
        # --proc lands under its own key so the in-process scaling numbers
        # and the process-isolation numbers are tracked side by side
        scaling_key = "replica_scaling_proc" if args.proc else "replica_scaling"
        result[scaling_key] = scaling
        # the replica-scaling arm is part of the per-PR BENCH_serving.json
        # story even without --profile: merge it into the existing artifact
        # (other sections preserved) so the tracked file carries both
        merge_section(scaling_key, scaling, result["recorded_at"])
    if args.page_size > 0:
        paging = paging_arm(model, config, params)
        result["paging"] = paging
        merge_section("paging", paging, result["recorded_at"])
    if args.kv_quant > 0:
        block = kv_quant_arm(model, config, params)
        result["kv_quant"] = block
        merge_section("kv_quant", block, result["recorded_at"])
    if args.priority_arm:
        priority = priority_arm(model, config, params)
        result["priority_preemption"] = priority
        merge_section("priority_preemption", priority, result["recorded_at"])
    if args.journal:
        journal = journal_arm(model, config, params)
        result["journal"] = journal
        merge_section("journal", journal, result["recorded_at"])
    if args.prefix_cache:
        block = prefix_cache_arm(model, config, params)
        result["prefix_cache"] = block
        merge_section("prefix_cache", block, result["recorded_at"])
    if args.chunked:
        block = chunked_arm(model, config, params)
        result["chunked_prefill"] = block
        merge_section("chunked_prefill", block, result["recorded_at"])
    if args.ragged:
        block = ragged_arm(model, config, params)
        result["ragged_tick"] = block
        merge_section("ragged_tick", block, result["recorded_at"])
    if args.rolling_restart:
        block = fleet_ops_arm(model, config, params)
        result["fleet_ops"] = block
        merge_section("fleet_ops", block, result["recorded_at"])

    tmp = args.out + ".tmp"  # atomic: a kill mid-write must not corrupt the artifact
    with open(tmp, "w") as f:
        json.dump(result, f, indent=1)
        f.write("\n")
    os.replace(tmp, args.out)
    manifest = write_run_manifest(args.out, config=vars(args))
    print(json.dumps(result))
    print(f"wrote {args.out} (+ {manifest})", file=sys.stderr)
    return result


if __name__ == "__main__":
    main()
