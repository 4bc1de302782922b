"""Fleet-operations tests (docs/serving.md "Fleet operations"): planned
cross-replica migration, rolling restart, live model-version rollout with
instant rollback, SLO-driven autoscaling, the drain×parked-continuation seam,
the mid-recycle breaker treatment, recovery dedup across the migration kill
window, the serving-metrics/v10 fleet gauges, and the
PERCEIVER_IO_TPU_DISABLE_FLEET_OPS kill-switch.

The identity bar is the failover contract's, re-pinned for PLANNED moves: a
migrated / restarted / rolled-back session's output is f64 token-identical
(greedy AND sampled — the rng chain re-advances through the forced replay) to
an undisturbed run, with zero new compiled decode programs and zero lost or
duplicated sessions.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from perceiver_io_tpu.models.core.config import CausalSequenceModelConfig
from perceiver_io_tpu.models.core.perceiver_ar import CausalSequenceModel
from perceiver_io_tpu.reliability import armed
from perceiver_io_tpu.reliability.faults import KilledMidWrite
from perceiver_io_tpu.serving import (
    RequestStatus,
    ServingEngine,
    ServingRouter,
    load_metrics_jsonl,
    read_journal,
)
from perceiver_io_tpu.serving.router import BREAKER_CLOSED, BREAKER_OPEN

VOCAB = 60
WINDOW = 12
LATENTS = 6


def _make_model(param_dtype=jnp.float64):
    config = CausalSequenceModelConfig(
        vocab_size=VOCAB, max_seq_len=WINDOW, max_latents=LATENTS,
        num_channels=16, num_heads=2, num_self_attention_layers=1,
        cross_attention_dropout=0.0,
    )
    model = CausalSequenceModel(config=config, param_dtype=param_dtype)
    rng = jax.random.PRNGKey(0)
    prompt = jax.random.randint(rng, (1, 8), 0, VOCAB)
    params = jax.jit(model.init, static_argnames="prefix_len")(rng, prompt, prefix_len=2)
    return model, params


def _variant_params(params, spike_token: int = 47):
    """A second param version with identical tree structure/shapes/dtypes but
    visibly different greedy behavior (an output-bias spike dominates the
    argmax) — version pins are then distinguishable from the tokens alone."""
    out = jax.tree_util.tree_map(lambda x: x, params)
    out["params"]["output_adapter"]["bias"] = (
        params["params"]["output_adapter"]["bias"].at[spike_token].add(100.0)
    )
    return out


def _reference(model, params, workload):
    """Undisturbed single-engine outputs for [(prompt, max_new, kwargs)]."""
    engine = ServingEngine(model, params, num_slots=max(len(workload), 1))
    handles = [engine.submit(p, max_new_tokens=m, **kw) for p, m, kw in workload]
    engine.run_until_drained(max_steps=500)
    assert all(h.ok for h in handles)
    return [h.result().tolist() for h in handles]


# ---------------------------------------------------------------- migration
def test_migrate_token_identity_greedy_and_sampled(x64):
    """Tentpole (a): a planned migration mid-decode lands the continuation on
    the destination f64 token-identical to an unmigrated run — greedy and
    sampled (rng chain included) — with zero new decode programs, zero
    failovers burned, and the v10 migration counters moving."""
    model, params = _make_model()
    workload = [
        ([1, 2, 3], 6, {}),
        ([4, 5], 6, dict(do_sample=True, temperature=0.9,
                         rng=jax.random.PRNGKey(7))),
    ]
    expected = _reference(model, params, workload)

    router = ServingRouter(model, params, num_replicas=2, num_slots=2)
    handles = [router.submit(p, max_new_tokens=m, **kw) for p, m, kw in workload]
    for _ in range(2):
        router.step()  # two tokens decoded: the moves are mid-request
    for h in handles:
        assert len(h.output_ids) == 2
        assert router.migrate(h.request_id, 1 - h.replica)
    router.run_until_drained(max_steps=300)
    for h, want in zip(handles, expected):
        assert h.ok and h.failovers == 0
        assert h.result().tolist() == want, "migration must be token-invisible"
    snap = router.snapshot()
    assert snap["schema"] == "serving-metrics/v13"
    assert snap["fleet_ops"]["migrations"] == 2
    assert snap["failovers"] == 0 and snap["breaker_transitions"] == {}
    for r in router.replicas:
        assert r.engine.decode_compilations <= 1  # replay compiled nothing new
    router.close()


def test_migrate_validation_refusal_and_repeat():
    """Malformed migrations raise; capacity refusals re-home the session
    without losing it; migrating to the current replica is a no-op."""
    model, params = _make_model(param_dtype=jnp.float32)
    router = ServingRouter(model, params, num_replicas=2, num_slots=1,
                           max_queue_depth=0)
    a = router.submit([1, 2, 3], max_new_tokens=6)
    b = router.submit([4, 5], max_new_tokens=6)
    router.step()  # one per replica
    with pytest.raises(ValueError, match="unknown replica"):
        router.migrate(a.request_id, 5)
    with pytest.raises(ValueError, match="unknown or terminal"):
        router.migrate(10_000, 0)
    assert router.migrate(a.request_id, a.replica) is True  # no-op
    # the destination's only slot is held by b and its queue bound is 0:
    # the migration refuses, and the session is re-homed (back on its own
    # replica — excluded only during drains, not targeted moves) or parked
    landed = router.migrate(a.request_id, b.replica)
    assert not a.done
    router.run_until_drained(max_steps=300)
    assert a.ok and len(a.output_ids) == 6
    assert b.ok and len(b.output_ids) == 6
    assert landed in (True, False)  # either way: nothing lost
    router.close()


def test_migrate_journal_exactly_once_before_and_after_close(x64, tmp_path):
    """Tentpole (a) durability: after a clean migration the origin journal's
    entry is CLOSED (recovery finds one session, on the destination); a kill
    inside the double-live window (destination accept durable, origin not yet
    closed — the ``router.migrate.kill`` point) recovers the session exactly
    ONCE via the session-id dedup, token-identically."""
    model, params = _make_model()
    expected = _reference(model, params, [([1, 2, 3], 6, {})])[0]
    template = str(tmp_path / "clean" / "r{i}")
    router = ServingRouter(model, params, num_replicas=2, num_slots=1,
                           journal=template)
    victim = router.submit([1, 2, 3], max_new_tokens=6)
    for _ in range(2):
        router.step()
    src = victim.replica
    assert router.migrate(victim.request_id, 1 - src)
    # origin closed, destination live — exactly one durable copy
    assert read_journal(template.format(i=src)).sessions == []
    assert len(read_journal(template.format(i=1 - src)).sessions) == 1
    router.run_until_drained(max_steps=300)
    assert victim.ok and victim.result().tolist() == expected
    router.close()

    # the kill window: both journals momentarily live -> dedup to one
    template = str(tmp_path / "kill" / "r{i}")
    router = ServingRouter(model, params, num_replicas=2, num_slots=1,
                           journal=template)
    victim = router.submit([1, 2, 3], max_new_tokens=6)
    for _ in range(2):
        router.step()
    src = victim.replica
    with armed("router.migrate.kill", times=1):
        with pytest.raises(KilledMidWrite):
            router.migrate(victim.request_id, 1 - src)
    assert [len(read_journal(template.format(i=i)).sessions)
            for i in range(2)] == [1, 1]
    # process death NOW: the router object is abandoned; recover dedupes
    router2, info = ServingRouter.recover(model, params, template,
                                          num_replicas=2, num_slots=1)
    assert info["sessions"] == 1 and info["deduped"] == 1
    router2.run_until_drained(max_steps=300)
    h = info["handles"][0]
    assert h.ok and h.result().tolist() == expected
    assert all(r.engine.decode_compilations <= 1 for r in router2.replicas)
    router2.close()


# ---------------------------------------------------------- rolling restart
def test_rolling_restart_under_load_token_identity(x64, tmp_path):
    """Tentpole (b): a rolling restart under sustained load recycles every
    replica (fresh engine objects, journal generation advanced) with zero
    lost or duplicated sessions, zero breaker transitions, and every output
    f64 token-identical to an undisturbed run."""
    model, params = _make_model()
    prompts = [[1, 2, 3], [4, 5], [6, 7, 8], [9, 10], [11, 12, 13], [14, 15]]
    workload = [(p, 8, {}) for p in prompts]
    expected = _reference(model, params, workload)

    template = str(tmp_path / "r{i}")
    router = ServingRouter(model, params, num_replicas=2, num_slots=2,
                           journal=template)
    handles = [router.submit(p, max_new_tokens=8) for p in prompts[:3]]
    for _ in range(2):
        router.step()
    assert router.begin_rolling_restart()
    engines_before = [id(r.engine) for r in router.replicas]
    i, steps = 3, 0
    while router.restart_in_progress:
        if i < len(prompts):  # sustained load DURING the restart
            handles.append(router.submit(prompts[i], max_new_tokens=8))
            i += 1
        router.step()
        steps += 1
        assert steps < 200, "restart must complete"
    assert all(a != b for a, b in zip(engines_before,
                                      (id(r.engine) for r in router.replicas)))
    while i < len(prompts):
        handles.append(router.submit(prompts[i], max_new_tokens=8))
        i += 1
    router.run_until_drained(max_steps=500)
    assert [h.result().tolist() for h in handles] == expected
    assert all(h.ok for h in handles)
    snap = router.snapshot()
    assert snap["fleet_ops"]["recycles"] == 2
    assert snap["breaker_transitions"] == {}  # a planned recycle never strikes
    assert (snap["requests_submitted"]
            == snap["requests_finished"] == len(prompts))
    # every journal holds nothing live and advanced a generation (recycle
    # recovery swapped it)
    for ridx in range(2):
        state = read_journal(template.format(i=ridx))
        assert state.sessions == [] and state.generation >= 2
    router.close()


def test_mid_recycle_replica_treated_as_open_no_strike_cascade(x64):
    """Satellite: a mid-recycle replica reads like an OPEN one — no dispatch,
    no ticks — and the rebuilt engine's compile ticks never strike the stall
    detector (the recycle resets the compile-tick baseline), so a rolling
    restart under a tight slow-tick threshold trips NO breaker, its own or a
    sibling's."""
    model, params = _make_model(param_dtype=jnp.float32)
    router = ServingRouter(
        model, params, num_replicas=2, num_slots=1,
        # tight threshold: any un-exempted compile tick would strike
        slow_tick_threshold_s=0.2, slow_ticks_to_open=1,
    )
    warm = [router.submit([1, 2], max_new_tokens=1) for _ in range(2)]
    router.run_until_drained(max_steps=30)
    assert all(h.ok for h in warm)
    handles = [router.submit([i + 1, i + 2], max_new_tokens=6)
               for i in range(2)]
    router.step()
    assert router.begin_rolling_restart()
    saw_recycling = False
    steps = 0
    while router.restart_in_progress:
        for r in router.replicas:
            if r.recycling:
                saw_recycling = True
                # treated as OPEN: holds no sessions, not a dispatch target
                assert not r.assigned, "recycling replica must hold no sessions"
                assert r not in router._serving_replicas(), \
                    "recycling replica must receive no work"
        router.step()
        steps += 1
        assert steps < 100
    assert saw_recycling
    router.run_until_drained(max_steps=300)
    assert all(h.ok for h in handles)
    # the rebuilt engines re-compiled from scratch; none of those slow ticks
    # may have struck the detector or opened a breaker
    snap = router.snapshot()
    assert snap["breaker_transitions"] == {}
    assert all(r.breaker == BREAKER_CLOSED and r.consecutive_slow == 0
               for r in router.replicas)
    router.close()


# ------------------------------------------------------- drain parked seam
def test_router_drain_finishes_parked_continuations(x64):
    """Satellite (the drain × parked-work seam): a failover continuation
    PARKED at the router (survivor's queue at its bound) is accepted
    mid-generation work — ``drain()`` finishes it token-identically instead
    of rejecting it with the never-accepted backlog, landing it on the
    draining sibling as a resume."""
    model, params = _make_model()
    expected = _reference(model, params, [([1, 2, 3], 6, {})])[0]
    router = ServingRouter(model, params, num_replicas=2, num_slots=1,
                           max_queue_depth=0, breaker_cooldown_ticks=64)
    a = router.submit([1, 2, 3], max_new_tokens=6)
    b = router.submit([4, 5], max_new_tokens=8)
    router.step()  # both running, one per replica
    with armed("replica.crash", slot=a.replica, times=1):
        router.step()  # crash -> failover; survivor at bound 0 -> a PARKS
    assert not a.done and a.status is RequestStatus.QUEUED
    drained = router.drain(max_steps=300)
    assert a.ok and a.result().tolist() == expected, \
        "drain must FINISH a parked continuation, not reject it"
    assert b.ok and len(b.output_ids) == 8
    assert {h.request_id for h in drained} == {a.request_id, b.request_id}
    # fresh parked submits still reject: the backlog contract is unchanged
    post = router.submit([9, 9], max_new_tokens=2)
    assert post.finish_reason == "draining"
    router.close()


# ------------------------------------------------------------------ rollout
def test_deploy_rollout_pins_rollback_and_metrics(x64, tmp_path):
    """Tentpole (c): deploy splits new admissions deterministically by
    fraction, each session decodes ENTIRELY under its pinned version (f64
    pinned against per-version references), per-version outcomes ride the
    v10 rollout table, rollback re-pins new admissions instantly, and the
    flipped replica returns to the base version once empty."""
    model, params1 = _make_model()
    params2 = _variant_params(params1)
    p = [1, 2, 3]
    r1 = _reference(model, params1, [(p, 5, {})])[0]
    r2 = _reference(model, params2, [(p, 5, {})])[0]
    assert r1 != r2  # versions must be distinguishable from tokens

    log = tmp_path / "router.jsonl"
    router = ServingRouter(model, params1, num_replicas=2, num_slots=2,
                           metrics_jsonl=str(log))
    v2 = router.deploy(params2, fraction=0.5)
    assert v2 == 1
    router.step()  # the targeted (empty) replica flips now
    assert sorted(r.version for r in router.replicas) == [0, 1]
    # fraction 0.5 -> admissions alternate base, v2 (floor-diff split)
    a = router.submit(p, max_new_tokens=5)
    b = router.submit(p, max_new_tokens=5)
    assert (a.version, b.version) == (0, 1)
    router.run_until_drained(max_steps=200)
    assert a.result().tolist() == r1, "pinned-to-base session must decode under v0"
    assert b.result().tolist() == r2, "pinned-to-v2 session must decode under v2"
    snap = router.snapshot()
    rollout = snap["fleet_ops"]["rollout"]
    assert rollout["rollout_version"] == 1 and rollout["fraction"] == 0.5
    assert rollout["versions"]["0"]["finished"] == 1
    assert rollout["versions"]["1"]["finished"] == 1
    assert rollout["versions"]["1"]["tokens_generated"] == 5

    # rollback: instant for new admissions; the flipped replica flips back
    assert router.rollback()
    c = router.submit(p, max_new_tokens=5)
    assert c.version == 0
    router.run_until_drained(max_steps=200)
    assert c.result().tolist() == r1
    for _ in range(3):
        router.step()
    assert all(r.version == 0 and r.target_version == 0
               for r in router.replicas)
    router.write_snapshot()
    router.close()
    events = {e["event"] for e in load_metrics_jsonl(str(log))["events"]}
    assert {"deploy", "rollback", "submit", "finish", "snapshot"} <= events


def test_version_flip_invalidates_prefix_cache(x64):
    """Code-review fix: a version flip (``set_params``) clears the radix
    prefix cache — its pages hold KV computed under the OLD weights and the
    keys are token content only, so a new-version prompt sharing a cached
    prefix would otherwise decode against stale KV."""
    model, params1 = _make_model()
    params2 = _variant_params(params1)
    # page-aligned shared preamble (latent boundary LATENTS): first pages
    # below it are cacheable
    preamble = [7] * 9
    p_a, p_b = preamble + [1], preamble + [2]
    # prompt (10) + budget (2) fits the 12-token window: the ring never
    # wraps, so the shared preamble's full pages are cacheable
    want_b_v2 = _reference(model, params2, [(p_b, 2, {})])[0]

    engine = ServingEngine(model, params1, num_slots=2, kv_page_size=2,
                           prefix_cache=True)
    donor = engine.submit(p_a, max_new_tokens=2)
    engine.run_until_drained(max_steps=200)  # cache warmed under v0 weights
    assert donor.ok and engine._prefix_cache.stats()["cached_pages"] > 0
    engine.set_params(params2)
    assert engine._prefix_cache.stats()["cached_pages"] == 0, \
        "a version flip must start the prefix cache cold"
    h = engine.submit(p_b, max_new_tokens=2)
    engine.run_until_drained(max_steps=200)
    assert h.ok and h.result().tolist() == want_b_v2, \
        "post-flip decode must not reuse pre-flip KV pages"
    engine.close()


def test_full_rollout_promotes_primary(x64):
    """Code-review fix: a fraction-1.0 deploy PROMOTES once every active
    replica has flipped — the rollout version becomes primary, so later
    scale-ups build it and rollback (nothing left to roll back) refuses."""
    model, params1 = _make_model()
    params2 = _variant_params(params1)
    router = ServingRouter(model, params1, num_replicas=2, num_slots=1)
    v2 = router.deploy(params2, fraction=1.0)
    for _ in range(3):
        router.step()  # both (empty) replicas flip, then promotion lands
    assert all(r.version == v2 for r in router.replicas)
    assert router._primary_version == v2
    assert router.rollback() is False  # promoted: no rollout left
    h = router.submit([1, 2, 3], max_new_tokens=4)
    assert h.version == v2  # new admissions pin the promoted version
    router.run_until_drained(max_steps=200)
    assert h.ok
    assert h.result().tolist() == _reference(model, params2,
                                             [([1, 2, 3], 4, {})])[0]
    router.close()


def test_migrate_respects_version_pin(x64):
    """Tentpole (c): migration refuses a destination serving a different
    version than the session's pin — a continuation is never re-decoded
    under weights that did not produce its prefix."""
    model, params1 = _make_model()
    params2 = _variant_params(params1)
    router = ServingRouter(model, params1, num_replicas=2, num_slots=2)
    router.deploy(params2, fraction=0.5)
    router.step()  # r1 flips to v1
    a = router.submit([1, 2, 3], max_new_tokens=6)  # pinned v0 -> r0
    router.step()
    assert a.version == 0 and router.replicas[a.replica].version == 0
    other = next(r.rid for r in router.replicas if r.version == 1)
    with pytest.raises(ValueError, match="version pin"):
        router.migrate(a.request_id, other)
    router.run_until_drained(max_steps=200)
    assert a.ok
    router.close()


# ---------------------------------------------------------------- autoscale
def test_autoscale_up_down_zero_lost(x64):
    """Tentpole (d): the tick-counted controller grows the fleet under a
    sustained queue and shrinks it back through the migrate-and-drain path
    when idle — every session finishes token-identically, none lost, and the
    v10 autoscale counters record the decisions."""
    model, params = _make_model()
    prompts = [[i + 1, i + 2] for i in range(8)]
    expected = _reference(model, params, [(p, 6, {}) for p in prompts])
    router = ServingRouter(
        model, params, num_replicas=1, num_slots=1,
        autoscale=dict(min_replicas=1, max_replicas=3, scale_up_load=2,
                       scale_down_load=0, every_ticks=2, patience=1),
    )
    handles = [router.submit(p, max_new_tokens=6) for p in prompts]
    seen_active = set()
    while router.step():
        seen_active.add(len([r for r in router.replicas
                             if not r.retired and not r.recycling]))
    assert max(seen_active) > 1, "the backlog must have scaled the fleet up"
    for _ in range(30):
        router.step()  # idle ticks: scale back down to min
    snap = router.snapshot()
    fo = snap["fleet_ops"]
    assert all(h.ok for h in handles)
    assert [h.result().tolist() for h in handles] == expected
    assert fo["scale_ups"] >= 1 and fo["scale_downs"] >= 1
    assert fo["replicas_active"] == 1
    accounted = (snap["requests_submitted"]
                 == snap["requests_finished"] + snap["rejected"]
                 + snap["timed_out"] + snap["failed"])
    assert accounted, "autoscaling must not lose or duplicate sessions"
    router.close()


def test_autoscale_knob_validation():
    model, params = _make_model(param_dtype=jnp.float32)
    with pytest.raises(ValueError, match="min_replicas"):
        ServingRouter(model, params, num_replicas=1,
                      autoscale=dict(min_replicas=2, max_replicas=4))
    with pytest.raises(ValueError, match="unknown autoscale"):
        ServingRouter(model, params, num_replicas=1,
                      autoscale=dict(max_replicas=2, bogus=1))
    with pytest.raises(ValueError, match="template"):
        ServingRouter(model, params, num_replicas=1, journal="/tmp/flat-j",
                      autoscale=dict(max_replicas=2))


# -------------------------------------------------------------- kill-switch
def test_fleet_ops_killswitch_inert(x64, tmp_path, monkeypatch):
    """PERCEIVER_IO_TPU_DISABLE_FLEET_OPS=1: every lifecycle API refuses
    without raising, no autoscaler runs, journal accepts carry no session
    ids, and the workload behaves exactly like the pre-fleet router."""
    from perceiver_io_tpu.serving.router import fleet_ops_enabled

    monkeypatch.setenv("PERCEIVER_IO_TPU_DISABLE_FLEET_OPS", "1")
    assert not fleet_ops_enabled()
    model, params = _make_model()
    expected = _reference(model, params, [([1, 2, 3], 5, {})])[0]
    template = str(tmp_path / "r{i}")
    router = ServingRouter(model, params, num_replicas=2, num_slots=1,
                           journal=template,
                           autoscale=dict(max_replicas=4))  # silently inert
    h = router.submit([1, 2, 3], max_new_tokens=5)
    router.step()
    assert router.migrate(h.request_id, 1 - h.replica) is False
    assert router.begin_rolling_restart() is False
    assert router.deploy(params, fraction=1.0) is None
    assert router.rollback() is False
    router.run_until_drained(max_steps=200)
    assert h.ok and h.result().tolist() == expected
    assert h.session_id is None
    # the journal's accept record carries no session field (byte-compatible
    # with the pre-fleet writer)
    state = read_journal(template.format(i=h.replica))
    assert state.sessions == []  # finished: entry closed
    snap = router.snapshot()
    assert snap["fleet_ops"]["migrations"] == 0
    assert snap["fleet_ops"]["recycles"] == 0
    router.close()


# -------------------------------------------------------------------- bench
@pytest.mark.slow  # three routers' worth of compiles + three streamed drains
def test_serve_bench_rolling_restart_smoke(tmp_path):
    """--rolling-restart merges the fleet-ops arm (inter-token blip during a
    restart vs steady state, sessions lost = 0, per-version rollout
    throughput) into BENCH_serving.json with a manifest sibling."""
    import importlib.util
    import json

    spec = importlib.util.spec_from_file_location(
        "serve_bench_fleet_ops_under_test",
        os.path.join(os.path.dirname(__file__), "..", "scripts", "serve_bench.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    out = tmp_path / "SERVE_BENCH.json"
    pout = tmp_path / "BENCH_serving.json"
    result = mod.main([
        "--preset", "tiny", "--slots", "1", "--requests", "4",
        "--rolling-restart", "--restart-replicas", "2",
        "--no-baseline", "--no-warmup",
        "--out", str(out), "--profile-out", str(pout),
    ])
    fo = result["fleet_ops"]
    assert fo["sessions_lost_total"] == 0
    assert fo["recycles"] == 2
    assert fo["steady_inter_token"]["n"] > 0
    assert fo["breaker_transitions_during_restart"] == {}
    versions = fo["rollout"]["per_version"]
    assert set(versions) == {"0", "1"}
    assert all(v["finished"] == v["submitted"] for v in versions.values())
    on_disk = json.loads(pout.read_text())
    assert on_disk["fleet_ops"]["slots_per_replica"] == 1
    manifest = json.loads((tmp_path / "BENCH_serving.manifest.json").read_text())
    assert manifest["schema"] == "run-manifest/v1"


# ------------------------------------------------------------------ metrics
def test_fleet_ops_metrics_v10_jsonl_and_reader(tmp_path):
    """RouterMetrics v10: migrate/recycle/deploy/rollback/autoscale events
    land in the stream, the snapshot carries the fleet_ops block, engine
    snapshots truthfully report fleet_ops: None, and the reader normalizes
    pre-v10 snapshots with None."""
    import json

    from perceiver_io_tpu.serving import EngineMetrics, RouterMetrics

    path = tmp_path / "router.jsonl"
    rm = RouterMetrics(num_replicas=2, jsonl_path=str(path))
    rm.record_submit(0, prompt_len=3, version=0)
    rm.record_migration(0, src=0, dst=1, emitted_tokens=2)
    rm.record_recycle(0, sessions_moved=1, leftover_sessions=0, tick=7)
    rm.record_deploy(1, fraction=0.25, target_replicas=[1])
    rm.record_autoscale("up", 2, active=3, load=5, tick=8)
    rm.record_rollback(1, 0)
    rm.record_finish(0, "finished", "length", new_tokens=6, failovers=0,
                     version=0)
    rm.write_snapshot({"r0": EngineMetrics(num_slots=2).snapshot()})
    rm.close()

    got = load_metrics_jsonl(str(path))
    events = {e["event"] for e in got["events"]}
    assert {"migrate", "recycle", "deploy", "autoscale", "rollback",
            "snapshot"} <= events
    snap = got["snapshots"][0]
    assert snap["schema"] == "serving-metrics/v13"
    fo = snap["fleet_ops"]
    assert fo["migrations"] == 1 and fo["recycles"] == 1
    assert fo["scale_ups"] == 1 and fo["scale_downs"] == 0
    assert fo["rollout"]["rollout_version"] == 1
    assert fo["rollout"]["versions"]["0"]["finished"] == 1
    # engines truthfully have no fleet lifecycle of their own
    assert snap["replicas"]["r0"]["fleet_ops"] is None

    # a pre-v10 snapshot normalizes to fleet_ops: None
    old = tmp_path / "old.jsonl"
    old.write_text(json.dumps({
        "event": "snapshot", "schema": "serving-metrics/v9",
        "requests_submitted": 1,
    }) + "\n")
    assert load_metrics_jsonl(str(old))["snapshots"][0]["fleet_ops"] is None
