"""Fault-tolerant multi-replica serving: a health-checked front-end router.

A single ``ServingEngine`` is a single failure domain: one crashed, stalled,
or NaN-poisoned engine takes every queued and running session with it.
Production TPU serving runs MANY engine replicas behind a front end (cf. the
Gemma-on-TPU serving comparison in PAPERS.md); ``ServingRouter`` is that
layer, built entirely from primitives the stack already proves out —
deterministic fault points (reliability/faults.py), bounded deterministic
backoff (reliability/retry.py), per-request deadlines and windowed p95
latency metrics (serving/metrics.py), and per-replica telemetry namespaces
(obs/). See docs/serving.md ("Multi-replica router") and
docs/reliability.md for the full contracts.

Design:

  * **Same surface as the engine.** ``submit()`` returns a handle
    immediately, ``step()`` runs one router tick, ``run_until_drained()`` /
    ``drain()`` close the loop — a caller written against ``ServingEngine``
    moves to N replicas by swapping the constructor.
  * **Dispatch by live load.** A new request goes to the least-loaded replica
    whose circuit breaker is CLOSED — load is ``SlotScheduler.load``
    (queue depth beyond free capacity, the same number the engine's own
    queue bound ranks on), ties break on the lowest replica index, so
    placement is deterministic given the submit/tick interleaving.
  * **Per-replica health + circuit breaker.** Health is tracked from tick
    heartbeats (a replica's tick ran this round), consecutive tick
    exceptions, slow-tick strikes (measured tick duration beyond
    ``slow_tick_threshold_s`` — the wedged-engine detector), and the
    NaN-containment count harvested from the replica's own metrics. A
    breaker runs CLOSED -> OPEN -> HALF_OPEN: OPEN replicas are not ticked
    and receive no work for a cooldown counted in ROUTER TICKS — the
    bounded-exponential schedule of ``reliability/retry.py`` with jitter 0,
    so like the fault registry there are no clocks and no randomness in the
    decision; then HALF_OPEN admits exactly one probe tick, closing on
    success (stale slots reclaimed first) and re-opening with a doubled
    cooldown on failure.
  * **Deterministic failover.** When a replica is lost, each of its queued
    and running requests is re-dispatched to a healthy replica as
    ``prompt + already-emitted tokens``: the new engine prefills the prompt
    exactly as the lost one did (same covering bucket — the parity-pinned
    admission path), then REPLAYS the emitted tokens through its compiled
    decode step as forced tokens, reconstructing the lost engine's decode
    trajectory — ring rotation, logits, and rng chain included — step for
    step. The continuation is therefore token-identical to the
    uninterrupted run (pinned in float64; even sampled requests continue
    identically, because the per-slot key chain re-advances through the
    replay). A naive re-prefill of prompt+tokens would NOT be equivalent:
    Perceiver AR's latent/prefix split at a position depends on how the
    state was built, not just which tokens are live. Each request survives
    at most ``max_failovers`` re-dispatches before terminating FAILED with
    its partial output preserved, the way TIMED_OUT eviction already
    preserves it.
  * **SLO-aware shedding.** A deadlined request is REJECTED at admission
    (``shed_infeasible``) when the windowed p95 queue-wait + prefill +
    ``max_new_tokens`` x p95 decode-step estimate — PR 2's metrics — says
    the deadline cannot be met on ANY healthy replica: under overload the
    router degrades by refusing doomed work instead of queueing it. Cold
    replicas (fewer than ``shed_min_samples`` decode steps) never shed.
  * **No request is silently lost.** Every submitted handle reaches an
    explicit terminal status — FINISHED, REJECTED (queue/shed/drain),
    TIMED_OUT, or FAILED (containment, ``max_failovers``) — while any
    replica still serves; ``drain()`` and the SIGTERM/SIGINT graceful path
    resolve the backlog explicitly. The one deliberate wait: a request with
    NO deadline parked during a FULL fleet outage stays QUEUED until a
    replica recovers or ``drain()`` rejects it — give requests deadlines (or
    set ``max_queue_depth``) when unbounded waiting is unacceptable, and
    pass ``max_steps`` to the drain loops as the last-resort guard.

Fleet operations (docs/serving.md "Fleet operations"): the zero-downtime
lifecycle layer composed from the reliability primitives above —

  * **Planned migration** (``migrate(request_id, dst)``): the session is
    evicted from its LIVE origin through the engine's own release path (the
    preemption device-side, no crash required), its emitted prefix salvaged,
    and the continuation lands on the destination via the same forced-replay
    submit failover uses — f64 token-identical to an unmigrated run, zero
    new compiled programs, and the failover budget untouched. Journal
    entries close/open exactly-once through the ``_journal_note_moved``
    seam: the origin's entry stays LIVE until the destination's fsynced
    accept is durable, and recovery dedupes the one double-live window
    (between that accept and the origin's close record) by the fleet-unique
    session id every accept now carries.
  * **Rolling restart** (``begin_rolling_restart``/``rolling_restart``):
    tick-driven, one replica at a time — sessions migrate to siblings (or
    park, staying durable via their origin journal), the replica recycles
    (engine torn down; journal-recovered on a fresh engine, which re-adopts
    any still-parked session of its own journal), health state resets, and
    the replica re-admits. A mid-recycle replica is treated like an OPEN
    one everywhere (no dispatch, no ticks, no heartbeat strikes), so a
    restart never trips its own or a sibling's breaker.
  * **Live model-version rollout** (``deploy(params, fraction)`` /
    ``rollback()``): the router holds N param versions; every session pins
    ONE version for its lifetime at submit (a deterministic counter splits
    admissions by ``fraction``), dispatch and migration only land a session
    on a replica serving its pin, and replicas flip versions
    (``engine.set_params`` — zero recompiles) only when empty. ``rollback``
    is instant for new admissions; in-flight sessions finish on their pin.
    Per-version outcomes ride the v10 ``fleet_ops.rollout`` table.
  * **SLO-driven autoscaling** (``autoscale=dict(...)``): a deterministic
    tick-counted controller scales the active replica count between
    min/max from the fleet-load signal (router-parked depth + per-replica
    queue-beyond-capacity) — scale-up revives or appends a replica,
    scale-down retires the highest-index one through the same
    migrate-and-drain path a recycle uses.

Kill-switch: ``PERCEIVER_IO_TPU_DISABLE_FLEET_OPS=1`` makes the whole layer
inert — ``migrate``/``deploy``/``rollback``/``begin_rolling_restart``
refuse (returning False/None, never raising: a rollback lever must not
crash the fleet it rolls back), the autoscaler is never constructed, and
accept records carry no session ids — behavior identical to the pre-fleet
router (pinned).

Observability: the router resolves ONE recorder and shares it with every
replica engine under per-replica span namespaces (``serving.r0.tick`` ...)
and the engines' collision-safe per-engine request categories, plus its own
``router.*`` spans/counters — ``scripts/obs_report.py`` renders per-replica
phase tables from the single trace. Metrics are ``serving-metrics/v10``:
router snapshots embed per-replica engine snapshots, the
failover/shed/breaker counters, the aggregated preemption counters
(request ``priority`` is forwarded to engines; engine-local preemption under
page-pool pressure is docs/serving.md's "Priority classes & preemption"),
and the ``fleet_ops`` migration/recycle/rollout/autoscale gauges.
"""

from __future__ import annotations

import itertools
import math
import os
import random
import time
import uuid
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Sequence

import jax
import numpy as np

from perceiver_io_tpu.generation.generate import GenerationConfig
from perceiver_io_tpu.obs.core import resolve_recorder
from perceiver_io_tpu.reliability import faults
from perceiver_io_tpu.reliability.preemption import (
    install_preemption_handler,
    restore_preemption_handler,
)
from perceiver_io_tpu.reliability.retry import RetryPolicy
from perceiver_io_tpu.serving.engine import (
    RequestStatus,
    ServedRequest,
    ServingEngine,
    _engine_compatible,
    _journal_config_payload,
)
from perceiver_io_tpu.serving.journal import (
    JournalSession,
    RequestJournal,
    journal_enabled,
    read_journal,
)
from perceiver_io_tpu.serving.metrics import RouterMetrics
from perceiver_io_tpu.serving.quant import tree_layout_mismatch
from perceiver_io_tpu.serving.transport import (
    EngineClient,
    WorkerDiedError,
    proc_replicas_enabled,
)

# breaker states (str values land in metrics transition keys and trace events)
BREAKER_CLOSED = "closed"
BREAKER_OPEN = "open"
BREAKER_HALF_OPEN = "half_open"

FLEET_OPS_ENV = "PERCEIVER_IO_TPU_DISABLE_FLEET_OPS"


def fleet_ops_enabled() -> bool:
    """Kill-switch for the fleet-operations layer (module docstring):
    ``PERCEIVER_IO_TPU_DISABLE_FLEET_OPS=1`` makes migration, rolling
    restart, versioned rollout, and autoscaling inert — the lifecycle APIs
    refuse without raising, no autoscaler runs, and journal accept records
    carry no session ids, so behavior is identical to the pre-fleet router.
    Checked at router construction, like the engine's feature switches."""
    return os.environ.get(FLEET_OPS_ENV, "0").lower() in ("0", "false", "")


@dataclass
class RoutedRequest:
    """Router-level handle returned by ``ServingRouter.submit``.

    Mirrors the ``ServedRequest`` surface (``status``/``ok``/``done``/
    ``finish_reason``/``result()``) but survives the engine that currently
    runs it: tokens emitted before a replica was lost are kept in
    ``_salvaged`` and the continuation decodes on another replica, so
    ``result()`` is always the full stream and ``output_ids`` never moves
    backwards while the replacement engine replays the prefix."""

    request_id: int
    prompt_ids: np.ndarray
    config: GenerationConfig
    rng: object
    # priority class, forwarded verbatim to whichever engine serves the
    # request — failover re-dispatch keeps it, so a continuation competes at
    # its original class on the new replica (docs/serving.md)
    priority: int = 0
    finish_reason: Optional[str] = None
    submitted_at: float = 0.0
    finished_at: Optional[float] = None
    deadline_s: Optional[float] = None
    failovers: int = 0  # re-dispatches survived so far
    replica: Optional[int] = None  # current replica index (None = unplaced)
    # param-version pin (docs/serving.md "Fleet operations"): chosen once at
    # submit, respected by every dispatch and migration for the session's
    # whole lifetime — a continuation never lands on a replica serving a
    # different version than the one that decoded its prefix
    version: int = 0
    # fleet-unique session identity, stamped on every journal accept this
    # session produces (origin and continuation alike): the recovery dedup
    # key for the migration double-live window. None with fleet ops disabled.
    session_id: Optional[str] = None
    # True once ANY engine accepted this request: accepted work is never
    # drain-rejected while parked and re-enters engines as resume submits
    _accepted: bool = field(default=False, repr=False)
    # pending close bookkeeping for _journal_note_moved: a planned migration
    # closes its origin entry as "moved"/"migrated" instead of the failover
    # default, so journal forensics can tell the two apart
    _move_note: Optional[tuple] = field(default=None, repr=False)
    # longest token prefix salvaged from any lost replica; the live engine
    # handle overtakes it as its forced replay catches up
    _salvaged: List[int] = field(default_factory=list, repr=False)
    _engine_handle: Optional[ServedRequest] = field(default=None, repr=False)
    # set once by the router's _resolve; None while the request is live
    _terminal_status: Optional[RequestStatus] = field(default=None, repr=False)
    # (replica index, engine request id) whose JOURNAL still holds this
    # session live after a failover: the continuation's durability anchor
    # while it is in flight between replicas. Closed (a terminal record
    # appended to the origin journal) exactly when the continuation becomes
    # durable elsewhere — a successful re-dispatch journals a fresh accept —
    # or resolves terminally while parked. Without this, a process death
    # mid-failover would either replay the session TWICE (old accept + new
    # accept both live) or lose a parked continuation whose origin entry was
    # closed too early (serving/journal.py; docs/serving.md).
    _journal_origin: Optional[tuple] = field(default=None, repr=False)
    # True while the ROUTER's accept journal holds this request live: a fresh
    # submit parked during a full-fleet outage is journaled at the router
    # level (the previously documented memory-only durability hole), and the
    # entry closes when the request either lands on an engine (whose own
    # accept record takes over as the durable anchor) or resolves terminally
    # while parked (docs/serving.md "Out-of-process replicas").
    _router_journaled: bool = field(default=False, repr=False)

    @property
    def status(self) -> RequestStatus:
        """Mirrors the engine handle's surface: QUEUED (router-parked or
        engine-queued), RUNNING (holding a slot somewhere), or the terminal
        status the router resolved. An engine-terminal-but-unharvested handle
        reads RUNNING for the within-tick instant before the router resolves
        it — ``done`` flips only through the router's own bookkeeping."""
        if self._terminal_status is not None:
            return self._terminal_status
        handle = self._engine_handle
        if handle is not None:
            if handle.status in (RequestStatus.QUEUED, RequestStatus.RUNNING,
                                 RequestStatus.PREEMPTED):
                return handle.status
            return RequestStatus.RUNNING
        return RequestStatus.QUEUED

    @property
    def done(self) -> bool:
        return self._terminal_status is not None

    @property
    def ok(self) -> bool:
        return self.status is RequestStatus.FINISHED

    @property
    def output_ids(self) -> List[int]:
        """All tokens emitted so far — MONOTONIC across failover. During a
        replay the new engine re-emits the salvaged prefix token by token;
        until its stream overtakes the salvage, the salvage is the answer
        (the replayed prefix is identical by construction), so a streaming
        consumer forwarding ``out[len(sent):]`` never sees a negative
        delta."""
        engine_out = self._engine_handle.output_ids if self._engine_handle else []
        if len(engine_out) >= len(self._salvaged):
            return list(engine_out)
        return list(self._salvaged)

    @property
    def admitted_at(self) -> Optional[float]:
        """``time.perf_counter()`` instant this request last reached a slot
        (None while queued/parked) — time-to-admission is the burst-capacity
        SLO the replica-scaling bench measures."""
        if self._engine_handle is None:
            return None
        return self._engine_handle.admitted_at

    @property
    def deadline_at(self) -> Optional[float]:
        if self.deadline_s is None:
            return None
        return self.submitted_at + self.deadline_s

    def result(self) -> np.ndarray:
        """Generated tokens (prompt excluded) across every replica that served
        this request. Partial for TIMED_OUT/FAILED — check ``ok``."""
        return np.asarray(self.output_ids, np.int32)


@dataclass
class _Replica:
    """One engine replica's router-side health record."""

    rid: int
    engine: ServingEngine
    breaker: str = BREAKER_CLOSED
    opened_at_tick: int = 0
    open_count: int = 0  # consecutive opens; indexes the backoff ladder
    cooldown_ticks: int = 0
    consecutive_failures: int = 0  # tick exceptions since last healthy tick
    consecutive_slow: int = 0  # slow-tick strikes since last fast tick
    nan_failures: int = 0  # cumulative nonfinite containments harvested
    last_tick: int = -1  # heartbeat: router tick of the last completed tick
    last_error: Optional[str] = None
    # engine request_id -> routed request, for every live hand-off
    assigned: Dict[int, RoutedRequest] = field(default_factory=dict)
    # engine request id -> routed request, for hand-offs failed over but not
    # yet reclaimed from the engine (the router never touches a DOWN engine;
    # reclaim happens at recovery). The routed request rides along so the
    # reclaim can tell a MOVED session (journal its terminal) from one still
    # anchored to this replica's journal (keep it live — see _journal_origin)
    orphaned: Dict[int, RoutedRequest] = field(default_factory=dict)
    # THIS replica's own dispatch+harvest time in the current tick — the
    # slow-tick detector's input. Never measured across siblings: one wedged
    # replica must not inflate a healthy neighbor's reading
    _own_tick_s: float = 0.0
    # engine program count at the last healthy tick: a tick that compiled
    # something is legitimately slow and must not strike the stall detector
    _programs_seen: int = 0
    # fleet-operations state (docs/serving.md "Fleet operations"):
    # the param version this replica's engine currently serves, and the
    # version it should serve (a mismatch marks a pending rollout flip —
    # the replica takes no new work and flips once empty)
    version: int = 0
    target_version: int = 0
    # mid-recycle (rolling restart / scale-down drain): treated like OPEN
    # everywhere — no dispatch, no ticks, no heartbeat strikes — without
    # touching the breaker ladder (a planned recycle is not a failure)
    recycling: bool = False
    # retired by the autoscaler: engine closed, excluded from everything;
    # a later scale-up revives the slot with a fresh engine
    retired: bool = False


class ServingRouter:
    """Front-end router over ``num_replicas`` engine replicas (module
    docstring; docs/serving.md). Same submit/step/drain surface as
    ``ServingEngine``."""

    def __init__(
        self,
        model,
        params,
        num_replicas: int = 2,
        num_slots: int = 4,
        cache_dtype=None,
        metrics_jsonl: Optional[str] = None,
        replica_metrics_jsonl: Optional[str] = None,
        prefill_buckets: Optional[Sequence[int]] = None,
        max_queue_depth: Optional[int] = None,
        default_deadline_s: Optional[float] = None,
        kv_page_size: Optional[int] = None,
        num_kv_pages: Optional[int] = None,
        prefill_chunk_tokens: Optional[int] = None,
        prefix_cache: bool = False,
        max_prefill_slots: Optional[int] = None,
        kv_quant: Optional[str] = None,
        weight_dtype: Optional[str] = None,
        priority_aging_ticks: Optional[int] = None,
        max_preemptions: int = 2,
        journal: Optional[str] = None,
        telemetry=None,
        handle_preemption: bool = False,
        # failover / breaker policy (docs/reliability.md failure-domain table)
        max_failovers: int = 2,
        failure_threshold: int = 1,
        slow_tick_threshold_s: Optional[float] = None,
        slow_ticks_to_open: int = 3,
        nan_failures_to_open: Optional[int] = 3,
        breaker_cooldown_ticks: int = 4,
        breaker_max_cooldown_ticks: int = 64,
        # SLO shedding
        shed_infeasible: bool = True,
        shed_min_samples: int = 3,
        # SLO-driven autoscaling (docs/serving.md "Fleet operations"): a
        # dict of controller knobs — min_replicas / max_replicas /
        # scale_up_load / scale_down_load / every_ticks / patience — or None
        # (fixed fleet, today's behavior). Deterministic: evaluated every
        # ``every_ticks`` router ticks on the fleet-load signal (parked
        # depth + per-replica queue-beyond-capacity), acting only after
        # ``patience`` consecutive over/under readings.
        autoscale: Optional[Dict] = None,
        # out-of-process replicas (docs/serving.md "Out-of-process
        # replicas"): "process" spawns each replica as a separate OS worker
        # behind serving/transport.py's framed RPC — same dispatch, breaker,
        # failover, and journal semantics across a boundary kill -9 can
        # sever. "inproc" (default) keeps today's in-interpreter engines,
        # byte-identical; PERCEIVER_IO_TPU_DISABLE_PROC_REPLICAS=1 forces it
        # even when the knob says "process".
        replica_mode: str = "inproc",
        # transport knob bundle forwarded to every EngineClient in process
        # mode (rpc_timeout_s / init_timeout_s / retry); ignored in-process
        transport: Optional[Dict] = None,
        # internal: recover() constructs the fleet journal-less, replays each
        # replica's journal, THEN attaches — never pass this yourself
        _from_recovery: bool = False,
    ):
        if num_replicas < 1:
            raise ValueError(f"num_replicas must be >= 1, got {num_replicas}")
        if max_failovers < 0:
            raise ValueError(f"max_failovers must be >= 0, got {max_failovers}")
        self.model = model
        self.num_replicas = num_replicas
        self._window = model.max_seq_len
        self.max_failovers = max_failovers
        self.failure_threshold = max(failure_threshold, 1)
        self.slow_tick_threshold_s = slow_tick_threshold_s
        self.slow_ticks_to_open = max(slow_ticks_to_open, 1)
        self.nan_failures_to_open = nan_failures_to_open
        self.shed_infeasible = shed_infeasible
        self.shed_min_samples = max(shed_min_samples, 1)
        self.default_deadline_s = default_deadline_s
        self.max_queue_depth = max_queue_depth
        # per-replica write-ahead journals (serving/journal.py): a directory
        # TEMPLATE with an "{i}" placeholder, one journal per engine —
        # request ids are engine-local, so replicas sharing one directory
        # would collide. ServingRouter.recover reads the same template back.
        if journal is not None and num_replicas > 1 and "{i}" not in journal:
            raise ValueError(
                "journal must be a per-replica template containing '{i}' "
                f"with num_replicas > 1, got {journal!r}"
            )
        self._journal_template = journal
        if replica_mode not in ("inproc", "process"):
            raise ValueError(
                f"replica_mode must be 'inproc' or 'process', got {replica_mode!r}"
            )
        self._replica_mode = ("process" if replica_mode == "process"
                              and proc_replicas_enabled() else "inproc")
        if self._replica_mode == "process" and jax.default_backend() == "tpu":
            # an accelerator belongs to ONE process: this one initialised the
            # TPU backend (it holds the params as device arrays), so a worker
            # that builds its engine on the same chips dies at backend
            # start-up (libtpu's lockfile; PERF.md, PR 21). Refuse here,
            # before any worker is spawned.
            raise RuntimeError(
                "replica_mode='process' cannot be served from a process whose "
                "JAX backend is the TPU: this process holds the chip(s), and a "
                "worker process that needs one fails at backend start-up. Use "
                "replica_mode='inproc' here; process replicas need a parent "
                "that never initialises the TPU backend."
            )
        self._transport_cfg = dict(transport or {})
        # router-level accept journal (the closed fleet durability boundary):
        # fresh submits that park because NO replica can accept are journaled
        # here, so a full-fleet outage no longer loses them — recover()
        # replays this directory back into _pending. Sited beside the
        # replica journals under the same template.
        self._router_journal: Optional[RequestJournal] = None
        self._router_journal_dir: Optional[str] = None
        if journal is not None:
            self._router_journal_dir = (
                journal.format(i="router") if "{i}" in journal
                else journal + "-router"
            )
        # cooldown ladder: reliability/retry.py's bounded-exponential schedule
        # in TICK units with jitter 0 — cooldown(nth consecutive open) =
        # min(max, base * 2^(n-1)) ticks. Deterministic: the rng argument is
        # demanded by the API but jitter 0 never consults it.
        self._breaker_policy = RetryPolicy(
            attempts=1,
            base_delay_s=float(max(breaker_cooldown_ticks, 1)),
            max_delay_s=float(max(breaker_max_cooldown_ticks, breaker_cooldown_ticks, 1)),
            jitter=0.0,
        )
        self._breaker_rng = random.Random(0)

        # one shared recorder for the router and every replica (per-replica
        # span namespaces keep phase tables separable; the engines' request
        # categories are already collision-safe per engine)
        self._obs, self._owns_telemetry = resolve_recorder(telemetry)
        self._obs_on = self._obs.enabled
        # per-engine knob bundle, kept for the fleet lifecycle: recycling a
        # replica (rolling restart), reviving a retired one, or growing the
        # fleet (autoscaler) rebuilds an engine with EXACTLY the geometry the
        # fleet was constructed with — the journal records requests, not
        # engine configuration, so the knobs must live here.
        # Per-replica notes: each engine owns its own page pool (a failover
        # replay allocates on the NEW replica's pool at the victim's exact
        # page count — pinned), its own chunked-admission/prefix-cache state
        # (a replay lands on the new replica's cache cold or warm,
        # token-identical either way), its own served (cast/quantized) param
        # copy, and its own priority/preemption policy; the router only
        # forwards classes and aggregates counters (docs/serving.md).
        self._engine_cfg = dict(
            num_slots=num_slots,
            cache_dtype=cache_dtype,
            prefill_buckets=prefill_buckets,
            max_queue_depth=max_queue_depth,
            kv_page_size=kv_page_size,
            num_kv_pages=num_kv_pages,
            prefill_chunk_tokens=prefill_chunk_tokens,
            prefix_cache=prefix_cache,
            max_prefill_slots=max_prefill_slots,
            kv_quant=kv_quant,
            weight_dtype=weight_dtype,
            priority_aging_ticks=priority_aging_ticks,
            max_preemptions=max_preemptions,
        )
        self._replica_metrics_jsonl = replica_metrics_jsonl
        # journal policy the recycle/revive rebuilds re-apply; recover()
        # overrides them from its own arguments so a fleet recovered with
        # fsync="always" is never silently downgraded by a later recycle
        self._journal_fsync = "accept"
        self._journal_segment_max = 4096
        # fleet-operations state (module docstring; docs/serving.md "Fleet
        # operations"). Param versions: version 0 is the constructor's tree;
        # deploy() registers more. Every session pins one version at submit.
        self._fleet_ops = fleet_ops_enabled()
        self._versions: Dict[int, object] = {0: params}
        self._next_version = 1
        self._primary_version = 0
        self._rollout: Optional[Dict] = None  # {"version","fraction","count","base"}
        # fleet-unique session-id prefix: distinct per router instance, so
        # two fleets sharing journal directories across restarts can never
        # collide on the dedup key
        self._fleet_id = uuid.uuid4().hex[:12]
        # rolling restart / scale-down state: rids awaiting recycle, the rid
        # mid-recycle, and whether that recycle rebuilds ("restart") or
        # retires ("retire") the replica
        self._restart_queue: List[int] = []
        self._recycle_rid: Optional[int] = None
        self._recycle_mode: Optional[str] = None
        self._recycle_moved = 0
        # autoscaler (None = fixed fleet, or fleet ops disabled)
        self._autoscale: Optional[Dict] = None
        if autoscale is not None and self._fleet_ops:
            cfg = dict(autoscale)
            self._autoscale = {
                "min_replicas": int(cfg.pop("min_replicas", 1)),
                "max_replicas": int(cfg.pop("max_replicas", num_replicas)),
                "scale_up_load": int(cfg.pop("scale_up_load", 1)),
                "scale_down_load": int(cfg.pop("scale_down_load", 0)),
                "every_ticks": max(int(cfg.pop("every_ticks", 8)), 1),
                "patience": max(int(cfg.pop("patience", 2)), 1),
            }
            if cfg:
                raise ValueError(f"unknown autoscale knobs {sorted(cfg)}")
            a = self._autoscale
            if not 1 <= a["min_replicas"] <= num_replicas <= a["max_replicas"]:
                raise ValueError(
                    "autoscale requires 1 <= min_replicas <= num_replicas "
                    f"<= max_replicas, got min={a['min_replicas']} "
                    f"start={num_replicas} max={a['max_replicas']}"
                )
            if journal is not None and a["max_replicas"] > 1 and "{i}" not in journal:
                raise ValueError(
                    "journal must be a per-replica '{i}' template when the "
                    "autoscaler can grow the fleet past one replica"
                )
        self._scale_up_streak = 0
        self._scale_down_streak = 0
        # constructed only after every knob validated — a rejected
        # constructor must not leave a journal directory behind (a later
        # construction would refuse to attach to the non-empty leftover)
        if (self._router_journal_dir is not None and not _from_recovery
                and journal_enabled()):
            self._router_journal = RequestJournal(self._router_journal_dir)
        self.replicas: List[_Replica] = [
            _Replica(rid=i, engine=self._make_engine(
                i,
                # _from_recovery leaves engines journal-less so recover()
                # can replay the existing directories before attaching them
                journal_path=journal.format(i=i)
                if journal and not _from_recovery else None,
            ))
            for i in range(num_replicas)
        ]
        self.metrics = RouterMetrics(num_replicas=num_replicas, jsonl_path=metrics_jsonl)
        self.finished: List[RoutedRequest] = []
        self._ids = itertools.count()
        self._tick = 0  # the breaker clock: cooldowns are counted in ticks
        self._pending: Deque[RoutedRequest] = deque()  # held while no replica can accept
        self._deadlines_seen = default_deadline_s is not None
        self._draining = False
        # SIGTERM/SIGINT graceful drain, same semantics as the engine's
        self.preempted = False
        self._preempt_requested = False
        self._preempt_flushed = False
        self._preempt_handler = None
        self._preempt_previous: dict = {}
        if handle_preemption:
            def _request_preempt():
                self._preempt_requested = True
            self._preempt_handler, self._preempt_previous = (
                install_preemption_handler(_request_preempt)
            )

    def _make_engine(self, rid: int, journal_path: Optional[str] = None,
                     version: Optional[int] = None) -> ServingEngine:
        """One replica engine at the fleet's configured geometry, serving
        ``version``'s params (the primary version by default) — the single
        construction point initial build, recycle, revive, and scale-up all
        share, so a rebuilt replica can never drift from the fleet's knobs.
        In process mode the same construction point returns an
        ``EngineClient`` — a worker process behind the framed RPC exposing
        the identical engine surface (serving/transport.py)."""
        version = self._primary_version if version is None else version
        metrics_jsonl = (self._replica_metrics_jsonl.format(i=rid)
                         if self._replica_metrics_jsonl else None)
        if self._replica_mode == "process":
            return EngineClient(
                self.model, self._versions[version],
                replica_id=rid,
                metrics_jsonl=metrics_jsonl,
                journal=journal_path,
                on_retry=self._note_rpc_retry,
                **self._transport_cfg,
                **self._engine_cfg,
            )
        return ServingEngine(
            self.model, self._versions[version],
            metrics_jsonl=metrics_jsonl,
            journal=journal_path,
            telemetry=self._obs if self._obs_on else False,
            obs_ns=f"serving.r{rid}",
            **self._engine_cfg,
        )

    def _note_rpc_retry(self, replica: int, op: str, attempt: int,
                        err: str, delay: float) -> None:
        """EngineClient's on_retry hook: every transport retry lands in the
        metrics stream as an ``rpc_retry`` event (serving-metrics/v12).
        Guarded: the init RPC fires before ``self.metrics`` exists."""
        metrics = getattr(self, "metrics", None)
        if metrics is not None:
            metrics.record_rpc_retry(replica, op, attempt, err, delay)

    def _active_replicas(self) -> List[_Replica]:
        """Every non-retired replica (recycling ones included — they are
        still part of the fleet, just momentarily out of service)."""
        return [r for r in self.replicas if not r.retired]

    # ---------------------------------------------------------------- recovery
    @classmethod
    def recover(cls, model, params, journal: str, num_replicas: int = 2,
                fsync: str = "accept", segment_max_records: int = 4096,
                versions: Optional[Dict[int, object]] = None,
                **router_kwargs):
        """Rebuild a router fleet from per-replica write-ahead journals after
        process death (docs/serving.md "Request journal"). ``journal`` is
        the same ``"{i}"`` directory template the dead process ran with;
        each replica's journal is replayed into ITS OWN replica (placement
        preserved — per-directory recovery keeps the swap atomic per
        journal, so a crash mid-recovery re-recovers cleanly: already-swapped
        replicas hold their sessions in their new generation, untouched ones
        still hold the old one). Returns ``(router, info)`` with
        ``info["handles"]`` the recovered ``RoutedRequest`` handles (replica
        order, accept order within a replica); run the router as usual and
        every recovered session completes f64 token-identical to an
        uninterrupted run. Recovered in-flight sessions resume as
        ``PREEMPTED`` continuations that ``drain()`` finishes; recovered
        never-admitted backlog rejects as ``draining`` — the engine drain
        contract, fleet-wide."""
        if num_replicas > 1 and "{i}" not in journal:
            raise ValueError(
                "journal must be a per-replica template containing '{i}' "
                f"with num_replicas > 1, got {journal!r}"
            )
        # accepted ⇒ durable cuts both ways: a journal directory on disk
        # BEYOND num_replicas holds accepted sessions this recovery would
        # silently never read (the dead fleet ran more replicas than the
        # caller asked to rebuild — e.g. relying on the signature default).
        # Probe a bounded index range past num_replicas and fail loudly.
        if "{i}" in journal:
            from perceiver_io_tpu.serving.journal import read_journal as _read

            # live sessions, not raw records: a fully DRAINED stray journal
            # (every session terminal) has nothing this recovery could drop,
            # and blocking on it would strand a legitimately down-sized fleet
            stray = [
                i for i in range(num_replicas, num_replicas + 64)
                if os.path.isdir(journal.format(i=i))
                and len(_read(journal.format(i=i)).sessions) > 0
            ]
            if stray:
                raise ValueError(
                    f"journal template {journal!r} holds live (non-terminal) "
                    f"sessions for replica indices {stray} beyond "
                    f"num_replicas={num_replicas} — recovering fewer "
                    f"replicas than the dead fleet ran would silently drop "
                    f"their accepted sessions (pass the fleet's real "
                    f"num_replicas)"
                )
        router = cls(model, params, num_replicas=num_replicas,
                     journal=journal, _from_recovery=True, **router_kwargs)
        router._journal_fsync = fsync
        router._journal_segment_max = segment_max_records
        # the param-version manifest (docs/serving.md "Fleet operations"):
        # ``params`` is version 0 (the primary); ``versions`` registers the
        # non-primary trees the dead fleet had deployed, keyed by the SAME
        # version numbers its accept records pinned. Journaled pins are then
        # honored below — a session recovered against different weights
        # than the ones that decoded its prefix would silently diverge.
        if versions:
            for v, tree in sorted(versions.items()):
                router._versions[int(v)] = tree
            router._next_version = max(router._versions) + 1
        # cross-journal session dedup (docs/serving.md "Fleet operations"):
        # a planned migration has ONE window — after the destination's
        # fsynced accept, before the origin's close record — where the same
        # fleet session is live in two replica journals. Pre-read every
        # journal and, per session id, keep only the copy with the LONGEST
        # emitted prefix (the destination's accept folds the origin's whole
        # prefix into its replay, so it is always >=; ties keep the
        # lowest-index replica — deterministic). The losers are skipped
        # BEFORE re-submission and omitted from the swapped generation, so
        # a re-crash re-dedupes identically and the caller sees the session
        # exactly once. Sessions without ids (engine-only journals,
        # pre-fleet records) are never deduped.
        from perceiver_io_tpu.serving.journal import read_journal as _read

        best: Dict[str, tuple] = {}  # session id -> (replica rid, emitted len)
        per_journal_ids: Dict[int, set] = {}
        states: Dict[int, object] = {}
        for r in router.replicas:
            ids = set()
            state = _read(journal.format(i=r.rid))
            states[r.rid] = state
            for s in state.sessions:
                if s.session is None:
                    continue
                ids.add(s.session)
                cur = best.get(s.session)
                if cur is None or len(s.emitted) > cur[1]:
                    best[s.session] = (r.rid, len(s.emitted))
            per_journal_ids[r.rid] = ids
        now = time.perf_counter()
        handles: List[RoutedRequest] = []
        per_replica: Dict[str, Dict] = {}
        for r in router.replicas:
            skip = frozenset(sid for sid in per_journal_ids[r.rid]
                             if best[sid][0] != r.rid)
            # honor the journaled version pins (the manifest): every live
            # session a replica keeps was accepted while IT served the
            # pinned version — dispatch and migration enforce that — so the
            # kept pins must agree; mixed pins mean a corrupt manifest or a
            # placement no real fleet produces, and recovering them under
            # any single tree would silently mis-decode some of them.
            pins = {s.version for s in states[r.rid].sessions
                    if s.version is not None and s.session not in skip}
            if len(pins) > 1:
                raise ValueError(
                    f"replica {r.rid} journal holds sessions pinned to "
                    f"multiple param versions {sorted(pins)} — corrupt "
                    f"version manifest (one replica serves one version)"
                )
            pin = pins.pop() if pins else router._primary_version
            if pin not in router._versions:
                raise ValueError(
                    f"replica {r.rid} journal pins its sessions to param "
                    f"version {pin}, which is no longer deployable — pass "
                    f"its tree via versions={{{pin}: params_v{pin}}} (the "
                    f"accept-record manifest refuses to rebuild a session "
                    f"against different weights than decoded its prefix)"
                )
            if pin != r.version:
                r.engine.set_params(router._versions[pin])
                r.version = r.target_version = pin
            info = r.engine._recover_attach(
                journal.format(i=r.rid), fsync=fsync,
                segment_max_records=segment_max_records,
                skip_session_ids=skip, _state=states[r.rid],
            )
            for handle in info.pop("handles"):
                routed = RoutedRequest(
                    request_id=next(router._ids),
                    prompt_ids=handle.prompt_ids,
                    config=handle.config,
                    rng=handle.rng,
                    priority=handle.priority,
                    submitted_at=now,
                    deadline_s=handle.deadline_s,
                    # the journaled pin survives process death (the accept
                    # record carries it — the param-version manifest); a
                    # pre-manifest record pins the replica's resolved
                    # version, which the consensus check above set
                    version=(handle.version if handle.version is not None
                             else r.version),
                    session_id=handle.session_id,
                )
                routed._engine_handle = handle
                routed._accepted = True
                routed.replica = r.rid
                r.assigned[handle.request_id] = routed
                if routed.deadline_s is not None:
                    router._deadlines_seen = True
                # the recovered request re-enters the router's books as a
                # fresh submit+dispatch pair so the lifetime accounting
                # (submitted == finished + rejected + ...) stays closed
                router.metrics.record_submit(routed.request_id,
                                             int(handle.prompt_ids.size),
                                             priority=routed.priority)
                router.metrics.record_dispatch(routed.request_id, r.rid,
                                               load=r.engine.load)
                if router._obs_on:
                    router._obs.async_begin("router.request", routed.request_id,
                                            prompt_len=int(handle.prompt_ids.size))
                handles.append(routed)
            per_replica[f"r{r.rid}"] = info
        # replay the ROUTER's accept journal (the closed full-outage
        # durability boundary): fresh submits that were parked — no healthy
        # replica could accept — when the whole fleet died never reached any
        # replica journal, so their only durable copy is here. Re-admit each
        # one to the parked queue; the first healthy tick dispatches them.
        # A parking entry whose session id also appears in a replica journal
        # is the OTHER half of the dispatch race: the engine accept landed
        # but the close record died with the process — the replica copy
        # (recovered above) is the session, the parking entry is stale.
        parked_handles: List[RoutedRequest] = []
        rj_dir = router._router_journal_dir
        if rj_dir is not None and journal_enabled():
            if os.path.isdir(rj_dir):
                rj_state = read_journal(rj_dir)
                dispatched = set().union(*per_journal_ids.values()) \
                    if per_journal_ids else set()
                mirror: List[tuple] = []
                now_wall = time.time()
                for s in rj_state.sessions:
                    if s.session is not None and s.session in dispatched:
                        continue
                    pin = (s.version if s.version is not None
                           else router._primary_version)
                    if pin not in router._versions:
                        raise ValueError(
                            f"router journal holds a parked admission pinned "
                            f"to param version {pin}, which is no longer "
                            f"deployable — pass its tree via versions="
                            f"{{{pin}: ...}}"
                        )
                    routed = RoutedRequest(
                        request_id=next(router._ids),
                        prompt_ids=np.asarray(s.prompt, np.int32),
                        config=GenerationConfig(**s.config),
                        rng=np.asarray(s.rng, np.uint32),
                        priority=s.priority,
                        submitted_at=now,
                        # deadlines keep counting through the outage — the
                        # journal discipline; an expired parked request dies
                        # of TTL at the first tick, never resurrects stale
                        deadline_s=s.remaining_deadline(now_wall),
                        version=pin,
                        session_id=s.session,
                    )
                    routed._router_journaled = True
                    if routed.deadline_s is not None:
                        router._deadlines_seen = True
                    router.metrics.record_submit(
                        routed.request_id, int(routed.prompt_ids.size),
                        priority=routed.priority,
                        version=pin if router._fleet_ops else None,
                    )
                    if router._obs_on:
                        router._obs.async_begin(
                            "router.request", routed.request_id,
                            prompt_len=int(routed.prompt_ids.size))
                    router._pending.append(routed)
                    parked_handles.append(routed)
                    mirror.append((routed.request_id, JournalSession(
                        rid=routed.request_id, prompt=list(s.prompt),
                        config=dict(s.config), rng=list(s.rng),
                        priority=s.priority, deadline_s=routed.deadline_s,
                        accepted_ts=now_wall, session=s.session,
                        version=s.version,
                    )))
                # generation swap, the journal recovery discipline: the new
                # generation holds exactly the re-admitted entries under
                # their new router ids; the old one stays durable until the
                # rename lands
                router._router_journal = RequestJournal(
                    rj_dir, fsync=fsync,
                    segment_max_records=segment_max_records,
                    _recovered_from=rj_state, _sessions=mirror,
                )
            else:
                router._router_journal = RequestJournal(
                    rj_dir, fsync=fsync,
                    segment_max_records=segment_max_records,
                )
        return router, {
            "sessions": len(handles),
            "replayed_tokens": sum(i["replayed_tokens"]
                                   for i in per_replica.values()),
            "deduped": sum(i["deduped"] for i in per_replica.values()),
            "replicas": per_replica,
            "handles": handles,
            "router_parked": len(parked_handles),
            "parked_handles": parked_handles,
        }

    # ------------------------------------------------------------------ submit
    def submit(
        self,
        prompt_ids: Sequence[int],
        config: Optional[GenerationConfig] = None,
        rng=None,
        deadline_s: Optional[float] = None,
        priority: int = 0,
        **kwargs,
    ) -> RoutedRequest:
        """Queue one request; returns its router-level handle. Semantics
        mirror ``ServingEngine.submit``: malformed requests raise, well-formed
        requests the fleet cannot serve come back terminal in REJECTED —
        including the router-only outcome ``shed_infeasible`` (the deadline
        cannot be met per the live latency estimates). ``priority`` is
        forwarded verbatim to the serving engine (higher wins; a class-k head
        blocked on pages/slots preempts strictly-lower-class running work
        there — docs/serving.md, "Priority classes & preemption")."""
        if config is None:
            config = GenerationConfig(**kwargs)
        elif kwargs:
            raise ValueError("pass either config or keyword options, not both")
        reason = _engine_compatible(config)
        if reason is not None:
            raise ValueError(f"GenerationConfig not servable by the engine: {reason}")
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must be non-empty (over-long prompts are "
                             "REJECTED at admission, empty ones are malformed)")
        if rng is None:
            rng = jax.random.PRNGKey(0)
        routed = RoutedRequest(
            request_id=next(self._ids),
            prompt_ids=prompt,
            config=config,
            rng=rng,
            priority=int(priority),
            submitted_at=time.perf_counter(),
            deadline_s=deadline_s if deadline_s is not None else self.default_deadline_s,
            # version pin (docs/serving.md "Fleet operations"): chosen HERE,
            # once, by the deterministic rollout split — every later
            # dispatch, failover, and migration respects it
            version=self._pick_version(),
        )
        if self._fleet_ops:
            routed.session_id = f"{self._fleet_id}:{routed.request_id}"
        if routed.deadline_s is not None:
            self._deadlines_seen = True
        # version rides the event stream only when fleet ops are live: the
        # kill-switch contract is a byte-identical pre-fleet stream
        self.metrics.record_submit(routed.request_id, int(prompt.size),
                                   priority=routed.priority,
                                   version=routed.version
                                   if self._fleet_ops else None)
        if self._obs_on:
            self._obs.async_begin("router.request", routed.request_id,
                                  prompt_len=int(prompt.size))
        if self._draining:
            return self._refuse(routed, "draining")
        if prompt.size > self._window:
            return self._refuse(routed, "prompt_too_long")
        if routed.deadline_s is not None and self.shed_infeasible:
            est = self._estimate_completion_s(config.max_new_tokens)
            if est is not None and est > routed.deadline_s:
                self.metrics.record_shed(routed.request_id, routed.deadline_s, est)
                return self._refuse(routed, "shed_infeasible")
        self._dispatch(routed)
        return routed

    def _refuse(self, routed: RoutedRequest, reason: str) -> RoutedRequest:
        self._resolve(routed, RequestStatus.REJECTED, reason)
        return routed

    # ---------------------------------------------------------------- dispatch
    def _pick_version(self) -> int:
        """The version pin for one new admission: the primary version, or —
        during a rollout — the rollout version for a deterministic
        ``fraction`` of admissions (admission k takes the new version iff
        ``floor((k+1)f) > floor(kf)``: a pure function of the submit count,
        no clocks, no randomness — the faults.py discipline)."""
        if self._rollout is None:
            return self._primary_version
        f = self._rollout["fraction"]
        k = self._rollout["count"]
        self._rollout["count"] = k + 1
        if math.floor((k + 1) * f) > math.floor(k * f):
            return self._rollout["version"]
        return self._primary_version

    def _serving_replicas(self, version: Optional[int] = None,
                          include_flipping: bool = False) -> List[_Replica]:
        """Replicas eligible for NEW work: breaker CLOSED, not mid-recycle or
        retired, serving ``version`` when one is given (dispatch and
        migration respect the session's pin) — least-loaded first, ties on
        the lowest index (deterministic placement). Replicas awaiting a
        version flip are excluded for fresh submits; with
        ``include_flipping`` (accepted-work continuations) they are eligible
        LAST — they still run the session's pinned params until they flip,
        and serving continuity outranks flip speed — so a continuation is
        never stranded while any engine of its version is alive."""
        eligible = [
            r for r in self.replicas
            if r.breaker == BREAKER_CLOSED and not r.recycling and not r.retired
            and (version is None or r.version == version)
            and (include_flipping or r.version == r.target_version)
        ]
        return sorted(eligible, key=lambda r: (r.version != r.target_version,
                                               r.engine.load, r.rid))

    def _remaining_deadline(self, routed: RoutedRequest, now: float) -> Optional[float]:
        """Deadline budget LEFT for an engine hand-off: the engine enforces
        TTLs from ITS submit instant, so time already spent at the router
        (queueing while all replicas were down, earlier failovers) must be
        subtracted — a failover never extends a request's deadline."""
        if routed.deadline_s is None:
            return None
        return max(routed.deadline_at - now, 0.0)

    def _dispatch(self, routed: RoutedRequest, requeue: bool = False,
                  exclude_rid: Optional[int] = None) -> bool:
        """Place one request (fresh, or a failover continuation) on the
        least-loaded healthy replica. Returns True when the request reached a
        terminal or assigned state, False when it was parked in the router
        queue. ``requeue`` marks ALREADY-ACCEPTED work (failover
        continuations, parked retries): fresh submits that find every
        healthy queue at its bound are terminally REJECTED/queue_full — the
        backpressure contract — but accepted work must never be killed by a
        momentary full queue; it parks and retries as capacity frees.

        Failover continuations hand the engine the ORIGINAL prompt plus the
        already-emitted tokens as a forced REPLAY stream: the new replica
        prefills the prompt exactly as the lost one did (same covering
        bucket — the parity-pinned admission path) and then replays the
        emitted tokens through the compiled decode step, reconstructing the
        lost engine's decode trajectory — rng chain included — step for
        step. The continuation is therefore token-identical to the
        uninterrupted run (pinned in f64; sampled requests too, since the
        key chain re-advances identically), a re-prefill of prompt+tokens
        could not be: Perceiver AR's latent/prefix split at a position
        depends on HOW the state was built, not just which tokens are live."""
        emitted = routed._salvaged
        if emitted and len(emitted) >= routed.config.max_new_tokens:
            # defensive: a continuation with nothing left to decode is a
            # completed request (the engine evicts at the emitting tick, so
            # this only happens if a failure landed mid-harvest)
            self._resolve(routed, RequestStatus.FINISHED, "length")
            return True
        now = time.perf_counter()
        saw_closed = False
        for r in self._serving_replicas(routed.version,
                                        include_flipping=requeue):
            if r.breaker != BREAKER_CLOSED:
                continue  # opened mid-scan by a dispatch-failure cascade
            if r.rid == exclude_rid:
                continue  # the replica being drained must not re-admit its own drain
            saw_closed = True
            load_at_decision = r.engine.load  # submit() bumps it
            try:
                handle = r.engine.submit(
                    routed.prompt_ids, config=routed.config, rng=routed.rng,
                    deadline_s=self._remaining_deadline(routed, now),
                    replay_ids=emitted if emitted else None,
                    priority=routed.priority,
                    # accepted work re-enters as a RESUME: a draining engine
                    # takes it (drain finishes in-flight work) while fresh
                    # submits keep today's refusal; the session id rides the
                    # accept record for cross-journal recovery dedup
                    resume=routed._accepted,
                    session_id=routed.session_id,
                    # the param-version manifest pin: the accept record
                    # carries the session's pinned version so a worker
                    # respawn / fleet recovery rebuilds it against the SAME
                    # weights. None with fleet ops off keeps the record
                    # byte-identical to pre-manifest journals.
                    version=routed.version if self._fleet_ops else None,
                )
            except BaseException as exc:  # noqa: BLE001
                # a dispatch-path failure — a journal append dying on real
                # ENOSPC/EIO, or a fail-stopped journal refusing appends —
                # is a REPLICA fault, not a router fault: the engine already
                # closed the request's own accounting (REJECTED /
                # journal_error), so contain it exactly like a tick
                # exception (breaker strike; at the threshold the replica
                # opens and its live work fails over) and keep trying THIS
                # request on the remaining healthy replicas. Letting it
                # propagate would crash the whole fleet on one replica's
                # disk fault — the opposite of the router's isolation
                # contract. Router-side validation already ran, so this is
                # never a malformed-input error the caller needs to see.
                self._on_tick_failure(r, exc)
                continue
            if handle.status is RequestStatus.REJECTED:
                if handle.finish_reason == "queue_full":
                    continue  # backpressure at this replica: try the next
                # prompt_too_long/draining from a fresh submit are terminal
                self._resolve(routed, RequestStatus.REJECTED, handle.finish_reason)
                return True
            routed._engine_handle = handle
            routed.replica = r.rid
            routed._accepted = True
            # the salvage buffer is NOT cleared: output_ids reports
            # max(salvage, engine stream), so the view stays monotonic while
            # the engine re-emits the replayed prefix
            r.assigned[handle.request_id] = routed
            # the new replica's journal now holds the continuation (fresh
            # accept, replay prefix included): close the origin's live entry
            # so a later fleet recovery replays the session ONCE — as
            # "moved"/"migrated" when a planned migration queued the note,
            # the failover default otherwise
            note = routed._move_note or ("failed", "replica_failover")
            routed._move_note = None
            self._journal_note_moved(routed, status=note[0], reason=note[1])
            # the engine's fsynced accept is now the durable anchor: close
            # the router-journal parking entry (if this submit ever parked)
            self._router_journal_close(routed, "moved", "dispatched")
            self.metrics.record_dispatch(routed.request_id, r.rid,
                                         load=load_at_decision)
            if self._obs_on:
                self._obs.async_instant("router.request", routed.request_id,
                                        "dispatch", replica=r.rid,
                                        failover_n=routed.failovers)
            return True
        routed.replica = None
        if requeue:
            # accepted work is never terminally rejected here; the CALLER
            # re-parks it (ordering among several victims is the caller's
            # to preserve)
            return False
        if saw_closed:
            # healthy replicas exist but every queue is at its bound: the
            # engine's own backpressure answer, surfaced unchanged
            self._resolve(routed, RequestStatus.REJECTED, "queue_full")
            return True
        # no healthy replica at all: park until a breaker closes (the
        # bound, when configured, still applies — an outage must not
        # grow an unbounded router backlog). A FRESH submit parked here has
        # never reached an engine, so it becomes durable through the
        # ROUTER's own accept journal — the previously documented
        # memory-only durability boundary, now closed: recover() replays
        # these accepts back into the parked queue. Failover continuations
        # stay durable via their origin journal entry instead.
        if self.max_queue_depth is not None and len(self._pending) >= self.max_queue_depth:
            self._resolve(routed, RequestStatus.REJECTED, "queue_full")
            return True
        if (self._router_journal is not None and not routed._accepted
                and not routed._router_journaled):
            try:
                self._router_journal.append_accept(
                    routed.request_id,
                    np.asarray(routed.prompt_ids).reshape(-1).tolist(),
                    _journal_config_payload(routed.config),
                    np.asarray(jax.device_get(routed.rng),
                               np.uint32).reshape(-1).tolist(),
                    priority=routed.priority,
                    deadline_s=routed.deadline_s,
                    session_id=routed.session_id,
                    version=routed.version if self._fleet_ops else None,
                )
                routed._router_journaled = True
            except BaseException:
                # the engine's journal discipline, applied at router level:
                # an accept that could not be made durable is REJECTED (the
                # caller was told the submit failed, never that it was
                # silently dropped) and the error propagates
                self._resolve(routed, RequestStatus.REJECTED, "journal_error")
                raise
        self._pending.append(routed)
        return False

    def _dispatch_pending(self) -> None:
        while self._pending and any(
            r.breaker == BREAKER_CLOSED and not r.recycling and not r.retired
            for r in self.replicas
        ):
            routed = self._pending.popleft()
            if routed.done:  # expired while parked
                continue
            if not self._dispatch(routed, requeue=True):
                self._pending.appendleft(routed)  # restore its place
                break

    def _expire_pending(self, now: float) -> None:
        """TTL enforcement for router-parked requests (engines enforce their
        own): expiry while every replica is down must still be an explicit
        TIMED_OUT, never a silent loss."""
        if not self._pending:
            return
        kept: Deque[RoutedRequest] = deque()
        for routed in self._pending:
            if routed.deadline_at is not None and now >= routed.deadline_at:
                self._resolve(routed, RequestStatus.TIMED_OUT, "deadline")
            else:
                kept.append(routed)
        self._pending = kept

    def _journal_note_moved(self, routed: RoutedRequest,
                            status: str = "failed",
                            reason: str = "replica_failover") -> None:
        """Close a failed-over session's entry in its ORIGIN replica's
        journal, once the continuation is durable elsewhere (a successful
        re-dispatch journaled a fresh accept) or terminal (resolved while
        parked). Until this runs, the origin journal deliberately keeps the
        session LIVE — it is the continuation's only durable copy while
        parked — and a fleet recovery would resume it there. Best-effort: a
        broken origin journal must not break dispatch (worst case one
        superseded replay candidate survives to the next recovery, where the
        duplicate is visible, not silent)."""
        origin = routed._journal_origin
        if origin is None:
            return
        routed._journal_origin = None
        replica_idx, engine_rid = origin
        journal = self.replicas[replica_idx].engine.journal
        if journal is None or journal.failed or not journal.tracks(engine_rid):
            return
        try:
            journal.append_tick([], {}, [(engine_rid, status, reason)])
        except Exception:  # noqa: BLE001 — durability bookkeeping, not control flow
            pass

    def _router_journal_close(self, routed: RoutedRequest,
                              status: str, reason: str) -> None:
        """Close a parked submit's live entry in the ROUTER's accept
        journal: on dispatch (the engine's fsynced accept takes over as the
        durable anchor) or on a terminal outcome while parked. Best-effort
        for the same reason as ``_journal_note_moved`` — a broken router
        journal must not break dispatch; the worst case is one already-
        dispatched submit surviving to the next recovery, where the
        session-id dedup against the replica journals drops it visibly."""
        if not routed._router_journaled:
            return
        routed._router_journaled = False
        journal = self._router_journal
        if (journal is None or journal.failed
                or not journal.tracks(routed.request_id)):
            return
        try:
            journal.append_tick([], {}, [(routed.request_id, status, reason)])
        except Exception:  # noqa: BLE001 — durability bookkeeping, not control flow
            pass

    # --------------------------------------------------------------- fleet ops
    def _find_live(self, request_id: int) -> Optional[RoutedRequest]:
        """The live routed handle for a router-level request id (assigned to
        any replica, or parked), or None for unknown/terminal ids."""
        for r in self.replicas:
            for routed in r.assigned.values():
                if routed.request_id == request_id and not routed.done:
                    return routed
        for routed in self._pending:
            if routed.request_id == request_id and not routed.done:
                return routed
        return None

    def _detach_session(self, r: _Replica, engine_rid: int,
                        routed: RoutedRequest, reason: str = "migrated") -> None:
        """Lift one live session off a LIVE replica (planned migration /
        recycle drain — the engine is healthy, unlike failover's lost one):
        the slot and pages release through the engine's own eviction path,
        the emitted prefix is salvaged as the continuation's replay stream,
        and the origin journal entry STAYS LIVE (``journal_terminal=False``)
        as the continuation's durability anchor until it lands elsewhere —
        the ``_journal_note_moved`` seam, reused exactly."""
        handle = routed._engine_handle
        r.assigned.pop(engine_rid, None)
        r.engine.evict_request(engine_rid, reason,
                               status=RequestStatus.REJECTED,
                               journal_terminal=False)
        # the evicted engine handle is router bookkeeping, not a terminal
        # outcome: drop it before a harvest could misread it as REJECTED
        r.engine.finished = [h for h in r.engine.finished if h is not handle]
        # keep the LONGEST known token prefix: an engine handle mid-replay
        # holds the full stream in replay_ids while output_ids still trails
        # (the _preempt discipline), and the existing salvage may already be
        # the longest — all are prefixes of the same true stream
        streams = [routed._salvaged]
        if handle is not None:
            streams.append(list(handle.output_ids))
            if handle.replay_ids is not None:
                streams.append([int(t) for t in handle.replay_ids])
        routed._salvaged = max(streams, key=len)
        if (r.engine.journal is not None
                and r.engine.journal.tracks(engine_rid)):
            routed._journal_origin = (r.rid, engine_rid)
        routed._engine_handle = None
        routed.replica = None

    def _hand_off_to(self, routed: RoutedRequest, r: _Replica) -> bool:
        """Land one continuation on a SPECIFIC replica (the migration
        targetting primitive; ``_dispatch`` keeps the least-loaded scan for
        everything else). True when the session landed; False leaves the
        session exactly as it was — parked/detached, durable via its origin
        anchor — for the caller to re-home."""
        emitted = routed._salvaged
        if emitted and len(emitted) >= routed.config.max_new_tokens:
            self._resolve(routed, RequestStatus.FINISHED, "length")
            return True
        load_at_decision = r.engine.load
        try:
            handle = r.engine.submit(
                routed.prompt_ids, config=routed.config, rng=routed.rng,
                deadline_s=self._remaining_deadline(routed, time.perf_counter()),
                replay_ids=emitted if emitted else None,
                priority=routed.priority,
                resume=routed._accepted,
                session_id=routed.session_id,
                version=routed.version if self._fleet_ops else None,
            )
        except BaseException as exc:  # noqa: BLE001 — replica fault containment
            self._on_tick_failure(r, exc)
            return False
        if handle.status is RequestStatus.REJECTED:
            return False  # backpressure (or refusal) at the target: not landed
        routed._engine_handle = handle
        routed.replica = r.rid
        routed._accepted = True
        r.assigned[handle.request_id] = routed
        # the destination's fsynced accept is durable HERE while the origin
        # entry is still live — the one double-live instant; the chaos
        # harness turns this fault point into a real child SIGKILL and pins
        # that recovery dedup resolves it to exactly one session
        faults.fire_migrate_kill()
        note = routed._move_note or ("failed", "replica_failover")
        routed._move_note = None
        self._journal_note_moved(routed, status=note[0], reason=note[1])
        self._router_journal_close(routed, "moved", "dispatched")
        self.metrics.record_dispatch(routed.request_id, r.rid,
                                     load=load_at_decision)
        if self._obs_on:
            self._obs.async_instant("router.request", routed.request_id,
                                    "dispatch", replica=r.rid,
                                    failover_n=routed.failovers)
        return True

    def migrate(self, request_id: int, dst: int) -> bool:
        """PLANNED cross-replica migration (module docstring): preempt the
        session on its origin through the live engine's own eviction path —
        no crash required — and land the continuation on replica ``dst`` via
        the forced-replay submit, f64 token-identical to an unmigrated run
        with zero new compiled programs and the failover budget untouched.
        Journal entries close/open exactly-once through the
        ``_journal_note_moved`` seam. Malformed calls (unknown/terminal
        request, bad or non-serving destination, a destination whose version
        differs from the session's pin) raise ValueError; a destination that
        refuses for capacity returns False with the session safely re-homed
        on any pin-matching replica (or parked, still durable). Returns True
        once the session runs on ``dst``. Inert (False) under the
        ``PERCEIVER_IO_TPU_DISABLE_FLEET_OPS`` kill-switch."""
        if not self._fleet_ops:
            return False
        if not 0 <= dst < len(self.replicas):
            raise ValueError(f"unknown replica index {dst}")
        routed = self._find_live(request_id)
        if routed is None:
            raise ValueError(f"unknown or terminal request {request_id}")
        r_dst = self.replicas[dst]
        if (r_dst.retired or r_dst.recycling
                or r_dst.breaker != BREAKER_CLOSED):
            raise ValueError(f"replica {dst} is not serving (breaker "
                             f"{r_dst.breaker}, recycling={r_dst.recycling}, "
                             f"retired={r_dst.retired})")
        if r_dst.version != routed.version or r_dst.version != r_dst.target_version:
            raise ValueError(
                f"migration respects the version pin: request "
                f"{request_id} is pinned to v{routed.version}, replica {dst} "
                f"serves v{r_dst.version} (target v{r_dst.target_version})"
            )
        if routed.replica == dst:
            return True  # already there: a no-op, not an error
        src = routed.replica
        handle = routed._engine_handle
        if src is not None and handle is not None:
            if handle.done:
                return False  # terminal at the engine; harvest resolves it
            self._detach_session(self.replicas[src], handle.request_id, routed)
        elif routed in self._pending:
            # a parked continuation migrates by simply landing on the target
            self._pending.remove(routed)
        routed._move_note = ("moved", "migrated")
        if self._hand_off_to(routed, r_dst):
            if routed.replica == dst:
                self.metrics.record_migration(
                    routed.request_id, src if src is not None else -1, dst,
                    emitted_tokens=len(routed._salvaged),
                )
                if self._obs_on:
                    self._obs.async_instant("router.request",
                                            routed.request_id, "migrate",
                                            src=src, dst=dst)
            # else: the hand-off resolved the session terminally (a salvaged
            # prefix already at max_new_tokens) — complete, but no move
            # happened, so the migration counters must not claim one
            return True
        # the destination would not take it (queue at bound, mid-scan
        # breaker trip): the session is accepted work — re-home it on any
        # pin-matching replica, else park at the FRONT (it is older than
        # anything a fresh submit parked behind it)
        routed._move_note = None
        if routed.done:
            return False  # the refusal resolved it (defensive)
        if not self._dispatch(routed, requeue=True):
            self._pending.appendleft(routed)
        return False

    def _drain_replica(self, r: _Replica, reason: str = "recycle") -> int:
        """Move every live session off a replica (recycle/retire/flip
        drains): detach through the live engine, then re-home each
        continuation on a pin-matching sibling — or park it (front of the
        router queue, admission order preserved), where it stays durable via
        its origin journal anchor and, for a recycle, is re-adopted by the
        rebuilt replica's own journal recovery. Returns the count that moved
        or parked."""
        moved = 0
        parked: List[RoutedRequest] = []
        for engine_rid, routed in sorted(r.assigned.items()):
            handle = routed._engine_handle
            if handle is not None and handle.done:
                # terminal at the engine but unharvested: the outcome stands
                r.assigned.pop(engine_rid, None)
                self._resolve(routed, handle.status, handle.finish_reason)
                continue
            self._detach_session(r, engine_rid, routed, reason=reason)
            routed._move_note = ("moved", reason)
            if not self._dispatch(routed, requeue=True, exclude_rid=r.rid):
                parked.append(routed)
            moved += 1
        if parked:
            # park as one block at the FRONT, admission order preserved
            # among themselves (extendleft reverses — the _failover_replica
            # discipline; per-item appendleft would invert the group)
            self._pending.extendleft(reversed(parked))
        return moved

    # ------------------------------------------------------- rolling restart
    @property
    def restart_in_progress(self) -> bool:
        return bool(self._restart_queue) or self._recycle_rid is not None

    def begin_rolling_restart(self) -> bool:
        """Start a tick-driven rolling restart: every active replica is
        recycled in index order, one at a time — sessions migrate to
        siblings (or park, durably anchored), the engine is torn down and
        journal-recovered fresh, health state resets, and the replica
        re-admits before the next one starts. ``step()`` advances it;
        ``rolling_restart()`` is the synchronous convenience. Returns False
        (refusing, never raising) under the kill-switch or while draining;
        True if a restart is now (or already was) in progress."""
        if not self._fleet_ops or self._draining:
            return False
        if self.restart_in_progress:
            return True
        self._restart_queue = [r.rid for r in self.replicas if not r.retired]
        return True

    def rolling_restart(self, max_steps: Optional[int] = None) -> bool:
        """Synchronous rolling restart: begin, then step the fleet until
        every replica has been recycled — requests submitted meanwhile are
        served throughout (the bounded-blip contract the serve_bench
        ``--rolling-restart`` arm measures). Returns False when refused
        (kill-switch, draining)."""
        if not self.begin_rolling_restart():
            return False
        steps = 0
        while self.restart_in_progress:
            self.step()
            steps += 1
            if max_steps is not None and steps > max_steps:
                raise RuntimeError(
                    f"rolling restart incomplete after {max_steps} steps"
                )
        return True

    def _start_recycle(self, r: _Replica, mode: str) -> None:
        """Take a replica out of service for recycling ("restart") or
        retirement ("retire"): the flag makes it read like an OPEN breaker
        everywhere — no dispatch, no ticks, no heartbeat strikes (a planned
        recycle is not a failure and must not climb the backoff ladder or
        cascade strikes onto siblings) — then its sessions drain out. The
        rebuild/close completes on the NEXT tick (_finish_recycle), so a
        mid-recycle window is observable and chaos-killable."""
        r.recycling = True
        self._recycle_rid = r.rid
        self._recycle_mode = mode
        self._recycle_moved = self._drain_replica(r, reason=mode)

    def _build_fresh(self, rid: int, version: int):
        """A fresh engine for a recycled/revived replica slot: when the
        fleet journals and this slot's directory already exists, the rebuild
        goes THROUGH journal recovery (an empty-live-session recovery in the
        normal case — the swap starts a new generation; any leftover live
        session is re-adopted by the caller), otherwise a plain construction
        with the journal attached directly."""
        journal_dir = (self._journal_template.format(i=rid)
                       if self._journal_template else None)
        if journal_dir is not None and os.path.isdir(journal_dir):
            fresh = self._make_engine(rid, journal_path=None, version=version)
            info = fresh._recover_attach(
                journal_dir, fsync=self._journal_fsync,
                segment_max_records=self._journal_segment_max,
            )
            return fresh, info
        return self._make_engine(rid, journal_path=journal_dir,
                                 version=version), None

    def _finish_recycle(self, r: _Replica) -> None:
        """Complete the recycle begun last tick: tear the old engine down
        (journal flushed+closed), rebuild through journal recovery (restart)
        or retire the slot (scale-down), re-adopt any parked session the old
        journal still anchored, and reset the replica's health record — a
        recycled replica earns a clean slate, INCLUDING the compile-tick
        baseline (a fresh engine's first ticks compile; a stale program
        count could collide with the fresh one and let those ticks strike
        the stall detector)."""
        mode, self._recycle_mode = self._recycle_mode, None
        self._recycle_rid = None
        r.engine.discard_pending_harvest()
        r.engine.close()
        if mode == "retire":
            r.retired = True
            r.recycling = False
            r.orphaned.clear()
            return
        # VERSION-PRESERVING rebuild: any session the journal recovery
        # re-adopts below is pinned to the version this replica was serving
        # (it ran here) — rebuilding at target_version would decode its
        # remaining tokens under different weights. A pending flip
        # (target != version) is the flip path's job: it fires as usual
        # once the rebuilt replica is empty.
        fresh, info = self._build_fresh(r.rid, r.version)
        r.engine = fresh
        leftovers = info["sessions"] if info else 0
        if info:
            self._adopt_recovered(r, info)
        r.recycling = False
        r.orphaned.clear()
        r.breaker = BREAKER_CLOSED
        r.consecutive_failures = 0
        r.consecutive_slow = 0
        r.nan_failures = 0
        r.open_count = 0
        r.cooldown_ticks = 0
        r._programs_seen = 0
        r.last_tick = self._tick
        r.last_error = None
        self.metrics.record_recycle(r.rid, sessions_moved=self._recycle_moved,
                                    leftover_sessions=leftovers,
                                    tick=self._tick)
        if self._obs_on:
            self._obs.instant("router.recycle", replica=r.rid,
                              sessions_moved=self._recycle_moved,
                              leftovers=leftovers)

    def _adopt_recovered(self, r: _Replica, info: Dict) -> None:
        """Wire a rebuilt replica's journal-recovered sessions back into the
        router's books. A recovered session whose fleet id matches a PARKED
        continuation is the SAME session (its drain-out couldn't land on a
        sibling): the parked handle adopts the fresh engine handle — no
        duplicate RoutedRequest, and the origin anchor clears because the
        swapped generation now holds the session under the new engine rid.
        Anything else (a session the drain somehow left behind) enters the
        books as a fresh submit+dispatch pair, the recover() discipline."""
        now = time.perf_counter()
        parked = {p.session_id: p for p in self._pending
                  if p.session_id is not None and not p.done}
        for handle in info.pop("handles"):
            routed = parked.get(handle.session_id)
            if routed is not None and routed.version != r.version:
                # pin mismatch (a revive at a different version than the
                # session decoded under): the session stays PARKED — lift it
                # back off this engine without journaling a terminal, and
                # re-anchor it to the NEW generation's accept (the swap
                # already made that its durable copy); it lands when a
                # pin-matching replica frees
                r.engine.evict_request(handle.request_id, "version_mismatch",
                                       status=RequestStatus.REJECTED,
                                       journal_terminal=False)
                r.engine.finished = [h for h in r.engine.finished
                                     if h is not handle]
                routed._journal_origin = (r.rid, handle.request_id)
                continue
            if routed is not None:
                self._pending.remove(routed)
                routed._journal_origin = None
                routed._move_note = None
            else:
                routed = RoutedRequest(
                    request_id=next(self._ids),
                    prompt_ids=handle.prompt_ids,
                    config=handle.config,
                    rng=handle.rng,
                    priority=handle.priority,
                    submitted_at=now,
                    deadline_s=handle.deadline_s,
                    # the rebuild is version-preserving (_finish_recycle):
                    # a recovered session decoded here, so its pin is the
                    # version this replica serves
                    version=r.version,
                    session_id=handle.session_id,
                )
                if routed.deadline_s is not None:
                    self._deadlines_seen = True
                self.metrics.record_submit(routed.request_id,
                                           int(handle.prompt_ids.size),
                                           priority=routed.priority,
                                           version=routed.version)
                if self._obs_on:
                    self._obs.async_begin("router.request", routed.request_id,
                                          prompt_len=int(handle.prompt_ids.size))
            routed._engine_handle = handle
            routed._accepted = True
            # accepted work: a later drain keeps it. Via the engine method
            # (not a bare attribute write) so the flag also crosses the
            # out-of-process boundary — an EngineClient mirror handle must
            # tell ITS worker, or the worker-side drain would prune the
            # session as backlog (serving/transport.py).
            r.engine.mark_resume(handle.request_id)
            routed.replica = r.rid
            r.assigned[handle.request_id] = routed
            self.metrics.record_dispatch(routed.request_id, r.rid,
                                         load=r.engine.load)

    # ---------------------------------------------------------------- rollout
    def deploy(self, params, fraction: float = 1.0) -> Optional[int]:
        """Register a new param version and roll it out LIVE: a
        deterministic ``fraction`` of new admissions pins the new version
        (``_pick_version``), and the last ``ceil(fraction * active)``
        replicas are targeted to flip to it — each flips (``set_params``,
        zero recompiles) only once empty of its current sessions, which
        either migrate to pin-matching siblings or finish in place. Returns
        the version id (None under the kill-switch / while draining).
        ``fraction=1.0`` is a full rollout; in-flight sessions still finish
        on the version that decoded their prefix — the lifetime pin."""
        if not self._fleet_ops or self._draining:
            return None
        if not 0.0 <= float(fraction) <= 1.0:
            raise ValueError(f"fraction must lie in [0, 1], got {fraction}")
        # validate the tree NOW, where the operator can react: a mismatch
        # discovered at flip time would raise out of step() on every tick
        # (engine.set_params refuses through the same shared gate, because
        # shape/dtype/structure drift would silently recompile every program)
        if tree_layout_mismatch(self._versions[self._primary_version], params):
            raise ValueError(
                "deploy requires a params tree with the structure, shapes, "
                "and dtypes of the serving versions (anything else would "
                "recompile every program at flip time)"
            )
        version = self._next_version
        self._next_version += 1
        self._versions[version] = params
        base = self._primary_version
        self._rollout = {"version": version, "fraction": float(fraction),
                         "count": 0, "base": base}
        active = self._active_replicas()
        k = math.ceil(float(fraction) * len(active)) if fraction > 0 else 0
        targets = [r.rid for r in active[len(active) - k:]] if k else []
        for r in active:
            r.target_version = version if r.rid in targets else base
        self.metrics.record_deploy(version, float(fraction), targets)
        self.metrics.set_fleet_gauges(len(active), self.restart_in_progress,
                                      self._primary_version)
        if self._obs_on:
            self._obs.instant("router.deploy", version=version,
                              fraction=float(fraction))
        return version

    def rollback(self) -> bool:
        """Instant rollback of the active rollout: new admissions pin the
        pre-deploy version again IMMEDIATELY; replicas re-target it and
        flip back as they empty; in-flight rollout-version sessions finish
        on their pin (never re-decoded under different weights). False when
        no rollout is active or the kill-switch is set."""
        if not self._fleet_ops or self._rollout is None:
            return False
        version = self._rollout["version"]
        base = self._rollout["base"]
        self._rollout = None
        self._primary_version = base
        for r in self.replicas:
            if not r.retired:
                r.target_version = base
        self._prune_versions()
        self.metrics.record_rollback(version, base)
        if self._obs_on:
            self._obs.instant("router.rollback", from_version=version,
                              to_version=base)
        return True

    def _prune_versions(self) -> None:
        """Drop param trees nothing references anymore — not the primary or
        active rollout, no replica's current/target version, no live
        session's pin. Without this a long-lived fleet doing periodic
        deploys retains one full model copy per deploy forever."""
        keep = {self._primary_version}
        if self._rollout is not None:
            keep.add(self._rollout["version"])
            keep.add(self._rollout["base"])
        for r in self.replicas:
            keep.add(r.version)
            keep.add(r.target_version)
        for r in self.replicas:
            keep.update(routed.version for routed in r.assigned.values())
        keep.update(p.version for p in self._pending)
        for v in [v for v in self._versions if v not in keep]:
            del self._versions[v]

    def _advance_rollout_flips(self) -> None:
        """Flip every target-mismatched replica that can flip: an empty one
        swaps params now (its in-cache state belongs to no session); a
        non-empty one drains to pin-matching siblings when any exist, else
        its sessions finish in place and the flip waits. A flip is deferred
        while parked work pinned to the replica's CURRENT version has no
        other replica still running that version — flipping would strand it
        (continuations may land on a flip-pending replica, new work may
        not)."""
        for r in self.replicas:
            if r.retired or r.recycling or r.version == r.target_version:
                continue
            if r.assigned:
                if self._serving_replicas(version=r.version):
                    self._drain_replica(r, reason="version_flip")
                continue  # re-checked next tick (sessions may finish/park)
            if r.engine.scheduler.has_work:
                continue  # engine-queued work (resumes) still pending
            others_running = any(
                o is not r and not o.retired and not o.recycling
                and o.version == r.version
                for o in self.replicas
            )
            if (not others_running
                    and any(p.version == r.version for p in self._pending)):
                continue  # last engine of a version with parked work: wait
            r.engine.set_params(self._versions[r.target_version])
            r.version = r.target_version
            if self._obs_on:
                self._obs.instant("router.version_flip", replica=r.rid,
                                  version=r.version)
        # FULL-rollout promotion: once a fraction-1.0 deploy has flipped
        # every active replica (and no parked work still pins the old
        # version), the rollout version BECOMES the primary — later
        # scale-ups/revives build it, and a fresh deploy rolls out against
        # it. Partial rollouts stay split by design until rollback or a
        # full deploy; rollback() after promotion is a no-op (there is no
        # rollout left to roll back — deploy the old params instead).
        if self._rollout is not None and self._rollout["fraction"] >= 1.0:
            v = self._rollout["version"]
            active = self._active_replicas()
            if (active
                    and all(r.version == v and r.target_version == v
                            for r in active)
                    and not any(p.version != v for p in self._pending)):
                self._primary_version = v
                self._rollout = None
                self._prune_versions()
                self.metrics.set_fleet_gauges(len(active),
                                              self.restart_in_progress, v)
                if self._obs_on:
                    self._obs.instant("router.version_promoted", version=v)

    # -------------------------------------------------------------- autoscale
    def _fleet_load(self) -> int:
        """The autoscaler's signal: router-parked depth plus every serving
        replica's queue-beyond-capacity — deterministic given the
        submit/tick history (no clocks), like every scaling decision."""
        load = len(self._pending)
        for r in self.replicas:
            if r.retired or r.recycling or r.breaker == BREAKER_OPEN:
                continue
            load += max(r.engine.load, 0)
        return load

    def _autoscale_eval(self) -> None:
        a = self._autoscale
        if self._tick % a["every_ticks"] != 0:
            return
        load = self._fleet_load()
        active = self._active_replicas()
        if load >= a["scale_up_load"]:
            self._scale_up_streak += 1
            self._scale_down_streak = 0
        elif load <= a["scale_down_load"]:
            self._scale_down_streak += 1
            self._scale_up_streak = 0
        else:
            self._scale_up_streak = 0
            self._scale_down_streak = 0
        if (self._scale_up_streak >= a["patience"]
                and len(active) < a["max_replicas"]):
            self._scale_up_streak = 0
            self._scale_up(load)
        elif (self._scale_down_streak >= a["patience"]
                and len(active) > a["min_replicas"]
                and self._recycle_rid is None
                and not self._restart_queue):
            self._scale_down_streak = 0
            self._scale_down(load)

    def _scale_up_version(self) -> int:
        """The param version a NEW replica should serve: the primary —
        unless a rollout is live and its version is under-placed for the
        fleet size the scale-up produces. The rollout pins ``fraction`` of
        new admissions to its version, so at least ``ceil(fraction * N)``
        of N active replicas must target it or the pinned admissions park
        with no eligible replica (building the primary unconditionally was
        exactly that bug — an admission black-hole the autoscaler itself
        dug)."""
        if self._rollout is None:
            return self._primary_version
        v = self._rollout["version"]
        want = math.ceil(self._rollout["fraction"]
                         * (len(self._active_replicas()) + 1))
        targeting = sum(1 for r in self.replicas
                        if not r.retired and r.target_version == v)
        return v if targeting < want else self._primary_version

    def _scale_up(self, load: int) -> None:
        """Add capacity: revive the lowest-index retired slot (its journal
        directory, if any, recovers — normally empty of live sessions), or
        append a brand-new replica at the next index. The new replica's
        version honors the live rollout split (``_scale_up_version``), not
        blindly the primary."""
        version = self._scale_up_version()
        retired = [r for r in self.replicas if r.retired]
        if retired:
            r = min(retired, key=lambda x: x.rid)
            fresh, info = self._build_fresh(r.rid, version)
            r.engine = fresh
            r.retired = False
            r.recycling = False
            r.breaker = BREAKER_CLOSED
            r.version = r.target_version = version
            r.consecutive_failures = r.consecutive_slow = 0
            r.nan_failures = r.open_count = r.cooldown_ticks = 0
            r._programs_seen = 0
            r.last_tick = self._tick
            r.last_error = None
            if info:
                self._adopt_recovered(r, info)
            rid = r.rid
        else:
            rid = len(self.replicas)
            fresh, info = self._build_fresh(rid, version)
            r = _Replica(rid=rid, engine=fresh,
                         version=version,
                         target_version=version)
            r.last_tick = self._tick
            self.replicas.append(r)
            if info:
                self._adopt_recovered(r, info)
        self.metrics.record_autoscale("up", rid,
                                      active=len(self._active_replicas()),
                                      load=load, tick=self._tick)

    def _scale_down(self, load: int) -> None:
        """Shed capacity through the SAME migrate-and-drain path a recycle
        uses: the highest-index active replica whose retirement strands
        nothing (its version must survive on a sibling while any session
        still pins it) drains its sessions to siblings and is closed next
        tick."""
        candidates = sorted(
            (r for r in self.replicas if not r.retired and not r.recycling),
            key=lambda x: -x.rid,
        )
        for r in candidates:
            others = any(
                o is not r and not o.retired and o.version == r.version
                for o in self.replicas
            )
            pinned = bool(r.assigned) or any(
                p.version == r.version for p in self._pending
            )
            if pinned and not others:
                continue  # retiring the last engine of a pinned version strands it
            if self._rollout is not None:
                # an ACTIVE rollout keeps pinning a fraction of new
                # admissions to its version: retiring the last replica
                # targeting it would park that fraction until the next
                # rollout-aware scale-up — still a needless availability
                # hole, so keep at least one
                v = self._rollout["version"]
                if r.target_version == v and not any(
                    o is not r and not o.retired and o.target_version == v
                    for o in self.replicas
                ):
                    continue
            self.metrics.record_autoscale(
                "down", r.rid, active=len(self._active_replicas()) - 1,
                load=load, tick=self._tick,
            )
            self._start_recycle(r, mode="retire")
            return

    def _advance_fleet_ops(self) -> None:
        """One tick of fleet-lifecycle progress, run inside ``step()``:
        complete the recycle begun last tick, then — unless draining —
        advance rollout flips, start the next rolling-restart recycle
        (after the previous one's parked work had a tick to land), and
        evaluate the autoscaler. All decisions are tick-counted and
        deterministic."""
        if not self._fleet_ops:
            return
        if self._recycle_rid is not None:
            self._finish_recycle(self.replicas[self._recycle_rid])
        if self._draining:
            # a draining fleet finishes the in-flight recycle (parked work
            # may need that replica back) but starts nothing new
            self._restart_queue = []
            return
        self._advance_rollout_flips()
        if self._recycle_rid is None and self._restart_queue:
            rid = self._restart_queue.pop(0)
            r = self.replicas[rid]
            if not r.retired:
                self._start_recycle(r, mode="restart")
        if self._autoscale is not None:
            self._autoscale_eval()

    # ----------------------------------------------------------------- breaker
    def _transition(self, r: _Replica, new: str) -> None:
        old, r.breaker = r.breaker, new
        self.metrics.record_breaker(r.rid, old, new, self._tick)
        if self._obs_on:
            self._obs.instant("router.breaker", replica=r.rid, transition=f"{old}->{new}")

    def _open_breaker(self, r: _Replica, cause: str) -> None:
        """Take a replica out of service: OPEN the breaker with the next
        cooldown on the ladder, then fail its live requests over."""
        if r.breaker == BREAKER_OPEN:
            # two triggers in one tick (e.g. NaN threshold at harvest AND a
            # slow-tick strike) must not double-open: the second would forge
            # an open->open transition and skip a rung of the backoff ladder
            return
        r.open_count += 1
        # retry.py's schedule in tick units (attempt = nth consecutive open);
        # jitter is 0 so the rng is never consulted — no randomness in the
        # firing decision, the faults.py discipline
        r.cooldown_ticks = max(int(self._breaker_policy.delay(r.open_count, self._breaker_rng)), 1)
        r.opened_at_tick = self._tick
        r.consecutive_failures = 0
        r.consecutive_slow = 0
        r.last_error = cause
        self._transition(r, BREAKER_OPEN)
        self._failover_replica(r)

    def _promote_breakers(self) -> None:
        for r in self.replicas:
            if r.recycling or r.retired:
                continue  # out of service by PLAN, not by the breaker
            if (
                r.breaker == BREAKER_OPEN
                and self._tick - r.opened_at_tick >= r.cooldown_ticks
            ):
                self._transition(r, BREAKER_HALF_OPEN)
                # reclaim the QUEUED orphans before the probe tick runs —
                # host-only bookkeeping, so it is safe on a suspect engine,
                # and without it the probe's admission phase would waste a
                # prefill + slot per stale entry on requests already running
                # elsewhere. Stale RUNNING slots wait for probe success
                # (_recover_replica): their release touches device state we
                # only trust after a healthy tick.
                for engine_req_id in sorted(r.orphaned):
                    routed = r.orphaned[engine_req_id]
                    # a PARKED continuation's origin entry is its only
                    # durable copy: reclaiming the stale engine bookkeeping
                    # must not journal a terminal until the continuation
                    # lands elsewhere (_journal_note_moved closes it then)
                    anchored = routed._journal_origin == (r.rid, engine_req_id)
                    if r.engine.evict_request(engine_req_id, "replica_failover",
                                              status=RequestStatus.FAILED,
                                              queued_only=True,
                                              journal_terminal=not anchored):
                        r.orphaned.pop(engine_req_id)

    # -------------------------------------------------------------- supervisor
    def _respawn_worker(self, r: _Replica) -> bool:
        """Process-mode supervisor: a replica whose WORKER PROCESS died
        (``WorkerDiedError`` — kill -9, OOM, segfault) is respawned through
        its own journal recovery, the same path a full-fleet ``recover``
        takes, so its sessions come back f64 token-identical while the
        SIBLINGS never miss a tick. Returns True when the respawn fully
        healed the replica (no breaker strike — process death is a fault the
        supervisor owns, not a health signal about the fresh worker); False
        falls through to the normal breaker/failover path.

        Respawn-with-recovery needs both a journal (the durable copy) and
        fleet ops (session ids are the re-adoption match key — without them
        recovered sessions would duplicate their failover continuations).
        Otherwise the dead client is swapped for a fresh empty worker so the
        slot can at least serve again after its breaker cooldown, and the
        sessions fail over from the client-side mirrors as usual."""
        if r.recycling or r.retired:
            return False
        journal_dir = (self._journal_template.format(i=r.rid)
                       if self._journal_template else None)
        journaled = journal_dir is not None and os.path.isdir(journal_dir)
        if not (journaled and self._fleet_ops):
            try:
                old = r.engine
                r.engine = self._make_engine(r.rid, journal_path=None,
                                             version=r.version)
                old.close()
            except Exception:  # noqa: BLE001 — breaker path owns a failed spawn
                pass
            return False
        # park every live hand-off exactly like a failover — EXCEPT the
        # failover budget: a respawn re-adopts the SAME sessions from the
        # replica's own journal, so no budget is spent and no re-dispatch
        # happens (the parked entries match the recovered sessions by
        # session id in _adopt_recovered below)
        victims = sorted(r.assigned.items())
        r.assigned.clear()
        parked: List[RoutedRequest] = []
        for engine_req_id, routed in victims:
            handle = routed._engine_handle
            if handle is not None and handle.done:
                self._resolve(routed, handle.status, handle.finish_reason)
                continue
            salvaged = list(handle.output_ids) if handle is not None else []
            if len(salvaged) > len(routed._salvaged):
                routed._salvaged = salvaged
            routed._engine_handle = None
            routed.replica = None
            # the on-disk journal holds the session live — the durable
            # anchor while the respawn is in flight
            routed._journal_origin = (r.rid, engine_req_id)
            parked.append(routed)
        if parked:
            self._pending.extendleft(reversed(parked))
        try:
            r.engine.close()  # reaps the dead child; never raises
        except Exception:  # noqa: BLE001
            pass
        try:
            fresh, info = self._build_fresh(r.rid, r.version)
        except Exception as exc:  # noqa: BLE001 — respawn failed: strike instead
            r.last_error = f"respawn failed: {type(exc).__name__}: {exc}"
            return False
        r.engine = fresh
        recovered = info["sessions"] if info else 0
        if info:
            # a recovered session that ALREADY continues on a sibling (its
            # failover landed before the respawn, so the dead worker never
            # journaled the close record) is superseded: evict it WITH a
            # terminal record, closing the resurrected entry exactly-once
            live_elsewhere = {
                routed.session_id
                for r2 in self.replicas if r2 is not r
                for routed in r2.assigned.values()
                if routed.session_id is not None and not routed.done
            }
            kept = []
            for handle in info["handles"]:
                if handle.session_id in live_elsewhere:
                    r.engine.evict_request(handle.request_id, "superseded",
                                           status=RequestStatus.FAILED,
                                           journal_terminal=True)
                    r.engine.finished = [h for h in r.engine.finished
                                         if h is not handle]
                    continue
                kept.append(handle)
            info["handles"] = kept
            self._adopt_recovered(r, info)
        # clean slate, the _finish_recycle discipline: the respawned worker
        # is a fresh process with a fresh health record (and fresh jit
        # caches — the compile-tick baseline must restart too)
        r.orphaned.clear()
        if r.breaker != BREAKER_CLOSED:
            self._transition(r, BREAKER_CLOSED)
        r.consecutive_failures = 0
        r.consecutive_slow = 0
        r.nan_failures = 0
        r.open_count = 0
        r.cooldown_ticks = 0
        r._programs_seen = 0
        r.last_tick = self._tick
        r.last_error = None
        self.metrics.record_respawn(r.rid, sessions=recovered,
                                    tick=self._tick)
        if self._obs_on:
            self._obs.instant("router.respawn", replica=r.rid,
                              sessions=recovered)
        return True

    def _on_tick_failure(self, r: _Replica, exc: BaseException) -> None:
        if (self._replica_mode == "process"
                and isinstance(exc, WorkerDiedError)
                and self._respawn_worker(r)):
            return  # supervisor healed it: no strike
        r.consecutive_failures += 1
        r.last_error = f"{type(exc).__name__}: {exc}"
        if r.breaker == BREAKER_HALF_OPEN:
            # a failed probe re-opens immediately with a longer cooldown
            self._open_breaker(r, r.last_error)
        elif r.consecutive_failures >= self.failure_threshold:
            self._open_breaker(r, r.last_error)

    def _on_tick_success(self, r: _Replica, duration_s: float) -> None:
        r.last_tick = self._tick  # heartbeat
        slow = (
            self.slow_tick_threshold_s is not None
            and duration_s > self.slow_tick_threshold_s
        )
        if slow:
            # compile-tick exemption: first-use and new-bucket jit compiles
            # take seconds and are NOT a wedged engine — a strike here would
            # open breakers on every cold replica (and re-pay the same
            # compiles on its sibling). Detected the same way the PR6
            # watchdog counts programs: the engine's jit cache sizes moved.
            programs = r.engine.total_compilations
            if programs != r._programs_seen:
                r._programs_seen = programs
                slow = False
        if slow:
            r.consecutive_slow += 1
            if r.breaker == BREAKER_HALF_OPEN:
                # a stalled probe is a failed probe
                self._open_breaker(r, f"slow probe tick ({duration_s:.3f}s)")
            elif r.consecutive_slow >= self.slow_ticks_to_open:
                self._open_breaker(r, f"{r.consecutive_slow} consecutive slow ticks")
            return
        r.consecutive_failures = 0
        r.consecutive_slow = 0
        if r.breaker == BREAKER_HALF_OPEN:
            self._recover_replica(r)

    def _recover_replica(self, r: _Replica) -> None:
        """A HALF_OPEN probe tick succeeded: reclaim the stale state the
        replica held when it went down — orphaned slots are evicted through
        the engine's own API (their requests moved on at failover; the
        handles are terminal bookkeeping) — and close the breaker. The
        backoff ladder resets: a recovered replica earns the base cooldown
        again."""
        r.engine.discard_pending_harvest()
        for engine_req_id, routed in sorted(r.orphaned.items()):
            # same anchoring rule as _promote_breakers: a still-parked
            # continuation's session must stay LIVE in this journal
            anchored = routed._journal_origin == (r.rid, engine_req_id)
            r.engine.evict_request(engine_req_id, "replica_failover",
                                   status=RequestStatus.FAILED,
                                   journal_terminal=not anchored)
        r.orphaned.clear()
        # drop the orphaned terminal handles (and any pre-crash finished ones
        # whose routed requests were failed over): nothing maps to them now
        r.engine.finished = [h for h in r.engine.finished
                             if h.request_id in r.assigned]
        r.open_count = 0
        r.nan_failures = 0
        self._transition(r, BREAKER_CLOSED)

    # ---------------------------------------------------------------- failover
    def _failover_replica(self, r: _Replica) -> None:
        """Re-dispatch every live request of a lost replica. The dead engine
        is NOT touched (a real crash leaves nothing to call into) — its
        stale slots are reclaimed if/when the replica recovers."""
        victims = sorted(r.assigned.items())  # engine request_id order = admission order
        r.assigned.clear()
        parked: List[RoutedRequest] = []
        for engine_req_id, routed in victims:
            handle = routed._engine_handle
            if handle is not None and handle.done:
                # terminal at the engine but unharvested (failure landed
                # between evict and harvest): the outcome stands
                self._resolve(routed, handle.status, handle.finish_reason)
                continue
            r.orphaned[engine_req_id] = routed
            if (
                r.engine.journal is not None
                and r.engine.journal.tracks(engine_req_id)
            ):
                # the lost replica's journal keeps this session LIVE until
                # the continuation is durable elsewhere or terminal — see
                # _journal_note_moved. Set BEFORE the dispatch below, which
                # closes it on a successful hand-off.
                routed._journal_origin = (r.rid, engine_req_id)
            # keep the LONGEST prefix seen: a crash mid-replay hands back a
            # handle shorter than the salvage it was rebuilding
            salvaged = list(handle.output_ids) if handle is not None else []
            if len(salvaged) > len(routed._salvaged):
                routed._salvaged = salvaged
            routed._engine_handle = None
            routed.replica = None
            routed.failovers += 1
            self.metrics.record_failover(routed.request_id, r.rid,
                                         emitted_tokens=len(routed._salvaged),
                                         failover_n=routed.failovers)
            if self._obs_on:
                self._obs.async_instant("router.request", routed.request_id,
                                        "failover", from_replica=r.rid,
                                        emitted=len(routed._salvaged))
            if routed.failovers > self.max_failovers:
                self._resolve(routed, RequestStatus.FAILED, "max_failovers")
                continue
            if not self._dispatch(routed, requeue=True):
                parked.append(routed)
        if parked:
            # continuations park at the FRONT of the router queue (they are
            # older than anything a fresh submit parked behind them), in
            # admission order among themselves — extendleft reverses, so
            # feed it the reversed list
            self._pending.extendleft(reversed(parked))

    # ----------------------------------------------------------------- harvest
    def _harvest_finished(self, r: _Replica) -> None:
        nan_hits = 0
        for handle in r.engine.finished:
            routed = r.assigned.pop(handle.request_id, None)
            if handle.finish_reason == "nonfinite_logits":
                nan_hits += 1
            if routed is None:
                continue  # orphan bookkeeping or warmup traffic: not ours
            self._resolve(routed, handle.status, handle.finish_reason)
        r.engine.finished.clear()
        if nan_hits:
            r.nan_failures += nan_hits
            if (
                self.nan_failures_to_open is not None
                and r.breaker == BREAKER_CLOSED
                and r.nan_failures >= self.nan_failures_to_open
            ):
                # a replica repeatedly producing non-finite logits is sick
                # (bad memory, corrupt weights) — stop feeding it. The count
                # stays visible on snapshots while the breaker is OPEN (an
                # operator inspecting a sick replica needs the WHY); recovery
                # resets it.
                self._open_breaker(r, f"{r.nan_failures} NaN containments")

    def _resolve(self, routed: RoutedRequest, status: RequestStatus,
                 reason: Optional[str]) -> None:
        """The ONE terminal-bookkeeping path: submit-time refusals, dispatch
        rejections, harvest outcomes, failover exhaustion, and drain all land
        here, so counters, JSONL, and trace spans can never diverge."""
        # a parked continuation resolving terminally (TTL expiry, drain,
        # max_failovers) must close its failover origin's journal entry with
        # the real outcome, or a later fleet recovery would resurrect a
        # request the caller already saw go terminal (the real outcome also
        # supersedes any queued migration note)
        routed._move_note = None
        self._journal_note_moved(routed, status=status.value,
                                 reason=reason or "resolved")
        self._router_journal_close(routed, status.value, reason or "resolved")
        routed._terminal_status = status
        routed.finish_reason = reason
        routed.finished_at = time.perf_counter()
        self.finished.append(routed)
        self.metrics.record_finish(
            routed.request_id, status.value, reason,
            new_tokens=len(routed.output_ids), failovers=routed.failovers,
            version=routed.version if self._fleet_ops else None,
        )
        if self._obs_on:
            self._obs.async_end("router.request", routed.request_id,
                                status=status.value, reason=reason,
                                new_tokens=len(routed.output_ids),
                                failovers=routed.failovers)

    # -------------------------------------------------------------------- step
    @property
    def has_work(self) -> bool:
        """True while any non-terminal request can still make progress —
        parked requests, live hand-offs, engine-side work on replicas the
        router still ticks — or while a rolling restart is mid-flight (a
        ``run_until_drained`` that exited with a replica half-recycled would
        strand it out of service until some later step; a restart always
        completes in bounded ticks, so this can never spin). A
        permanently-OPEN replica's stale slots do NOT count — their requests
        already moved on."""
        return (
            bool(self._pending)
            or self.restart_in_progress
            or any(r.assigned for r in self.replicas)
            or any(
                r.breaker != BREAKER_OPEN and not r.recycling and not r.retired
                and r.engine.scheduler.has_work
                for r in self.replicas
            )
        )

    def step(self) -> bool:
        """One router tick: promote breakers, place parked work, then tick
        every serving replica in two phases — DISPATCH all (each replica's
        decode starts on-device), then HARVEST all (sync + evict) — so one
        replica's device step overlaps its siblings' host work. Returns True
        while work remains anywhere in the fleet."""
        if self._preempt_requested and not self._draining:
            self.preempted = True
            self._begin_drain()
        self._tick += 1
        with self._obs.span("router.tick"):
            now = time.perf_counter()
            if self._deadlines_seen:
                self._expire_pending(now)
            self._promote_breakers()
            # fleet lifecycle (module docstring): finish last tick's recycle,
            # advance rollout flips, start the next restart recycle, evaluate
            # the autoscaler — BEFORE pending dispatch, so work parked by a
            # drain (and capacity returned by a rebuild) lands this tick
            self._advance_fleet_ops()
            self._dispatch_pending()
            # CLOSED replicas serve; HALF_OPEN replicas always get their probe
            # tick (even idle — an un-probed idle replica would never close).
            # Mid-recycle and retired replicas are never ticked: a planned
            # recycle reads like an OPEN breaker everywhere, so it can never
            # strike its own or a sibling's detector (docs/serving.md)
            ticking = [r for r in self.replicas
                       if r.breaker != BREAKER_OPEN
                       and not r.recycling and not r.retired]
            dispatched: List[_Replica] = []
            for r in ticking:
                try:
                    t0 = time.perf_counter()
                    faults.fire_replica_tick(r.rid)
                    r.engine.step_dispatch()
                    r._own_tick_s = time.perf_counter() - t0
                    dispatched.append(r)
                except Exception as e:  # noqa: BLE001 — replica loss IS the domain
                    self._on_tick_failure(r, e)
            for r in dispatched:
                try:
                    t0 = time.perf_counter()
                    r.engine.step_harvest()
                    r._own_tick_s += time.perf_counter() - t0
                except Exception as e:  # noqa: BLE001
                    self._on_tick_failure(r, e)
                    continue
                self._harvest_finished(r)
                self._on_tick_success(r, r._own_tick_s)
            if self._obs_on:
                self._obs.gauge_set("router.pending", len(self._pending))
                self._obs.gauge_set(
                    "router.replicas_closed",
                    sum(1 for r in self.replicas if r.breaker == BREAKER_CLOSED),
                )
        has_work = self.has_work
        self._maybe_flush_preempted(has_work)
        return has_work

    def run_until_drained(self, max_steps: Optional[int] = None) -> List[RoutedRequest]:
        """Step until every submitted request reached a terminal status;
        returns (and drains) the requests finished since the last drain."""
        steps = 0
        while self.step():
            steps += 1
            if max_steps is not None and steps > max_steps:
                raise RuntimeError(f"router not drained after {max_steps} steps")
        drained, self.finished = self.finished, []
        return drained

    def _begin_drain(self) -> None:
        """Close admission fleet-wide: reject the router-parked backlog and
        every replica's queued backlog; active slots keep decoding. Parked
        CONTINUATIONS are not backlog — a failover/migration continuation is
        accepted mid-generation work, with tokens possibly already streamed
        to a client and a live journal entry anchoring it — so like the
        engine's PREEMPTED continuations they stay parked and FINISH through
        the drain loop (landing on draining engines as resumes); only
        never-accepted fresh submits reject (the drain×parked-work seam, the
        PR 10 drain×recovery audit re-run at the router layer). A rolling
        restart in progress is cancelled (its queued recycles never start;
        the one in flight completes so parked work can re-land)."""
        self._draining = True
        kept: Deque[RoutedRequest] = deque()
        while self._pending:
            routed = self._pending.popleft()
            if routed._accepted:
                kept.append(routed)
            else:
                self._resolve(routed, RequestStatus.REJECTED, "draining")
        self._pending = kept
        self._restart_queue = []
        for r in self.replicas:
            if r.breaker == BREAKER_OPEN or r.recycling or r.retired:
                continue  # nothing to reject; its requests already moved on
            r.engine._begin_drain()

    def drain(self, max_steps: Optional[int] = None) -> List[RoutedRequest]:
        """Graceful fleet shutdown: refuse new work, reject all backlogs,
        finish every active slot. Returns the drained terminal handles."""
        self._begin_drain()
        return self.run_until_drained(max_steps=max_steps)

    def _maybe_flush_preempted(self, has_work: bool) -> None:
        if self.preempted and not self._preempt_flushed and not has_work:
            self._preempt_flushed = True
            self.write_snapshot()
            self.close()

    # --------------------------------------------------------------- shedding
    def _estimate_completion_s(self, max_new_tokens: int) -> Optional[float]:
        """Best completion-time estimate across healthy replicas, from the
        windowed p95 latency stats PR 2's metrics already maintain:
        ``p95(queue wait) + p95(prefill dispatch) + max_new * p95(decode
        step)``. None while every healthy replica is cold (< shed_min_samples
        decode steps) — a cold fleet must never shed."""
        best = None
        for r in self.replicas:
            if r.breaker != BREAKER_CLOSED or r.recycling or r.retired:
                continue
            est = r.engine.metrics.latency_estimates()
            if est is None or est["decode_steps"] < self.shed_min_samples:
                continue
            total = (
                est["queue_wait_p95_s"]
                + est["prefill_p95_s"]
                + max_new_tokens * est["decode_step_p95_s"]
            )
            if best is None or total < best:
                best = total
        return best

    # -------------------------------------------------------------- telemetry
    @property
    def telemetry(self):
        return self._obs

    def snapshot(self) -> Dict:
        """serving-metrics/v10 router snapshot with per-replica sections."""
        return self.metrics.snapshot(self._replica_snapshots())

    def write_snapshot(self) -> Dict:
        return self.metrics.write_snapshot(self._replica_snapshots())

    def _transport_stats(self) -> Optional[Dict]:
        """Fleet-aggregated transport gauges for the v12 ``transport``
        snapshot block: RPC counts/retries/timeouts, frame and byte totals,
        and p50/p95 RPC latency pooled across every process replica. None
        in-process — the block's absence IS the mode marker."""
        if self._replica_mode != "process":
            return None
        totals = {"rpcs": 0, "retries": 0, "timeouts": 0, "frames_sent": 0,
                  "frames_recv": 0, "bytes_sent": 0, "bytes_recv": 0}
        samples: List[float] = []
        workers_alive = 0
        for r in self.replicas:
            stats_fn = getattr(r.engine, "transport_stats", None)
            if stats_fn is None:
                continue
            stats = stats_fn()
            for key in totals:
                totals[key] += stats[key]
            samples.extend(stats["rpc_ms"])
            if getattr(r.engine, "alive", False):
                workers_alive += 1
        totals["workers_alive"] = workers_alive
        totals["rpc_p50_ms"] = (round(float(np.percentile(samples, 50)), 3)
                                if samples else None)
        totals["rpc_p95_ms"] = (round(float(np.percentile(samples, 95)), 3)
                                if samples else None)
        return totals

    def _replica_snapshots(self) -> Dict[str, Dict]:
        self.metrics.set_fleet_gauges(
            len([r for r in self._active_replicas() if not r.recycling]),
            self.restart_in_progress,
            self._primary_version,
        )
        self.metrics.set_transport(self._transport_stats())
        out = {}
        for r in self.replicas:
            snap = r.engine.metrics.snapshot()
            snap["breaker"] = r.breaker
            snap["last_tick"] = r.last_tick
            snap["nan_failures"] = r.nan_failures
            if r.recycling:
                snap["recycling"] = True
            if r.retired:
                snap["retired"] = True
            if self._next_version > 1:
                # version markers only once a rollout exists — single-version
                # snapshots stay byte-compatible with the pre-fleet shape
                snap["version"] = r.version
                snap["target_version"] = r.target_version
            if r.last_error:
                snap["last_error"] = r.last_error
            out[f"r{r.rid}"] = snap
        return out

    def telemetry_summary(self) -> Optional[Dict]:
        """Shared-recorder summary plus the merged per-replica compile report
        (watch names are namespace-prefixed, so merging never collides)."""
        if not self._obs_on:
            return None
        out = self._obs.summary()
        per_fn: Dict = {}
        unexpected: List = []
        backend = 0
        for r in self.replicas:
            if r.retired or r.engine.watchdog is None:
                continue
            s = r.engine.watchdog.summary()
            per_fn.update(s["per_function"])
            unexpected.extend(s["unexpected"])
            backend = max(backend, s.get("backend_compiles", 0))
        out["compile"] = {
            "per_function": per_fn,
            "backend_compiles": backend,
            "unexpected": unexpected,
        }
        return out

    def close(self) -> None:
        """Release every replica's observability resources, the router's
        metrics handle, and — when the router created the shared recorder —
        the recorder itself. Idempotent."""
        restore_preemption_handler(self._preempt_handler, self._preempt_previous)
        self._preempt_handler = None
        for r in self.replicas:
            r.engine.close()
        if self._router_journal is not None:
            try:
                self._router_journal.close()
            except Exception:  # noqa: BLE001 — close is best-effort teardown
                pass
        self.metrics.close()
        if self._owns_telemetry:
            self._obs.close()
