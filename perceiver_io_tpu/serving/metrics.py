"""Engine observability: counters, timers, and a JSONL event log.

The metrics layer is deliberately jax-free (a dict + an append-only
JSONL file, numpy only for percentiles) so bench drivers can pin numbers
without scraping stdout:
``scripts/serve_bench.py`` embeds ``EngineMetrics.snapshot()`` verbatim in
its artifact, and ``docs/serving.md`` documents the schema.

Two throughput views are reported because they answer different questions:
  * ``decode_tokens_per_s``  — useful tokens per second of *decode step* time
    (the steady-state serving rate the batch geometry buys).
  * ``wall_tokens_per_s``    — useful tokens per second of wall clock between
    the first submit and the snapshot (what a client actually observes,
    including prefill, scheduling, and host bookkeeping).

Schema history:
  * ``serving-metrics/v1`` — counters + ``queue_wait_s.{mean,max}``.
  * ``serving-metrics/v2`` — adds p50/p95 latency percentiles for queue wait,
    prefill dispatch, and decode step (``queue_wait_s``/``prefill_s``/
    ``decode_step_s`` sub-dicts; ALL latency stats incl. mean/max cover the
    most recent ``LATENCY_WINDOW`` events, where v1's mean/max were
    lifetime) and a per-admission ``bucket`` field on ``admit`` events (the
    bucketed-prefill ladder). With non-blocking
    admission ``prefill_s`` measures DISPATCH time — device prefill cost
    lands in the next decode-step sync.
  * ``serving-metrics/v3`` — adds the admission-control outcome counters
    ``rejected`` (queue bound / over-long prompt / draining engine),
    ``timed_out`` (deadline expiry, queued or running), and ``failed``
    (non-finite-logits containment) to snapshots, plus ``reject`` events and
    a ``status`` field on ``finish`` events (docs/reliability.md).
    ``queue_depth`` was already snapshotted. ``load_metrics_jsonl`` reads all
    versions (older snapshots are normalized with ``None`` for the fields
    their writers did not record).
  * ``serving-metrics/v4`` — the multi-replica schema (docs/serving.md,
    router section): snapshots gain ``failovers`` (requests re-dispatched to
    a surviving replica after their engine was lost), ``shed_infeasible``
    (admission-time SLO sheds — deadlines the windowed latency estimates say
    cannot be met), and ``breaker_transitions`` (circuit-breaker state-change
    counters keyed ``"closed->open"`` etc.). Router snapshots additionally
    carry a ``replicas`` section mapping replica name -> that engine's own
    snapshot, and the router JSONL stream adds ``dispatch`` / ``failover`` /
    ``shed`` / ``breaker`` events. Plain-engine snapshots report the new
    counters as 0 (an engine cannot fail over or shed by estimate); the
    reader normalizes v3-and-older snapshots with ``None`` — "not recorded"
    stays distinguishable from "none happened", the v2->v3 discipline.
  * ``serving-metrics/v5`` — the paged-KV schema (docs/serving.md, paging
    section): every snapshot carries a ``page_pool`` field — ``None`` where
    no pool exists (a router's own snapshot), else a dict of
    ``pages_total`` / ``pages_in_use`` / ``alloc_failures`` (head-of-line
    blocking episodes — a request's reservation did not fit the free list) /
    ``pages_per_request`` p50/p95 over the latency window. ``admit`` events
    gain a ``pages`` field (the request's reservation) and the stream gains
    ``alloc_failure`` events. Router snapshots report ``page_pool: None``
    (pools are per-engine; the embedded replica sections carry the real
    gauges). The reader normalizes pre-v5 snapshots with ``None``.
  * ``serving-metrics/v6`` — the priority/preemption schema (docs/serving.md,
    "Priority classes & preemption"): snapshots gain ``preemptions`` (running
    slots evicted under priority pressure), ``preempted_replays`` (preempted
    continuations re-admitted as forced replays), and
    ``queue_wait_by_priority`` (per-priority-class submit→admit p50/p95 over
    the latency window; ``None`` on router snapshots — queue waits are
    measured per engine, the replica sections carry the real stats). The
    stream gains ``preempt`` events, ``submit`` events carry ``priority``,
    and ``admit`` events carry ``priority`` (+ ``preempted_replay: true`` on
    a resume). Router snapshots aggregate ``preemptions`` /
    ``preempted_replays`` over their replica sections. The reader normalizes
    pre-v6 snapshots with ``None`` — the v2→v3 discipline throughout.
  * ``serving-metrics/v7`` — the crash-durability schema (docs/serving.md,
    "Request journal"): every snapshot carries a ``journal`` field — ``None``
    on engines running without a write-ahead journal (and on router
    snapshots: journals are per-engine, the replica sections carry the real
    gauges), else a dict of ``bytes_written`` / ``records_appended`` /
    ``fsyncs`` / ``compactions`` / ``live_sessions`` / ``generation`` /
    ``sessions_recovered`` / ``replayed_tokens``. The stream gains a
    ``recovery`` event (sessions recovered, replayed tokens, torn-tail
    truncation stats) emitted by ``ServingEngine.recover``. The reader
    normalizes pre-v7 snapshots with ``None``.
  * ``serving-metrics/v8`` — the chunked-prefill + prefix-cache schema
    (docs/serving.md "Chunked prefill" / "Prefix cache"): every snapshot
    carries a ``prefix_cache`` field — ``None`` on engines without the
    radix cache (and on router snapshots — caches are per-replica, the
    replica sections carry the real gauges), else ``hits`` / ``misses`` /
    ``hit_rate`` / ``cached_pages`` / ``shared_pages_in_use`` /
    ``inserted_pages`` / ``evicted_pages`` / ``evictions`` — and a
    ``chunked_prefill`` field — ``None`` unless the engine runs chunked
    admission, else ``chunk_tokens`` / ``chunks_dispatched`` /
    ``chunked_admissions``. The stream gains ``prefix_hit`` events (shared
    pages + tokens a new request reused), ``prefix_evict`` events
    (refcount-aware LRU reclaims under pool pressure), and ``chunk`` events
    (one per dispatched prefill chunk); ``admit`` events gain ``chunks``
    and ``shared_pages`` fields on chunked/shared admissions. The reader
    normalizes pre-v8 snapshots with ``None`` for both sections — "not
    recorded" stays distinguishable from "feature off", the v2→v3
    discipline throughout.
  * ``serving-metrics/v9`` — the quantized-serving schema (docs/serving.md
    "Quantized KV pages & weight serving"): every snapshot carries a
    ``kv_quant`` field — ``None`` on engines serving full-precision pages
    (and on router snapshots — pools are per-engine, the replica sections
    carry the real gauges), else ``mode`` ("int8"), ``bytes_per_token_fp``
    / ``bytes_per_token`` (K+V bytes one resident token costs,
    full-precision vs quantized, per-page-per-head scale sidecars
    amortized over the page), and greedy-agreement sample counters
    ``agreement_tokens`` / ``agreement_matched`` / ``agreement_rate``
    (populated by harnesses running a quantized arm against an fp
    reference — ``serve_bench --kv-quant``; rate ``None`` when unsampled) —
    and a ``weight_serving`` field — ``None`` when params are served
    untouched, else ``dtype`` ("bf16"|"int8") / ``param_bytes`` /
    ``param_bytes_fp``. The reader normalizes pre-v9 snapshots with
    ``None`` for both sections — the v2→v3 discipline throughout.
  * ``serving-metrics/v10`` — the fleet-operations schema (docs/serving.md
    "Fleet operations"): every snapshot carries a ``fleet_ops`` field —
    ``None`` on plain engines (fleet lifecycle is a ROUTER behavior; also
    the reading of every pre-v10 snapshot), else a dict of ``migrations``
    (planned cross-replica session moves), ``recycles`` (replicas drained
    and rebuilt by rolling restart), ``scale_ups`` / ``scale_downs``
    (autoscaler replica-count changes), ``replicas_active`` (replicas
    currently serving — retired ones excluded), ``restart_in_progress``,
    and ``rollout`` — ``None`` with a single param version, else
    ``primary_version`` / ``rollout_version`` / ``fraction`` and a
    per-version ``versions`` table ({version: {submitted, finished,
    tokens_generated}}). The stream gains ``migrate`` / ``recycle`` /
    ``deploy`` / ``rollback`` / ``autoscale`` events, and ``submit`` /
    ``finish`` events on version-pinned routers carry a ``version`` field.
    The reader normalizes pre-v10 snapshots with ``None``.
  * ``serving-metrics/v11`` — the unified-ragged-tick schema (docs/serving.md
    "Unified ragged tick"): every snapshot carries a ``ragged_tick`` field —
    ``None`` on router snapshots (tick dispatch is per-engine), else
    ``enabled`` (True on every engine: the fused tick is its one dispatcher), ``ticks`` (dispatching ticks recorded),
    ``programs_per_tick`` p50/p95 (the headline gauge: 1 steady-state;
    short prompts' prefill + install and evictions add theirs),
    ``chunk_items`` / ``finish_items`` / ``decode_items`` p50/p95 (the
    mixed-batch composition per tick), and ``descriptor_build_s`` p50/p95
    (host-side lane packing). The stream is unchanged — the block is
    windowed gauges only. The reader normalizes pre-v11 snapshots with
    ``None``. Added since, without a version of their own (additive keys):
    ``resident_descriptor_tick_pct`` (share of the fused tick's dispatches,
    over the latency window, that passed the device-resident decode-only
    descriptor; ``None`` before the first), ``descriptor_transfers``
    p50/p95 (host-to-device transfers of the descriptor a tick: 0 or 1),
    ``slots`` (the pool's size) and ``decoding_slots`` mean/p50/p95 (the
    slots a tick decodes: what the decode kernels' time follows), ``lanes``
    (the tick program's compiled lane count: the descriptor's size) and
    ``chunk_lanes`` mean/p50/p95 (the chunk lanes a tick carried, over the
    ticks that carried one: the trips of the model's chunk phase) and
    ``riding_chunk_lanes`` (a total: the chunk lanes that rode the decode
    step's pass over the layers instead of a trip of their own,
    models/core/serving_api.py (h); 0 for a model that does not state it).
  * ``serving-metrics/v12`` — the out-of-process-replica schema
    (docs/serving.md "Out-of-process replicas"): every snapshot carries a
    ``transport`` field — ``None`` on plain engines and on in-process
    routers (no RPC boundary exists), else the fleet-aggregated gauges
    ``rpcs`` / ``retries`` / ``timeouts`` (recv timeouts observed) /
    ``frames_sent`` / ``frames_recv`` / ``bytes_sent`` / ``bytes_recv`` /
    ``workers_alive`` / ``rpc_p50_ms`` / ``rpc_p95_ms`` (pooled over the
    latency window) / ``worker_respawns`` (dead worker processes the
    supervisor respawned through journal recovery). The stream gains
    ``respawn`` events (one per supervisor respawn) and ``rpc_retry``
    events (one per transport retry, with op/attempt/error/delay). The
    reader normalizes pre-v12 snapshots with ``None``.
  * ``serving-metrics/v13`` — the request-life schema (docs/observability.md
    "A request's life"): the ENGINE stamps when a slot was claimed and when a
    request's first free-running token left it (``ServedRequest
    .slot_claimed_at`` / ``.first_token_at``), and every engine snapshot
    reports, over the latency window, ``first_token_s`` (first token minus
    slot claim: the prefill layer's latency, chunk ticks included),
    ``ttft_s`` (first token minus enqueue) and ``inter_token_s`` (gap
    between one request's successive tokens; one stamp per tick, taken right
    after the tick's sync), plus the lifetime counters
    ``prefix_hit_tokens`` (prompt tokens served from shared prefix pages)
    and ``prompt_tokens_admitted`` (prompt tokens of every decode-ready
    admission). ``queue_wait_s`` now spans enqueue to SLOT CLAIM on both
    admission paths (the one-shot path used to stamp it after its prefill
    dispatch). The stream gains ``first_token`` events. Router snapshots
    carry the three windows as ``None`` (measured per engine) and sum the two
    counters over their replica sections. No reader back-fill: a pre-v13
    snapshot simply lacks the keys.
"""

from __future__ import annotations

import json
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Tuple

import numpy as np

SCHEMA = "serving-metrics/v13"
KNOWN_SCHEMAS = (
    "serving-metrics/v1",
    "serving-metrics/v2",
    "serving-metrics/v3",
    "serving-metrics/v4",
    "serving-metrics/v5",
    "serving-metrics/v6",
    "serving-metrics/v7",
    "serving-metrics/v8",
    "serving-metrics/v9",
    "serving-metrics/v10",
    "serving-metrics/v11",
    "serving-metrics/v12",
    "serving-metrics/v13",
)
_V3_COUNTERS = ("rejected", "timed_out", "failed")
_V4_FIELDS = ("failovers", "shed_infeasible", "breaker_transitions")
_V6_FIELDS = ("preemptions", "preempted_replays", "queue_wait_by_priority")
_V8_FIELDS = ("prefix_cache", "chunked_prefill")
_V9_FIELDS = ("kv_quant", "weight_serving")
_PRE_V5 = KNOWN_SCHEMAS[:4]
_PRE_V6 = KNOWN_SCHEMAS[:5]
_PRE_V7 = KNOWN_SCHEMAS[:6]
_PRE_V8 = KNOWN_SCHEMAS[:7]
_PRE_V9 = KNOWN_SCHEMAS[:8]
_PRE_V10 = KNOWN_SCHEMAS[:9]
_PRE_V11 = KNOWN_SCHEMAS[:10]
_PRE_V12 = KNOWN_SCHEMAS[:11]

_PERCENTILE_KEYS = ("p50", "p95")
_MEAN_AND_PERCENTILE_KEYS = ("mean",) + _PERCENTILE_KEYS

# Latency histories are bounded ring buffers: a long-lived engine records one
# decode-step sample per generated token forever, so unbounded lists would be
# a slow host-memory leak and snapshot() would sort ever-growing history. ALL
# latency statistics (mean/max/p50/p95) therefore describe the most recent
# window — v1's mean/max were lifetime — while the scalar counters
# (requests, tokens, *_seconds) remain lifetime totals.
LATENCY_WINDOW = 4096


def _latency_dict(xs) -> Dict[str, float]:
    if not xs:
        return {"mean": 0.0, "max": 0.0, "p50": 0.0, "p95": 0.0}
    arr = list(xs)
    p50, p95 = np.percentile(arr, [50, 95])
    return {
        "mean": round(sum(arr) / len(arr), 6),
        "max": round(max(arr), 6),
        "p50": round(float(p50), 6),
        "p95": round(float(p95), 6),
    }


def _load_max_over_mean(assignments: np.ndarray) -> float:
    """The busiest expert's assignments over its layer's mean, the worst layer's
    (1.0: perfectly even; 0.0 before any assignment)."""
    mean = assignments.mean(axis=-1)
    if not (mean > 0).any():
        return 0.0
    return round(float((assignments.max(axis=-1)[mean > 0] / mean[mean > 0]).max()), 6)


def load_metrics_jsonl(path: str) -> Dict:
    """Version-tolerant reader for engine JSONL logs.

    Returns ``{"events": [...], "snapshots": [...]}`` where every snapshot is
    normalized to the v2 shape: v1 snapshots (no percentile sub-dicts) get
    ``prefill_s``/``decode_step_s`` filled with ``None`` values and their
    ``queue_wait_s`` dict extended with ``p50: None, p95: None``. Unknown
    schema strings raise ``ValueError`` (corrupt/foreign files fail loudly,
    missing fields of known versions do not)."""
    events: List[Dict] = []
    snapshots: List[Dict] = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            record = json.loads(line)
            events.append(record)
            if record.get("event") != "snapshot":
                continue
            schema = record.get("schema")
            if schema not in KNOWN_SCHEMAS:
                raise ValueError(f"unknown metrics schema {schema!r} in {path}")
            snap = dict(record)
            if schema == "serving-metrics/v1":
                wait = dict(snap.get("queue_wait_s") or {})
                for k in _PERCENTILE_KEYS:
                    wait.setdefault(k, None)
                wait.setdefault("mean", None)
                wait.setdefault("max", None)
                snap["queue_wait_s"] = wait
                none_lat = {"mean": None, "max": None, "p50": None, "p95": None}
                snap.setdefault("prefill_s", dict(none_lat))
                snap.setdefault("decode_step_s", dict(none_lat))
            if schema in ("serving-metrics/v1", "serving-metrics/v2"):
                # pre-v3 writers had no admission-control outcomes: None, not
                # 0 — "not recorded" must stay distinguishable from "none"
                for k in _V3_COUNTERS:
                    snap.setdefault(k, None)
            if schema in ("serving-metrics/v1", "serving-metrics/v2", "serving-metrics/v3"):
                # pre-v4 writers had no multi-replica counters: same None
                # discipline (a v3 engine never measured failovers — it did
                # not run zero of them)
                for k in _V4_FIELDS:
                    snap.setdefault(k, None)
            if schema in _PRE_V5:
                # pre-v5 writers had no page pool; None also matches a
                # router's truthful "no pool exists"
                snap.setdefault("page_pool", None)
            if schema in _PRE_V6:
                # pre-v6 writers had no priority/preemption counters: None,
                # not 0 — "not recorded" stays distinguishable from "none"
                for k in _V6_FIELDS:
                    snap.setdefault(k, None)
            if schema in _PRE_V7:
                # pre-v7 writers had no request journal; None also matches a
                # newer engine's truthful "no journal configured"
                snap.setdefault("journal", None)
            if schema in _PRE_V8:
                # pre-v8 writers had neither a prefix cache nor chunked
                # prefill: None, NOT 0 — "not recorded" must stay
                # distinguishable from "feature off / nothing happened"
                for k in _V8_FIELDS:
                    snap.setdefault(k, None)
            if schema in _PRE_V9:
                # pre-v9 writers served full-precision pages and untouched
                # params; None also matches a newer fp engine's truthful
                # "quantization off"
                for k in _V9_FIELDS:
                    snap.setdefault(k, None)
            if schema in _PRE_V10:
                # pre-v10 writers had no fleet-operations layer; None also
                # matches a newer plain engine's truthful "no fleet"
                snap.setdefault("fleet_ops", None)
            if schema in _PRE_V11:
                # pre-v11 writers had no unified ragged tick; None also
                # matches a router's truthful "no tick dispatcher"
                snap.setdefault("ragged_tick", None)
            if schema in _PRE_V12:
                # pre-v12 writers had no out-of-process transport; None also
                # matches a newer in-process fleet's truthful "no RPC
                # boundary exists"
                snap.setdefault("transport", None)
            snapshots.append(snap)
    return {"events": events, "snapshots": snapshots}


class _JsonlMetrics:
    """Shared JSONL-emitter plumbing for ``EngineMetrics``/``RouterMetrics``:
    one line-buffered append handle for the owner's lifetime, terminal
    idempotent ``close()``, and shutdown-race-guarded teardown. Subclasses are
    dataclasses providing ``jsonl_path``/``_jsonl_file``/``_closed`` fields."""

    def _emit(self, event: str, **fields) -> None:
        if self.jsonl_path is None or self._closed:
            # a closed metrics object silently drops events instead of
            # resurrecting its handle: close() is a real end-of-life, and an
            # _emit racing interpreter teardown must not call open()
            return
        if self._jsonl_file is None:
            # one line-buffered handle for the owner's lifetime: _emit runs
            # once per decoded token, so per-event open/close syscalls would
            # tax the hot decode loop; line buffering keeps readers current
            self._jsonl_file = open(self.jsonl_path, "a", buffering=1)
        record = {"event": event, "ts": round(time.time(), 6), **fields}
        self._jsonl_file.write(json.dumps(record) + "\n")

    def _route_status(self, status: str) -> None:
        """Route one terminal outcome into the shared counter fields. Both
        metrics classes carry the same four counters; ONE router keeps the
        JSONL status strings and the snapshot counters from diverging (an
        eviction recorded as "rejected" must never count as finished)."""
        if status == "timed_out":
            self.requests_timed_out += 1
        elif status == "failed":
            self.requests_failed += 1
        elif status == "rejected":
            self.requests_rejected += 1
        else:
            self.requests_finished += 1

    def close(self) -> None:
        """Release the JSONL handle. Terminal and idempotent: a second close
        is a no-op, and later ``_emit`` calls are dropped instead of
        resurrecting the handle. Guarded against interpreter-shutdown races —
        ``getattr`` with a True default means a close racing module teardown
        (``__del__`` during finalization, partially torn-down instance) bails
        out instead of raising."""
        if getattr(self, "_closed", True):
            return
        self._closed = True
        f = self._jsonl_file
        self._jsonl_file = None
        if f is not None:
            try:
                f.close()
            except Exception:
                pass  # a handle torn down by interpreter exit is already closed

    def __del__(self):  # best-effort backstop; close() is the real contract
        try:
            self.close()
        except Exception:
            pass


@dataclass
class EngineMetrics(_JsonlMetrics):
    """Mutable counters owned by one ``ServingEngine``; never touches jax."""

    num_slots: int
    jsonl_path: Optional[str] = None

    requests_submitted: int = 0
    requests_admitted: int = 0
    requests_finished: int = 0  # successful completions (eos / length)
    requests_rejected: int = 0  # refused at submit (queue bound, prompt, drain)
    requests_timed_out: int = 0  # deadline expiry, queued or running
    requests_failed: int = 0  # evicted by non-finite-logits containment
    tokens_generated: int = 0  # useful tokens only (active slots)
    decode_steps: int = 0
    prefills: int = 0
    decode_seconds: float = 0.0
    prefill_seconds: float = 0.0
    queue_depth: int = 0
    # page-pool gauges (serving-metrics/v5): pages_total None <=> no pool
    # (a router's own metrics) and snapshots report page_pool: None
    pages_total: Optional[int] = None
    pages_in_use: int = 0
    alloc_failures: int = 0  # head-of-line blocking episodes on the free list
    # priority/preemption counters (serving-metrics/v6, docs/serving.md)
    preemptions: int = 0  # running slots evicted under priority pressure
    preempted_replays: int = 0  # preempted continuations re-admitted (replay)
    # write-ahead journal gauges (serving-metrics/v7): None <=> the engine
    # runs without a journal and snapshots report journal: None
    journal: Optional[Dict] = None
    # prefix-cache gauges (serving-metrics/v8): None <=> no radix cache
    # configured; the engine mirrors PrefixCache.stats() here per tick,
    # plus the live shared-page gauge
    prefix_cache: Optional[Dict] = None
    # chunked-prefill counters (serving-metrics/v8): chunk_tokens None <=>
    # chunked admission off and snapshots report chunked_prefill: None
    chunk_tokens: Optional[int] = None
    chunks_dispatched: int = 0
    chunked_admissions: int = 0
    # quantized-serving gauges (serving-metrics/v9): mode None <=> fp pages
    # and snapshots report kv_quant: None; agreement counters are fed by
    # quant-vs-fp harnesses (serve_bench --kv-quant), 0/unsampled otherwise
    kv_quant_mode: Optional[str] = None
    kv_bytes_per_token_fp: Optional[float] = None
    kv_bytes_per_token: Optional[float] = None
    agreement_tokens: int = 0
    agreement_matched: int = 0
    # weight-serving gauges (serving-metrics/v9): None <=> params untouched
    weight_serving: Optional[Dict] = None
    # unified-ragged-tick gauges (serving-metrics/v11): ragged_enabled None
    # <=> no tick dispatcher (a router's own metrics) and snapshots report
    # ragged_tick: None; True on every engine
    ragged_enabled: Optional[bool] = None
    ragged_lanes: Optional[int] = None  # the tick program's compiled lane count
    ragged_ticks: int = 0
    riding_chunk_lanes: int = 0
    _tick_program_counts: Deque[int] = field(default_factory=lambda: deque(maxlen=LATENCY_WINDOW))
    _tick_chunk_counts: Deque[int] = field(default_factory=lambda: deque(maxlen=LATENCY_WINDOW))
    _tick_finish_counts: Deque[int] = field(default_factory=lambda: deque(maxlen=LATENCY_WINDOW))
    _tick_decode_counts: Deque[int] = field(default_factory=lambda: deque(maxlen=LATENCY_WINDOW))
    _tick_build_times: Deque[float] = field(default_factory=lambda: deque(maxlen=LATENCY_WINDOW))
    # host-to-device transfers of the fused tick's descriptor, per dispatch
    # of the tick program: 0 (the resident decode-only descriptor) or 1
    _tick_transfer_counts: Deque[int] = field(default_factory=lambda: deque(maxlen=LATENCY_WINDOW))
    # recurrent-state gauges: None <=> the served model keeps no recurrent
    # state beside its pages and snapshots report recurrent_state: None
    recurrent_state_bytes: Optional[int] = None
    recurrent_resets: int = 0
    recurrent_chunks_carried: int = 0
    # routed-expert gauges: None <=> the served model has no expert layer (or
    # counts nothing) and snapshots report experts: None. (expert layers,
    # experts) int64 sums of what the ticks' counters returned, and per
    # decoding tick the mean number of experts a layer that received a row
    expert_assignments: Optional[np.ndarray] = None
    # (first, count) of the counters' experts that are held here, and the decode
    # steps' assignments that fell to them / to any expert, since the start
    experts_held: Tuple[int, int] = (0, 0)
    decode_assignments_held: int = 0
    decode_assignments: int = 0
    _experts_touched: Deque[float] = field(default_factory=lambda: deque(maxlen=LATENCY_WINDOW))
    _start_time: Optional[float] = None
    _occupancy_sum: float = 0.0  # sum over steps of active_slots / num_slots
    _pages_per_request: Deque[int] = field(default_factory=lambda: deque(maxlen=LATENCY_WINDOW))
    _queue_waits_by_priority: Dict[int, Deque] = field(default_factory=dict)
    _queue_waits: Deque[float] = field(default_factory=lambda: deque(maxlen=LATENCY_WINDOW))
    _prefill_times: Deque[float] = field(default_factory=lambda: deque(maxlen=LATENCY_WINDOW))
    _decode_times: Deque[float] = field(default_factory=lambda: deque(maxlen=LATENCY_WINDOW))
    # a request's life, stamped by the engine (serving-metrics/v13)
    prefix_hit_tokens: int = 0  # prompt tokens served from shared prefix pages
    prompt_tokens_admitted: int = 0  # prompt tokens of decode-ready admissions
    _first_token_times: Deque[float] = field(default_factory=lambda: deque(maxlen=LATENCY_WINDOW))
    _ttfts: Deque[float] = field(default_factory=lambda: deque(maxlen=LATENCY_WINDOW))
    _inter_token_gaps: Deque[float] = field(default_factory=lambda: deque(maxlen=LATENCY_WINDOW))
    _jsonl_file: Optional[object] = field(default=None, repr=False)
    _closed: bool = field(default=False, repr=False)

    # ------------------------------------------------------------------ events
    def record_submit(self, request_id: int, prompt_len: int,
                      priority: int = 0) -> None:
        if self._start_time is None:
            self._start_time = time.perf_counter()
        self.requests_submitted += 1
        self.queue_depth += 1
        self._emit("submit", request_id=request_id, prompt_len=prompt_len,
                   priority=priority)

    def record_admit(
        self, request_id: int, slot: int, wait_s: float, prefill_s: float,
        bucket: Optional[int] = None, pages: Optional[int] = None,
        priority: int = 0, preempted_replay: bool = False,
        chunks: Optional[int] = None, shared_pages: Optional[int] = None,
        prompt_tokens: int = 0,
    ) -> None:
        """One request became decode-ready. ``wait_s`` spans enqueue to SLOT
        CLAIM, ``prefill_s`` slot claim to decode-ready (dispatch time on the
        one-shot path; every chunk tick on the split path)."""
        self.requests_admitted += 1
        self.prompt_tokens_admitted += prompt_tokens
        self.prefills += 1
        self.prefill_seconds += prefill_s
        self.queue_depth = max(self.queue_depth - 1, 0)
        self._queue_waits.append(wait_s)
        # per-priority-class queue-wait window (serving-metrics/v6): the
        # per-class p50/p95 is what the preemption bench's SLO story ranks on
        self._queue_waits_by_priority.setdefault(
            int(priority), deque(maxlen=LATENCY_WINDOW)
        ).append(wait_s)
        self._prefill_times.append(prefill_s)
        extra = {} if bucket is None else {"bucket": bucket}
        if pages is not None:  # paged engines: the request's page reservation
            self._pages_per_request.append(pages)
            extra["pages"] = pages
        if preempted_replay:  # a preempted continuation re-admitted as replay
            self.preempted_replays += 1
            extra["preempted_replay"] = True
        if chunks is not None:  # v8: a chunk-phased admission's planned chunks
            self.chunked_admissions += 1
            extra["chunks"] = chunks
        if shared_pages:  # v8: prefix-cache pages this admission reused
            extra["shared_pages"] = shared_pages
        self._emit("admit", request_id=request_id, slot=slot,
                   wait_s=round(wait_s, 6), prefill_s=round(prefill_s, 6),
                   priority=priority, **extra)

    def record_chunk(self, request_id: int, slot: int, tokens: int,
                     seconds: float) -> None:
        """One dispatched prefill chunk (serving-metrics/v8): ``tokens`` real
        prompt tokens whose KV rows this tick's chunk program wrote;
        ``seconds`` is DISPATCH time (non-blocking, like prefill_s)."""
        self.chunks_dispatched += 1
        self._emit("chunk", request_id=request_id, slot=slot, tokens=tokens,
                   seconds=round(seconds, 6))

    def record_prefix_hit(self, request_id: int, shared_pages: int,
                          shared_tokens: int) -> None:
        """One prefix-cache HIT at admission (serving-metrics/v8): the new
        request retained ``shared_pages`` cached pages covering
        ``shared_tokens`` prompt tokens — KV it neither recomputes nor
        re-stores."""
        self.prefix_hit_tokens += shared_tokens
        self._emit("prefix_hit", request_id=request_id,
                   shared_pages=shared_pages, shared_tokens=shared_tokens)

    def record_prefix_evict(self, pages_freed: int, pages_needed: int) -> None:
        """One refcount-aware LRU eviction episode under pool pressure
        (serving-metrics/v8): cached-but-unreferenced pages yielded to a live
        reservation before admission saw queue_full."""
        self._emit("prefix_evict", pages_freed=pages_freed,
                   pages_needed=pages_needed)

    def set_prefix_cache(self, stats: Dict, shared_pages_in_use: int) -> None:
        """Refresh the v8 prefix-cache gauges (the engine hands in
        ``PrefixCache.stats()`` plus the live count of table entries
        currently backed by shared pages)."""
        self.prefix_cache = dict(stats)
        self.prefix_cache["shared_pages_in_use"] = shared_pages_in_use

    def set_chunked_prefill(self, chunk_tokens: int) -> None:
        """Mark chunked admission active (serving-metrics/v8): snapshots
        report the chunked_prefill section instead of None."""
        self.chunk_tokens = chunk_tokens

    def set_kv_quant(self, mode: str, bytes_per_token_fp: float,
                     bytes_per_token: float) -> None:
        """Mark quantized KV pages active (serving-metrics/v9): snapshots
        report the kv_quant section — mode plus the per-token KV byte
        economics (scale sidecars amortized) — instead of None."""
        self.kv_quant_mode = mode
        self.kv_bytes_per_token_fp = round(bytes_per_token_fp, 3)
        self.kv_bytes_per_token = round(bytes_per_token, 3)

    def record_quant_agreement(self, matched: int, total: int) -> None:
        """Fold one greedy-agreement sample batch into the v9 counters: a
        harness decoded ``total`` tokens on this quantized engine against an
        fp reference and ``matched`` of them agreed (serve_bench --kv-quant
        feeds this before its terminal snapshot — the agreement rate then
        rides the snapshot instead of living only in a bench artifact)."""
        self.agreement_matched += int(matched)
        self.agreement_tokens += int(total)
        self._emit("quant_agreement", matched=int(matched), total=int(total))

    def set_recurrent_state(self, state_bytes: int) -> None:
        """Mark a model whose every slot holds a recurrent state of fixed size
        beside its pages (docs/serving.md "A slot's two kinds of state"):
        snapshots report the recurrent_state section instead of None.
        ``state_bytes``: what the pool's recurrent states take on the device."""
        self.recurrent_state_bytes = int(state_bytes)

    def set_expert_counters(self, layers: int, experts: int,
                            held: Optional[Tuple[int, int]] = None) -> None:
        """Mark a model whose routed expert layers count their assignments on
        the device (models/core/serving_api.py (f)): snapshots report the
        experts section instead of None. ``held`` = (first, count): the experts
        whose matrices lie here; None: all of them."""
        self.expert_assignments = np.zeros((int(layers), int(experts)), np.int64)
        self.experts_held = (0, int(experts)) if held is None else (int(held[0]), int(held[1]))

    def record_expert_counts(self, counts: np.ndarray) -> Tuple[int, float, int]:
        """One harvested tick's counters, ``counts`` (2, expert layers, experts):
        the assignments each expert received in the tick's decode step (row 0)
        and in chunk lanes since the last harvested tick (row 1). Returns what
        goes on the tick's record: (assignments, both rows; the mean number of
        HELD experts a layer with a row in the DECODE step, the call whose
        weight stream bounds a decoding tick: an expert that lies elsewhere
        is not read here; the decode step's assignments that fell to held
        experts, the ones computed). Windowed, no JSONL event: it fires every
        tick."""
        self.expert_assignments += counts.sum(axis=0)
        first, count = self.experts_held
        held = counts[0, :, first:first + count]
        touched, computed = float((held > 0).sum(axis=-1).mean()), int(held.sum())
        self._experts_touched.append(touched)
        self.decode_assignments_held += computed
        self.decode_assignments += int(counts[0].sum())
        return int(counts.sum()), touched, computed

    def record_recurrent_chunk(self, reset: bool) -> None:
        """One chunk lane of a recurrent model: it either starts its slot's
        state from zero (the slot was just claimed) or carries it on from the
        chunk before. Counters only: this fires a lane, not a request."""
        if reset:
            self.recurrent_resets += 1
        else:
            self.recurrent_chunks_carried += 1

    def set_ragged_tick(self, enabled: bool, lanes: int) -> None:
        """Mark a paged engine's tick dispatcher (serving-metrics/v11):
        snapshots report the ragged_tick section instead of None. Every
        paged engine passes True (the fused tick is its one dispatcher) and
        the lane count its tick program was compiled with."""
        self.ragged_enabled = bool(enabled)
        self.ragged_lanes = int(lanes)

    def record_tick_dispatch(self, programs: int, chunk_items: int,
                             finish_items: int, decode_items: int,
                             build_s: float,
                             descriptor_transfers: Optional[int] = None) -> None:
        """One DISPATCHING tick's program/work accounting (v11): how many
        compiled programs the tick launched (ragged steady-state: exactly 1),
        the tick's mixed-batch composition (prefill chunk lanes, latent
        finish lanes, decoding slots), the host-side descriptor build
        time, and how many
        host-to-device transfers the fused tick's descriptor cost (0: the
        resident decode-only descriptor; 1: a packed one; None: the tick
        dispatched no fused program). Windowed, no JSONL
        event: this fires every tick, and the stream already carries
        decode_step/chunk events for per-tick forensics."""
        self.ragged_ticks += 1
        self._tick_program_counts.append(int(programs))
        self._tick_chunk_counts.append(int(chunk_items))
        self._tick_finish_counts.append(int(finish_items))
        self._tick_decode_counts.append(int(decode_items))
        self._tick_build_times.append(float(build_s))
        if descriptor_transfers is not None:
            self._tick_transfer_counts.append(int(descriptor_transfers))

    def record_riding_chunk_lanes(self, lanes: int) -> None:
        """Chunk lanes of one dispatching tick that rode the decode step's pass
        over the layers (models/core/serving_api.py (h)); a total over the
        engine's life, and never called for a model that does not state it."""
        self.riding_chunk_lanes += int(lanes)

    def set_weight_serving(self, dtype: str, param_bytes: int,
                           param_bytes_fp: int) -> None:
        """Mark the weight-serving transform active (serving-metrics/v9)."""
        self.weight_serving = {
            "dtype": dtype,
            "param_bytes": int(param_bytes),
            "param_bytes_fp": int(param_bytes_fp),
        }

    def record_preempt(self, request_id: int, slot: int, preempted_by: int,
                       pages_freed: int, emitted_tokens: int,
                       priority: int) -> None:
        """One priority preemption: a running slot evicted so a higher-class
        blocked request can admit; the victim re-enters the queue (the
        ``queue_depth`` gauge moves back up) and will re-admit as a forced
        replay (``preempted_replay`` on its next ``admit`` event)."""
        self.preemptions += 1
        self.queue_depth += 1
        self._emit("preempt", request_id=request_id, slot=slot,
                   preempted_by=preempted_by, pages_freed=pages_freed,
                   emitted_tokens=emitted_tokens, priority=priority)

    def record_alloc_failure(self, request_id: int, pages_needed: int, pages_free: int) -> None:
        """One head-of-line BLOCKING EPISODE: the head request's page
        reservation exceeded the free list (backpressure, not an error) — it
        stays queued and retries every tick, but the engine reports each
        blocked request once per episode, not once per tick, so a long block
        cannot flood the JSONL stream or inflate the counter."""
        self.alloc_failures += 1
        self._emit("alloc_failure", request_id=request_id,
                   pages_needed=pages_needed, pages_free=pages_free)

    def set_page_pool(self, total: int, in_use: int) -> None:
        """Refresh the page-pool occupancy gauges (called by the paged engine
        after admissions and evictions change the free list)."""
        self.pages_total = total
        self.pages_in_use = in_use

    def set_journal(self, stats: Dict) -> None:
        """Refresh the v7 journal gauges (the engine hands in
        ``RequestJournal.stats()`` once per tick flush — the snapshot copies
        the latest block verbatim)."""
        self.journal = dict(stats)

    def record_recovery(self, sessions: int, replayed_tokens: int,
                        truncated: bool, dropped_records: int,
                        generation: int) -> None:
        """One process-restart recovery (``ServingEngine.recover``): how many
        live sessions were rebuilt, how many tokens their forced replays
        carry, and whether the read hit a torn tail (with how many records
        it dropped) — the event an operator audits after a crash."""
        self._emit("recovery", sessions=sessions,
                   replayed_tokens=replayed_tokens, truncated=truncated,
                   dropped_records=dropped_records, generation=generation)

    def record_decode_step(self, active_slots: int, seconds: float, tokens: int) -> None:
        self.decode_steps += 1
        self.decode_seconds += seconds
        self.tokens_generated += tokens
        self._occupancy_sum += active_slots / max(self.num_slots, 1)
        self._decode_times.append(seconds)
        self._emit("decode_step", active_slots=active_slots,
                   seconds=round(seconds, 6), tokens=tokens)

    def record_first_token(self, request_id: int, first_token_s: float,
                           ttft_s: float) -> None:
        """A request's first free-running token left the engine
        (serving-metrics/v13): ``first_token_s`` since its slot was claimed
        (the prefill layer's latency), ``ttft_s`` since it was enqueued."""
        self._first_token_times.append(first_token_s)
        self._ttfts.append(ttft_s)
        self._emit("first_token", request_id=request_id,
                   first_token_s=round(first_token_s, 6), ttft_s=round(ttft_s, 6))

    def record_token_gap(self, seconds: float) -> None:
        """Gap between two successive tokens of one request (no event: this
        runs once per occupied slot per tick)."""
        self._inter_token_gaps.append(seconds)

    def record_finish(
        self, request_id: int, slot: int, new_tokens: int, reason: str,
        status: str = "finished",
    ) -> None:
        """Terminal event for a request that held a slot. ``status`` routes
        the counter: "finished" (success), "timed_out", "failed", or
        "rejected" (a cancelled-while-running eviction)."""
        self._route_status(status)
        self._emit("finish", request_id=request_id, slot=slot,
                   new_tokens=new_tokens, reason=reason, status=status)

    def record_reject(self, request_id: int, reason: str) -> None:
        """Terminal event for a request refused admission (it was submitted —
        ``record_submit`` counted it and bumped ``queue_depth`` — but never
        reached a slot)."""
        self.requests_rejected += 1
        self.queue_depth = max(self.queue_depth - 1, 0)
        self._emit("reject", request_id=request_id, reason=reason)

    def record_timeout_queued(self, request_id: int, reason: str = "deadline",
                              new_tokens: int = 0) -> None:
        """Terminal event for a QUEUED request whose deadline expired while
        waiting. ``new_tokens`` is nonzero for a PREEMPTED continuation that
        held a slot before parking — its decode work must not vanish from
        the event stream."""
        self.record_evict_queued(request_id, reason, status="timed_out",
                                 new_tokens=new_tokens)

    def record_evict_queued(self, request_id: int, reason: str, status: str,
                            new_tokens: int = 0) -> None:
        """Terminal event for a QUEUED request evicted before (re)reaching a
        slot (deadline expiry, cancellation, failover reclaim). ``status``
        routes the counter exactly as ``record_finish`` does for
        slot-holders; ``new_tokens`` carries the tokens a preempted
        continuation emitted before it was parked (0 for never-admitted
        requests), so the terminal event agrees with the handle and with the
        ``preempt`` event's ``emitted_tokens``."""
        self._route_status(status)
        self.queue_depth = max(self.queue_depth - 1, 0)
        self._emit("finish", request_id=request_id, slot=None,
                   new_tokens=new_tokens, reason=reason, status=status)

    # ---------------------------------------------------------------- snapshot
    def latency_estimates(self) -> Optional[Dict[str, float]]:
        """Windowed p95s for the router's SLO feasibility estimate
        (serving/router.py): queue wait, prefill dispatch, decode step, plus
        the lifetime decode-step count as the warm-up gate. None until the
        engine has decoded at all — cold estimates must never drive
        admission decisions. Cheaper than ``snapshot()`` (three percentiles,
        no dict assembly) because the router may call it per submit."""
        if not self._decode_times:
            return None
        return {
            "queue_wait_p95_s": float(np.percentile(list(self._queue_waits), 95))
            if self._queue_waits else 0.0,
            "prefill_p95_s": float(np.percentile(list(self._prefill_times), 95))
            if self._prefill_times else 0.0,
            "decode_step_p95_s": float(np.percentile(list(self._decode_times), 95)),
            "decode_steps": self.decode_steps,
        }

    def snapshot(self) -> Dict:
        wall = (time.perf_counter() - self._start_time) if self._start_time else 0.0
        decoding_slots = {  # slots a dispatching tick decodes, latency window
            k: v for k, v in _latency_dict(self._tick_decode_counts).items()
            if k in _MEAN_AND_PERCENTILE_KEYS
        }
        snap = {
            "schema": SCHEMA,
            "num_slots": self.num_slots,
            "requests_submitted": self.requests_submitted,
            "requests_admitted": self.requests_admitted,
            "requests_finished": self.requests_finished,
            "rejected": self.requests_rejected,
            "timed_out": self.requests_timed_out,
            "failed": self.requests_failed,
            # v4 fields, constant at a single engine: failing over, shedding
            # by estimate, and breaker state are ROUTER behaviors — 0 here
            # (truthfully "none happened"), real values in RouterMetrics
            "failovers": 0,
            "shed_infeasible": 0,
            "breaker_transitions": {},
            "queue_depth": self.queue_depth,
            "tokens_generated": self.tokens_generated,
            "decode_steps": self.decode_steps,
            "prefills": self.prefills,
            "decode_seconds": round(self.decode_seconds, 6),
            "prefill_seconds": round(self.prefill_seconds, 6),
            "wall_seconds": round(wall, 6),
            "decode_tokens_per_s": round(self.tokens_generated / self.decode_seconds, 3)
            if self.decode_seconds > 0 else 0.0,
            "wall_tokens_per_s": round(self.tokens_generated / wall, 3) if wall > 0 else 0.0,
            "mean_slot_occupancy": round(self._occupancy_sum / self.decode_steps, 4)
            if self.decode_steps > 0 else 0.0,
            "queue_wait_s": _latency_dict(self._queue_waits),
            "prefill_s": _latency_dict(self._prefill_times),
            "decode_step_s": _latency_dict(self._decode_times),
            # v13: a request's life as the engine stamps it
            "first_token_s": _latency_dict(self._first_token_times),
            "ttft_s": _latency_dict(self._ttfts),
            "inter_token_s": _latency_dict(self._inter_token_gaps),
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "prompt_tokens_admitted": self.prompt_tokens_admitted,
            # v6 (docs/serving.md, priority section): preemption counters +
            # per-class queue-wait percentiles over the latency window
            "preemptions": self.preemptions,
            "preempted_replays": self.preempted_replays,
            "queue_wait_by_priority": {
                str(p): {k: v for k, v in _latency_dict(xs).items()
                         if k in _PERCENTILE_KEYS}
                for p, xs in sorted(self._queue_waits_by_priority.items())
            },
            # v7: None without a write-ahead journal (same reading as a
            # pre-v7 snapshot), the live gauge block otherwise
            "journal": None if self.journal is None else dict(self.journal),
            # v8: None without a radix prefix cache / without chunked
            # admission (same reading as a pre-v8 snapshot), live otherwise
            "prefix_cache": None if self.prefix_cache is None
            else dict(self.prefix_cache),
            "chunked_prefill": None if self.chunk_tokens is None else {
                "chunk_tokens": self.chunk_tokens,
                "chunks_dispatched": self.chunks_dispatched,
                "chunked_admissions": self.chunked_admissions,
            },
            # v9: None on fp-page engines / untouched params (same reading
            # as a pre-v9 snapshot), the quantized-serving gauges otherwise
            "kv_quant": None if self.kv_quant_mode is None else {
                "mode": self.kv_quant_mode,
                "bytes_per_token_fp": self.kv_bytes_per_token_fp,
                "bytes_per_token": self.kv_bytes_per_token,
                "agreement_tokens": self.agreement_tokens,
                "agreement_matched": self.agreement_matched,
                "agreement_rate": round(
                    self.agreement_matched / self.agreement_tokens, 4
                ) if self.agreement_tokens else None,
            },
            "weight_serving": None if self.weight_serving is None
            else dict(self.weight_serving),
            # v10: fleet lifecycle (migration / rolling restart / rollout /
            # autoscale) is a ROUTER behavior — a plain engine truthfully
            # has none (same reading as a pre-v10 snapshot)
            "fleet_ops": None,
            # v12: the RPC transport is a ROUTER/client behavior — a plain
            # engine truthfully has no process boundary (same reading as a
            # pre-v12 snapshot)
            "transport": None,
            # None unless the served model keeps a recurrent state in each
            # slot: the pool's state bytes, how many slots hold one, how many
            # chunk lanes zeroed a just-claimed slot's state (resets) or
            # carried one on across a chunk boundary, and how many slots a
            # tick decodes (each has its state read and written whole)
            "recurrent_state": None if self.recurrent_state_bytes is None else {
                "bytes": self.recurrent_state_bytes,
                "slots": self.num_slots,
                "resets": self.recurrent_resets,
                "chunks_carried": self.recurrent_chunks_carried,
                "decoding_slots": decoding_slots,
            },
            # None unless the served model has routed expert layers that count
            # on the device: assignments per layer and expert since the start
            # (prefill and decode alike), per decoding tick the experts a layer
            # that received a row in the decode step, and the busiest expert's
            # assignments over the mean of its layer's, the worst layer's;
            # ``held`` the experts whose matrices lie here (``touched_per_step``
            # counts among them) and the share of the decode steps'
            # assignments that fell to them (100 where every expert is held)
            "experts": None if self.expert_assignments is None else {
                "layers": int(self.expert_assignments.shape[0]),
                "experts": int(self.expert_assignments.shape[1]),
                "held": list(self.experts_held),
                "held_assignment_pct": (100.0 * self.decode_assignments_held / self.decode_assignments
                                        if self.decode_assignments else None),
                "assignments": self.expert_assignments.tolist(),
                "touched_per_step": {
                    k: v for k, v in _latency_dict(self._experts_touched).items()
                    if k in _MEAN_AND_PERCENTILE_KEYS
                },
                "load_max_over_mean": _load_max_over_mean(self.expert_assignments),
            },
            # v11: None on a router's own snapshot (no tick dispatcher exists
            # — same reading as a pre-v11 snapshot); on engines the per-tick
            # program/work gauges
            "ragged_tick": None if self.ragged_enabled is None else {
                "enabled": self.ragged_enabled,
                "ticks": self.ragged_ticks,
                "programs_per_tick": {
                    k: v for k, v in _latency_dict(self._tick_program_counts).items()
                    if k in _PERCENTILE_KEYS
                },
                "chunk_items": {
                    k: v for k, v in _latency_dict(self._tick_chunk_counts).items()
                    if k in _PERCENTILE_KEYS
                },
                "finish_items": {
                    k: v for k, v in _latency_dict(self._tick_finish_counts).items()
                    if k in _PERCENTILE_KEYS
                },
                "decode_items": {k: decoding_slots[k] for k in _PERCENTILE_KEYS},
                # the pool's size and the slots a tick decodes (mean too):
                # only those cost the decode kernels bytes or compute
                "slots": self.num_slots,
                "decoding_slots": decoding_slots,
                # the lanes the descriptor has room for against the chunk
                # lanes a tick carried (over the ticks that carried one): a
                # model's chunk phase runs the carried ones only
                "lanes": self.ragged_lanes,
                "chunk_lanes": {
                    k: v for k, v in _latency_dict(
                        [n for n in self._tick_chunk_counts if n]).items()
                    if k in _MEAN_AND_PERCENTILE_KEYS
                },
                # of the chunk lanes the ticks carried, those that rode the
                # decode step's one pass over the layers (a model that states
                # it: models/core/serving_api.py (h)); a total
                "riding_chunk_lanes": self.riding_chunk_lanes,
                "descriptor_build_s": {
                    k: v for k, v in _latency_dict(self._tick_build_times).items()
                    if k in _PERCENTILE_KEYS
                },
                # share of the fused tick's dispatches (latency window) that
                # passed the device-resident decode-only descriptor: nothing
                # packed, nothing sent
                "resident_descriptor_tick_pct": (
                    100.0 * self._tick_transfer_counts.count(0)
                    / len(self._tick_transfer_counts)
                    if self._tick_transfer_counts else None
                ),
                "descriptor_transfers": {
                    k: v for k, v in _latency_dict(self._tick_transfer_counts).items()
                    if k in _PERCENTILE_KEYS
                },
            },
            # v5: None on a router's own snapshot (no pool exists — same
            # reading as a pre-v5 snapshot), real gauges on engines
            "page_pool": None if self.pages_total is None else {
                "pages_total": self.pages_total,
                "pages_in_use": self.pages_in_use,
                "alloc_failures": self.alloc_failures,
                "pages_per_request": {
                    k: v for k, v in _latency_dict(self._pages_per_request).items()
                    if k in ("p50", "p95")
                },
            },
        }
        return snap

    def write_snapshot(self) -> Dict:
        """Append the snapshot as a terminal JSONL event and return it."""
        snap = self.snapshot()
        self._emit("snapshot", **snap)
        return snap


@dataclass
class RouterMetrics(_JsonlMetrics):
    """Counters owned by one ``ServingRouter`` (serving/router.py): the
    router-level outcomes — dispatch, failover, shed, breaker transitions —
    plus per-replica engine snapshots embedded under ``replicas``. The JSONL
    stream interleaves router events (``submit``/``dispatch``/``failover``/
    ``shed``/``breaker``/``finish``) with a terminal v4 ``snapshot``;
    per-engine streams stay separate (``ServingRouter`` forwards its
    ``replica_metrics_jsonl`` template — ``"{i}"`` = replica index — to each
    engine's own JSONL knob)."""

    num_replicas: int
    jsonl_path: Optional[str] = None

    requests_submitted: int = 0
    requests_dispatched: int = 0  # engine submits accepted by a replica
    requests_finished: int = 0
    requests_rejected: int = 0  # all router-level refusals, sheds included
    requests_timed_out: int = 0
    requests_failed: int = 0  # containment + max_failovers exhaustion
    failovers: int = 0  # re-dispatches of a lost replica's live requests
    shed_infeasible: int = 0  # admission-time SLO sheds (subset of rejected)
    breaker_transitions: Dict[str, int] = field(default_factory=dict)
    # fleet-operations counters (serving-metrics/v10, docs/serving.md
    # "Fleet operations"): planned migrations, rolling-restart recycles,
    # autoscaler replica-count changes, and the per-version rollout table
    migrations: int = 0  # planned cross-replica session moves
    recycles: int = 0  # replicas drained + rebuilt (rolling restart)
    scale_ups: int = 0
    scale_downs: int = 0
    replicas_active: Optional[int] = None  # None until the router gauges it
    restart_in_progress: bool = False
    # version -> {"submitted": n, "finished": n, "tokens_generated": n};
    # empty until a second param version exists (single-version fleets
    # report rollout: None — the feature-off reading)
    versions: Dict[str, Dict[str, int]] = field(default_factory=dict)
    rollout_state: Optional[Dict] = None  # {primary_version, rollout_version, fraction}
    # out-of-process transport counters (serving-metrics/v12, docs/serving.md
    # "Out-of-process replicas"): supervisor respawns and transport retries
    # are lifetime totals here; the windowed RPC gauges arrive per tick via
    # set_transport (None in-process — no RPC boundary exists)
    worker_respawns: int = 0
    rpc_retries: int = 0
    transport_state: Optional[Dict] = None
    _start_time: Optional[float] = None
    _jsonl_file: Optional[object] = field(default=None, repr=False)
    _closed: bool = field(default=False, repr=False)

    # ------------------------------------------------------------------ events
    def record_submit(self, request_id: int, prompt_len: int,
                      priority: int = 0, version: Optional[int] = None) -> None:
        if self._start_time is None:
            self._start_time = time.perf_counter()
        self.requests_submitted += 1
        extra = {}
        if version is not None:
            self._version_row(version)["submitted"] += 1
            extra["version"] = version
        self._emit("submit", request_id=request_id, prompt_len=prompt_len,
                   priority=priority, **extra)

    def _version_row(self, version: int) -> Dict[str, int]:
        return self.versions.setdefault(
            str(version), {"submitted": 0, "finished": 0, "tokens_generated": 0}
        )

    def record_dispatch(self, request_id: int, replica: int, load: int) -> None:
        """One accepted hand-off to a replica's engine (initial dispatch or a
        failover re-dispatch); ``load`` is the replica's queue-beyond-capacity
        score at decision time — the dispatch policy's own input, logged so
        imbalance is diagnosable from the stream alone."""
        self.requests_dispatched += 1
        self._emit("dispatch", request_id=request_id, replica=replica, load=load)

    def record_failover(self, request_id: int, from_replica: int,
                        emitted_tokens: int, failover_n: int) -> None:
        self.failovers += 1
        self._emit("failover", request_id=request_id, from_replica=from_replica,
                   emitted_tokens=emitted_tokens, failover_n=failover_n)

    def record_shed(self, request_id: int, deadline_s: float, estimate_s: float) -> None:
        """An admission-time SLO shed: the windowed latency estimate says the
        deadline cannot be met, so the request is REJECTED before it queues
        (``shed_infeasible``) — the estimate is logged with the decision."""
        self.shed_infeasible += 1
        self._emit("shed", request_id=request_id, deadline_s=round(deadline_s, 6),
                   estimate_s=round(estimate_s, 6))

    def record_breaker(self, replica: int, old: str, new: str, tick: int) -> None:
        key = f"{old}->{new}"
        self.breaker_transitions[key] = self.breaker_transitions.get(key, 0) + 1
        self._emit("breaker", replica=replica, transition=key, tick=tick)

    def record_migration(self, request_id: int, src: int, dst: int,
                         emitted_tokens: int) -> None:
        """One PLANNED cross-replica migration (serving-metrics/v10): the
        session left ``src`` through the engine's eviction path and landed on
        ``dst`` as a forced replay of ``emitted_tokens`` tokens — unlike a
        ``failover`` event, no replica was lost and the handle's failover
        budget is untouched."""
        self.migrations += 1
        self._emit("migrate", request_id=request_id, src=src, dst=dst,
                   emitted_tokens=emitted_tokens)

    def record_recycle(self, replica: int, sessions_moved: int,
                       leftover_sessions: int, tick: int) -> None:
        """One rolling-restart recycle: the replica's sessions were migrated
        to siblings (``sessions_moved``), its engine torn down and rebuilt
        (journal-recovered when configured — ``leftover_sessions`` counts
        live journal entries the rebuild re-adopted, normally 0), and the
        replica re-admitted to the fleet."""
        self.recycles += 1
        self._emit("recycle", replica=replica, sessions_moved=sessions_moved,
                   leftover_sessions=leftover_sessions, tick=tick)

    def record_deploy(self, version: int, fraction: float,
                      target_replicas: List[int]) -> None:
        """One ``router.deploy``: a new param version entered the rollout at
        ``fraction`` of new admissions, targeting ``target_replicas``."""
        self.rollout_state = {"rollout_version": version,
                              "fraction": round(float(fraction), 4)}
        self._version_row(version)  # the table shows the version from tick 0
        self._emit("deploy", version=version, fraction=round(float(fraction), 4),
                   target_replicas=list(target_replicas))

    def record_rollback(self, from_version: int, to_version: int) -> None:
        """One ``router.rollback``: new admissions pin ``to_version`` again,
        instantly; in-flight ``from_version`` sessions finish on their pin."""
        self._emit("rollback", from_version=from_version, to_version=to_version)

    def record_autoscale(self, direction: str, replica: int, active: int,
                         load: int, tick: int) -> None:
        """One autoscaler decision ("up" adds/revives a replica, "down"
        retires one through the migrate-and-drain path); ``load`` is the
        fleet-load signal at decision time, logged with the decision."""
        if direction == "up":
            self.scale_ups += 1
        else:
            self.scale_downs += 1
        self.replicas_active = active
        self._emit("autoscale", direction=direction, replica=replica,
                   active=active, load=load, tick=tick)

    def record_respawn(self, replica: int, sessions: int, tick: int) -> None:
        """One supervisor worker respawn (serving-metrics/v12): the
        replica's dead worker PROCESS was replaced and re-attached through
        its own journal recovery — ``sessions`` live sessions came back,
        f64 token-identical, with no breaker strike and no failover spent."""
        self.worker_respawns += 1
        self._emit("respawn", replica=replica, sessions=sessions, tick=tick)

    def record_rpc_retry(self, replica: int, op: str, attempt: int,
                         err: str, delay: float) -> None:
        """One transport-level RPC retry (serving-metrics/v12): attempt
        ``attempt`` of ``op`` on ``replica`` failed with ``err`` and the
        deterministic backoff schedule sleeps ``delay`` before the next."""
        self.rpc_retries += 1
        self._emit("rpc_retry", replica=replica, op=op, attempt=attempt,
                   err=err, delay_s=round(float(delay), 6))

    def set_transport(self, stats: Optional[Dict]) -> None:
        """Refresh the v12 transport gauges (the router aggregates its
        EngineClients' counters per snapshot; None in-process)."""
        self.transport_state = stats

    def set_fleet_gauges(self, replicas_active: int,
                         restart_in_progress: bool,
                         primary_version: Optional[int] = None) -> None:
        """Refresh the v10 fleet gauges (the router calls this per tick).
        ``primary_version`` only surfaces in the snapshot's rollout section
        once a deploy has registered a second version — a single-version
        fleet keeps the feature-off ``rollout: None`` reading."""
        self.replicas_active = replicas_active
        self.restart_in_progress = restart_in_progress
        if primary_version is not None and self.rollout_state is not None:
            self.rollout_state["primary_version"] = primary_version

    def record_finish(self, request_id: int, status: str, reason: Optional[str],
                      new_tokens: int, failovers: int,
                      version: Optional[int] = None) -> None:
        """Terminal router-level outcome (counter routing shared with the
        engine via ``_route_status``; rejected here covers queue/shed/drain
        refusals)."""
        self._route_status(status)
        extra = {}
        if version is not None:
            row = self._version_row(version)
            if status == "finished":
                row["finished"] += 1
            row["tokens_generated"] += int(new_tokens)
            extra["version"] = version
        self._emit("finish", request_id=request_id, status=status, reason=reason,
                   new_tokens=new_tokens, failovers=failovers, **extra)

    # ---------------------------------------------------------------- snapshot
    def snapshot(self, replicas: Optional[Dict[str, Dict]] = None) -> Dict:
        """Router snapshot: router-level counters plus aggregates over the
        per-replica engine snapshots handed in (tokens are generated by
        engines — the router only aggregates; wall-clock is the honest
        denominator because replica decode windows overlap)."""
        wall = (time.perf_counter() - self._start_time) if self._start_time else 0.0
        replicas = replicas or {}
        tokens = sum(s.get("tokens_generated", 0) for s in replicas.values())
        snap = {
            "schema": SCHEMA,
            "num_replicas": self.num_replicas,
            "requests_submitted": self.requests_submitted,
            "requests_dispatched": self.requests_dispatched,
            "requests_finished": self.requests_finished,
            "rejected": self.requests_rejected,
            "timed_out": self.requests_timed_out,
            "failed": self.requests_failed,
            "failovers": self.failovers,
            "shed_infeasible": self.shed_infeasible,
            "breaker_transitions": dict(sorted(self.breaker_transitions.items())),
            # v6: preemptions happen inside engines — the router aggregates
            # its replica sections (0 with no replicas handed in); queue
            # waits are measured per engine, so the per-class stats live in
            # the replica sections (None here, the page_pool discipline)
            "preemptions": sum(s.get("preemptions") or 0 for s in replicas.values()),
            "preempted_replays": sum(
                s.get("preempted_replays") or 0 for s in replicas.values()
            ),
            "queue_wait_by_priority": None,
            # v13: a request's life is stamped per engine (windows None
            # here); the two token counters sum over the replica sections
            "first_token_s": None,
            "ttft_s": None,
            "inter_token_s": None,
            "prefix_hit_tokens": sum(s.get("prefix_hit_tokens") or 0 for s in replicas.values()),
            "prompt_tokens_admitted": sum(
                s.get("prompt_tokens_admitted") or 0 for s in replicas.values()
            ),
            # pools, journals, prefix caches, chunked admission, and the
            # quantized-serving modes are per-engine: the embedded replica
            # sections carry the real gauges, the router itself truthfully
            # has none of them
            "page_pool": None,
            "journal": None,
            "prefix_cache": None,
            "chunked_prefill": None,
            "kv_quant": None,
            "weight_serving": None,
            "ragged_tick": None,
            # v10: the fleet-operations gauges (docs/serving.md "Fleet
            # operations") — the router owns the lifecycle, so unlike the
            # per-engine sections above this one is real HERE. The rollout
            # sub-section stays None until a deploy registers a second
            # param version (the feature-off reading).
            "fleet_ops": {
                "migrations": self.migrations,
                "recycles": self.recycles,
                "scale_ups": self.scale_ups,
                "scale_downs": self.scale_downs,
                "replicas_active": (self.replicas_active
                                    if self.replicas_active is not None
                                    else self.num_replicas),
                "restart_in_progress": self.restart_in_progress,
                "rollout": None if self.rollout_state is None else {
                    **self.rollout_state,
                    # numeric order: string keys would misplace v10 after v1
                    "versions": {v: dict(row)
                                 for v, row in sorted(self.versions.items(),
                                                      key=lambda kv: int(kv[0]))},
                },
            },
            # v12: the fleet-aggregated RPC transport gauges — None on
            # in-process fleets (no RPC boundary exists, the pre-v12
            # reading); the lifetime respawn/retry totals ride the block
            "transport": None if self.transport_state is None else {
                **self.transport_state,
                "worker_respawns": self.worker_respawns,
                "rpc_retries": self.rpc_retries,
            },
            "tokens_generated": tokens,
            "wall_seconds": round(wall, 6),
            "wall_tokens_per_s": round(tokens / wall, 3) if wall > 0 else 0.0,
            "replicas": replicas,
        }
        return snap

    def write_snapshot(self, replicas: Optional[Dict[str, Dict]] = None) -> Dict:
        snap = self.snapshot(replicas)
        self._emit("snapshot", **snap)
        return snap
