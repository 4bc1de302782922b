"""Continuous-batching inference engine over one paged KV pool.

The single-request decode stack (generation/generate.py) compiles one program
per prompt shape and serves one request per scan. Serving heavy traffic needs
the opposite: MANY heterogeneous requests advancing inside ONE compiled step
whose shapes never change as requests come and go — the "Ragged Paged
Attention" recipe (PAPERS.md): a shared page pool addressed through per-slot
page tables, and one fused tick program.

Design (see docs/serving.md for the full writeup):

  * The engine owns ``num_slots`` decode slots over one model-built paged
    cache (``model.init_paged_cache``; batch axis = slot index). Every slot
    sits at the SAME fill level at all times: the pool is pinned at full
    capacity; per-request left-pad counts live in the cache's ``shift`` and
    ``live`` fields.
  * Admission of a prompt shorter than the model's ``split_from`` = one
    batch-1 prefill at the smallest BUCKET covering the prompt (a small
    geometric ladder of compiled shapes, ``prefill_buckets`` — prefill cost
    is O(bucket), not O(window)) + a scatter of the bucket's rows into the
    request's pages (``install_slot``). Compile count stays bounded: <= one
    prefill program per bucket, pinned by test. Longer prompts ride the tick
    as chunk and finish lanes. Admission is NON-BLOCKING: prefill/install
    are dispatched without a device sync so they overlap the decode stream,
    and all free slots are filled before the tick's single sync point.
  * One jitted tick advances ALL slots one token: per-slot sampling
    parameters are traced (B,) arrays (``process_logits_batched``), so any
    mix of greedy/temperature/top-k/top-p requests shares the one program.
    Free slots decode pad tokens whose outputs are discarded — compute is
    wasted, recompilation never happens. Per-slot live lengths ride in the
    cache's ``live`` so the decode kernel skips KV pages below each slot's
    live region (ragged length-aware decode, ops/paged_decode_kernel.py).
  * EOS/length bookkeeping is host-side: the scheduler evicts finished
    requests and admits queued ones between steps. ``max_new_tokens`` is a
    host counter, not a compiled loop bound, so mixed lengths are free.

Admission control (docs/reliability.md): the queue is BOUNDED — once the
backlog exceeds free slot capacity by ``max_queue_depth``, a submit returns a
handle already terminal in ``REJECTED`` instead of letting the backlog grow
without limit — and over-long prompts are rejected
the same way at submit time (a well-formed request the pool cannot serve is an
admission outcome, not a crash; malformed requests still raise). Requests
carry an optional ``deadline_s`` TTL enforced at tick boundaries: expired
requests — queued or running — are evicted as ``TIMED_OUT`` while survivors'
outputs stay token-identical (slots never interact across the batch axis;
f64-pinned). Non-finite logits on an active slot (numerical blowup, poisoned
weights) are CONTAINED: the decode step reports per-slot finiteness alongside
the sampled tokens (same single sync), the poisoned slot is evicted as
``FAILED`` with its cache/state rows zeroed, and slot-mates are unaffected.
``drain()`` is the graceful shutdown: the queued backlog is rejected, active
slots run to completion, and further submits are refused. With no deadline
set, no bound configured, and no fault armed, all of this is bit-inert —
compile counts and greedy parity are unchanged (pinned).

Telemetry (docs/observability.md): ``ServingEngine(telemetry=...)`` (or the
``PERCEIVER_IO_TPU_TELEMETRY`` env) turns on phase spans that TILE the tick
(schedule > admit > prefill dispatch / install / chunk / finish; decode
dispatch; sample-sync; harvest > evict) plus the sync-to-dispatch host gap,
per-request lifecycle spans keyed by request id (joinable against the
serving-metrics/v7 JSONL events), and a compile watchdog that flags any
program count growing past the churn-never-recompiles budgets at runtime.
Off by default; the disabled path holds the shared no-op recorder and the
greedy-parity and compile-count pins run through it unchanged.

Paged KV cache (docs/serving.md "Paged KV cache"; serving/paging.py): the
cross-attention cache is a shared physical PAGE POOL of ``kv_page_size``-token
pages addressed through per-slot page tables (``kv_page_size=None``: one page
a window) — HBM cost scales with live tokens, not pool capacity. Admission
allocates the request's whole reservation (covering bucket + max_new_tokens,
capped at the window) from a refcounted, deterministic free list and scatters
the bucket KV into those pages; eviction returns the pages (no O(window) row
zeroing); the compiled decode step appends O(1) per token at each slot's ring
offset instead of rolling the whole buffer. Pool exhaustion head-blocks the
FIFO queue, so it surfaces as the existing ``queue_full`` backpressure —
never a crash or a stalled running slot (mid-decode page faults cannot exist
by construction). Free slots' tables point at the reserved trash page; the
churn contract is unchanged (one decode program, <= one install program per
bucket, pinned).

Priority classes + preemption (docs/serving.md "Priority classes &
preemption"): ``submit(..., priority=k)`` places a request in class ``k``
(small int, default 0, higher wins); the scheduler admits by (effective
priority desc, submit order) with an optional anti-starvation aging rule
(``priority_aging_ticks`` — a queued request rises one class per N ticks
waited; tick-counted, no clocks). When the admission-order head is blocked
on pages or slots, the engine PREEMPTS the cheapest set of strictly-lower-
class running slots that frees enough: each victim is evicted through the
existing release/release-pages programs into the non-terminal ``PREEMPTED``
status, its pages return to the pool, and its continuation re-queues at its
original priority (and original seniority) as a prompt + emitted-tokens
REPLAY — the same forced-decode mux the router's failover uses, now
intra-engine, so the resumed output is f64 token-identical to an
uncontended run (rng chain included) and a preempt/resume cycle compiles
NOTHING new. Victim selection is a pure function of (priority, admission
order, page count); each request survives at most ``max_preemptions``
preemptions, then runs to completion untouchable (no livelock).

Unified ragged tick (docs/serving.md "Unified ragged tick"): the engine
buffers each tick's prefill chunks, latent finishes, scale resets, and decode
step into ONE host-built descriptor — one int32 array, sent in one
transfer, or not at all when the tick carries nothing but decode
(serving/tick_descriptor.py) — and dispatches ONE fused program per
tick (``ragged_tick``). The constructor's arguments alone decide which page
format, admission path, and prefill ladder an engine runs: ``kv_page_size``
(None = one page a window), ``kv_quant`` / ``weight_dtype`` (None = full
precision pages / untouched params), ``prefill_chunk_tokens`` (None =
unchunked admission), ``prefix_cache`` (False = no sharing),
``prefill_buckets`` (``(window,)`` = the single full-window bucket).

Kill-switches, for what no argument sets:
``PERCEIVER_IO_TPU_DISABLE_RAGGED_DECODE=1`` disables live-length masking
and block skipping (pad masking alone; under paging only the kernel's
dead-page skip — the visibility bound is load-bearing there);
``PERCEIVER_IO_TPU_DISABLE_DECODE_KERNEL=1`` disables the fused kernel;
``PERCEIVER_IO_TPU_DISABLE_PREEMPTION=1`` restores strict submit-order FIFO
(priorities ignored, no aging, no preemption — behavior bit-identical to
the pre-priority engine, pinned by the ``preempt_disabled_inert`` chaos
scenario); ``PERCEIVER_IO_TPU_DISABLE_JOURNAL=1`` makes a configured
request journal inert — no files touched, behavior bit-identical to
``journal=None`` (serving/journal.py, tests/test_journal.py).

Quantized serving (docs/serving.md "Quantized KV pages & weight serving"):
``kv_quant="int8"`` stores the paged KV pools as int8 with per-page-per-head
scale sidecars — dequant fused into the paged decode kernel, the identical
XLA fallback on CPU/sharded pools, every write path quantizing
deterministically (whole-page stamps for install/chunk writes so prefix
pages stay byte-interchangeable; a ratcheting requantize for the per-token
ring append) — and ``weight_dtype="bf16"|"int8"`` shrinks the served params
alongside (serving/quant.py: bf16 cast, or per-tensor int8 dequantized on
program entry). Quantization is lossy by design: quality is MEASURED
(greedy agreement + CE deltas, ``serve_bench --kv-quant``), never assumed,
and with the knobs off the engine is bit-exactly its pre-quantization self.

Crash durability (serving/journal.py; docs/serving.md "Request journal"):
with ``journal=<dir>`` every accepted request is durable before ``submit``
returns (write-ahead accept record, fsynced), per-tick emissions and
terminal outcomes land as one buffered journal write per tick, and
``ServingEngine.recover(model, params, journal_dir, ...)`` rebuilds the
queue and all in-flight sessions on a fresh process as forced replays —
f64 token-identical continuations, zero extra compiled programs.

Greedy engine output is token-identical to ``generate()`` on the same
canonical form (tests/test_serving.py pins this in float64); sampled output
is reproducible per request seed but follows the engine's own key chain.
"""

from __future__ import annotations

import itertools
import os
import time
from dataclasses import dataclass, field
from enum import Enum
from functools import partial
from types import MappingProxyType
from typing import Dict, List, NamedTuple, Optional, Sequence

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np

from perceiver_io_tpu.generation.generate import GenerationConfig, _cache_dtype
from perceiver_io_tpu.generation.sampling import process_logits_batched, sample_token_batched
from perceiver_io_tpu.obs.core import resolve_recorder
from perceiver_io_tpu.obs.watchdog import CompileWatchdog
from perceiver_io_tpu.reliability import faults
from perceiver_io_tpu.reliability.preemption import (
    install_preemption_handler,
    restore_preemption_handler,
)
from perceiver_io_tpu.serving.journal import (
    JournalCorruptError,
    JournalSession,
    RequestJournal,
    journal_enabled,
    read_journal,
)
from perceiver_io_tpu.serving.metrics import EngineMetrics
from perceiver_io_tpu.serving.paging import (
    PagePool,
    PrefixCache,
    page_keys_for_prompt,
)
from perceiver_io_tpu.serving.quant import (
    WEIGHT_DTYPES,
    kv_bytes_per_token,
    serve_params,
    tree_layout_mismatch,
)
from perceiver_io_tpu.serving.scheduler import SlotScheduler, preemption_enabled
from perceiver_io_tpu.serving.tick_descriptor import TickDescriptorLayout
from perceiver_io_tpu.serving.weight_layout import merge_rows, missing_leaves, split_rows


class SlotState(flax.struct.PyTreeNode):
    """Per-slot device state advanced by the compiled decode step.

    ``next_hidden``: (B, C) each slot's last hidden row, the head's input:
        the next step's head pass turns it into the logits that step samples
        from — written by prefill at admission, by decode afterwards. The
        (B, V) logits themselves live inside one step and are never state.
    ``rng``: (B, 2) per-slot PRNG keys, split once per step.
    ``active``: (B,) bool; inactive rows decode their pad token.
    ``temperature``/``top_k``/``top_p``/``do_sample``: per-slot sampling
        parameters in the traced encodings of ``process_logits_batched``.
    ``pad_id``: (B,) token fed through inactive rows.
    """

    next_hidden: jax.Array
    rng: jax.Array
    active: jax.Array
    temperature: jax.Array
    top_k: jax.Array
    top_p: jax.Array
    do_sample: jax.Array
    pad_id: jax.Array

    @staticmethod
    def create(num_slots: int, hidden_size: int, dtype=jnp.float32) -> "SlotState":
        return SlotState(
            next_hidden=jnp.zeros((num_slots, hidden_size), dtype),
            rng=jnp.zeros((num_slots, 2), jnp.uint32),
            active=jnp.zeros((num_slots,), bool),
            temperature=jnp.ones((num_slots,), jnp.float32),
            top_k=jnp.zeros((num_slots,), jnp.int32),
            top_p=jnp.ones((num_slots,), jnp.float32),
            do_sample=jnp.zeros((num_slots,), bool),
            pad_id=jnp.zeros((num_slots,), jnp.int32),
        )


class RequestStatus(str, Enum):
    QUEUED = "queued"
    RUNNING = "running"
    # NON-terminal: evicted from its slot under priority pressure, re-queued
    # at its original priority awaiting replay re-admission (docs/serving.md)
    PREEMPTED = "preempted"
    FINISHED = "finished"  # completed normally (eos / length)
    REJECTED = "rejected"  # refused admission (queue bound, prompt, draining)
    TIMED_OUT = "timed_out"  # deadline expired, queued or running
    FAILED = "failed"  # evicted by non-finite-logits containment


# statuses from which a request never advances again
TERMINAL_STATUSES = frozenset(
    {RequestStatus.FINISHED, RequestStatus.REJECTED, RequestStatus.TIMED_OUT, RequestStatus.FAILED}
)


@dataclass
class ServedRequest:
    """Handle returned by ``ServingEngine.submit``; mutated by the engine."""

    request_id: int
    prompt_ids: np.ndarray
    config: GenerationConfig
    rng: jax.Array
    status: RequestStatus = RequestStatus.QUEUED
    slot: Optional[int] = None
    # priority class (higher wins) and how many times this request has been
    # preempted — at the engine's max_preemptions it becomes untouchable
    priority: int = 0
    preemptions: int = 0
    output_ids: List[int] = field(default_factory=list)
    # "eos" | "length" | rejection/expiry/failure detail ("queue_full",
    # "prompt_too_long", "draining", "deadline", "nonfinite_logits")
    finish_reason: Optional[str] = None
    submitted_at: float = 0.0
    # the instant this request last ENTERED the queue (submit, or the latest
    # preemption): the per-class queue-wait stats measure the current wait,
    # not a sum over preemption cycles
    enqueued_at: float = 0.0
    # the instant a slot (and the whole page reservation) was claimed
    # for this request — the end of its queue wait on BOTH admission paths
    # (a preempted continuation: its latest claim)
    slot_claimed_at: Optional[float] = None
    # DECODE-READY: the one-shot prefill + install are dispatched, or the
    # split admission's finish lane is buffered — after every chunk tick. Not
    # the end of the queue wait (that is ``slot_claimed_at``)
    admitted_at: Optional[float] = None
    # host time right after the sync of the tick that emitted this request's
    # first FREE-RUNNING token (replayed tokens were delivered before), and of
    # the tick that emitted its latest one (the inter-token gap's left edge)
    first_token_at: Optional[float] = None
    last_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    deadline_s: Optional[float] = None  # TTL from submit; enforced at ticks
    # the request's page reservation, computed ONCE at submit (it is a pure
    # function of the prompt/config — engine.load and the admission gate read
    # it per tick, so re-deriving it would make the queue-bound check
    # O(queue * ladder))
    pages_reserved: Optional[int] = None
    # the admission's actual allocation (== pages_reserved once RUNNING) —
    # the router's failover test pins replay reservations against this
    pages_allocated: Optional[int] = None
    # deterministic state replay (router failover, docs/serving.md): tokens
    # force-fed through the compiled decode step after prefill, reproducing
    # the source engine's exact decode trajectory — including the rng chain —
    # before free-running generation resumes. Replayed tokens are re-emitted
    # into ``output_ids`` (the handle carries the full stream).
    replay_ids: Optional[np.ndarray] = None
    replay_pos: int = 0
    # prefix-cache engines: the prompt's CACHEABLE page keys (page-aligned
    # token tuples strictly below the latent boundary — serving/paging.py),
    # computed ONCE at submit; the admission gate and engine.load walk the
    # queue with them per tick, so re-deriving would be O(queue * prompt)
    page_keys: Optional[tuple] = None
    # fleet-level session identity (router-stamped, journaled on the accept
    # record): lets ServingRouter.recover dedupe a session momentarily live
    # in two replica journals mid-migration. None on engine-only callers.
    session_id: Optional[str] = None
    # True for already-ACCEPTED work re-entering this engine (router
    # failover/migration continuations): such a submit bypasses the
    # draining refusal — drain's contract is that in-flight work FINISHES,
    # and a continuation is in-flight work whichever replica it lands on —
    # and _begin_drain keeps it queued the way PREEMPTED continuations are
    is_resume: bool = False
    # router param-version pin, journaled on the accept record so a rollout
    # pin survives process death (the per-replica param-version manifest,
    # docs/serving.md "Fleet operations"). Opaque to the engine itself —
    # the ROUTER chooses which weights serve which replica; this field only
    # rides the durability path. None on engine-only callers.
    version: Optional[int] = None

    @property
    def done(self) -> bool:
        """Terminal — FINISHED, REJECTED, TIMED_OUT, or FAILED. Check
        ``status``/``ok`` to distinguish success from an admission-control or
        containment outcome."""
        return self.status in TERMINAL_STATUSES

    @property
    def ok(self) -> bool:
        return self.status is RequestStatus.FINISHED

    @property
    def deadline_at(self) -> Optional[float]:
        """Absolute ``time.perf_counter()`` expiry, or None (no deadline)."""
        if self.deadline_s is None:
            return None
        return self.submitted_at + self.deadline_s

    def result(self) -> np.ndarray:
        """Generated tokens (prompt excluded), truncated at EOS inclusive.
        TIMED_OUT requests keep the tokens decoded before expiry; REJECTED
        and FAILED requests yield an empty/partial array — check ``ok``."""
        return np.asarray(self.output_ids, np.int32)


def _engine_compatible(config: GenerationConfig) -> Optional[str]:
    """None if the config runs on the engine, else the reason it cannot."""
    if config.num_beams > 1:
        return "beam search decodes k dependent continuations per request"
    if config.penalty_alpha is not None and config.penalty_alpha > 0:
        return "contrastive search re-scores k candidates per step"
    if config.decode_chunk > 1:
        return "chunked speculation shares one scalar commit length per batch"
    if config.max_new_tokens < 1:
        return "max_new_tokens must be >= 1"
    # temperature is irrelevant under greedy decoding (argmax is invariant to
    # positive scaling and the scaling is never applied): greedy requests with
    # temperature <= 0 are admitted and installed with the neutral 1.0 encoding
    if config.do_sample and config.temperature <= 0.0:
        return f"temperature must be > 0 for sampling, got {config.temperature}"
    return None


# the GenerationConfig fields a servable request can carry (everything
# _engine_compatible admits); the journal's accept record persists exactly
# these, and GenerationConfig(**payload) reconstructs an equivalent config —
# the non-default values of every other field are rejected at submit, so
# dropping them loses nothing
_JOURNAL_CONFIG_FIELDS = (
    "max_new_tokens", "do_sample", "temperature", "top_k", "top_p",
    "eos_token_id", "pad_token_id",
)


def _journal_config_payload(config: GenerationConfig) -> dict:
    return {k: getattr(config, k) for k in _JOURNAL_CONFIG_FIELDS}


@dataclass
class _PrefillTask:
    """Host-side state of one slot's SPLIT admission prefill (docs/serving.md
    "Chunked prefill"): the slot is claimed and its reservation allocated,
    but the request decodes nothing until the finish step activates it —
    between ticks the slot's in-cache page table stays trash so interleaved
    decode appends cannot touch the half-built pages (chunks write through
    ``table_row`` directly)."""

    request: ServedRequest
    table_row: np.ndarray  # (P,) trash-padded reservation (shared + private)
    n: int  # prompt length
    bucket: int  # covering ladder bucket (metrics continuity)
    next_pos: int  # next prompt position whose KV is still unwritten
    chunk_budget: int  # tokens per chunk dispatch
    shared_pages: int  # prefix-cache pages reused (page-aligned head)
    t0: float  # first-chunk dispatch time (prefill_s measures the span)
    resumed: bool = False  # a PREEMPTED continuation re-admitting (replay)
    chunks: int = 0  # chunks dispatched so far


# distinguishes concurrent engines' lifecycle spans in a shared recorder
_ENGINE_IDS = itertools.count()

# Stable ``jax.named_scope`` names of the phases INSIDE the tick program (the
# fused ``ragged_tick``): they ride every operation's ``op_name`` into a
# profiler trace, where device time is summed per phase
# (docs/observability.md "Named scopes"). Inside
# ``tick.decode`` the model's own scopes split further: ``cache_append`` and
# ``decode_attention`` (ops/attention.py), the flax ``mlp`` modules, and
# ``head`` (models/core/perceiver_ar.py): the step's one pass of the head over
# the slots' carried rows, the first thing under ``tick.decode``; no other
# phase runs a head. Renaming one is a change to what the trace tools read.
TICK_SCOPES = {phase: f"tick.{phase}" for phase in (
    "resets", "chunk_lanes", "finish_lanes", "poison", "sample", "decode")}
# what a model with a recurrent state (models/core/falcon_h1.py) names inside
# two of those phases: the state update, attention and MLP of a decode step,
# the chunked scan and attention of a chunk lane
TICK_SCOPES.update({part: f"tick.{part}" for part in (
    "decode/ssm_update", "decode/attention", "decode/mlp",
    "chunk_lanes/ssd_scan", "chunk_lanes/attention")})
# and a model of short-convolution, attention and routed expert layers
# (models/core/lfm2_moe.py), in both phases alike: the router and the grouped
# expert products under ``moe``, the gated short convolution
TICK_SCOPES.update({f"{phase}/{part}": f"tick.{phase}/{part}" for phase in ("decode", "chunk_lanes")
                    for part in ("moe/route", "moe/experts", "short_conv", "attention", "mlp")})
# and a model whose layers are Mamba-2, expert and attention layers alone in one
# stack (models/core/nemotron_h.py), in both phases alike: the mixer's
# projections, convolution and recurrence under ``ssm``, the shared expert's
# two dense products under ``moe/shared``
TICK_SCOPES.update({f"{phase}/{part}": f"tick.{phase}/{part}" for phase in ("decode", "chunk_lanes")
                    for part in ("ssm", "moe/shared")})


class TickRecord(NamedTuple):
    """What one dispatching tick carried (docs/observability.md "The tick,
    tiled"): made once where ``step_dispatch`` knows it, handed to
    ``EngineMetrics.record_tick_dispatch`` and, with telemetry on, to the
    tick's spans as their arguments, which is how it reaches a profiler trace
    (``benchmark/trace/ticks.py`` joins it to the tick's device execution)."""

    tick: int
    programs: int  # compiled programs launched since the tick's entry (fused steady state: 1)
    oneshot_admissions: int  # admissions by prefill + install programs, queued AHEAD of the tick program
    chunk_lanes: int
    finish_lanes: int
    chunk_tokens: int  # prompt tokens the chunk lanes carry
    resets: int
    decoding: int  # slots the tick decodes
    transfers: int  # the descriptor's host-to-device transfers: 0 = the resident one, 1 = a packed one
    after_empty: int  # 1: the engine held no request at some point since the previous dispatch
    riding_chunk_lanes: int  # chunk lanes that rode the decode step's pass over the layers (serving_api.py (h))


# span arguments of a tick with telemetry off: nothing is built
_NO_FIELDS = MappingProxyType({})


def default_prefill_buckets(window: int, max_latents: int) -> tuple:
    """Geometric (halving) ladder of prefill bucket lengths, from the full
    window down to the smallest bucket that still fits ``max_latents`` latents
    (prefill at bucket L uses ``prefix_len = L - max_latents``, so L >=
    max_latents). Ascending order; always contains ``window``."""
    floor = max(max_latents, 1)
    buckets = [window]
    b = window
    while b // 2 >= floor:
        b //= 2
        buckets.append(b)
    return tuple(sorted(buckets))


class ServingEngine:
    """In-process continuous-batching engine over a fixed slot pool.

    ``submit()`` returns a handle immediately; ``step()`` runs one scheduler
    tick (admit -> one batched decode token -> harvest/evict);
    ``run_until_drained()`` loops until queue and slots are empty.
    """

    def __init__(
        self,
        model,
        params,
        num_slots: int = 4,
        cache_dtype=None,
        metrics_jsonl: Optional[str] = None,
        prefill_buckets: Optional[Sequence[int]] = None,
        max_queue_depth: Optional[int] = None,
        default_deadline_s: Optional[float] = None,
        telemetry=None,
        obs_ns: str = "serving",
        handle_preemption: bool = False,
        kv_page_size: Optional[int] = None,
        num_kv_pages: Optional[int] = None,
        priority_aging_ticks: Optional[int] = None,
        max_preemptions: int = 2,
        journal=None,
        prefill_chunk_tokens: Optional[int] = None,
        prefix_cache: bool = False,
        max_prefill_slots: Optional[int] = None,
        kv_quant: Optional[str] = None,
        weight_dtype: Optional[str] = None,
    ):
        self.model = model
        # what the engine asks of the model instead of knowing its
        # architecture (models/core/serving_api.py): the window a slot holds,
        # which prompts take the split admission, what a lane carries, and
        # which options the model cannot be served with yet — refused here,
        # before anything is built or touched on disk, never run wrong
        traits = self._traits = model.serving_traits()
        options = {"prefix_cache": prefix_cache, "kv_quant": kv_quant,
                   "handle_preemption": handle_preemption, "journal": journal}
        for option, reason in traits.unsupported.items():
            if options.get(option):
                raise ValueError(
                    f"{type(model).__name__} cannot be served with {option} yet: {reason}")
        # Weight-serving transform (serving/quant.py; docs/serving.md
        # "Quantized KV pages & weight serving"): bf16 casts float leaves,
        # int8 stores matmul-grade leaves as int8 + per-tensor scale and the
        # compiled programs dequantize on entry — resident param HBM drops
        # alongside the KV pool's. weight_dtype=None passes the tree
        # through UNTOUCHED: the f64 parity pins run the identity path.
        if weight_dtype is not None and weight_dtype not in WEIGHT_DTYPES:
            raise ValueError(
                f"weight_dtype must be one of {WEIGHT_DTYPES} or None, got {weight_dtype!r}"
            )
        self.weight_dtype = weight_dtype
        # serving_api.py (g): leaves the model says the tick must receive
        # row-major are laid out so here, once (serving/weight_layout.py: in
        # a shape whose default layout is row-major), and every program views
        # them as the model's matrices again in its entry hook, beside the
        # dequantization; a model that names none is handed its tree as it came
        absent = missing_leaves(params, traits.row_major_leaves)
        if absent:
            raise ValueError(
                f"{type(model).__name__}.serving_traits().row_major_leaves names "
                f"{absent}, which are no leaves of the parameter tree")
        (self.params, self._dequant_params,
         self._param_bytes, self._param_bytes_fp) = self._serve_params(params)
        self.num_slots = num_slots
        # observability namespace: a router fronting N engines on ONE shared
        # recorder gives each replica its own prefix ("serving.r0", ...) so
        # phase tables stay per-replica (scripts/obs_report.py); standalone
        # engines keep the documented "serving.*" names
        self._obs_ns = obs_ns
        self._span_tick = f"{obs_ns}.tick"
        self._span_admit = f"{obs_ns}.admit"
        self._span_prefill = f"{obs_ns}.prefill_dispatch"
        self._span_install = f"{obs_ns}.install"
        self._span_decode_dispatch = f"{obs_ns}.decode_dispatch"
        self._span_sample_sync = f"{obs_ns}.sample_sync"
        self._span_evict = f"{obs_ns}.evict"
        self._span_chunk = f"{obs_ns}.prefill_chunk"
        self._span_finish = f"{obs_ns}.prefill_finish"
        # the phases that tile the stretch in which the device waits for the
        # host, from one tick's sync to the next tick's dispatch
        # (docs/observability.md "The tick, tiled"): harvest | the caller's
        # time between steps | schedule | decode_dispatch = host_gap. The
        # spans are recorded every tick; the gap and its four parts are
        # BOOKED (observed intervals) only for steady-state gaps, so that the
        # parts' means add up to the gap's over the same ticks.
        self._span_schedule = f"{obs_ns}.schedule"
        self._span_harvest = f"{obs_ns}.harvest"
        self._phase_host_gap = f"{obs_ns}.host_gap"
        self._phase_gap_harvest = f"{obs_ns}.host_gap.harvest"
        self._phase_between = f"{obs_ns}.between_steps"
        self._phase_gap_schedule = f"{obs_ns}.host_gap.schedule"
        self._phase_gap_dispatch = f"{obs_ns}.host_gap.dispatch"
        self._phase_wall_decode = f"{obs_ns}.tick_wall.decode_only"
        self._phase_wall_prefill = f"{obs_ns}.tick_wall.with_prefill"
        self.cache_dtype = cache_dtype if cache_dtype is not None else _cache_dtype(model)
        # Priority classes + engine-local preemption (docs/serving.md): the
        # kill-switch disables the WHOLE feature — queue order reverts to
        # strict submit-order FIFO and running slots are never preempted, so
        # behavior is bit-identical to the pre-priority engine (chaos-pinned).
        if max_preemptions < 0:
            raise ValueError(f"max_preemptions must be >= 0, got {max_preemptions}")
        self.priority_preemption = preemption_enabled()
        self.max_preemptions = max_preemptions
        self.priority_aging_ticks = priority_aging_ticks if self.priority_preemption else None
        self.scheduler: SlotScheduler[ServedRequest] = SlotScheduler(
            num_slots, aging_ticks=self.priority_aging_ticks
        )
        self.metrics = EngineMetrics(num_slots=num_slots, jsonl_path=metrics_jsonl)
        # unified telemetry (docs/observability.md): phase spans per tick,
        # per-request lifecycle spans keyed by request id (joinable against
        # the serving-metrics/v7 events carrying the same request_id), and a
        # compile watchdog policing the churn-never-recompiles invariant at
        # runtime. Off by default: ``telemetry=None`` defers to the
        # PERCEIVER_IO_TPU_TELEMETRY env, and the disabled surface is the
        # shared NULL_RECORDER — instrumented paths stay inert (the f64
        # parity pins run THROUGH them, recorder on and off).
        self._obs, self._owns_telemetry = resolve_recorder(telemetry)
        self._obs_on = self._obs.enabled
        # every phase this engine can emit exists (empty) from here on: a
        # reader tells "no chunk lane all run" (count 0) from a renamed span
        self._obs.declare_phases((
            self._span_tick, self._span_schedule, self._span_admit,
            self._span_prefill, self._span_install, self._span_chunk,
            self._span_finish, self._span_decode_dispatch,
            self._span_sample_sync, self._span_harvest, self._span_evict,
            self._phase_host_gap, self._phase_gap_harvest, self._phase_between,
            self._phase_gap_schedule, self._phase_gap_dispatch,
            self._phase_wall_decode, self._phase_wall_prefill,
        ))
        self._tick_no = 0  # the ``tick=`` argument of the per-tick spans
        # recorder-clock readings the host gap is measured from: the last
        # sync's return and the last harvest's end — None across a stretch in
        # which the engine held no request (an idle gap is not the loop's
        # cost). A gap in which a program compiled is not booked either
        # (total_compilations moved since the sync: the router's compile-tick
        # rule) — the watchdog's ``jax.compile.backend`` phase has that time.
        self._gap_from: Optional[float] = None
        self._harvest_end: Optional[float] = None
        # the engine held no request (none queued, prefilling or decoding) at
        # some point since the previous dispatch: the next tick's
        # ``after_empty``. Kept with telemetry off too (one boolean)
        self._was_empty = True
        self._gap_compilations = 0
        # per-engine async-span category: request ids restart at 0 per engine,
        # so two engines sharing one caller-owned recorder would otherwise
        # collide on (cat, id) and corrupt the trace's lifetime joins
        self._span_cat = f"request.e{next(_ENGINE_IDS)}"
        self.watchdog: Optional[CompileWatchdog] = (
            CompileWatchdog(recorder=self._obs) if self._obs_on else None
        )
        self.finished: List[ServedRequest] = []
        self._ids = itertools.count()
        self._requests: Dict[int, ServedRequest] = {}
        # admission control (docs/reliability.md): None = unbounded/undeadlined
        # — the pre-hardening behavior, bit-inert. max_queue_depth bounds the
        # backlog beyond available slot capacity (0 = accept only what free
        # slots will absorb at the next tick).
        if max_queue_depth is not None and max_queue_depth < 0:
            raise ValueError(f"max_queue_depth must be >= 0, got {max_queue_depth}")
        self.max_queue_depth = max_queue_depth
        self.default_deadline_s = default_deadline_s
        # write-ahead request journal (serving/journal.py, docs/serving.md
        # "Request journal"): accepted ⇒ durable. ``journal`` is a directory
        # path (the engine owns a default-policy RequestJournal there) or a
        # caller-built RequestJournal (custom fsync/segment policy). The
        # kill-switch forces None — behavior bit-identical to journal=None,
        # pinned in tests/test_journal.py. Per-tick changes are BUFFERED here
        # and land as one write per tick (append_tick) so the hot decode loop
        # pays no per-token journal syscalls.
        self.journal: Optional[RequestJournal] = None
        if journal is not None and journal_enabled():
            self.journal = (journal if isinstance(journal, RequestJournal)
                            else RequestJournal(os.fspath(journal)))
        self._journal_admits: List[int] = []
        self._journal_tokens: Dict[int, List[int]] = {}
        self._journal_terminals: List[tuple] = []
        if self.journal is not None:
            self.metrics.set_journal(self.journal.stats())
        self._draining = False
        # ticks skip the deadline scan entirely until any request carries one
        # — a no-deadline engine with a deep backlog must not pay O(queue)
        # predicate calls per generated token
        self._deadlines_seen = default_deadline_s is not None
        # dispatch/harvest split state: the in-flight (occupied, tok, finite,
        # t0, t_dispatch, TickRecord) of a dispatched-but-not-synced decode
        # step (see step_dispatch)
        self._pending_harvest = None
        # slots currently replaying a forced token stream (slot -> request);
        # empty on the hot path, where the cached all-zeros device arrays
        # below make the forced-token mux free of host->device transfers
        self._replay_slots: Dict[int, ServedRequest] = {}
        # SIGTERM/SIGINT graceful drain (docs/reliability.md): the handler
        # only sets a flag; the next tick closes admission and rejects the
        # backlog, active slots run to completion, and the final
        # metrics snapshot + telemetry flush land before the loop exits —
        # a signal mid-tick must not strand the JSONL or the trace.
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

        self._window = traits.window
        self._latents = traits.finish_ids

        # Prefill bucket ladder (ascending, ends at the window): a prompt is
        # prefilled at the smallest covering bucket — cost O(bucket) — and
        # install_slot widens the bucket rows into the slot's tail. One
        # compiled prefill program per bucket, ever.
        if prefill_buckets is None:
            ladder = default_prefill_buckets(self._window, traits.prefill_floor)
        else:
            ladder = tuple(sorted({int(b) for b in prefill_buckets} | {self._window}))
            bad = [b for b in ladder if not traits.prefill_floor <= b <= self._window]
            if bad:
                raise ValueError(
                    f"prefill_buckets must lie in [max_latents={traits.prefill_floor}.."
                    f"window={self._window}], got {bad}"
                )
        self.prefill_buckets: tuple = ladder

        # The page pool (serving/paging.py; module docstring): the engine's
        # one KV pool. kv_page_size=None is one page a window — a slot's
        # whole window in a single page.
        if kv_page_size is None:
            kv_page_size = self._window
        if not 1 <= int(kv_page_size) <= self._window:
            raise ValueError(
                f"kv_page_size must lie in [1..window={self._window}], got {kv_page_size}"
            )
        # Quantized KV pages (docs/serving.md "Quantized KV pages & weight
        # serving"): int8 pool + per-page-per-head scale sidecars.
        from perceiver_io_tpu.ops.paged_decode_kernel import KV_QUANT_MODES

        if kv_quant is not None and kv_quant not in KV_QUANT_MODES:
            raise ValueError(
                f"kv_quant must be one of {KV_QUANT_MODES} or None, got {kv_quant!r}"
            )
        self.kv_quant: Optional[str] = kv_quant
        self.kv_page_size = int(kv_page_size)
        self._pages_per_slot = -(-self._window // self.kv_page_size)
        # default pool = one full window per slot + the reserved trash page:
        # the page size alone never ADDS admission blocking
        pages = (
            int(num_kv_pages) if num_kv_pages is not None
            else num_slots * self._pages_per_slot + 1
        )
        if pages < self._pages_per_slot + 1:
            # the worst-case single reservation is a full window of pages;
            # a smaller pool would head-block that request forever
            raise ValueError(
                f"num_kv_pages must be >= pages_per_slot + 1 = "
                f"{self._pages_per_slot + 1} (worst-case reservation + trash "
                f"page), got {pages}"
            )
        self._pool = PagePool(pages, reserved=1)
        self._slot_pages: List[Optional[List[int]]] = [None] * num_slots
        # request id currently head-blocked on the free list, so a long
        # block reports one alloc_failure episode rather than one per tick
        self._alloc_blocked_id: Optional[int] = None
        # the factory pins live at the window, and the self-attention
        # ring (RingKVCache) is full by construction: every slot sits at
        # the same fill level at all times
        self._cache = model.init_paged_cache(
            num_slots, pages, self.kv_page_size, dtype=self.cache_dtype,
            kv_quant=self.kv_quant,
        )
        self.metrics.set_page_pool(self._pool.num_pages - self._pool.reserved, 0)
        # Chunked admission prefill + cross-request radix prefix cache
        # (docs/serving.md "Chunked prefill" / "Prefix cache"): chunks write
        # pool pages, the cache shares them.
        if prefill_chunk_tokens is not None and int(prefill_chunk_tokens) < 1:
            raise ValueError(f"prefill_chunk_tokens must be >= 1, got "
                             f"{prefill_chunk_tokens}")
        if max_prefill_slots is not None and max_prefill_slots < 1:
            raise ValueError(f"max_prefill_slots must be >= 1, got {max_prefill_slots}")
        self.chunked = prefill_chunk_tokens is not None
        self.prefill_chunk_tokens = (int(prefill_chunk_tokens)
                                     if self.chunked else None)
        if (self.kv_quant is not None and self.chunked
                and self.prefill_chunk_tokens % self.kv_page_size != 0):
            # quantized chunk writes are whole-page block writes: every chunk
            # must start page-aligned or a later chunk would overwrite a
            # partially quantized page (ops/paged_decode_kernel.write_rows)
            raise ValueError(
                f"prefill_chunk_tokens ({self.prefill_chunk_tokens}) must be a "
                f"multiple of kv_page_size ({self.kv_page_size}) under kv_quant"
            )
        self.max_prefill_slots = (int(max_prefill_slots)
                                  if max_prefill_slots is not None else num_slots)
        # Unified ragged tick (docs/serving.md "Unified ragged tick"; module
        # docstring): the engine buffers the tick's prefill chunks / latent
        # finishes / scale resets / decode into ONE host-built descriptor and
        # dispatches ONE fused program.
        # Lane counts are STATIC program shapes (the descriptor's size; the
        # model's phases run the lanes a tick carries, not these). At most
        # one chunk and one finish lane per slot per tick; chunked engines
        # are further bounded by 2 x max_prefill_slots (advancing tasks plus
        # the admissions their finishes just unblocked).
        self._ragged_lanes = (min(num_slots, 2 * self.max_prefill_slots)
                              if self.chunked else num_slots)
        # fixed chunk row capacity — chunk shapes STOP riding the bucket
        # ladder (no per-rung programs): the chunk cap under chunking, else
        # the window (the widest single-dispatch tail)
        self._ragged_chunk_cap = (self.prefill_chunk_tokens
                                  if self.chunked else self._window)
        # per-tick ragged work buffers (host side of the descriptor)
        self._tick_chunks: List[tuple] = []
        self._tick_finishes: List[tuple] = []
        self._tick_resets: List[tuple] = []
        self._tick_poison: Optional[int] = None
        self._tick_programs = 0
        self._tick_chunk_items = 0
        self._tick_chunk_tokens = 0
        self._tick_finish_items = 0
        self._tick_oneshot = 0
        self._tick_build_s = 0.0
        # descriptor transfers of the tick's fused dispatch (0 or 1); None
        # while the tick has dispatched no fused program
        self._tick_transfers: Optional[int] = None
        self._prefix_cache: Optional[PrefixCache] = None
        if prefix_cache:
            # the cache is keyed on the pool's byte layout: its mode is fixed
            # at construction. A cache built HERE trivially matches this
            # engine, so this ensure_mode cannot fire today — it stands as
            # the attach-point contract: any future externally-supplied or
            # persisted cache MUST pass through the same check before its
            # pages are served (an fp reader handed int8 pages would gather
            # garbage magnitudes — the seam tests pin both directions).
            self._prefix_cache = PrefixCache(self._pool, self.kv_page_size,
                                             kv_quant=self.kv_quant)
            self._prefix_cache.ensure_mode(self.kv_quant)
        # slot -> in-flight split-prefill task (chunk phase; empty on the
        # classic one-shot path, where admission completes inside _admit)
        self._prefilling: Dict[int, _PrefillTask] = {}
        if self.chunked:
            self.metrics.set_chunked_prefill(self.prefill_chunk_tokens)
        # serving-metrics/v11: the fused tick's block
        self.metrics.set_ragged_tick(True, self._ragged_lanes)
        if self._prefix_cache is not None:
            self.metrics.set_prefix_cache(self._prefix_cache.stats(), 0)
        # serving-metrics/v9 gauges: quantized-page byte economics and the
        # weight-serving dtype/bytes — None (off) on fp engines
        if traits.recurrent_state:
            self.metrics.set_recurrent_state(num_slots * traits.recurrent_bytes_per_slot)
        if traits.expert_counters is not None:
            self.metrics.set_expert_counters(*traits.expert_counters, traits.experts_held)
        if self.kv_quant is not None:
            cfg = model.config
            fp_b, served_b = kv_bytes_per_token(
                cfg.num_channels, self.cache_dtype, self.kv_quant,
                self.kv_page_size, cfg.num_heads,
            )
            self.metrics.set_kv_quant(self.kv_quant, fp_b, served_b)
        if self.weight_dtype is not None:
            self.metrics.set_weight_serving(
                self.weight_dtype, self._param_bytes, self._param_bytes_fp
            )
        # a slot's row carries the cache/compute dtype (f64 parity tests, bf16
        # TPU serving): the dtype the head receives it in, so carrying it
        # between ticks rounds nothing
        self._state = SlotState.create(num_slots, traits.hidden_size, dtype=self.cache_dtype)
        # device-resident constants for the no-replay case: the forced-token
        # mux costs no host->device transfer on ordinary ticks
        self._forced_none = jnp.zeros((num_slots,), jnp.int32)
        self._use_forced_none = jnp.zeros((num_slots,), bool)
        # the fused tick's descriptor is ONE int32 array (its layout:
        # serving/tick_descriptor.py). A tick that carries a lane, a reset or
        # poison packs a copy of the idle template ``_desc_idle_host`` and
        # sends it in one transfer; a tick that carries nothing but decode
        # passes ``_desc_decode_only``, built here once and resident on the
        # device — the program only reads its descriptor and it is never
        # donated, so it stays valid.
        self._desc_layout = TickDescriptorLayout(
            self._ragged_lanes, self._ragged_chunk_cap,
            self._pages_per_slot, self._latents,
            recurrent=traits.recurrent_state)
        self._desc_idle_host = self._desc_layout.idle(any_decode=False)
        # both paths hand the jit a device array placed as the pool's own
        # cache and state are (uncommitted, default device): one call
        # signature, one compiled program. A COMMITTED descriptor would
        # commit the tick's outputs, so the donated cache and state would
        # change signature after the first call (a second entry in the jit's
        # cache, a second compile).
        self._desc_decode_only = jax.device_put(
            self._desc_layout.idle(any_decode=True))
        self._build_jits()
        if self.watchdog is not None:
            # the engine's own compile-count pins, as runtime budgets: one
            # tick/release/quarantine program ever, <= one prefill program
            # per ladder bucket (tests/test_serving.py churn test). The whole
            # steady-state tick — chunks, finishes, poison, decode — is ONE
            # program whatever the tick mix (every phase gates on traced
            # flags, lanes are fixed-shape)
            self.watchdog.watch(f"{obs_ns}.ragged_tick",
                                self._jit_ragged_tick, budget=1)
            self.watchdog.watch(f"{obs_ns}.prefill", self._jit_prefill,
                                budget=len(self.prefill_buckets))
            # install consumes the BUCKET-shaped req_cache, so like prefill it
            # owns one legitimate program per ladder bucket (the churn test's
            # "<= ladder prefill+install programs" bound)
            self.watchdog.watch(f"{obs_ns}.install", self._jit_install,
                                budget=len(self.prefill_buckets))
            self.watchdog.watch(f"{obs_ns}.release", self._jit_release, budget=1)
            self.watchdog.watch(f"{obs_ns}.quarantine", self._jit_quarantine, budget=1)
            self.watchdog.watch(f"{obs_ns}.release_pages", self._jit_release_pages, budget=1)

    # ------------------------------------------------------------------- jits
    def _build_jits(self):
        """Per-engine jit wrappers so ``_cache_size()`` counts THIS engine's
        compilations (the churn test asserts decode compiles exactly once and
        prefill compiles at most once per bucket)."""
        model, dtype = self.model, self.cache_dtype
        n_latents = self._latents
        # weight serving (serving/quant.py): int8 trees dequantize as the
        # FIRST op of every params-consuming program — the resident tree
        # stays int8, the dequantized copy is a per-execution transient.
        # Identity for weight_dtype None/bf16: the traces are untouched.
        # Leaves the model states row-major (_serve_params) become its
        # matrices again in the same hook: a bitcast, no transient.
        dq = self._dequant_params

        @partial(jax.jit, static_argnames=("bucket",))
        def prefill_one(params, ids, pad_mask, bucket):
            # bucket-capacity cross-attention cache: prefill cost is
            # O(bucket), and the bucket always yields exactly max_latents
            # latents (prefix_len = bucket - max_latents) so the pool's
            # shared self-attention length stays uniform
            params = dq(params)
            cache = model.init_cache(batch_size=1, dtype=dtype, max_seq_len=bucket)
            rows, cache = model.apply(
                params, ids, bucket - n_latents, cache, pad_mask=pad_mask, method=type(model).prefill_rows
            )
            return rows[0], cache

        def _install_state(state, slot, row, rng,
                           temperature, top_k, top_p, do_sample, pad_id):
            # ``row`` (C,): the last hidden row of the slot's prompt
            return state.replace(
                next_hidden=state.next_hidden.at[slot].set(row),
                rng=state.rng.at[slot].set(rng),
                active=state.active.at[slot].set(True),
                temperature=state.temperature.at[slot].set(temperature),
                top_k=state.top_k.at[slot].set(top_k),
                top_p=state.top_p.at[slot].set(top_p),
                do_sample=state.do_sample.at[slot].set(do_sample),
                pad_id=state.pad_id.at[slot].set(pad_id),
            )

        # cache/state buffers are donated everywhere the caller immediately
        # rebinds them: without donation every decoded token would COPY the
        # full slot-pool KV cache (num_slots x layers x window x channels)
        # instead of updating it in place. (CPU jax warns donation is
        # unsupported and falls back to copies — correct either way.)
        @partial(jax.jit, donate_argnums=(0, 1))
        def install(cache, state, slot, table_row, req_cache, row, rng,
                    temperature, top_k, top_p, do_sample, pad_id):
            # one-shot admission: scatter the BUCKET-shaped prefill cache into
            # the freshly allocated pages and write the slot's page-table row
            # (reservation + trash padding). It consumes the bucket-shaped
            # req_cache, so it owns one legitimate program per ladder bucket
            # — table_row is a fixed (P,) array, so varying reservations
            # never add programs.
            cache = cache.install_slot(slot, table_row, req_cache)
            state = _install_state(state, slot, row, rng,
                                   temperature, top_k, top_p, do_sample, pad_id)
            return cache, state

        @partial(jax.jit, donate_argnums=(0,))
        def release(state, slot):
            # reset sampling fields to their neutral encodings: a stale
            # do_sample/top_k/top_p on a freed row would keep the decode
            # step's any-row lax.cond branches (sampling.py) live and make
            # all-greedy batches pay the vocab sorts forever. rng/next_hidden
            # are zeroed too so freed-slot state is canonical and pool dumps
            # are reproducible (they never feed a harvested output).
            return state.replace(
                active=state.active.at[slot].set(False),
                do_sample=state.do_sample.at[slot].set(False),
                temperature=state.temperature.at[slot].set(1.0),
                top_k=state.top_k.at[slot].set(0),
                top_p=state.top_p.at[slot].set(1.0),
                rng=state.rng.at[slot].set(0),
                next_hidden=state.next_hidden.at[slot].set(0),
            )

        @partial(jax.jit, donate_argnums=(0,))
        def release_pages(cache, slot):
            # eviction's device half: table row -> trash page, ring
            # offset 0, live pinned full, the slot's self-attention ring no
            # longer read (the free-slot canonical form). NOT
            # hygiene — a freed slot goes on appending, and a stale table entry
            # would route its writes into a page since handed to a new
            # tenant. The page CONTENTS are untouched: returning ids to the
            # free list is all an eviction costs.
            return cache.release_slot(slot)

        decode_method = type(model).decode_rows_paged

        def sample_step(params, state, forced, use_forced):
            # The first half of THE decode step: process logits -> sample, as
            # _generate_single's loop body does per row. The logits are the
            # head of the row each slot carries (models/core/serving_api.py
            # (e)): ONE pass of the head a step, over every slot's row — a
            # decoding slot's from the step before, a just-finished prompt's
            # from a finish lane — and the (B, V) logits die in the sampler:
            # never state, never a cond operand or a loop carry. Inactive rows
            # decode their pad token; their outputs are never harvested.
            # ``finite`` is the containment probe (docs/reliability.md): per
            # ACTIVE slot, were the logits this step sampled from all finite?
            # Computed in the same program, harvested with the same device
            # sync as the tokens — detection costs no extra host round-trip,
            # and the token math is untouched (parity pins unaffected).
            with jax.named_scope(TICK_SCOPES["decode"]):
                logits = model.apply(params, state.next_hidden, method=type(model)._head)
                # the sampler reads values of the logits' dtype and no wider:
                # fused into the head, XLA would hand it the matmul's float32
                # (excess precision), which breaks ties that logits of the
                # served dtype hold — another token stream than the same
                # logits give once they have been stored. Identity in float32.
                width = jnp.finfo(logits.dtype)
                logits = jax.lax.reduce_precision(logits, width.nexp, width.nmant)
            with jax.named_scope(TICK_SCOPES["sample"]):
                finite = jnp.all(jnp.isfinite(logits), axis=-1) | ~state.active
                processed = process_logits_batched(
                    logits, state.temperature, state.top_k, state.top_p
                )
                keys = jax.vmap(jax.random.split)(state.rng)  # (B, 2, 2)
                tok = sample_token_batched(keys[:, 1], processed, state.do_sample)
                tok = jnp.where(state.active, tok, state.pad_id).astype(jnp.int32)
                # deterministic replay mux (router failover): a replaying
                # slot's token is FORCED to the known stream while the rng
                # chain, cache appends, and rows advance exactly as in the
                # original run — so free-running continuation is
                # bit-identical. With use_forced all-False (every ordinary
                # tick) this is a no-op select and the f64 parity pins run
                # through it.
                tok = jnp.where(use_forced, forced, tok).astype(jnp.int32)
            return tok, finite, keys

        def advance_state(state, live, rows, keys):
            # the slots that decoded carry their new rows and rng on; the rest
            # keep their (zeroed-at-release) rng/row frozen: freed-slot state
            # stays canonical across steps, so pool dumps are reproducible
            # regardless of how long slots idle between requests
            return state.replace(
                next_hidden=jnp.where(live[:, None], rows, state.next_hidden),
                rng=jnp.where(live[:, None], keys[:, 0], state.rng),
            )

        def decode_body(params, cache, state, forced, use_forced):
            # THE decode step, the fused tick's decode phase (``params``
            # already dequantized): sample, then one cached model step on the
            # tokens
            tok, finite, keys = sample_step(params, state, forced, use_forced)
            with jax.named_scope(TICK_SCOPES["decode"]):
                rows, cache = model.apply(
                    params, tok[:, None], cache, method=decode_method
                )
            return tok, finite, cache, advance_state(state, state.active, rows, keys)

        @partial(jax.jit, donate_argnums=(0,))
        def quarantine(cache, slot, table_row):
            # containment eviction: the cache zeroes the condemned slot's own
            # rows and every page its table references BEFORE the pages
            # return to the free list. A normally-evicted page's stale FINITE
            # garbage is safe for the next tenant (gathered at softmax weight
            # 0), but a NaN would poison the sum through 0 * NaN. O(pages),
            # not O(window * slots), and only on the containment path.
            return cache.quarantine_slot(slot, table_row)

        quantized = self.kv_quant is not None
        unpack = self._desc_layout.unpack

        @partial(jax.jit, donate_argnums=(1, 2))
        def ragged_tick(params_, cache, state, descriptor, forced, use_forced):
            # ONE program per tick, its phases in dependency order:
            # scale resets, prefill chunks, latent finishes, fault
            # poison, batched decode (for a model whose chunk rows ride
            # its decode pass, serving_api.py (h): resets, poison, the
            # head and the sampler, the rows, the finishes). Every phase
            # is gated by a TRACED
            # any-flag (lax.cond), so one compiled program covers every
            # tick mix and the watchdog budget is exactly 1. Per-slot
            # state is disjoint across a phase's lanes, so the lanes of
            # one loop do not interact (f64-pinned against generate()).
            # The phases carry STABLE jax.named_scope names (TICK_SCOPES;
            # metadata only — the program's instructions are unchanged):
            # a profiler trace's device time is read per phase from the
            # operations' op_name (benchmark/trace/gaps.py).
            params = dq(params_)
            # the tick's work arrives as ONE int32 array: static slices
            # and bitcasts name its fields (serving/tick_descriptor.py)
            d = unpack(descriptor)
            poison_slot, any_decode = d.poison, d.any_decode

            if quantized:
                # quantized split admission: zero the PRIVATE
                # reservations' scale sidecars before any chunk writes,
                # so a page's first ratcheted append starts from scale 0
                # and zeroes stale tenant bytes
                # (ops/paged_decode_kernel.reset_page_scales). Shared
                # prefix pages are never in ``reset_ids`` — their scales
                # belong to the cache.
                with jax.named_scope(TICK_SCOPES["resets"]):
                    cache = jax.lax.cond(
                        d.any_reset,
                        lambda c: c.replace(ca=c.ca.reset_page_scales(d.reset_ids)),
                        lambda c: c, cache,
                    )

            def poison(state):
                # serving.nan fault point: before decode's head reads the
                # rows (a NaN row gives NaN logits)
                with jax.named_scope(TICK_SCOPES["poison"]):
                    return jax.lax.cond(
                        poison_slot >= 0,
                        lambda s: s.replace(next_hidden=s.next_hidden.at[
                            jnp.maximum(poison_slot, 0)].set(jnp.nan)),
                        lambda s: s, state,
                    )

            def finish_lanes(cache, state):
                with jax.named_scope(TICK_SCOPES["finish_lanes"]):
                    return jax.lax.cond(
                        d.any_finish,
                        lambda a: model.serving_finish_phase(params, a[0], a[1], d, _install_state),
                        lambda a: a, (cache, state)
                    )

            slots = self.num_slots
            if self._traits.chunk_rides_decode:
                # serving_api.py (h): the head and the sampler over the
                # slots that decode at the tick's entry, then the rows —
                # the decode step rides the first carried chunk lane's
                # loop over the layers, or runs alone where the tick
                # carries none — then the finish lanes, whose slots the
                # NEXT tick samples
                state = poison(state)
                tok, finite, keys = jax.lax.cond(
                    any_decode,
                    lambda s: sample_step(params, s, forced, use_forced),
                    lambda s: (jnp.zeros((slots,), jnp.int32), jnp.ones((slots,), bool),
                               jnp.zeros((slots, 2) + s.rng.shape[1:], s.rng.dtype)),
                    state)
                any_chunk = d.any_chunk

                def lanes_ridden(cache):
                    # the model loops its carried lanes and names its two
                    # groups' operations itself
                    return model.serving_ride_phase(params, cache, d, tok[:, None], any_decode)

                def decode_alone(cache):
                    return model.apply(params, tok[:, None], cache, method=decode_method)

                # a tick takes one of the two or neither; each cond has
                # the shape of the other order's
                rows, cache = jax.lax.cond(
                    any_chunk, lanes_ridden, lambda c: (jnp.zeros_like(state.next_hidden), c), cache)
                with jax.named_scope(TICK_SCOPES["decode"]):
                    rows, cache = jax.lax.cond(
                        any_decode & ~any_chunk, decode_alone, lambda c: (rows, c), cache)
                state = advance_state(state, state.active & any_decode, rows, keys)
                cache, state = finish_lanes(cache, state)
            else:
                # the two prefill phases are the MODEL's (its chunk step and
                # what ends a prompt: models/core/serving_api.py); the engine
                # gates each on the tick's flags and hands it the lanes
                with jax.named_scope(TICK_SCOPES["chunk_lanes"]):
                    cache = jax.lax.cond(
                        d.any_chunk,
                        lambda c: model.serving_chunk_phase(params, c, d),
                        lambda c: c, cache)
                # the finishes install their rows before the poison and the
                # decode phase's head: their slots are sampled in this tick
                cache, state = finish_lanes(cache, state)
                state = poison(state)

                def decode_phase(args):
                    return decode_body(params, *args, forced, use_forced)

                def no_decode(args):
                    cache, state = args
                    return (jnp.zeros((slots,), jnp.int32),
                            jnp.ones((slots,), bool), cache, state)

                tok, finite, cache, state = jax.lax.cond(
                    any_decode, decode_phase, no_decode, (cache, state))
            if self._traits.expert_counters is not None:
                # serving_api.py (f): the experts' counters ride the token
                # output, so the host's one readback of the tokens brings
                # them; a tick that decodes nothing is not read, and its
                # chunk lanes' counts wait in the cache for the next that is
                counts, cache = cache.take_expert_counts(any_decode)
                tok = jnp.concatenate([tok, counts.reshape(-1)])
            return tok, finite, cache, state

        self._jit_ragged_tick = ragged_tick

        self._jit_prefill = prefill_one
        self._jit_install = install
        self._jit_release = release
        self._jit_release_pages = release_pages
        self._jit_quarantine = quarantine

    @property
    def ragged(self) -> bool:
        """Always True: every engine's tick is the fused ``ragged_tick``.
        Kept because benchmark/harness/loops/_serving.py:66 reads it; a
        ``benchmark`` PR drops that read, then this property goes."""
        return True

    @property
    def decode_compilations(self) -> int:
        """Number of programs compiled for the steady-state tick step
        (target: 1): the fused tick — chunks, finishes, and decode in a
        single launch."""
        return self._jit_ragged_tick._cache_size()

    @property
    def prefill_compilations(self) -> int:
        """Number of compiled prefill programs (target: <= len(prefill_buckets))."""
        return self._jit_prefill._cache_size()

    @property
    def total_compilations(self) -> int:
        """Total compiled programs across every engine jit — the router's
        compile-tick detector: a tick whose count moved paid a compile, so
        its duration must not count as a stall strike (a handful of int
        reads, cheap enough per tick)."""
        jits = (
            self._jit_prefill, self._jit_install, self._jit_release,
            self._jit_quarantine, self._jit_release_pages,
        )
        return self.decode_compilations + sum(f._cache_size() for f in jits)

    def lower_tick(self):
        """The steady-state tick program, the fused ragged tick, lowered at
        this engine's shapes (a ``jax.stages.Lowered``).
        ``.compile().as_text()`` shows which attention path the program
        holds: a Pallas kernel is a ``tpu_custom_call``. Lowers an idle
        descriptor; dispatches nothing."""
        return self._jit_ragged_tick.lower(
            *self._ragged_args(True, self._forced_none, self._use_forced_none))

    # ----------------------------------------------------------------- params
    def _serve_params(self, params):
        """``serve_params`` under this engine's ``weight_dtype``, then the
        layouts the model states (serving_api.py (g)) on the leaves the
        transform left in place: the tree construction and ``set_params`` hand
        the compiled programs, and the hook that undoes both on entry."""
        served, dq, served_bytes, fp_bytes = serve_params(params, self.weight_dtype)
        names = self._traits.row_major_leaves
        if not names:
            return served, dq, served_bytes, fp_bytes
        return split_rows(served, names), (lambda p: merge_rows(dq(p), names)), served_bytes, fp_bytes

    def set_params(self, params) -> None:
        """Swap the served parameters IN PLACE — the live model-version
        rollout primitive (docs/serving.md "Fleet operations"). The compiled
        programs take params as an ordinary argument, so a swap whose tree
        structure, shapes, and dtypes match the current served tree costs
        ZERO new compilations; anything else would silently recompile every
        program on the next tick, so it is refused loudly. The same
        weight-serving transform (``weight_dtype``) is re-applied, and the
        dequant hook captured by the compiled closures is the module-level
        ``dequantize_params`` (int8) or the identity — both data-independent,
        so the existing traces serve the new tree unchanged. The caller (the
        router's version flip) is responsible for only swapping an engine
        that holds no in-flight sessions: a running slot's KV was built by
        the OLD params and continuing it under new ones would break the
        token-identity contract."""
        served, _dq, served_bytes, fp_bytes = self._serve_params(params)
        if tree_layout_mismatch(self.params, served):
            raise ValueError(
                "set_params requires a tree with the structure, shapes, and "
                "dtypes of the currently served params (anything else would "
                "recompile every program) — deploy a matching version or "
                "construct a fresh engine"
            )
        self.params = served
        self._param_bytes, self._param_bytes_fp = served_bytes, fp_bytes
        if self._prefix_cache is not None:
            # the radix prefix cache deliberately outlives sessions, and its
            # pages hold KV computed under the OLD params — serving them to
            # a new-version prompt would decode against stale weights (the
            # keys are token content only). A version flip starts the cache
            # cold; its pages return to the pool.
            self._prefix_cache.clear()
            self.metrics.set_prefix_cache(self._prefix_cache.stats(),
                                          self._shared_pages_in_use())
        if self.weight_dtype is not None:
            self.metrics.set_weight_serving(
                self.weight_dtype, self._param_bytes, self._param_bytes_fp
            )

    # -------------------------------------------------------------- capacity
    @property
    def load(self) -> int:
        """Backlog beyond free capacity — the engine's queue-bound metric and
        the router's dispatch-ranking input (one definition of "how full").
        Capacity = free PAGES as much as free rows: the count of queued
        requests (FIFO order — admission is head-of-line) the free slots and
        free pages can absorb, plus worst-case-sized headroom beyond the
        queue. Conservative under page pressure; ``SlotScheduler.load``
        (queue depth minus free slots) when the pool is unconstrained (the
        default sizing)."""
        slots = self.scheduler.free_slots
        pages = self._pool.free_pages
        # prefix-cache accounting (the shared-reservation seam fix,
        # docs/serving.md "Prefix cache"): a queued request whose prompt
        # extends a cached prefix will RETAIN those pages, not allocate
        # them — counting its full reservation would under-admit the very
        # workload the cache exists for. Cached pages nobody references
        # (refcount 1) additionally count as available supply: the
        # admission gate's LRU eviction frees them before backpressure —
        # minus any a queued request's own match would pin (a page cannot
        # be both "shared, free of charge" and "evictable supply").
        reclaim = (set(self._prefix_cache.reclaimable_page_ids())
                   if self._prefix_cache is not None else set())
        pages += len(reclaim)
        absorbed = 0
        for request in self.scheduler.queued():
            if slots <= 0:
                break
            need = self._pages_for(request)
            if self._prefix_cache is not None and request.page_keys:
                matched = self._prefix_cache.peek_match_pages(request.page_keys)
                need -= len(matched)
                pinned = reclaim.intersection(matched)
                reclaim -= pinned
                pages -= len(pinned)  # retained by the hit: no longer supply
            if need > pages:
                break  # head-of-line: later requests wait behind this one
            slots -= 1
            pages -= need
            absorbed += 1
        headroom = min(slots, pages // self._pages_per_slot)
        return self.scheduler.queue_depth - absorbed - headroom

    def _pages_for(self, request: ServedRequest) -> int:
        """The request's up-front page reservation (serving/paging.py):
        covering bucket + full generation budget, capped at the window.
        Computed once per request (at submit) and cached on the handle —
        ``load`` walks the queue with it per tick."""
        if request.pages_reserved is None:
            n = int(request.prompt_ids.size)
            request.pages_reserved = self.model.serving_pages(
                n, request.config.max_new_tokens, self.kv_page_size, self._bucket_for(n)
            )
        return request.pages_reserved

    def _shared_match(self, request: ServedRequest) -> int:
        """Pages the head request's prompt currently shares with the radix
        cache (no LRU/hit-rate side effects — accounting only)."""
        if self._prefix_cache is None or not request.page_keys:
            return 0
        return self._prefix_cache.peek_match(request.page_keys)

    def _can_admit_paged(self, request: ServedRequest) -> bool:
        """Admission gate for ``SlotScheduler.pop_admissible``: does the free
        list cover the head request's reservation — counting pages its
        prompt shares with the prefix cache ONCE (they are retained, not
        allocated)? Under pressure, cached-but-unreferenced pages are
        reclaimed refcount-aware-LRU FIRST (after touching the head's own
        match so eviction cannot grow the very need being fitted), so a full
        pool of stale cache yields to live reservations before admission
        ever reports backpressure. A blocked head counts one
        ``alloc_failure`` per blocking EPISODE (not per tick — a long block
        must not flood the metrics stream) and stays queued — pool exhaustion
        is never a crash and never skips FIFO order."""
        reservation = self._pages_for(request)
        need = reservation - self._shared_match(request)
        if not self._pool.can_allocate(need) and self._prefix_cache is not None:
            self._prefix_cache.touch(request.page_keys or ())
            freed = self._prefix_cache.evict(need - self._pool.free_pages)
            if freed:
                self.metrics.record_prefix_evict(freed, need)
                self.metrics.set_prefix_cache(
                    self._prefix_cache.stats(), self._shared_pages_in_use()
                )
            # eviction can only SHRINK the match (never grow it), so the
            # recheck below uses the post-eviction supply and match together
            need = reservation - self._shared_match(request)
        if self._pool.can_allocate(need):
            if self._alloc_blocked_id == request.request_id:
                self._alloc_blocked_id = None  # episode over
            return True
        if self._alloc_blocked_id != request.request_id:
            self._alloc_blocked_id = request.request_id
            self.metrics.record_alloc_failure(request.request_id, need, self._pool.free_pages)
        return False

    def _shared_pages_in_use(self) -> int:
        """Live page-table entries currently backed by SHARED pages (pool
        refcount >= 2 counting the cache's own hold) — the v8 gauge that
        makes 'sessions at fixed HBM' legible from a snapshot."""
        return sum(
            self._pool.shared_count(pages)
            for pages in self._slot_pages
            if pages
        )

    # ------------------------------------------------------------------ submit
    def submit(
        self,
        prompt_ids: Sequence[int],
        config: Optional[GenerationConfig] = None,
        rng: Optional[jax.Array] = None,
        deadline_s: Optional[float] = None,
        replay_ids: Optional[Sequence[int]] = None,
        priority: int = 0,
        resume: bool = False,
        session_id: Optional[str] = None,
        version: Optional[int] = None,
        **kwargs,
    ) -> ServedRequest:
        """Queue one request; returns its handle. ``config``/kwargs follow
        ``generate()``'s convention (pass one or the other). ``deadline_s``
        is a TTL from now (falls back to the engine's ``default_deadline_s``);
        an expired request is evicted ``TIMED_OUT`` at the next tick.
        ``priority`` is the request's class (small int, default 0, higher
        wins): admission is FIFO within a class, higher classes first, and a
        class-k head blocked on pages/slots may preempt strictly-lower-class
        running work (docs/serving.md; inert under the kill-switch).
        ``replay_ids`` force-feeds a known token stream through the decode
        step after prefill — deterministic state reconstruction for router
        failover (the replayed tokens are re-emitted into ``output_ids`` and
        count toward ``max_new_tokens``); generation free-runs after the
        stream is exhausted. ``resume=True`` marks already-ACCEPTED work
        re-entering this engine (a failover or planned-migration
        continuation): it bypasses the draining refusal — in-flight work
        finishes under drain, whichever replica it lands on — while every
        other admission rule (queue bound, prompt length) applies unchanged.
        ``session_id`` is the router's fleet-unique identity, journaled on
        the accept record for cross-journal recovery dedup. ``version`` is
        the router's param-version pin, journaled alongside it (the manifest
        entry a recovery rebuilds the session against) — opaque here.

        MALFORMED requests (empty prompt, unservable config) raise ValueError
        — they are caller bugs. WELL-FORMED requests the pool cannot serve
        right now (queue at its bound, prompt longer than the window, engine
        draining) return a handle already terminal in ``REJECTED`` — the
        admission-control path, validated here at submit instead of crashing
        inside a prefill the request already queued behind (check
        ``handle.ok``)."""
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
        elif jnp.issubdtype(rng.dtype, jax.dtypes.prng_key):
            # SlotState.rng is a raw (B, 2) uint32 buffer (rows of one batched
            # array cannot hold typed key objects); accept both key flavors
            rng = jax.random.key_data(rng)
        now = time.perf_counter()
        request = ServedRequest(
            request_id=next(self._ids),
            prompt_ids=prompt,
            config=config,
            rng=rng,
            priority=int(priority),
            submitted_at=now,
            enqueued_at=now,
            deadline_s=deadline_s if deadline_s is not None else self.default_deadline_s,
            replay_ids=np.asarray(replay_ids, np.int32).reshape(-1)
            if replay_ids is not None and len(replay_ids) else None,
            session_id=session_id,
            is_resume=bool(resume),
            version=None if version is None else int(version),
        )
        if request.deadline_s is not None:
            self._deadlines_seen = True
        if self._prefix_cache is not None:
            # cacheable page keys, once per request (serving/paging.py):
            # the admission gate and engine.load re-walk the queue with them
            # every tick, so deriving here keeps those walks O(pages).
            # RING-ROTATION gate: a session whose prompt + generation budget
            # exceeds the window wraps its ring mid-decode — append writes
            # land back at position 0, IN ITS OWN OLDEST PAGES. Those pages
            # must never be shared (a fork would watch its prefix mutate) or
            # donated (the cache would serve mid-overwrite garbage), so such
            # a request neither probes nor inserts. Worst-case by
            # construction, like the page reservation itself: EOS may stop
            # the wrap from ever happening, but admission cannot know that.
            if int(prompt.size) + int(config.max_new_tokens) <= self._window:
                request.page_keys = page_keys_for_prompt(
                    prompt.tolist(), self.kv_page_size, self._latents
                )
        self.metrics.record_submit(request.request_id, int(prompt.size),
                                   priority=request.priority)
        if self._obs_on:
            # lifecycle span: submit -> queued -> prefill -> ... -> terminal,
            # keyed by request id (the join key against serving-metrics events)
            self._obs.async_begin(self._span_cat, request.request_id,
                                  prompt_len=int(prompt.size))
            self._obs.async_instant(self._span_cat, request.request_id, "queued")
        if self._draining and not request.is_resume:
            # a RESUME (accepted-work continuation) is exempt: drain finishes
            # in-flight work, and the router may land a failover/migration
            # continuation on a draining sibling — refusing it here would
            # turn a planned drain into a lost session (docs/serving.md
            # "Fleet operations"; the PR 10 drain×recovery seam, re-audited)
            return self._reject(request, "draining")
        if prompt.size > self._window:
            return self._reject(request, "prompt_too_long")
        # the bound limits the backlog BEYOND available capacity: every
        # submit transits the queue (admission happens at tick boundaries),
        # so a raw queue_depth check would reject a burst into an idle
        # engine while its slots sit free. max_queue_depth=0 therefore
        # means "no waiting beyond what the free capacity will absorb" —
        # under paging, capacity counts free PAGES as much as free slots
        # (engine.load), which is how pool exhaustion surfaces as the same
        # queue_full backpressure instead of a new failure mode.
        if self.max_queue_depth is not None and self.load >= self.max_queue_depth:
            return self._reject(request, "queue_full")
        if self.journal is not None:
            # the durability point (docs/serving.md "Request journal"): the
            # accept record — prompt, servable config, raw rng key, priority,
            # TTL, any replay prefix — is on disk (fsynced under the default
            # policy) BEFORE the handle exists anywhere the caller can see.
            # Every rejection above returned first: rejected ⇒ never journaled.
            try:
                self.journal.append_accept(
                    request.request_id, prompt.tolist(),
                    _journal_config_payload(config),
                    np.asarray(request.rng, np.uint32).reshape(-1).tolist(),
                    priority=request.priority, deadline_s=request.deadline_s,
                    replay=request.replay_ids.tolist()
                    if request.replay_ids is not None else None,
                    session_id=request.session_id,
                    version=request.version,
                )
            except BaseException:
                # durability cannot be promised, so the accept must not
                # stand — but record_submit and the lifecycle span already
                # fired above, and an exception alone would leave them
                # dangling forever (submitted != finished+rejected+..., a
                # leaked async span). Close the accounting as a rejection,
                # THEN surface the failure. tracks() is False for a failed
                # append, so _reject's journal-terminal note is a no-op.
                self._reject(request, "journal_error")
                raise
        self._requests[request.request_id] = request
        # seq = the monotone request id, so FIFO-within-class is submit order
        # and a later preemption re-queue resumes the same seniority; with
        # the feature killed the class collapses to 0 — strict global FIFO,
        # bit-identical to the pre-priority engine
        self.scheduler.enqueue(request,
                               priority=request.priority if self.priority_preemption else 0,
                               seq=request.request_id)
        return request

    def _reject(self, request: ServedRequest, reason: str) -> ServedRequest:
        """Refuse admission: the handle goes terminal immediately and is
        still drained through ``finished`` so batch callers get one result
        per submit."""
        self._requests.pop(request.request_id, None)
        request.status = RequestStatus.REJECTED
        request.finish_reason = reason
        request.finished_at = time.perf_counter()
        self.finished.append(request)
        # pre-acceptance refusals were never journaled (tracks() is False);
        # a drain-time rejection of an ACCEPTED queued request must journal
        # its terminal outcome or compaction would carry it forever
        self._journal_note_terminal(request, RequestStatus.REJECTED, reason)
        self.metrics.record_reject(request.request_id, reason)
        if self._obs_on:
            self._obs.async_end(self._span_cat, request.request_id,
                                status="rejected", reason=reason)
        return request

    # ------------------------------------------------------------------- admit
    def _bucket_for(self, n: int) -> int:
        """Smallest ladder bucket covering an n-token prompt."""
        for b in self.prefill_buckets:
            if b >= n:
                return b
        raise AssertionError(f"no bucket covers length {n}")  # submit() bounds n <= window

    def _bucket_prompt(self, request: ServedRequest, bucket: int):
        """Left-pad the prompt to its covering bucket; pad positions are masked
        and position-shifted exactly as in the padded-batch pipeline path, and
        ``install_slot`` grows the left-pad to the full window at install."""
        n = request.prompt_ids.size
        ids = np.full((1, bucket), request.config.pad_token_id, np.int32)
        pad = np.ones((1, bucket), bool)
        ids[0, bucket - n:] = request.prompt_ids
        pad[0, bucket - n:] = False
        return jnp.asarray(ids), jnp.asarray(pad)

    def _admit(self, slot: int, request: ServedRequest) -> None:
        cfg = request.config
        t0 = time.perf_counter()
        # the queue wait ends HERE on both admission paths: the slot is this
        # request's from now on (the split path claims its pages below)
        request.slot_claimed_at = t0
        if self._obs_on:
            self._obs.async_instant(self._span_cat, request.request_id,
                                    "slot_claimed", slot=slot)
        n = int(request.prompt_ids.size)
        bucket = self._bucket_for(n)
        # SPLIT admission (docs/serving.md "Chunked prefill" / "Prefix
        # cache" / "Unified ragged tick"): every prompt the finish step
        # fits (n >= max_latents: the finish consumes the last L prompt
        # tokens) rides the tick's descriptor — a prompt extending a
        # cached prefix retains those pages and chunk-prefills only the
        # uncached tail; a long prompt on a chunked engine spreads its
        # KV writes one chunk per tick; the chunks and the finish fuse
        # into the tick program. The finish computes its latents against
        # the slot's pages AS STORED (gather_slot dequant on a quantized
        # pool), so a cache-hit fork and a cold admission of the same
        # prompt see byte-identical KV — the cache-on == cache-off token
        # identity survives quantization. Shorter prompts keep the
        # classic prefill + install programs below, the documented
        # exception: they have no cacheable pages (page keys lie below
        # the latent boundary), so no identity is at stake.
        if n >= self._traits.split_from:
            shared_run: List[int] = []
            if self._prefix_cache is not None and request.page_keys:
                shared_run = self._prefix_cache.probe(request.page_keys)
            self._admit_split(slot, request, bucket, shared_run, t0)
            return
        # the ONLY allocation point (serving/paging.py): the whole
        # reservation — bucket + generation budget — is claimed here, so
        # a running slot can never page-fault. pop_admissible's
        # _can_admit_paged gate guaranteed the fit.
        pages = self._pages_for(request)
        page_ids = self._pool.allocate(pages)
        self._slot_pages[slot] = page_ids
        table_row = np.zeros((self._pages_per_slot,), np.int32)
        table_row[: len(page_ids)] = page_ids  # trash-padded reservation
        self._tick_programs += 2  # classic path: prefill + install programs
        self._tick_oneshot += 1
        with self._obs.span(self._span_prefill, request_id=request.request_id):
            ids, pad_mask = self._bucket_prompt(request, bucket)
            req_row, req_cache = self._jit_prefill(self.params, ids, pad_mask, bucket=bucket)
        with self._obs.span(self._span_install, request_id=request.request_id):
            # greedy requests ignore temperature/top_k/top_p (argmax survives
            # scaling and filtering): install the neutral encodings so any
            # user value — including temperature <= 0 — shares the one
            # compiled step, and a greedy slot never keeps the batch-wide
            # vocab-sort filter branches live (see _jit_release)
            sampling = (
                float(cfg.temperature) if cfg.do_sample else 1.0,
                int(cfg.top_k) if (cfg.do_sample and cfg.top_k) else 0,
                float(cfg.top_p) if (cfg.do_sample and cfg.top_p is not None) else 1.0,
                bool(cfg.do_sample),
                int(cfg.pad_token_id),
            )
            self._cache, self._state = self._jit_install(
                self._cache, self._state, slot, jnp.asarray(table_row),
                req_cache, req_row, request.rng, *sampling,
            )
        # NON-BLOCKING: no device sync here — the prefill/install dispatch
        # overlaps the decode stream, and step() syncs once per tick (its
        # np.asarray on the decoded tokens). prefill_s is therefore dispatch
        # time; device prefill cost lands in the next decode_step sync.
        now = time.perf_counter()
        resumed = request.status is RequestStatus.PREEMPTED
        request.status = RequestStatus.RUNNING
        request.slot = slot
        request.pages_allocated = pages
        if self.journal is not None:
            # buffered; lands with the tick's one journal write. "Admitted"
            # marks in-flight work: a recovery's drain() finishes it instead
            # of rejecting it with the never-admitted backlog
            self._journal_admits.append(request.request_id)
        if request.replay_ids is not None and request.replay_pos < request.replay_ids.size:
            self._replay_slots[slot] = request
        request.admitted_at = now
        self.metrics.record_admit(
            request.request_id, slot, wait_s=t0 - request.enqueued_at,
            prefill_s=now - t0, bucket=bucket, pages=pages,
            priority=request.priority, preempted_replay=resumed,
            prompt_tokens=n,
        )
        self.metrics.set_page_pool(
            self._pool.num_pages - self._pool.reserved, self._pool.pages_in_use
        )
        if self._obs_on:
            self._obs.async_instant(self._span_cat, request.request_id, "prefill",
                                    slot=slot, bucket=bucket)

    def _admit_split(self, slot: int, request: ServedRequest, bucket: int,
                     shared_run: List[int], t0: float) -> None:
        """Claim the slot and the reservation for a SPLIT admission: shared
        prefix pages are RETAINED (the O(page-table copy) fork —
        serving/paging.py), only the remainder is allocated, and a
        ``_PrefillTask`` drives chunk dispatches across ticks (one chunk per
        tick with chunking on; straight to the finish otherwise). The
        request holds its slot from here — RUNNING for every scheduler
        purpose — but decodes nothing until the finish step activates it."""
        cfg = request.config
        n = int(request.prompt_ids.size)
        reservation = self._pages_for(request)
        shared = len(shared_run)
        if shared:
            self._pool.retain(shared_run)
        private = self._pool.allocate(reservation - shared)
        page_ids = list(shared_run) + private
        self._slot_pages[slot] = page_ids
        table_row = np.zeros((self._pages_per_slot,), np.int32)
        table_row[: len(page_ids)] = page_ids  # trash-padded reservation
        if self.kv_quant is not None:
            # quantized pools: zero the PRIVATE pages' scale sidecars before
            # any chunk writes them — a fresh page must start from scale 0 so
            # its first ratcheted append zeroes stale tenant bytes; shared
            # prefix pages keep theirs (the scales ARE part of the cached
            # bytes). Trash-padded tail entries re-zero page 0 harmlessly.
            # Rides the tick descriptor: the fused program's reset phase
            # runs before any chunk lane.
            ids_row = np.zeros((self._pages_per_slot,), np.int32)
            ids_row[: len(private)] = private
            self._tick_resets.append((slot, ids_row))
        shared_tokens = shared * self.kv_page_size
        budget = (self.prefill_chunk_tokens if self.chunked
                  else max(n - shared_tokens, 1))
        task = _PrefillTask(
            request=request, table_row=table_row, n=n, bucket=bucket,
            next_pos=shared_tokens, chunk_budget=budget, shared_pages=shared,
            t0=t0, resumed=request.status is RequestStatus.PREEMPTED,
        )
        self._prefilling[slot] = task
        request.status = RequestStatus.RUNNING
        request.slot = slot
        if self.journal is not None:
            # "admitted" marks in-flight work the moment the slot is
            # claimed: a crash mid-chunk recovers this session as a
            # PREEMPTED continuation (drain finishes it), exactly like a
            # one-shot admission that died between install and first token
            self._journal_admits.append(request.request_id)
        if shared:
            self.metrics.record_prefix_hit(request.request_id, shared,
                                           shared_tokens)
        self.metrics.set_page_pool(
            self._pool.num_pages - self._pool.reserved, self._pool.pages_in_use
        )
        # first chunk dispatches THIS tick; with chunking off (a pure
        # cache-hit fork) the whole tail + finish lands now, single-tick,
        # like the classic path
        self._advance_prefill(slot, task)
        while not self.chunked and slot in self._prefilling:
            self._advance_prefill(slot, task)

    def _advance_prefill(self, slot: int, task: _PrefillTask) -> None:
        """Dispatch ONE prefill chunk for a mid-admission slot (step_dispatch
        calls this once per prefilling slot per tick — the bounded-stall
        contract: a decode tick never waits on more than one chunk's worth
        of prefill work per prefilling slot). When the last chunk lands,
        the finish step runs in the same tick, so the slot starts decoding
        with no idle tick in between."""
        request = task.request
        remaining = task.n - task.next_pos
        if remaining > 0:
            c = min(task.chunk_budget, remaining)
            self._tick_chunk_items += 1
            self._tick_chunk_tokens += c
            t0 = time.perf_counter()
            # the chunk's host work: packing a descriptor lane
            with self._obs.span(self._span_chunk, request_id=request.request_id):
                # FIXED row capacity — chunk shapes do not ride the bucket
                # ladder (chunk math is row-independent and write_rows routes
                # pad rows to the trash page, so the padding never reaches a
                # real row)
                ids = np.full((self._ragged_chunk_cap,),
                              request.config.pad_token_id, np.int32)
                ids[:c] = request.prompt_ids[task.next_pos: task.next_pos + c]
                self._tick_chunks.append(
                    (slot, ids, task.next_pos, c,
                     self._traits.latent_start(task.n), task.table_row)
                )
                if self._traits.recurrent_state:
                    # the lane starts the slot's state from zero (the first
                    # chunk after the claim) or carries it on
                    self.metrics.record_recurrent_chunk(reset=task.next_pos == 0)
            task.next_pos += c
            task.chunks += 1
            if self.chunked:
                # chunk events/counters belong to CHUNKED admission only: a
                # pure cache-hit fork on an unchunked engine rides this same
                # split path (one tail dispatch) but must not emit a stream
                # the snapshot's chunked_prefill: None disclaims
                self.metrics.record_chunk(request.request_id, slot, c,
                                          time.perf_counter() - t0)
        if self._prefix_cache is not None and request.page_keys:
            # INCREMENTAL donor insert: every cacheable page fully covered by
            # the chunks written so far is final (the wrap gate pins pages
            # below the latent boundary immutable for this session's whole
            # lifetime), so it joins the trie NOW — a same-burst sibling
            # admitted next tick forks the half-prefilled prompt instead of
            # recomputing it. insert() leaves already-cached nodes (the
            # shared head this task itself forked) untouched.
            upto = min(task.next_pos // self.kv_page_size,
                       len(request.page_keys))
            if upto:
                self._prefix_cache.insert(
                    request.page_keys[:upto],
                    [int(p) for p in task.table_row[:upto]],
                )
        if task.next_pos >= task.n:
            self._finish_prefill(slot, task)

    def _finish_prefill(self, slot: int, task: _PrefillTask) -> None:
        """The split admission's FINISH: a fixed-shape lane of the tick
        program computes the latents against the slot's pages, installs the
        page table / ring offset / SA cache, and activates the slot's decode
        state — the moment this request is DECODE-READY (``admitted_at``; its
        queue wait ended at ``slot_claimed_at``, every chunk tick before this
        one)."""
        request = task.request
        cfg = request.config
        self._tick_finish_items += 1
        # the finish's host work: packing a descriptor lane
        with self._obs.span(self._span_finish, request_id=request.request_id):
            ids_latent = np.asarray(
                request.prompt_ids[task.n - self._latents:], np.int32
            )
            sampling = (
                float(cfg.temperature) if cfg.do_sample else 1.0,
                int(cfg.top_k) if (cfg.do_sample and cfg.top_k) else 0,
                float(cfg.top_p) if (cfg.do_sample and cfg.top_p is not None) else 1.0,
                bool(cfg.do_sample),
                int(cfg.pad_token_id),
            )
            # the fused program's finish phase runs after every chunk lane
            # (this slot's tail chunk included) and before decode, so the
            # newly active slot decodes THIS tick; for a model whose chunk
            # rows ride its decode pass (serving_api.py (h)) after the rows,
            # so it decodes from the NEXT tick on
            self._tick_finishes.append(
                (slot, task.table_row, ids_latent, task.n,
                 np.asarray(request.rng), sampling)
            )
        del self._prefilling[slot]
        # (donor insert already happened incrementally, chunk by chunk, in
        # _advance_prefill — by the last chunk it covered every cacheable key)
        now = time.perf_counter()
        request.pages_allocated = len(self._slot_pages[slot] or [])
        if request.replay_ids is not None and request.replay_pos < request.replay_ids.size:
            self._replay_slots[slot] = request
        request.admitted_at = now
        self.metrics.record_admit(
            request.request_id, slot, wait_s=task.t0 - request.enqueued_at,
            prefill_s=now - task.t0, bucket=task.bucket,
            pages=request.pages_allocated, priority=request.priority,
            preempted_replay=task.resumed,
            chunks=task.chunks if self.chunked else None,
            shared_pages=task.shared_pages or None,
            prompt_tokens=task.n,
        )
        if self._prefix_cache is not None:
            self.metrics.set_prefix_cache(
                self._prefix_cache.stats(), self._shared_pages_in_use()
            )
        if self._obs_on:
            self._obs.async_instant(self._span_cat, request.request_id,
                                    "prefill", slot=slot, bucket=task.bucket,
                                    chunks=task.chunks,
                                    shared_pages=task.shared_pages)

    def _drop_tick_work(self, slot: int) -> None:
        """Drop a slot's buffered ragged-tick descriptors. A victim evicted
        or preempted MID-TICK (deadline expiry, NaN quarantine, page-pressure
        preemption all fire between the buffering pass and dispatch) must not
        leave chunk/finish/reset lanes behind: its pages return to the free
        list at eviction, so a stale lane would write into pages the NEXT
        tenant already owns. Buffers never persist across ticks — they are
        filled and drained inside one step_dispatch — so this is the only
        seam where stale lanes could exist."""
        self._tick_chunks = [w for w in self._tick_chunks if w[0] != slot]
        self._tick_finishes = [w for w in self._tick_finishes if w[0] != slot]
        self._tick_resets = [w for w in self._tick_resets if w[0] != slot]
        if self._tick_poison == slot:
            self._tick_poison = None

    def _evict(
        self, slot: int, request: ServedRequest, reason: str,
        status: RequestStatus = RequestStatus.FINISHED,
        journal_terminal: bool = True,
    ) -> None:
        self.scheduler.release(slot)
        self._replay_slots.pop(slot, None)
        self._prefilling.pop(slot, None)  # a mid-chunk admission dies whole
        self._drop_tick_work(slot)
        self._tick_programs += 1
        self._state = self._jit_release(self._state, slot)
        # reset the slot's table to the trash page on device (a freed slot
        # goes on appending — stale entries would corrupt reallocated pages)
        # and return the ids to the free list. No O(window) row zeroing —
        # that is the point. A SHARED page's release only drops this slot's
        # reference: the prefix cache and any sibling sessions keep theirs
        # (serving/paging.py).
        self._tick_programs += 1
        self._cache = self._jit_release_pages(self._cache, slot)
        pages = self._slot_pages[slot]
        if pages:
            self._pool.release(pages)
        self._slot_pages[slot] = None
        self.metrics.set_page_pool(
            self._pool.num_pages - self._pool.reserved, self._pool.pages_in_use
        )
        request.status = status
        request.finish_reason = reason
        request.finished_at = time.perf_counter()
        request.slot = None
        self._requests.pop(request.request_id, None)  # engines are long-lived: no per-request residue
        self.finished.append(request)
        if journal_terminal:
            self._journal_note_terminal(request, status, reason)
        self.metrics.record_finish(
            request.request_id, slot, len(request.output_ids), reason,
            status=status.value,
        )
        if self._obs_on:
            self._obs.async_end(self._span_cat, request.request_id,
                                status=status.value, reason=reason,
                                new_tokens=len(request.output_ids))

    def evict_request(
        self, request_id: int, reason: str = "cancelled",
        status: RequestStatus = RequestStatus.FAILED,
        queued_only: bool = False,
        journal_terminal: bool = True,
    ) -> Optional[ServedRequest]:
        """Cancel one non-terminal request wherever it sits — queued (leaves
        the queue, never costs a prefill) or running (slot released, partial
        output preserved on the handle exactly as TIMED_OUT eviction keeps
        it). Returns the now-terminal handle, or None for an unknown/already
        terminal id. ``queued_only`` restricts the cancel to host-side
        bookkeeping (a running eviction touches device state, which a caller
        probing a suspect engine may not trust yet). This is the eviction API
        the router's failover uses to reclaim a lost replica's stale requests
        (serving/router.py); it is also the building block for client-side
        cancellation. ``journal_terminal=False`` evicts WITHOUT journaling a
        terminal record: the router's orphan reclaim passes it for sessions
        whose failover continuation is still parked fleet-side — this
        journal's live entry is that continuation's only durable copy, and
        the router closes it (``_journal_note_moved``) exactly when the
        continuation lands durably elsewhere or resolves terminally."""
        request = self._requests.get(request_id)
        if request is None:
            return None
        if request.slot is not None:
            if queued_only:
                return None
            self._evict(request.slot, request, reason, status=status,
                        journal_terminal=journal_terminal)
            return request
        removed = self.scheduler.prune_queue(lambda r: r is request)
        if not removed:  # defensive: _requests said queued but the queue disagrees
            return None
        self._requests.pop(request_id, None)
        request.status = status
        request.finish_reason = reason
        request.finished_at = time.perf_counter()
        self.finished.append(request)
        if journal_terminal:
            self._journal_note_terminal(request, status, reason)
        self.metrics.record_evict_queued(request_id, reason, status=status.value,
                                         new_tokens=len(request.output_ids))
        if self._obs_on:
            self._obs.async_end(self._span_cat, request_id,
                                status=status.value, reason=reason,
                                new_tokens=len(request.output_ids))
        return request

    def mark_resume(self, request_id: int) -> None:
        """Flag a live request as a failover/migration continuation. The
        router sets this on adopted handles so ``_begin_drain``'s queue prune
        keeps them (accepted-elsewhere work is never backlog); it is a method
        rather than a bare attribute write so the flag crosses the
        out-of-process replica boundary (serving/transport.py) too."""
        request = self._requests.get(request_id)
        if request is not None:
            request.is_resume = True

    # -------------------------------------------------------------- preemption
    def _select_victims(self, request: ServedRequest) -> List:
        """The cheapest set of strictly-lower-class running slots whose
        eviction lets ``request`` (the blocked admission-order head) admit —
        a PURE function of (priority, admission order, page count), so chaos
        scenarios pin exact victim identity across repeat runs:

          * candidates: running requests with base priority STRICTLY below
            the head's base priority (aging raises queue rank, never
            preemption eligibility) that still have preemption budget left
            (``preemptions < max_preemptions`` — past it a request runs to
            completion untouchable, so no livelock);
          * order: lowest class first; within a class the LARGEST page
            reservation first (fewest victims free the most pages), then the
            youngest admission (highest request id — least replay work lost);
          * take greedily until the head's missing slot and missing pages are
            covered; if the full candidate set still cannot cover them,
            preempt NOBODY (a useless eviction would burn a replay for
            nothing and still not admit the head).
        """
        need_slot = self.scheduler.free_slots == 0
        # shared-reservation accounting (the prefix-cache seam fix): a head
        # whose prompt extends a cached prefix RETAINS those pages, so only
        # the uncovered remainder needs freeing — preempting for pages the
        # cache already supplies would burn replays for nothing
        need_pages = (self._pages_for(request) - self._shared_match(request)
                      - self._pool.free_pages)
        if not need_slot and need_pages <= 0:
            return []  # the head is not resource-blocked: nothing to free
        candidates = [
            (slot, r) for slot, r in self.scheduler.occupied()
            if r.priority < request.priority and r.preemptions < self.max_preemptions
        ]
        candidates.sort(key=lambda sr: (
            sr[1].priority,
            -len(self._slot_pages[sr[0]] or ()),
            -sr[1].request_id,
        ))

        # what a victim set ACTUALLY frees for the head: releasing a shared
        # page only drops a refcount (PagePool.release), so raw page-list
        # lengths overcount under prefix sharing — preempting a fork whose
        # pages a live sibling still holds would burn its replay without
        # unblocking anything. A page counts IFF, after every chosen victim
        # releases, it reaches refcount 0 (returns to the free list now) or
        # refcount 1 with the cache the only holder left (the admission
        # gate's refcount-aware LRU reclaims it before reporting
        # backpressure). Without a prefix cache every page is refcount 1, so
        # this degrades to the plain page-list length.
        cached = (self._prefix_cache.cached_page_ids()
                  if self._prefix_cache is not None else frozenset())

        def sim_freed(victims) -> int:
            drops: Dict[int, int] = {}
            for slot, _r in victims:
                for p in self._slot_pages[slot] or []:
                    drops[p] = drops.get(p, 0) + 1
            return sum(
                1 for p, d in drops.items()
                if (rc := self._pool.refcount(p) - d) == 0
                or (rc == 1 and p in cached)
            )

        chosen, freed_slots = [], 0
        for slot, r in candidates:
            if sim_freed(chosen) >= need_pages and freed_slots >= (1 if need_slot else 0):
                break
            chosen.append((slot, r))
            freed_slots += 1
        if sim_freed(chosen) < need_pages or (need_slot and freed_slots < 1):
            return []
        # minimization pass: the cross-class greedy can pick a cheap
        # low-class victim that a later, larger victim then makes redundant
        # (class-0 holding 2 pages chosen before the class-1 holding 10 that
        # covers the need alone) — evicting it would burn its preemption
        # budget and a full replay for zero admission benefit. Drop, in the
        # same deterministic selection order, every victim whose contribution
        # is no longer needed for coverage.
        for slot, r in list(chosen):
            trial = [v for v in chosen if v[0] != slot]
            if (sim_freed(trial) >= need_pages
                    and (not need_slot or len(trial) >= 1)):
                chosen = trial
                freed_slots -= 1
        return chosen

    def _preempt(self, slot: int, request: ServedRequest, preemptor: ServedRequest) -> None:
        """Evict one victim UNDER PRIORITY PRESSURE: device-side this is
        exactly the normal eviction (release program + pages back to the
        pool — zero new compiled programs), host-side the handle stays LIVE:
        it re-queues at its original priority and seniority as a prompt +
        emitted-tokens replay, so the resumed decode trajectory — rng chain
        included — is f64 token-identical to an uncontended run (the router
        failover mechanism, reused intra-engine)."""
        self.scheduler.release(slot)
        self._replay_slots.pop(slot, None)
        # a victim preempted MID-SPLIT-PREFILL loses the half-built chunk
        # work (no tokens were emitted, so nothing is owed): its task dies
        # here and the re-admission chunk-prefills from scratch — buffered
        # ragged lanes die with it (its pages are about to be reallocated)
        self._prefilling.pop(slot, None)
        self._drop_tick_work(slot)
        self._tick_programs += 1
        self._state = self._jit_release(self._state, slot)
        self._tick_programs += 1
        self._cache = self._jit_release_pages(self._cache, slot)
        pages = self._slot_pages[slot] or []
        pages_freed = len(pages)
        if pages:
            self._pool.release(pages)
        self._slot_pages[slot] = None
        self.metrics.set_page_pool(
            self._pool.num_pages - self._pool.reserved, self._pool.pages_in_use
        )
        # the replay stream is the LONGEST known token prefix: normally the
        # emitted tokens, but a victim preempted mid-replay (failover replay,
        # or a second preemption) still owes the tail of its previous stream
        # — truncating to output_ids would silently drop it
        if request.replay_ids is not None and request.replay_ids.size > len(request.output_ids):
            stream = request.replay_ids
        elif request.output_ids:
            stream = np.asarray(request.output_ids, np.int32)
        else:
            stream = None
        request.replay_ids = stream
        request.replay_pos = 0
        request.status = RequestStatus.PREEMPTED
        request.slot = None
        request.pages_allocated = None
        request.preemptions += 1
        request.enqueued_at = time.perf_counter()
        self.scheduler.enqueue(request, priority=request.priority,
                               seq=request.request_id)
        self.metrics.record_preempt(
            request.request_id, slot, preempted_by=preemptor.request_id,
            pages_freed=pages_freed, emitted_tokens=len(request.output_ids),
            priority=request.priority,
        )
        if self._obs_on:
            self._obs.async_instant(self._span_cat, request.request_id,
                                    "preempted", by=preemptor.request_id,
                                    emitted=len(request.output_ids))

    def _preempt_for_blocked_head(self, can_admit) -> None:
        """Admission's second pass: while the admission-order head is blocked
        on pages/slots and a set of strictly-lower-class victims can free
        enough, preempt them and re-run admission so the head admits THIS
        tick. Bounded by the slot count per tick (each pass admits at least
        one request or stops)."""
        for _ in range(self.num_slots):
            head = self.scheduler.peek()
            if head is None:
                return
            # same chunk-aware bound as the first pass: admission via
            # preemption must not schedule more concurrent chunk streams
            # than max_prefill_slots allows either — the bounded-stall
            # contract has no priority exemption. Checked BEFORE victim
            # selection: an exhausted chunk budget must not burn replays
            # for an admission that cannot happen this tick.
            limit = (max(self.max_prefill_slots - len(self._prefilling), 0)
                     if self.chunked else None)
            if limit == 0:
                return
            victims = self._select_victims(head)
            if not victims:
                return
            for slot, victim in victims:
                self._preempt(slot, victim, preemptor=head)
            admitted = False
            for slot, request in self.scheduler.pop_admissible(can_admit, limit=limit):
                self._admit(slot, request)
                admitted = True
            if not admitted:
                return  # defensive: the gate disagreed with the selection

    # ----------------------------------------------------------------- journal
    def _journal_note_terminal(self, request: ServedRequest,
                               status: RequestStatus, reason: str) -> None:
        """Buffer one terminal outcome for the tick's journal write — only
        for requests the journal actually tracks (an accepted request;
        pre-acceptance rejections never had an accept record)."""
        if self.journal is not None and self.journal.tracks(request.request_id):
            self._journal_terminals.append(
                (request.request_id, status.value, reason)
            )

    def _journal_flush(self) -> None:
        """Land the tick's buffered admissions / tokens / terminals as ONE
        journal write, and refresh the v7 journal gauges."""
        if self.journal is None or self.journal.failed:
            # fail-stopped journal (an append died mid-line): nothing more
            # can land; close() must still succeed so the caller can move to
            # recovery, which reads the durable prefix. The tick buffers are
            # DROPPED, not retained — they can never be written, and a caller
            # that keeps stepping the degraded engine must not grow them by
            # one entry per emitted token for the rest of the process
            self._journal_admits = []
            self._journal_tokens = {}
            self._journal_terminals = []
            return
        if self._journal_admits or self._journal_tokens or self._journal_terminals:
            self.journal.append_tick(self._journal_admits, self._journal_tokens,
                                     self._journal_terminals)
            self._journal_admits = []
            self._journal_tokens = {}
            self._journal_terminals = []
        self.metrics.set_journal(self.journal.stats())

    def _recover_attach(self, journal_path, fsync: str = "accept",
                        segment_max_records: int = 4096,
                        skip_session_ids=frozenset(), _state=None) -> dict:
        """Core of ``recover()``: replay a journal directory into THIS
        (freshly constructed, journal-less, empty) engine, then atomically
        swap the journal to a new generation reflecting the recovered state
        and attach it for ongoing appends. Split out so ``ServingRouter.
        recover`` can run it per replica engine.

        Order is the crash-safety argument: the old generation on disk stays
        untouched until every live session is re-submitted and the new
        generation's rename lands — a crash ANYWHERE during recovery leaves
        the old generation the durable truth and a re-run recovers
        identically. Re-submitted sessions keep their original priority
        class, and accept order + the engine's monotone request ids preserve
        original seniority within each class; emitted tokens ride in as the
        forced-replay stream (the router-failover mux), so recovered
        continuations are f64 token-identical to an uninterrupted run — rng
        chain included — and replay compiles nothing beyond the standard
        per-bucket programs. Sessions that had EVER reached a slot resume as
        ``PREEMPTED`` continuations (in-flight work a process death
        displaced): ``drain()`` finishes them, while never-admitted queue
        entries reject as backlog — the established drain contract."""
        if self.journal is not None or self._requests or self.scheduler.has_work:
            raise JournalCorruptError(
                "recovery needs a fresh journal-less engine (construct with "
                "journal=None and no submitted work)"
            )
        journal_path = os.path.abspath(os.fspath(journal_path))
        # _state lets ServingRouter.recover hand in the JournalState its
        # dedup pre-scan already parsed — crash recovery is the latency-
        # critical moment, so large journals are not read twice
        state = read_journal(journal_path) if _state is None else _state
        handles: List[ServedRequest] = []
        mirror = []
        deduped = 0
        now = time.time()
        saved_bound = self.max_queue_depth
        # accepted work is never killed by the queue bound (the router's
        # requeue discipline): the bound gates NEW admissions, and every one
        # of these was already accepted before the process died
        self.max_queue_depth = None
        try:
            for session in state.sessions:
                if (session.session is not None
                        and session.session in skip_session_ids):
                    # a SUPERSEDED migration origin (ServingRouter.recover
                    # found the same fleet session live in another replica's
                    # journal with an equal-or-longer emitted prefix):
                    # skipping it here — before re-submission — is what makes
                    # exactly-once hold across the migration kill window; the
                    # generation swap below omits it, closing the entry
                    deduped += 1
                    continue
                emitted = session.emitted
                handle = self.submit(
                    session.prompt,
                    config=GenerationConfig(**session.config),
                    rng=np.asarray(session.rng, np.uint32),
                    deadline_s=session.remaining_deadline(now),
                    replay_ids=emitted if emitted else None,
                    priority=session.priority,
                    session_id=session.session,
                    version=session.version,
                )
                if handle.status is RequestStatus.REJECTED:  # defensive: it fit once
                    raise JournalCorruptError(
                        f"recovered session rid={session.rid} rejected "
                        f"({handle.finish_reason}) — engine geometry does not "
                        f"match the journaled fleet"
                    )
                if session.admitted:
                    handle.status = RequestStatus.PREEMPTED
                # the handle carries the salvage from tick one, exactly like
                # an intra-engine preemption victim (which keeps output_ids
                # alongside replay_ids): if the TTL expires before the
                # continuation re-admits, the terminal event and result()
                # still surface the journaled partial tokens instead of
                # silently dropping work the journal durably holds. Replay
                # re-emission appends only PAST len(output_ids), so the
                # stream stays monotonic and nothing double-counts.
                handle.output_ids = [int(t) for t in emitted]
                handles.append(handle)
                # the new generation's view of this session: the NEW request
                # id, the remaining TTL re-anchored at recovery time, and the
                # whole emitted prefix folded into the replay field
                mirror.append((handle.request_id, JournalSession(
                    rid=handle.request_id, prompt=session.prompt,
                    config=session.config, rng=session.rng,
                    priority=session.priority, deadline_s=handle.deadline_s,
                    accepted_ts=now, admitted=session.admitted,
                    replay=emitted, tokens=[], session=session.session,
                    version=session.version,
                )))
        finally:
            self.max_queue_depth = saved_bound
        replayed = sum(len(s.emitted) for s in state.sessions
                       if not (s.session and s.session in skip_session_ids))
        if journal_enabled():
            self.journal = RequestJournal(
                journal_path, fsync=fsync,
                segment_max_records=segment_max_records,
                _recovered_from=state, _sessions=mirror,
            )
            self.metrics.set_journal(self.journal.stats())
        self.metrics.record_recovery(
            sessions=len(handles), replayed_tokens=replayed,
            truncated=state.truncated, dropped_records=state.dropped_records,
            generation=state.generation,
        )
        return {
            "sessions": len(handles),
            "replayed_tokens": replayed,
            "in_flight": sum(
                1 for s in state.sessions
                if s.admitted and not (s.session and s.session in skip_session_ids)
            ),
            "deduped": deduped,
            "truncated": state.truncated,
            "dropped_records": state.dropped_records,
            "records": state.records,
            "generation": state.generation,
            "handles": handles,
        }

    @classmethod
    def recover(cls, model, params, journal, fsync: str = "accept",
                segment_max_records: int = 4096, **engine_kwargs):
        """Rebuild a serving engine from a write-ahead journal after process
        death (docs/serving.md "Request journal"): every accepted,
        non-terminal request re-enters the queue at its original priority
        and seniority as a prompt + emitted-token replay. Returns
        ``(engine, info)`` where ``info["handles"]`` are the recovered
        request handles in original accept order; step/drain the engine as
        usual and each completes f64 token-identical to an uninterrupted
        run. ``engine_kwargs`` must describe the same pool geometry the dead
        process ran (slot count, buckets, paging) — the journal records
        requests, not engine configuration. With the
        ``PERCEIVER_IO_TPU_DISABLE_JOURNAL`` kill-switch set, recovery still
        REBUILDS the sessions (an explicit call to read explicit state) but
        attaches no journal and leaves the directory untouched."""
        engine = cls(model, params, journal=None, **engine_kwargs)
        info = engine._recover_attach(journal, fsync=fsync,
                                      segment_max_records=segment_max_records)
        return engine, info

    # --------------------------------------------------------------- deadlines
    def _expire_deadlines(self, now: float) -> None:
        """Tick-boundary TTL enforcement: expired QUEUED requests leave the
        queue without ever costing a prefill; expired RUNNING requests free
        their slot before the decode dispatch, so the tick never spends device
        work on a request nobody is waiting for. Survivors are untouched —
        slots never interact across the batch axis, so their token streams
        stay identical to a run without the expiry (f64-pinned)."""
        expired = self.scheduler.prune_queue(
            lambda r: r.deadline_at is not None and now >= r.deadline_at
        )
        for request in expired:
            self._requests.pop(request.request_id, None)
            request.status = RequestStatus.TIMED_OUT
            request.finish_reason = "deadline"
            request.finished_at = now
            self.finished.append(request)
            self._journal_note_terminal(request, RequestStatus.TIMED_OUT, "deadline")
            # a PREEMPTED continuation expiring in the queue DID hold a slot:
            # its emitted tokens ride the terminal event (0 for the
            # never-admitted case), keeping the stream's accounting honest
            self.metrics.record_timeout_queued(request.request_id,
                                               new_tokens=len(request.output_ids))
            if self._obs_on:
                self._obs.async_end(self._span_cat, request.request_id,
                                    status="timed_out", reason="deadline",
                                    new_tokens=len(request.output_ids))
        for slot, request in list(self.scheduler.occupied()):
            if request.deadline_at is not None and now >= request.deadline_at:
                self._evict(slot, request, "deadline", status=RequestStatus.TIMED_OUT)

    def _maybe_inject_nan(self) -> None:
        """serving.nan fault point (reliability/faults.py): poison one slot's
        row, so the next step's logits for it are NaN — the containment path
        must then evict exactly that slot as FAILED while slot-mates decode on
        untouched."""
        spec = faults.fire_serving_nan()
        if spec is None:
            return
        slot = spec.slot
        if slot is None:
            occupied = next(iter(self.scheduler.occupied()), None)
            if occupied is None:
                return
            slot = occupied[0]
        # stash for the fused program's poison phase — applied between the
        # finish lanes (which install rows) and decode, without an eager
        # host-side device op
        self._tick_poison = slot

    def _ragged_args(self, any_decode: bool, forced, use_forced) -> tuple:
        """The fused tick program's arguments. The tick's buffered work —
        scale resets, prefill chunks, latent finishes, fault poison, the
        decode flag — travels as ONE int32 descriptor
        (serving/tick_descriptor.py). A tick that carries nothing but decode
        passes the device-resident ``_desc_decode_only``: nothing is packed
        and nothing is sent. Any other tick packs a copy of the idle
        template (pure numpy; lanes are packed FROM LANE 0, the contract of
        models/core/serving_api.py: the model's phases run the carried ones
        and never reach the idle rest, which keeps the template's trash
        tables / zero counts) and sends it in one explicit transfer. Leaves
        the tick's buffers as they are."""
        lanes, P = self._ragged_lanes, self._pages_per_slot
        n_ch, n_fin = len(self._tick_chunks), len(self._tick_finishes)
        n_reset = len(self._tick_resets)
        if n_ch > lanes or n_fin > lanes or n_reset > lanes:
            # the lane bound is structural (one chunk + one finish per
            # distinct slot per tick, admission-capped) — exceeding it is a
            # scheduling bug, not load
            raise RuntimeError(
                f"ragged tick overflow: {n_ch} chunks / {n_fin} finishes / "
                f"{n_reset} resets into {lanes} lanes"
            )
        if any_decode and not (n_ch or n_fin or n_reset) and self._tick_poison is None:
            descriptor = self._desc_decode_only
        else:
            # a host array of the tick's own, never written after it is
            # sent: the runtime may still read it once device_put has
            # returned (a prefill-only tick has no sync before the next tick
            # packs, and ONE buffer packed tick after tick had its chunk
            # lanes overwritten under the transfer)
            buf = self._desc_idle_host.copy()
            v = self._desc_layout.views(buf)
            v.any_reset[...] = bool(n_reset)
            v.any_chunk[...] = bool(n_ch)
            v.any_finish[...] = bool(n_fin)
            if self._tick_poison is not None:
                v.poison[...] = int(self._tick_poison)
            v.any_decode[...] = bool(any_decode)
            for i, (_slot, ids_row) in enumerate(self._tick_resets):
                v.reset_ids[i * P:(i + 1) * P] = ids_row
            recurrent = self._traits.recurrent_state
            for i, (slot, ids, off, c, lstart, trow) in enumerate(self._tick_chunks):
                v.ch_ids[i] = ids
                v.ch_offset[i] = off
                v.ch_count[i] = c
                v.ch_latent_start[i] = lstart
                v.ch_tables[i] = trow
                if recurrent:
                    # a recurrent model's lane also names the slot whose
                    # state it carries, zeroed where the prompt starts (such a
                    # model shares no prefix, so a claimed slot's first chunk
                    # is the one at position 0)
                    v.ch_slot[i] = slot
                    v.ch_reset[i] = off == 0
            for i, (slot, trow, ids_latent, n, rng, sampling) in enumerate(self._tick_finishes):
                v.fin_active[i] = True
                v.fin_slot[i] = slot
                v.fin_tables[i] = trow
                v.fin_ids[i] = ids_latent
                v.fin_n[i] = n
                v.fin_rng[i] = rng
                (v.fin_temp[i], v.fin_tk[i], v.fin_tp[i], v.fin_ds[i],
                 v.fin_pad[i]) = sampling
            descriptor = jax.device_put(buf)
        return (self.params, self._cache, self._state, descriptor,
                forced, use_forced)

    def _dispatch_ragged(self, any_decode: bool, forced, use_forced):
        """Dispatch the tick's ONE fused program over the descriptor
        ``_ragged_args`` hands it (its wall time is the metrics'
        ``descriptor_build_s``; a resident descriptor counts as no
        transfer). Returns the decode outputs; when ``any_decode`` is False
        they are the no-decode sentinels and the caller discards them."""
        t0 = time.perf_counter()
        args = self._ragged_args(any_decode, forced, use_forced)
        self._tick_build_s = time.perf_counter() - t0
        self._tick_transfers = int(args[3] is not self._desc_decode_only)
        self._tick_programs += 1
        tok, finite, self._cache, self._state = self._jit_ragged_tick(*args)
        self._tick_chunks.clear()
        self._tick_finishes.clear()
        self._tick_resets.clear()
        self._tick_poison = None
        return tok, finite

    # -------------------------------------------------------------------- step
    def step_dispatch(self) -> bool:
        """First half of a tick: expire deadlines, admit queued requests into
        free slots, DISPATCH the batched decode step — no device sync.
        Returns True when a decode is now in flight (``step_harvest`` must run
        before the next dispatch). The split exists for the router
        (serving/router.py): dispatching every replica's decode before
        harvesting any overlaps each replica's device step with its siblings'
        sync + host bookkeeping — the aggregate-throughput win ``serve_bench
        --replicas`` measures. ``step()`` composes the halves back into the
        single-engine tick, unchanged."""
        if self._pending_harvest is not None:
            raise RuntimeError("step_harvest() must run before the next step_dispatch()")
        obs = self._obs
        # the entry's clock reading: the caller's own time (between_steps)
        # ends and the tick and its schedule phase begin here
        t_entry = obs.now() if self._obs_on else None
        faults.fire_serving_tick_delay()  # injected stall (deadline-overrun chaos)
        if self._preempt_requested and not self._draining:
            # signal-initiated graceful drain: admission closes and the
            # backlog is rejected HERE, at a tick boundary — never inside the
            # signal handler, which only sets the flag
            self.preempted = True
            self._begin_drain()
        # tick span as a begin/end pair: it brackets both halves, which the
        # obs core pairs per (thread, name) — router-interleavable. An
        # exception anywhere in the half must still balance the spans (a dead
        # replica's dangling begin would sit in the recorder's open-span
        # stack forever). ``schedule`` is the tick's first phase: everything
        # from this method's entry to the start of the dispatch.
        self._tick_no += 1
        tick = self._tick_no
        obs.span_begin(self._span_tick, at=t_entry, tick=tick)
        obs.span_begin(self._span_schedule, at=t_entry, tick=tick)
        try:
            # per-tick program/work accounting (serving-metrics/v11
            # ragged_tick block). Buffers are re-cleared defensively: they
            # drain inside this method, so leftovers can only mean a prior
            # tick died between buffering and dispatch — stale lanes would
            # reference pages that eviction has since recycled.
            self._tick_programs = 0
            self._tick_chunk_items = 0
            self._tick_chunk_tokens = 0
            self._tick_finish_items = 0
            self._tick_oneshot = 0
            self._tick_build_s = 0.0
            self._tick_transfers = None
            self._tick_chunks.clear()
            self._tick_finishes.clear()
            self._tick_resets.clear()
            self._tick_poison = None
            self.scheduler.advance_tick()  # the priority-aging clock (int add)
            if self._deadlines_seen:
                self._expire_deadlines(time.perf_counter())
            # chunked prefill's interleave (docs/serving.md "Chunked
            # prefill"): slots mid-split-admission advance ONE chunk per
            # tick, BEFORE new admissions — oldest work first, and a finish
            # here frees prefill-slot budget the admission pass below can
            # hand out. Snapshotted so a task enqueued by this tick's own
            # admissions (which dispatch their first chunk inside
            # _admit_split) never advances twice in one tick.
            if self._prefilling:
                for slot, task in list(self._prefilling.items()):
                    if self._prefilling.get(slot) is task:
                        self._advance_prefill(slot, task)
            if not self._draining or self.scheduler.queue_depth:
                # while draining, the queue can only hold PREEMPTED
                # continuations (fresh submits are refused and _begin_drain
                # rejected the never-admitted backlog): they are accepted
                # mid-generation work, so they re-admit as capacity frees and
                # FINISH — drain's "in-flight work is finished, not dropped"
                # contract covers a victim parked by preemption
                with obs.span(self._span_admit, tick=tick):
                    can_admit = self._can_admit_paged
                    # chunk-aware admission bound: a chunked engine schedules
                    # at most max_prefill_slots concurrent chunk streams, so
                    # per-tick prefill work stays bounded at (budget x chunk)
                    # no matter how deep the queue is
                    limit = (max(self.max_prefill_slots - len(self._prefilling), 0)
                             if self.chunked else None)
                    for slot, request in self.scheduler.pop_admissible(can_admit, limit=limit):
                        self._admit(slot, request)
                    if self.priority_preemption and not self._draining:
                        # second pass: a higher-class head blocked on
                        # pages/slots may evict strictly-lower-class running
                        # work and admit this tick (docs/serving.md)
                        self._preempt_for_blocked_head(can_admit)
            self._maybe_inject_nan()
            occupied = list(self.scheduler.occupied())
            # slots mid-split-prefill hold no decode state yet (their
            # SlotState row is inactive, their in-cache table trash): they
            # are claimed for every scheduler purpose but must not be
            # harvested — the decode step would hand them pad tokens
            occupied = [(s, r) for s, r in occupied if s not in self._prefilling]
            rides = self._traits.chunk_rides_decode
            if rides and self._tick_finishes:
                # serving_api.py (h): this tick samples before its finish
                # lanes install their rows, so a slot whose prompt ends here
                # is harvested from the next tick on
                finishing = {w[0] for w in self._tick_finishes}
                occupied = [(s, r) for s, r in occupied if s not in finishing]
            tick_work = bool(self._tick_chunks or self._tick_finishes
                             or self._tick_resets)
            if not occupied and not tick_work:
                t_end = obs.span_end(self._span_schedule)
                if self._tick_programs:
                    # eviction/admission programs ran but nothing decodes:
                    # still a dispatching tick for the programs-per-tick view
                    record = self._record_tick_dispatch(tick, 0, 0, 0, 0, 0, 0)
                    obs.span_end(self._span_tick, at=t_end, **self._span_fields(record))
                else:
                    obs.span_end(self._span_tick, at=t_end)
                self._gap_from = None  # nothing dispatched: no gap to close
                if not self.scheduler.has_work:
                    self._was_empty = True  # a deadline expiry emptied the engine
                return False

            if self._replay_slots:
                forced_np = np.zeros((self.num_slots,), np.int32)
                use_np = np.zeros((self.num_slots,), bool)
                for slot, request in self._replay_slots.items():
                    forced_np[slot] = int(request.replay_ids[request.replay_pos])
                    use_np[slot] = True
                forced, use_forced = jnp.asarray(forced_np), jnp.asarray(use_np)
            else:
                forced, use_forced = self._forced_none, self._use_forced_none
            t0 = time.perf_counter()
            # schedule ends where the dispatch begins: one clock reading, no seam
            t_dispatch = obs.span_end(self._span_schedule)
            n_resets = len(self._tick_resets)  # the dispatch drains them
            # the first carried chunk lane rides the decode step of a tick that decodes
            riding = int(rides and bool(occupied) and bool(self._tick_chunks))
            if self._obs_on:
                # what the pack-and-send is about to carry: the lanes are
                # buffered, the transfer is not known yet. A tick that
                # decodes nothing has no sample_sync (the record's carrier in
                # a profiler trace): what classes it rides here
                obs.span_begin(self._span_decode_dispatch, at=t_dispatch, tick=tick,
                               chunk_lanes=self._tick_chunk_items,
                               finish_lanes=self._tick_finish_items,
                               decoding=len(occupied),
                               after_empty=int(self._was_empty))
            # the tick's ONE program: resets + chunks + finishes + poison +
            # decode, fused (docs/serving.md "Unified ragged tick"); the span
            # holds the descriptor build. Dispatch only — the jit call
            # returns before the device step finishes; the device cost lands
            # in the sample-sync at harvest
            tok, finite = self._dispatch_ragged(bool(occupied),
                                                forced, use_forced)
            t_dispatched = obs.span_end(self._span_decode_dispatch)
            if self._gap_from is not None:
                self._book_host_gap(t_entry, t_dispatch, t_dispatched)
            record = self._record_tick_dispatch(
                tick, self._tick_chunk_items, self._tick_finish_items,
                self._tick_chunk_tokens, n_resets, len(occupied), riding)
            if not occupied:
                # ragged tick that only carried prefill work: nothing to
                # harvest (the finish lanes activate slots for NEXT tick's
                # decode: when the tail chunk and finish split across ticks,
                # and always for a model of serving_api.py (h))
                obs.span_end(self._span_tick, **self._span_fields(record))
                return False
        except BaseException:
            self._end_tick_spans()
            raise
        self._pending_harvest = (occupied, tok, finite, t0, t_dispatch, record)
        return True

    def _record_tick_dispatch(self, tick: int, chunk_lanes: int, finish_lanes: int,
                              chunk_tokens: int, resets: int, decoding: int,
                              riding_chunk_lanes: int) -> TickRecord:
        """The dispatching tick's composition, made ONCE: the metrics' books
        and the tick's spans are handed the same values."""
        record = TickRecord(
            tick, self._tick_programs, self._tick_oneshot, chunk_lanes,
            finish_lanes, chunk_tokens, resets, decoding,
            self._tick_transfers or 0, int(self._was_empty), riding_chunk_lanes)
        self._was_empty = False
        self.metrics.record_tick_dispatch(
            record.programs, record.chunk_lanes, record.finish_lanes,
            record.decoding, self._tick_build_s, self._tick_transfers)
        if riding_chunk_lanes:
            self.metrics.record_riding_chunk_lanes(riding_chunk_lanes)
        return record

    def _span_fields(self, record: TickRecord):
        """The record as span arguments; with telemetry off nothing is built."""
        return record._asdict() if self._obs_on else _NO_FIELDS

    def _book_host_gap(self, t_entry: float, t_dispatch: float, t_dispatched: float) -> None:
        """The previous sync's return to this dispatch's return — the stretch
        in which the device had nothing of this engine's to run — and its
        four parts, which tile it: the harvest after that sync, the caller's
        time between the two steps, this tick's schedule phase and its
        dispatch. Booked for steady-state gaps only."""
        obs, t_sync, t_harvested = self._obs, self._gap_from, self._harvest_end
        self._gap_from = None
        if self.total_compilations != self._gap_compilations:
            return  # a program compiled inside this gap: the watchdog's time to report
        obs.observe(self._phase_host_gap, t_dispatched - t_sync)
        obs.observe(self._phase_gap_harvest, t_harvested - t_sync)
        obs.observe(self._phase_between, t_entry - t_harvested)
        obs.observe(self._phase_gap_schedule, t_dispatch - t_entry)
        obs.observe(self._phase_gap_dispatch, t_dispatched - t_dispatch)

    def _end_tick_spans(self) -> None:
        """Balance whatever tick spans a dying half left open (an unmatched
        end is ignored), innermost first."""
        for name in (self._span_decode_dispatch, self._span_schedule,
                     self._span_sample_sync, self._span_harvest, self._span_tick):
            self._obs.span_end(name)

    def step_harvest(self) -> bool:
        """Second half of a tick: the tick's ONE device sync on the dispatched
        tokens, then harvest/evict finished (or contained) requests. Returns
        True while work remains (occupied slots or queued requests). A no-op
        returning ``has_work`` when nothing was dispatched."""
        pending, self._pending_harvest = self._pending_harvest, None
        if pending is None:
            # ticks with no dispatch still flush: a drain that rejected the
            # backlog on an idle engine must journal those terminals now
            self._journal_flush()
            self._maybe_flush_preempted()
            return self.scheduler.has_work
        try:
            return self._harvest(pending)
        except BaseException:
            # balance the tick span opened by step_dispatch even when the
            # sync/evict path dies (the replica-loss domain)
            self._end_tick_spans()
            raise

    def _harvest(self, pending) -> bool:
        occupied, tok, finite, t0, t_dispatch, record = pending
        obs, tick, fields = self._obs, record.tick, self._span_fields(record)
        # the span that brackets the device's run of this tick carries the
        # whole record at its BEGIN, where the profiler's annotation is entered
        obs.span_begin(self._span_sample_sync, **fields)
        tok = np.asarray(tok)  # blocks: the step's ONE device sync point
        expert_fields = _NO_FIELDS
        if self._traits.expert_counters is not None:
            # the experts' counters came with the tokens (serving_api.py (f)):
            # what they say rides, beside the record, the spans that begin or
            # end after this readback (harvest, tick)
            tok, counts = tok[:self.num_slots], tok[self.num_slots:].reshape(2, *self._traits.expert_counters)
            assignments, touched, held = self.metrics.record_expert_counts(counts)
            if self._obs_on:
                expert_fields = {"expert_assignments": assignments, "experts_touched": touched,
                                 "experts_held_assignments": held}
        # NOT free: the program is done, but this is a second device-to-host
        # copy after the first (0.4 ms a tick on a TPU v5 lite, PERF.md 6 PR 38)
        finite = np.asarray(finite)
        t_sync = obs.span_end(self._span_sample_sync)
        # harvest: everything from the sync's return to the end of the step's
        # host work (the device idles from here until the next dispatch)
        obs.span_begin(self._span_harvest, at=t_sync, tick=tick, **expert_fields)
        # the ONE host time of this tick's tokens: every slot's first-token
        # and inter-token stamps below share it
        now = time.perf_counter()
        decode_s = now - t0
        if self._obs_on:
            # a tick with a chunk or finish lane charges every decoding slot
            # its prefill work: its wall time is booked apart
            with_prefill = record.chunk_lanes or record.finish_lanes
            obs.observe(self._phase_wall_prefill if with_prefill
                        else self._phase_wall_decode, t_sync - t_dispatch)
            self._gap_from = t_sync
            self._gap_compilations = self.total_compilations
        # tokens_generated counts USEFUL tokens only: a quarantined slot's
        # garbage sample is never emitted, and a REPLAYED token was already
        # delivered once by the engine that originally generated it — counting
        # it again would double-book the salvaged prefix in a router
        # snapshot's per-replica sum (decode_steps/decode_seconds still count
        # the replay's device work, honestly)
        useful = sum(
            1 for slot, _ in occupied
            if finite[slot] and slot not in self._replay_slots
        )
        self.metrics.record_decode_step(len(occupied), decode_s, tokens=useful)

        with obs.span(self._span_evict, tick=tick):
            for slot, request in occupied:
                if self.scheduler.occupant(slot) is not request:
                    # the request left its slot between dispatch and harvest
                    # (evict_request cancellation, deadline expiry): its
                    # in-flight token must not land on a terminal handle, and
                    # a re-evict would double-free the slot
                    continue
                if not finite[slot]:
                    # containment: the token sampled from non-finite logits
                    # is garbage — never emitted — and the slot's rows and
                    # pages are zeroed so nothing non-finite survives in the
                    # pool
                    row = np.zeros((self._pages_per_slot,), np.int32)
                    pages = self._slot_pages[slot] or []
                    if self._prefix_cache is not None:
                        # invalidate the cache subtree reached through
                        # this slot's prefix FIRST, so the possibly-
                        # tainted run is never served again — and so a
                        # poisoned page the CACHE alone shared drops to
                        # refcount 1 here and is zeroed below before its
                        # release returns it to the free list (filtering
                        # before invalidating would let it back into the
                        # pool with the NaN bytes intact). Pages sibling
                        # forks still hold (refcount >= 2 after the
                        # invalidation) must not be zeroed — that would
                        # corrupt a healthy sibling's prefix mid-decode;
                        # they route to the trash entry instead, and the
                        # siblings keep their own containment
                        # (docs/serving.md).
                        if request.page_keys:
                            dropped = self._prefix_cache.invalidate(
                                request.page_keys
                            )
                            if dropped:
                                self.metrics.set_prefix_cache(
                                    self._prefix_cache.stats(),
                                    self._shared_pages_in_use(),
                                )
                        pages = [p for p in pages
                                 if self._pool.refcount(p) < 2]
                    row[: len(pages)] = pages
                    self._tick_programs += 1
                    self._cache = self._jit_quarantine(
                        self._cache, slot, jnp.asarray(row)
                    )
                    self._evict(slot, request, "nonfinite_logits",
                                status=RequestStatus.FAILED)
                    continue
                token = int(tok[slot])
                if slot in self._replay_slots:
                    # one replayed token landed; free-running resumes when
                    # the forced stream is exhausted. A fresh failover handle
                    # re-emits the replayed prefix into output_ids; a
                    # PREEMPTED handle already holds it (the stream must stay
                    # monotonic for streaming consumers), so append only past
                    # what the handle has — the replayed token is identical
                    # by construction either way
                    if len(request.output_ids) <= request.replay_pos:
                        request.output_ids.append(token)
                    request.replay_pos += 1
                    if request.replay_pos >= request.replay_ids.size:
                        del self._replay_slots[slot]
                else:
                    request.output_ids.append(token)
                    # a request's life, stamped where the token leaves the
                    # engine (serving-metrics/v13)
                    last = request.last_token_at
                    request.last_token_at = now
                    if last is not None:
                        self.metrics.record_token_gap(now - last)
                    elif request.first_token_at is None:
                        request.first_token_at = now
                        self.metrics.record_first_token(
                            request.request_id, now - request.slot_claimed_at,
                            now - request.enqueued_at)
                        if self._obs_on:
                            obs.async_instant(self._span_cat, request.request_id,
                                              "first_token")
                    if self.journal is not None:
                        # only FREE-RUNNING emissions are journaled: a
                        # replayed token is already covered by its accept
                        # record's replay prefix (failover/recovery) or by the
                        # tick record that journaled its first emission
                        # (preemption resume) — journaling it again would
                        # duplicate it in the recovered stream
                        self._journal_tokens.setdefault(
                            request.request_id, []
                        ).append(token)
                cfg = request.config
                if cfg.eos_token_id is not None and token == cfg.eos_token_id:
                    self._evict(slot, request, "eos")
                elif len(request.output_ids) >= cfg.max_new_tokens:
                    self._evict(slot, request, "length")
        if self.watchdog is not None:
            # per-tick budget poll: one int read per watched program — any
            # growth past the churn-never-recompiles budgets is flagged
            # (counter compile.unexpected + instant trace event), never raised
            self.watchdog.check()
        # the tick's ONE journal write: admissions + emitted tokens +
        # terminal outcomes, buffered above, land together (flushed; fsynced
        # only under fsync="always" — docs/serving.md "Request journal")
        self._journal_flush()
        has_work = self.scheduler.has_work
        self._harvest_end = obs.span_end(self._span_harvest)
        obs.span_end(self._span_tick, at=self._harvest_end, **fields, **expert_fields)
        if not has_work:
            # the engine holds no request: whatever passes until the next
            # one arrives is not the loop's cost
            self._gap_from = None
            self._was_empty = True
        # after the tick span: the terminal close of a signal-initiated drain
        # writes the recorder's trace, which must hold this last tick
        self._maybe_flush_preempted()
        return has_work

    def step(self) -> bool:
        """One scheduler tick: expire deadlines, admit queued requests into
        free slots, advance every occupied slot one token, harvest/evict
        finished (or contained) requests. Returns True while work remains
        (occupied slots or queued requests)."""
        self.step_dispatch()
        return self.step_harvest()

    def discard_pending_harvest(self) -> None:
        """Drop a dispatched-but-unharvested decode step without syncing it
        (defensive; the router calls it before reusing a recovered replica in
        case a failure ever lands between dispatch and harvest). Such a
        half-tick's requests were failed over, so its tokens must never
        land; the orphaned step's device-side effect is per-slot state that
        the next admission's install fully overwrites — the normal
        churn contract. Balances the tick span the dispatch opened (a
        dangling begin would sit in the recorder's open-span stack
        forever)."""
        if self._pending_harvest is not None:
            self._pending_harvest = None
            self._obs.span_end(self._span_tick)

    def _maybe_flush_preempted(self) -> None:
        """Once a signal-initiated drain has emptied the engine, flush the
        terminal metrics snapshot and close the telemetry/JSONL surfaces —
        the whole point of the graceful path is that the artifacts land."""
        if self.preempted and not self._preempt_flushed and not self.scheduler.has_work:
            self._preempt_flushed = True
            self.metrics.write_snapshot()
            self.close()

    def run_until_drained(self, max_steps: Optional[int] = None) -> List[ServedRequest]:
        """Step until every submitted request finished; returns (and drains)
        the requests finished since the last drain, in completion order, so a
        long-lived engine holds no per-request state between serving calls.
        ``max_steps`` guards runaway loops in tests."""
        steps = 0
        while self.step():
            steps += 1
            if max_steps is not None and steps > max_steps:
                raise RuntimeError(f"engine not drained after {max_steps} steps")
        drained, self.finished = self.finished, []
        return drained

    def _begin_drain(self) -> None:
        """Close admission and reject the queued backlog (shared by explicit
        ``drain()`` and the SIGTERM/SIGINT graceful path). PREEMPTED
        continuations are NOT backlog — they are mid-generation work a
        higher class displaced, with tokens possibly already streamed to a
        client — so they stay queued and finish through the drain loop the
        way running slots do (REJECTED is documented as "never reached a
        slot", which would misreport them). RESUME submits (router
        failover/migration continuations that landed here) are accepted
        mid-generation work for exactly the same reason and get exactly the
        same treatment."""
        self._draining = True
        for request in self.scheduler.prune_queue(
            lambda r: r.status is not RequestStatus.PREEMPTED
            and not r.is_resume
        ):
            self._reject(request, "draining")

    def drain(self, max_steps: Optional[int] = None) -> List[ServedRequest]:
        """Graceful shutdown: stop admitting (subsequent submits are
        REJECTED), reject the queued backlog, and run the ACTIVE slots to
        completion — in-flight work is finished, not dropped. Returns the
        drained terminal handles (completion order, rejected backlog first)."""
        self._begin_drain()
        return self.run_until_drained(max_steps=max_steps)

    # --------------------------------------------------------------- telemetry
    @property
    def telemetry(self):
        """The engine's recorder (the shared no-op recorder when disabled).
        Read-only: the recorder is bound at construction, together with the
        watchdog and the enabled gate."""
        return self._obs

    def telemetry_summary(self) -> Optional[dict]:
        """Phase breakdown + compile report when telemetry is on, else None —
        the block ``serve_bench --profile`` embeds (docs/observability.md)."""
        if not self._obs_on:
            return None
        out = self._obs.summary()
        if self.watchdog is not None:
            out["compile"] = self.watchdog.summary()
        return out

    def close(self) -> None:
        """Release observability resources: the metrics JSONL handle, the
        compile watchdog's monitoring hook, and — when the engine created its
        recorder from a knob/env rather than being handed one — the recorder
        itself (which writes its Chrome trace if a path was configured).
        Idempotent; caller-owned recorders are left open."""
        restore_preemption_handler(self._preempt_handler, self._preempt_previous)
        self._preempt_handler = None
        if self.journal is not None:
            # land any buffered tick state, then fsync+close: a graceful
            # shutdown leaves the journal byte-complete for the next process
            self._journal_flush()
            self.journal.close()
        self.metrics.close()
        if self.watchdog is not None:
            self.watchdog.close()
        if self._owns_telemetry:
            self._obs.close()
