"""Slot scheduler: priority-class admission of queued requests into free
decode slots.

The scheduler is pure host-side bookkeeping — it never touches jax. The
engine owns the device state (batched cache + slot state pytree); the
scheduler decides WHICH request occupies WHICH batch row and when. Keeping
the policy isolated here means alternative policies (shortest-prompt-first,
deadline-aware eviction) can be dropped in without touching the compiled
decode path.

Admission order (docs/serving.md, "Priority classes & preemption"):

  * every queued entry carries a small-int **priority class** (default 0,
    higher wins) and a monotone **sequence number** (the engine passes its
    request id, so a preempted request re-queued mid-flight keeps its
    original seniority);
  * order is (effective priority descending, sequence ascending) — strict
    FIFO within a class, deterministic across classes;
  * **aging** (anti-starvation): with ``aging_ticks=N``, a queued entry's
    effective priority rises by one class every N scheduler ticks it has
    waited — tick-counted like the router's breaker cooldowns, no clocks, no
    randomness, so the order is a pure function of the submit/tick history.
    Aging affects queue ORDER only; preemption eligibility (serving/engine.py)
    always compares base priorities, so an aged class-0 request can outwait
    higher classes but never evict them.

Design constraints inherited from the device side (docs/serving.md):
  * the slot count is static — it is the batch dimension of the compiled
    decode step, so the scheduler can never grow it, only multiplex over it;
  * admission is one request at a time (each admission is one prefill call),
    so ``pop_admissible`` yields (slot, request) pairs for the engine to
    install sequentially;
  * eviction frees the slot immediately — the engine's decode step feeds pad
    tokens through inactive rows, so a freed slot costs the batch-wide
    matmuls its row (not, on the paged pool, the attention over its
    self-attention ring: ops/attention.RingKVCache.active) but never
    correctness.
"""

from __future__ import annotations

import itertools
import os
from collections import deque
from typing import Callable, Deque, Generic, Iterator, List, Optional, Tuple, TypeVar

T = TypeVar("T")


def preemption_enabled() -> bool:
    """Kill-switch for the priority/preemption feature:
    ``PERCEIVER_IO_TPU_DISABLE_PREEMPTION=1`` pins engines to the pre-PR
    behavior — the queue is strict submit-order FIFO (priorities ignored, no
    aging) and running slots are never preempted, so pool pressure surfaces
    exclusively as the old ``queue_full`` backpressure. Checked at engine
    construction, like the paged-KV switch; f64 parity when off is pinned by
    the ``preempt_disabled_inert`` chaos scenario."""
    return os.environ.get("PERCEIVER_IO_TPU_DISABLE_PREEMPTION", "0").lower() in ("0", "false", "")


class _Entry:
    """One queued request with its ordering metadata."""

    __slots__ = ("request", "priority", "seq", "tick")

    def __init__(self, request, priority: int, seq: int, tick: int):
        self.request = request
        self.priority = priority
        self.seq = seq
        self.tick = tick


class SlotScheduler(Generic[T]):
    """Priority queue + free-list over a fixed pool of ``num_slots`` decode
    slots. With default priorities and no aging this degenerates to the
    original FIFO (the pre-priority behavior, bit-identical — pinned by the
    ``preempt_disabled_inert`` chaos scenario)."""

    def __init__(self, num_slots: int, aging_ticks: Optional[int] = None):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if aging_ticks is not None and aging_ticks < 1:
            raise ValueError(f"aging_ticks must be >= 1, got {aging_ticks}")
        self.num_slots = num_slots
        self.aging_ticks = aging_ticks
        self.ticks = 0  # the aging clock: advanced once per engine tick
        self._queue: List[_Entry] = []
        self._slots: List[Optional[T]] = [None] * num_slots
        self._free: Deque[int] = deque(range(num_slots))
        self._seq = itertools.count()  # fallback when the caller passes no seq
        self.total_admissions = 0

    # ------------------------------------------------------------------- state
    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    @property
    def active_slots(self) -> int:
        return self.num_slots - len(self._free)

    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def has_work(self) -> bool:
        return bool(self._queue) or self.active_slots > 0

    @property
    def load(self) -> int:
        """Backlog beyond free capacity: ``queue_depth - free_slots``. Negative
        = idle headroom. The engine's queue bound and the router's
        least-loaded dispatch (serving/router.py) both rank on this number, so
        "how full is this pool" has exactly one definition. Preempted
        continuations parked back in the queue count like any other entry —
        the router's dispatch sees preempted-replay parking as real load."""
        return len(self._queue) - len(self._free)

    def occupant(self, slot: int) -> Optional[T]:
        return self._slots[slot]

    def occupied(self) -> Iterator[Tuple[int, T]]:
        """(slot, request) pairs for every occupied slot, slot order."""
        for slot, req in enumerate(self._slots):
            if req is not None:
                yield slot, req

    def queued(self) -> Iterator[T]:
        """Queued requests in ADMISSION order (read-only view) — the engine's
        paged capacity estimate walks this to simulate head-of-line
        admissions against the free page count (serving/engine.py)."""
        return (e.request for e in self._ordered())

    def queue_snapshot(self) -> List[Tuple[T, int, int]]:
        """(request, effective priority, seq) triples in admission order — a
        read-only view of the whole ordering decision. Journal recovery pins
        its seniority contract through this (tests/test_journal.py): a
        rebuilt queue must rank recovered sessions exactly as the dead
        process ranked the originals, and asserting on the (priority, seq)
        keys catches an ordering regression the eventual token outputs might
        mask (same tokens can emerge from a different admission order when
        slots are plentiful)."""
        return [(e.request, self.effective_priority(e), e.seq)
                for e in self._ordered()]

    # ------------------------------------------------------------------ policy
    def advance_tick(self) -> None:
        """Advance the aging clock (one call per engine tick). A no-op cost
        when aging is off; with ``aging_ticks=N`` every queued entry's
        effective priority rises by one class per N ticks waited."""
        self.ticks += 1

    def effective_priority(self, entry: _Entry) -> int:
        if self.aging_ticks is None:
            return entry.priority
        return entry.priority + (self.ticks - entry.tick) // self.aging_ticks

    def _order_key(self, entry: _Entry):
        # higher effective class first; FIFO (sequence) within a class
        return (-self.effective_priority(entry), entry.seq)

    def _ordered(self) -> List[_Entry]:
        return sorted(self._queue, key=self._order_key)

    def enqueue(self, request: T, priority: int = 0, seq: Optional[int] = None) -> None:
        """Queue one request at ``priority`` (higher wins). ``seq`` is the
        FIFO tiebreaker within a class — the engine passes its monotone
        request id so a preempted request re-queued mid-flight resumes its
        ORIGINAL seniority instead of going to the back; callers that pass
        nothing get an internal counter (plain FIFO)."""
        self._queue.append(_Entry(
            request, priority, next(self._seq) if seq is None else seq, self.ticks
        ))

    def peek(self) -> Optional[T]:
        """The request ``pop_admissible`` would admit next (admission-order
        head), or None — the engine's preemption trigger inspects it without
        claiming a slot."""
        if not self._queue:
            return None
        return min(self._queue, key=self._order_key).request

    def prune_queue(self, predicate: Callable[[T], bool]) -> List[T]:
        """Remove and return every QUEUED request matching ``predicate``
        (insertion order), preserving the remaining entries' priorities and
        seniority — the admission-control primitive behind deadline expiry of
        waiting requests and the reject-the-backlog step of a graceful drain
        (serving/engine.py). Requests already occupying slots are untouched
        (evicting a running request is the engine's job: it owns the device
        state)."""
        kept: List[_Entry] = []
        removed: List[T] = []
        for entry in self._queue:
            if predicate(entry.request):
                removed.append(entry.request)
            else:
                kept.append(entry)
        if removed:  # nothing matched: keep the original list untouched
            self._queue = kept
        return removed

    def pop_admissible(
        self,
        can_admit: Optional[Callable[[T], bool]] = None,
        limit: Optional[int] = None,
    ) -> Iterator[Tuple[int, T]]:
        """Yield (slot, request) admissions in admission order until slots or
        queue run out. The slot is claimed as soon as the pair is yielded, so
        the engine can interleave prefill/install work with further
        admissions.

        ``can_admit`` adds a per-request resource gate (the paged engine's
        free-page check): when the HEAD request (highest effective priority,
        FIFO within its class) fails it, admission stops — head-of-line
        blocking on purpose, because skipping ahead would break the priority
        order's fairness and make page-allocation order depend on queue
        composition rather than history (determinism contract,
        serving/paging.py). A head blocked on resources is the engine's cue
        to preempt (serving/engine.py).

        ``limit`` caps admissions THIS call (None = unlimited, the classic
        behavior): the chunk-aware accounting — a chunked-prefill engine
        admits at most its remaining prefill-slot budget per tick, so a
        burst of long prompts cannot schedule more concurrent chunk streams
        than ``max_prefill_slots`` allows and the per-tick prefill work
        stays bounded at (budget x chunk) regardless of queue depth
        (serving/engine.py, docs/serving.md "Chunked prefill")."""
        admitted = 0
        while self._queue and self._free:
            if limit is not None and admitted >= limit:
                return
            head = min(self._queue, key=self._order_key)
            if can_admit is not None and not can_admit(head.request):
                return
            slot = self._free.popleft()
            self._queue.remove(head)
            self._slots[slot] = head.request
            self.total_admissions += 1
            admitted += 1
            yield slot, head.request

    def release(self, slot: int) -> T:
        """Free a slot (request finished or cancelled); returns the occupant.
        Freed slots recycle LIFO-last so reuse is observable in tests."""
        request = self._slots[slot]
        if request is None:
            raise ValueError(f"slot {slot} is not occupied")
        self._slots[slot] = None
        self._free.append(slot)
        return request
