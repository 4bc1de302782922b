"""KV page pool + per-slot page-table bookkeeping for the serving engine.

This is the HOST side of the paged KV cache subsystem (ROADMAP item 1; the
Ragged Paged Attention recipe of PAPERS.md): a refcounted free list over a
fixed pool of physical KV pages, with fully deterministic allocation order —
no clocks, no randomness, no hashing — so a replayed admission sequence
allocates byte-identical page layouts (the discipline reliability/faults.py
established for fault injection, applied to memory management).

The DEVICE side lives in ops/paged_decode_kernel.py (``PagedKVCache``: the
physical page pool + page-table arrays the compiled decode step reads) and
models/core/perceiver_ar.py (``PagedPerceiverARCache``: install/release/ring
arithmetic). The engine (serving/engine.py) composes the two: this allocator
decides WHICH physical pages back WHICH slot, the device arrays mirror that
decision.

Allocation policy (docs/serving.md, "Paged KV cache"):

  * page 0 is RESERVED as the shared trash page — free slots' table entries
    point at it, their per-tick writes land in it, and it is never allocated;
  * a request's admission reserves ``pages_for_request`` pages UP FRONT: the
    covering prefill bucket plus the full ``max_new_tokens`` decode budget
    (capped at the window). Admission is therefore the ONLY allocation point —
    a mid-decode page fault cannot exist, so pool exhaustion surfaces
    exclusively as admission backpressure (the existing ``queue_full``
    contract) and never as a stalled or corrupted running slot;
  * eviction returns the pages to the free list — O(pages) id pushes, no
    O(window) row zeroing (quarantine of a NaN-contained slot additionally
    zeroes the returned pages' contents on device: stale non-finite values
    must never be gathered — even weight-0 — into a later tenant's softmax);
  * the free list is kept SORTED ascending, so the allocator always hands out
    the lowest free page ids: allocation order is a pure function of the
    admission/eviction history.

Refcounts are the prefix-sharing fork primitive: the cross-request RADIX
PREFIX CACHE below (``PrefixCache``, docs/serving.md "Prefix cache") maps
page-aligned prompt prefixes onto page-id runs in this pool — a new request
whose prompt extends a cached prefix ``retain()``s those pages and copies
them into its page table (O(page-table copy), zero KV duplication or
recompute), and the cache itself holds one reference per cached page so a
cached run outlives the request that built it.

The engine's arguments alone size the pages (``kv_page_size``) and turn the
cache and the chunking on (``prefix_cache``, ``prefill_chunk_tokens``;
serving/engine.py): this module reads no environment.
"""

from __future__ import annotations

import itertools
from collections import Counter
from heapq import heapify, heappop, heappush
from typing import Dict, List, Optional, Sequence, Set, Tuple


def page_keys_for_prompt(prompt, page_size: int, max_latents: int) -> Tuple[Tuple[int, ...], ...]:
    """The prompt's CACHEABLE page keys: one tuple of ``page_size`` token ids
    per full page that lies strictly below the prompt's latent-region
    boundary (position ``n - max_latents``). Pages touching the latent region
    are never shared or cached: the one-shot prefill normalizes latent-region
    rows with ``q_norm`` instead of ``kv_norm`` (models/core/modules.py), so
    their KV content depends on the PROMPT LENGTH, not just the prefix — a
    donor's latent-region page would be wrong for any consumer with a
    different n. Computed once per request at submit (the admission gate and
    ``engine.load`` walk the queue with it per tick)."""
    n = len(prompt)
    boundary = max(n - max_latents, 0)
    full = boundary // page_size
    return tuple(
        tuple(int(t) for t in prompt[k * page_size:(k + 1) * page_size])
        for k in range(full)
    )


def pages_for_tokens(tokens: int, page_size: int) -> int:
    """Pages needed to back ``tokens`` ring positions."""
    return -(-tokens // page_size)


def pages_for_request(bucket: int, max_new_tokens: int, window: int, page_size: int) -> int:
    """A request's up-front page reservation: its covering prefill bucket plus
    the whole generation budget, capped at the window (the ring wraps past it
    back into already-reserved pages). Worst-case by construction — EOS may
    finish earlier — which is exactly what makes admission the only
    allocation point."""
    return pages_for_tokens(min(bucket + max_new_tokens, window), page_size)


class PagePool:
    """Refcounted allocator over ``num_pages`` physical KV pages.

    Deterministic: the free list is a min-heap over page ids, so ``allocate``
    always returns the lowest free ids in ascending order — the same
    admission/eviction history yields the same physical layout, which is what
    lets chaos scenarios pin survivor token identity across contended runs
    and the router's failover test pin exact page counts.
    """

    def __init__(self, num_pages: int, reserved: int = 1):
        if num_pages < reserved + 1:
            raise ValueError(
                f"num_pages must exceed the {reserved} reserved page(s), got {num_pages}"
            )
        self.num_pages = num_pages
        self.reserved = reserved
        self._refcount = [0] * num_pages
        self._free: List[int] = list(range(reserved, num_pages))
        heapify(self._free)
        self.total_allocations = 0

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return (self.num_pages - self.reserved) - len(self._free)

    def can_allocate(self, n: int) -> bool:
        return n <= len(self._free)

    def allocate(self, n: int) -> List[int]:
        """Claim ``n`` pages (refcount 1 each), lowest ids first. Raises when
        the pool cannot satisfy the request — callers gate on
        ``can_allocate`` (the admission loop's head-of-line check), so a
        raise here is a caller bug, not backpressure."""
        if n < 0:
            raise ValueError(f"cannot allocate {n} pages")
        if n > len(self._free):
            raise RuntimeError(
                f"page pool exhausted: need {n}, have {len(self._free)} free "
                f"(of {self.num_pages - self.reserved} allocatable)"
            )
        pages = [heappop(self._free) for _ in range(n)]
        for p in pages:
            self._refcount[p] = 1
        self.total_allocations += n
        return pages

    def _validate_ids(self, pages: Sequence[int]) -> None:
        bad = [p for p in pages if not 0 <= p < self.num_pages]
        if bad:
            raise ValueError(f"page id(s) {bad} outside pool of {self.num_pages}")

    def refcount(self, page: int) -> int:
        """Current reference count of one page — the prefix cache's
        eviction policy reads it (a cached page at refcount 1 is held by the
        cache ALONE, so releasing it actually frees a page; higher counts
        mean live sessions still share it)."""
        self._validate_ids([page])
        return self._refcount[page]

    def shared_count(self, pages: Sequence[int]) -> int:
        """How many of ``pages`` are currently referenced more than once —
        one validation pass for the whole list (the per-tick shared-page
        gauge walks every slot's table; per-page ``refcount()`` calls would
        pay the validation list per page)."""
        self._validate_ids(pages)
        return sum(1 for p in pages if self._refcount[p] >= 2)

    def retain(self, pages: Sequence[int]) -> None:
        """Add one reference to each page — the prefix-sharing primitive
        (ROADMAP item 3: forking a shared prompt retains its pages and copies
        the page table). Validates the WHOLE list before touching any
        refcount: an invalid id mid-list must leave the pool exactly as it
        was (validate-then-mutate; a partial retain would leak references on
        the raise path)."""
        self._validate_ids(pages)
        for p in pages:
            if self._refcount[p] < 1:
                raise ValueError(f"page {p} is not allocated")
        for p in pages:
            self._refcount[p] += 1

    def release(self, pages: Sequence[int]) -> None:
        """Drop one reference per page; pages reaching refcount 0 return to
        the free list. Double-free raises (a slot's page list is consumed
        exactly once, at eviction) — and raises BEFORE any refcount moves:
        validation covers the whole list first (duplicate ids counted against
        the refcount, so ``release([p, p])`` of a once-held page is caught),
        so a double-free mid-list leaves the pool state untouched instead of
        half-released and inconsistent."""
        self._validate_ids(pages)
        for p, n in Counter(pages).items():
            if self._refcount[p] < n:
                raise ValueError(f"double free of page {p}")
        for p in pages:
            self._refcount[p] -= 1
            if self._refcount[p] == 0:
                heappush(self._free, p)


class _TrieNode:
    """One cached page: its token key, its pool page id, children keyed by
    the NEXT page's token tuple, and a monotone last-used stamp (the LRU
    clock is touch-counted, not wall-clock — determinism contract)."""

    __slots__ = ("key", "page", "children", "parent", "last_used")

    def __init__(self, key, page: int, parent, last_used: int):
        self.key = key
        self.page = page
        self.children: Dict[tuple, "_TrieNode"] = {}
        self.parent = parent
        self.last_used = last_used


class PrefixCache:
    """Cross-request radix prefix cache over a shared ``PagePool``
    (docs/serving.md "Prefix cache"; the Ragged Paged Attention paper's
    page-granular reuse recipe on the host side).

    A TRIE keyed on page-aligned prompt-token tuples (exact keys — a lossy
    hash could collide two prefixes and silently serve wrong KV; Python's
    dict hashing gives the O(1) lookup without the risk) maps each cached
    prefix to a run of page ids in the pool, one node per page. The cache
    holds ONE pool reference per cached page (``retain`` at insert), so a
    cached run outlives the request that built it; a probe's consumer takes
    its own reference per shared page (the engine retains before copying ids
    into the slot's table). Everything is a pure page-table/refcount
    transform — no KV bytes move, no layout is touched (the compiler-first
    O(1)-caching discipline of PAPERS.md).

    Eviction (``evict``) is REFCOUNT-AWARE LRU over leaves: only leaf nodes
    whose page refcount is exactly 1 (cache-held alone) are released —
    releasing a page a live session still shares would free nothing now and
    forfeit future hits — in (last_used, page id) order, cascading to
    parents that become leaves, until the requested page count is free or no
    reclaimable leaf remains. Deterministic: the LRU stamp is a touch
    counter driven solely by the probe/insert history.
    """

    def __init__(self, pool: PagePool, page_size: int,
                 kv_quant: Optional[str] = None):
        # the cache's pages carry the POOL'S byte layout: int8 + scale
        # sidecars under kv_quant, full-precision rows otherwise. The mode is
        # part of the cache's identity — a pool toggled between runs must
        # never serve int8 pages to an fp reader (or vice versa), so the
        # engine validates its own mode against the cache it builds
        # (``ensure_mode``) and any future persisted/shared cache must carry
        # the mode with its keys.
        self.pool = pool
        self.page_size = page_size
        self.kv_quant = kv_quant
        self._children: Dict[tuple, _TrieNode] = {}  # root's children
        self._nodes: Set[_TrieNode] = set()  # flat view for eviction scans
        self._clock = itertools.count()
        # lifetime counters (serving-metrics/v8 mirrors these)
        self.hits = 0  # probes that matched >= 1 page
        self.misses = 0  # probes that matched none
        self.inserted_pages = 0
        self.evicted_pages = 0
        self.evictions = 0  # eviction EPISODES (an evict() call that freed)

    # ------------------------------------------------------------------ state
    def ensure_mode(self, kv_quant: Optional[str]) -> None:
        """Validate that a reader's quantization mode matches the bytes this
        cache's pages hold (the quant × prefix-cache seam, docs/serving.md):
        an fp reader handed int8 pages would gather garbage magnitudes, a
        quantized reader handed fp pages would mis-scale every prefix — both
        silent wrong-KV outcomes, so a mismatch REJECTS loudly instead."""
        if kv_quant != self.kv_quant:
            raise ValueError(
                f"prefix cache holds {self.kv_quant or 'full-precision'} pages "
                f"but the reader runs {kv_quant or 'full-precision'} — a cache "
                "never serves pages across quantization modes"
            )

    @property
    def cached_pages(self) -> int:
        return len(self._nodes)

    def reclaimable_page_ids(self) -> List[int]:
        """Ids of cached pages held by the cache ALONE (refcount 1) — the
        pages an eviction pass could actually return to the free list. The
        admission accounting (``engine.load``) counts these as available
        under pressure, minus any a queued request's own match would pin."""
        return [n.page for n in self._nodes if self.pool.refcount(n.page) == 1]

    def reclaimable_pages(self) -> int:
        return len(self.reclaimable_page_ids())

    def cached_page_ids(self) -> Set[int]:
        """Ids of EVERY cached page, whatever its refcount — the preemption
        victim-selection accounting reads it (a victim's page shared with the
        cache alone becomes reclaimable at the admission gate once the victim
        releases, so it counts toward what preempting the victim frees)."""
        return {n.page for n in self._nodes}

    def stats(self) -> Dict:
        total = self.hits + self.misses
        return {
            "hits": self.hits,
            "misses": self.misses,
            "hit_rate": round(self.hits / total, 4) if total else 0.0,
            "cached_pages": self.cached_pages,
            "inserted_pages": self.inserted_pages,
            "evicted_pages": self.evicted_pages,
            "evictions": self.evictions,
        }

    # ------------------------------------------------------------------ probe
    def probe(self, keys: Sequence[tuple]) -> List[int]:
        """Longest cached run matching ``keys`` (the prompt's page keys, in
        order): returns the matched page ids WITHOUT taking references — the
        caller retains before using them (same tick, nothing can evict in
        between: eviction only runs inside the engine's admission path).
        Touches the matched path's LRU stamps root-to-leaf (parents never go
        staler than children, so leaf-first eviction is well-ordered)."""
        run: List[int] = []
        children = self._children
        for key in keys:
            node = children.get(key)
            if node is None:
                break
            node.last_used = next(self._clock)
            run.append(node.page)
            children = node.children
        if run:
            self.hits += 1
        elif keys:
            self.misses += 1
        return run

    def peek_match_pages(self, keys: Sequence[tuple]) -> List[int]:
        """Page ids a probe WOULD match, without touching LRU stamps or
        hit/miss counters — the per-tick accounting walk (``engine.load``,
        the admission gate) must not skew the cache's recency or hit rate."""
        run: List[int] = []
        children = self._children
        for key in keys:
            node = children.get(key)
            if node is None:
                break
            run.append(node.page)
            children = node.children
        return run

    def peek_match(self, keys: Sequence[tuple]) -> int:
        return len(self.peek_match_pages(keys))

    def touch(self, keys: Sequence[tuple]) -> None:
        """Refresh the matched path's LRU stamps without counting a hit —
        the admission gate calls this BEFORE evicting under pressure so a
        blocked head's own matched prefix is the last thing LRU reclaims
        (evicting it would grow the very reservation being fitted)."""
        children = self._children
        for key in keys:
            node = children.get(key)
            if node is None:
                break
            node.last_used = next(self._clock)
            children = node.children

    # ----------------------------------------------------------------- insert
    def insert(self, keys: Sequence[tuple], pages: Sequence[int]) -> int:
        """Cache the prompt's page run: walk ``keys``, creating a node per
        page not already cached and RETAINING that page (the cache's own
        reference). Pages already cached along the path are left alone —
        their existing node already holds the reference (the donor found
        them via probe). Returns the number of newly cached pages."""
        if len(pages) < len(keys):
            raise ValueError(f"page run ({len(pages)}) shorter than keys ({len(keys)})")
        added = 0
        children = self._children
        parent: Optional[_TrieNode] = None
        for key, page in zip(keys, pages):
            node = children.get(key)
            if node is None:
                self.pool.retain([page])
                node = _TrieNode(key, int(page), parent, next(self._clock))
                children[key] = node
                self._nodes.add(node)
                added += 1
            else:
                node.last_used = next(self._clock)
            parent = node
            children = node.children
        self.inserted_pages += added
        return added

    # ----------------------------------------------------------------- evict
    def _drop(self, node: _TrieNode) -> None:
        siblings = node.parent.children if node.parent is not None else self._children
        del siblings[node.key]
        self._nodes.discard(node)
        self.pool.release([node.page])

    def evict(self, pages_needed: int) -> int:
        """Free up to ``pages_needed`` pages by releasing cache-only
        (refcount-1) leaves in LRU order, cascading into parents that become
        reclaimable leaves. Returns the number of pages actually freed —
        possibly fewer (live sessions pin their shared prefixes; those nodes
        stay, deliberately)."""
        freed = 0
        # ONE scan builds a min-heap of reclaimable leaves; parents that
        # become reclaimable leaves as their children drop are pushed as the
        # cascade reaches them — O(N + k log N) for k freed pages, not the
        # O(k*N) a rescan-per-page would cost inside the admission gate.
        # (last_used, page) is unique per node, so heap order never compares
        # nodes and matches the rescan formulation exactly.
        heap = [
            (n.last_used, n.page, n) for n in self._nodes
            if not n.children and self.pool.refcount(n.page) == 1
        ]
        heapify(heap)
        while freed < pages_needed and heap:
            _, _, victim = heappop(heap)
            parent = victim.parent
            self._drop(victim)
            freed += 1
            if (parent is not None and not parent.children
                    and self.pool.refcount(parent.page) == 1):
                heappush(heap, (parent.last_used, parent.page, parent))
        if freed:
            self.evictions += 1
            self.evicted_pages += freed
        return freed

    def invalidate(self, keys: Sequence[tuple]) -> int:
        """Drop the cached subtree REACHED THROUGH ``keys[0]`` — the NaN
        containment hook (serving/engine.py): when a poisoned slot's table
        holds cache-shared pages, every cached prefix routed through its
        first page is suspect (any deeper node's prefix includes that page),
        so the whole subtree's references are released and the cache never
        serves the possibly-tainted run again. The PAGES are not zeroed
        here — the engine's quarantine handles device bytes; still-live
        sibling sessions keep their own references and their own
        containment. Returns the number of cached pages released."""
        if not keys:
            return 0
        root = self._children.get(keys[0])
        if root is None:
            return 0
        # post-order: children drop before parents so _drop's leaf-first
        # bookkeeping invariants hold throughout
        stack, order = [root], []
        while stack:
            node = stack.pop()
            order.append(node)
            stack.extend(node.children.values())
        for node in reversed(order):
            self._drop(node)
        # NOT counted in evictions/evicted_pages: those gauges mean
        # refcount-aware LRU reclaims under pool pressure (the v8 schema's
        # words), and conflating containment drops with them would make NaN
        # containment read as cache thrashing on a dashboard. The caller
        # gets the count; cached_pages reflects the drop.
        return len(order)

    def clear(self) -> int:
        """Release EVERY cached reference (leaves inward, so parent/child
        invariants hold throughout) — the explicit flush a drain-to-empty
        check or a fleet shutdown uses. Pages shared by live sessions stay
        allocated under their remaining references. Returns pages released."""
        released = 0
        # one post-order walk per root (invalidate's formulation): children
        # drop before parents, O(N) total — peeling one leaf layer per
        # full rescan would be O(depth x N) on a deep shared preamble
        for root in list(self._children.values()):
            stack, order = [root], []
            while stack:
                node = stack.pop()
                order.append(node)
                stack.extend(node.children.values())
            for node in reversed(order):
                self._drop(node)
                released += 1
        return released
