"""Unified telemetry core: counters, gauges, and span timers — jax-free.

The stack's pinned invariants ("churn never recompiles", "one sync per tick",
"only log-boundary host syncs") are checked in tests but invisible at runtime:
nothing says WHERE a serving tick or a train step spent its time, and a silent
recompile or prefetch starvation only surfaces when a bench regresses. This
module is the runtime signal: a thread-safe in-process recorder that the
serving engine and training loop instrument with PHASE spans (admit / prefill /
decode dispatch / sample-sync / fetch-wait / log-sync / ...), exportable as a
Chrome ``trace_event`` JSON viewable in Perfetto (obs/trace.py) and as a
per-phase aggregate summary (`summary()`) that ``scripts/obs_report.py`` and
the bench ``--profile`` artifacts embed.

Inertness discipline (same as reliability/faults.py): telemetry is OFF by
default. A disabled surface holds the shared ``NULL_RECORDER`` whose every
method is a constant-return no-op — an instrumented hot path costs an
attribute lookup and a call into an empty method, never an allocation, a lock,
or (critically) a host sync. The float64 parity pins of the serving and
training suites run THROUGH the instrumented paths with the recorder both off
and on (tests/test_obs.py): spans only ever *time* existing host-side calls,
they never touch device values.

Spans NEST: every span records the span that was open on its thread when it
began (``parent`` in its trace event — a per-thread stack shared by ``span()``
and ``span_begin``/``span_end``), and ``summary()`` reports each phase's SELF
time (``self_total_s``: its duration minus what its direct children cover), so
a phase table sums to wall time instead of counting nested work twice.

Profiler bridge: an enabled recorder also enters a
``jax.profiler.TraceAnnotation`` of the same name for every span, so in any
``jax.profiler`` trace the program's spans sit in the ``/host:CPU`` plane on
the clock the device's ``XLA Ops`` are on (docs/observability.md "Reading the
spans in a profiler trace"). jax is never imported from here: the bridge arms
itself only once jax is already loaded in the process, and with no profiler
session a ``TraceMe`` is a flag test.

Clocks are injectable (``clock=`` takes any () -> float seconds callable) so
span math is exactly reproducible under a fake clock in tests. The recorder
never calls jax: it can be imported, exercised, and unit-tested without a
backend, and recording from worker threads (prefetcher, checkpoint writer) is
safe by construction (one lock, no reentrancy; span stacks are per thread).

Enablement:
  * explicit: ``ServingEngine(telemetry=...)`` / ``TrainerConfig.telemetry`` —
    ``True`` (in-memory recorder), a path string (recorder + Chrome trace
    written there on close), or a ``TelemetryRecorder`` you own;
  * ambient: the ``PERCEIVER_IO_TPU_TELEMETRY`` env var with the same
    encoding ("1"/"true" = in-memory, anything else non-empty = trace path),
    consulted only when the knob is ``None``;
  * ``False`` always wins over the env (a surface can opt out).
"""

from __future__ import annotations

import os
import sys
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

TELEMETRY_ENV = "PERCEIVER_IO_TPU_TELEMETRY"

# ``summary()``'s shape. v2: phases carry ``self_total_s`` and instrumented
# surfaces declare their phases up front (a declared phase that never ran
# reads count 0; a name that is absent is not one the program emits). v1, the
# unversioned summary before it, had neither — a reader finding no ``schema``
# key is looking at a program older than the tick's tiling phases.
SUMMARY_SCHEMA = "telemetry-summary/v2"

# Bounded event history: a long-lived engine records several events per
# generated token forever; an unbounded list is a slow host-memory leak and an
# ever-growing trace file. Aggregates (counters/histograms) stay lifetime;
# only the raw trace-event history is windowed, and the drop count is reported
# (``trace.events_dropped`` counter) — truncation is never silent.
MAX_TRACE_EVENTS = 200_000

# per-phase duration histograms keep a bounded recent window for percentiles
# (mirrors serving/metrics.py LATENCY_WINDOW rationale)
HISTOGRAM_WINDOW = 4096


class _NullSpan:
    """Reusable no-op context manager — the disabled span costs no allocation."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class NullRecorder:
    """The disabled telemetry surface: every method is an inert no-op.

    One shared instance (``NULL_RECORDER``) is installed wherever telemetry is
    off, so ``recorder.span(...)``/``counter_inc(...)`` on a hot path is a
    method call returning a shared constant — the zero-overhead contract the
    tests pin. Never subclassed by the real recorder: ``enabled`` is the one
    flag instrumented code may branch on to skip argument construction.
    """

    enabled = False

    def span(self, name: str, **args) -> _NullSpan:
        return _NULL_SPAN

    def span_begin(self, name: str, at: Optional[float] = None, **args) -> None:
        return None

    def span_end(self, name: str, at: Optional[float] = None, **args) -> None:
        return None

    def now(self) -> float:
        return 0.0

    def declare_phases(self, names) -> None:
        return None

    def async_begin(self, name: str, span_id, **args) -> None:
        return None

    def async_instant(self, name: str, span_id, phase_name: str, **args) -> None:
        return None

    def async_end(self, name: str, span_id, **args) -> None:
        return None

    def instant(self, name: str, **args) -> None:
        return None

    def counter_inc(self, name: str, n=1) -> None:
        return None

    def gauge_set(self, name: str, value) -> None:
        return None

    def observe(self, name: str, seconds: float) -> None:
        return None

    def summary(self) -> Dict:
        return {}

    def chrome_trace(self) -> Dict:
        return {"traceEvents": []}

    def write_chrome_trace(self, path: str) -> None:
        return None

    def close(self) -> None:
        return None


NULL_RECORDER = NullRecorder()


def _new_hist() -> list:
    """A phase's books: [count, total, max, recent window, self total]."""
    return [0, 0.0, 0.0, deque(maxlen=HISTOGRAM_WINDOW), 0.0]


class _Frame:
    """One open span on its thread's stack."""

    __slots__ = ("name", "t0", "args", "parent", "children_s", "annotation", "open")

    def __init__(self, name: str, t0: float, args: Dict, parent: Optional["_Frame"]):
        self.name = name
        self.t0 = t0
        self.args = args
        self.parent = parent
        self.children_s = 0.0  # what direct children covered (self time = dur - this)
        self.annotation = None  # the jax.profiler.TraceAnnotation twin, if armed
        self.open = True


class _Span:
    """Context manager recording one complete ("X") span on exit."""

    __slots__ = ("_rec", "_name", "_args", "_frame")

    def __init__(self, rec: "TelemetryRecorder", name: str, args: Dict):
        self._rec = rec
        self._name = name
        self._args = args
        self._frame = None

    def __enter__(self):
        self._frame = self._rec._push(self._name, self._args, None)
        return self

    def __exit__(self, *exc):
        self._rec._pop(self._frame)
        return False


class TelemetryRecorder:
    """Thread-safe in-process telemetry: counters, gauges, span timers.

    ``clock`` is any monotonic () -> float seconds callable (injectable for
    deterministic tests; defaults to ``time.monotonic``). All event timestamps
    are offsets from the recorder's construction instant, so traces from
    different processes align at zero.

    ``trace_path`` + ``flush_interval_s``: with a path set, ``close()`` writes
    the final Chrome trace there; a positive flush interval additionally
    starts a background flush thread (``perceiver-telemetry-flush``) that
    rewrites the file periodically so a crashed run still leaves a readable
    trace. The thread is a daemon (an owner that dies without close() must
    not hang interpreter shutdown) but ``close()`` always stops and joins it.
    """

    enabled = True

    def __init__(
        self,
        clock: Callable[[], float] = time.monotonic,
        trace_path: Optional[str] = None,
        flush_interval_s: Optional[float] = None,
        max_events: int = MAX_TRACE_EVENTS,
    ):
        self._clock = clock
        self._origin = clock()
        self._lock = threading.Lock()
        # deque eviction is O(1): list.pop(0) would memmove the whole buffer
        # under the lock on every hot-path event once the cap is hit
        self._events: deque = deque(maxlen=max_events)
        self._dropped = 0
        self._max_events = max_events
        self.counters: Dict[str, float] = {}
        self.gauges: Dict[str, float] = {}
        self._hist: Dict[str, list] = {}  # name -> _new_hist()
        # per-thread stack of open spans (span() and span_begin/span_end
        # alike): the top at a span's begin is its parent
        self._local = threading.local()
        # the jax.profiler.TraceAnnotation class once jax is loaded in this
        # process (None = not resolved yet, False = unavailable)
        self._annotation_cls = None
        self.trace_path = trace_path
        self._closed = False
        self._flush_stop = threading.Event()
        self._flush_thread: Optional[threading.Thread] = None
        if trace_path and flush_interval_s and flush_interval_s > 0:
            # daemon: an owner that crashes without close() must not hang the
            # interpreter on a non-daemon join at shutdown — the thread's
            # bound-method target keeps this recorder referenced, so the
            # __del__ backstop could never fire. close() still stops AND
            # joins it deterministically, and the crash-trace guarantee is
            # exactly the periodic flushes already written.
            self._flush_thread = threading.Thread(
                target=self._flush_loop,
                args=(float(flush_interval_s),),
                name="perceiver-telemetry-flush",
                daemon=True,
            )
            self._flush_thread.start()

    # ---------------------------------------------------------------- recording
    def _now(self) -> float:
        return self._clock() - self._origin

    def _append_event(self, event: Dict) -> None:
        # caller holds the lock; the deque's maxlen performs the eviction
        if len(self._events) >= self._max_events:
            self._dropped += 1
        self._events.append(event)

    def now(self) -> float:
        """The recorder's clock: for intervals an instrumented surface
        measures itself (``observe``) on the clock its spans are on."""
        return self._clock()

    def _trace_annotation(self):
        """``jax.profiler.TraceAnnotation`` if jax is loaded in this process,
        else None. Never imports jax itself (the core stays jax-free)."""
        cls = self._annotation_cls
        if cls is None:
            if "jax" not in sys.modules:
                return None  # a later span asks again
            try:
                from jax.profiler import TraceAnnotation as cls
            except ImportError:  # a jax without the profiler: no bridge
                cls = False
            self._annotation_cls = cls
        return cls or None

    def _push(self, name: str, args: Dict, at: Optional[float]) -> _Frame:
        try:
            stack = self._local.stack
        except AttributeError:
            stack = self._local.stack = []
        frame = _Frame(name, self._clock() if at is None else at, args,
                       stack[-1] if stack else None)
        annotation = self._trace_annotation()
        if annotation is not None:
            # the same span on the profiler's clock (/host:CPU plane); with
            # no profiler session this is a flag test
            frame.annotation = annotation(name, **args)
            frame.annotation.__enter__()
        stack.append(frame)
        return frame

    def _pop(self, frame: _Frame, at: Optional[float] = None) -> float:
        t1 = self._clock() if at is None else at
        if frame.annotation is not None:
            frame.annotation.__exit__(None, None, None)
        stack = self._local.stack
        if stack and stack[-1] is frame:
            stack.pop()
        else:  # begin/end pairs that interleave (router replicas on one thread)
            stack.remove(frame)
        frame.open = False
        dur = t1 - frame.t0
        parent = frame.parent
        if parent is not None and parent.open:
            parent.children_s += dur
        event = {"ph": "X", "name": frame.name, "ts": frame.t0 - self._origin,
                 "dur": dur, "tid": threading.get_ident()}
        if parent is not None:
            event["parent"] = parent.name
        if frame.args:
            event["args"] = frame.args
        with self._lock:
            self._observe_locked(frame.name, dur, max(dur - frame.children_s, 0.0))
            self._append_event(event)
        return t1

    def span(self, name: str, **args) -> _Span:
        """Time a with-block as one complete span (also feeds the histogram)."""
        return _Span(self, name, args)

    def span_begin(self, name: str, at: Optional[float] = None, **args) -> None:
        """Open a span closed later by ``span_end`` on the SAME thread (for
        phases that do not nest as a with-block, e.g. a tick bracketing two
        calls). ``at`` (here and on ``span_end``) places the edge at a clock
        reading the caller already holds — ``span_end`` returns its reading —
        so consecutive phases tile with no seam between them."""
        self._push(name, args, at)

    def span_end(self, name: str, at: Optional[float] = None, **args) -> Optional[float]:
        """Close the innermost open span of this name on this thread; returns
        the clock reading it ended at (None for an unmatched end, which is
        ignored rather than corrupting the trace)."""
        for frame in reversed(getattr(self._local, "stack", ())):
            if frame.name == name:
                if args:
                    frame.args = {**frame.args, **args}
                return self._pop(frame, at)
        return None

    def declare_phases(self, names) -> None:
        """Create the (empty) histograms of phases an instrumented surface
        will emit, so ``summary()`` tells "never happened" (count 0) from "no
        such phase" (a renamed span)."""
        with self._lock:
            for name in names:
                self._hist.setdefault(name, _new_hist())

    def async_begin(self, name: str, span_id, **args) -> None:
        """Open an async span (Chrome "b"): a lifecycle that crosses ticks and
        threads, keyed by id (e.g. a request id — joinable against the
        serving-metrics JSONL events carrying the same ``request_id``)."""
        with self._lock:
            self._append_event({
                "ph": "b", "cat": name, "name": name, "id": span_id,
                "ts": self._now(), "tid": threading.get_ident(),
                **({"args": args} if args else {}),
            })

    def async_instant(self, name: str, span_id, phase_name: str, **args) -> None:
        """Mark a named milestone ("n") inside an open async span."""
        with self._lock:
            self._append_event({
                "ph": "n", "cat": name, "name": phase_name, "id": span_id,
                "ts": self._now(), "tid": threading.get_ident(),
                **({"args": args} if args else {}),
            })

    def async_end(self, name: str, span_id, **args) -> None:
        with self._lock:
            self._append_event({
                "ph": "e", "cat": name, "name": name, "id": span_id,
                "ts": self._now(), "tid": threading.get_ident(),
                **({"args": args} if args else {}),
            })

    def instant(self, name: str, **args) -> None:
        """One timestamped marker event ("i") — e.g. an unexpected recompile."""
        with self._lock:
            self._append_event({
                "ph": "i", "name": name, "ts": self._now(), "s": "t",
                "tid": threading.get_ident(), **({"args": args} if args else {}),
            })

    def counter_inc(self, name: str, n=1) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n

    def gauge_set(self, name: str, value) -> None:
        with self._lock:
            self.gauges[name] = value

    def _observe_locked(self, name: str, seconds: float, self_seconds: Optional[float] = None) -> None:
        h = self._hist.get(name)
        if h is None:
            h = self._hist[name] = _new_hist()
        h[0] += 1
        h[1] += seconds
        h[2] = max(h[2], seconds)
        h[3].append(seconds)
        h[4] += seconds if self_seconds is None else self_seconds

    def observe(self, name: str, seconds: float) -> None:
        """Feed a duration into a phase histogram without a trace event (for
        pre-measured intervals)."""
        with self._lock:
            self._observe_locked(name, seconds)

    # ----------------------------------------------------------------- reading
    def summary(self) -> Dict:
        """Aggregate view: per-phase duration stats + counters + gauges.
        Percentiles cover the recent ``HISTOGRAM_WINDOW``; count/total/self
        total (duration minus what direct child spans covered) are lifetime.
        This is what the bench ``--profile`` artifacts embed."""
        with self._lock:
            phases = {}
            for name, (count, total, mx, window, self_total) in sorted(self._hist.items()):
                w = sorted(window)
                phases[name] = {
                    "count": count,
                    "total_s": round(total, 6),
                    "self_total_s": round(self_total, 6),
                    "mean_s": round(total / count, 6) if count else 0.0,
                    "p50_s": round(_quantile(w, 0.50), 6),
                    "p95_s": round(_quantile(w, 0.95), 6),
                    "max_s": round(mx, 6),
                }
            out = {
                "schema": SUMMARY_SCHEMA,
                "phases": phases,
                "counters": dict(sorted(self.counters.items())),
                "gauges": dict(sorted(self.gauges.items())),
            }
            if self._dropped:
                out["trace_events_dropped"] = self._dropped
            return out

    def chrome_trace(self) -> Dict:
        from perceiver_io_tpu.obs.trace import to_chrome_trace

        with self._lock:
            events = list(self._events)
            dropped = self._dropped
        return to_chrome_trace(events, summary=self.summary(), dropped=dropped)

    def write_chrome_trace(self, path: str) -> str:
        from perceiver_io_tpu.obs.trace import write_chrome_trace

        return write_chrome_trace(path, self.chrome_trace())

    # ---------------------------------------------------------------- lifecycle
    def _flush_loop(self, interval: float) -> None:
        while not self._flush_stop.wait(interval):
            try:
                self.write_chrome_trace(self.trace_path)
            except Exception:
                # a failed periodic flush must never kill the flush thread —
                # the close()-time write still gets its chance to fail loudly
                pass

    def close(self) -> None:
        """Flush the final trace (when ``trace_path`` is set) and join the
        flush thread. Idempotent, and guarded against interpreter-shutdown
        races: a second close, or a close racing module teardown, is a no-op
        instead of an AttributeError storm (same contract as
        serving/metrics.py EngineMetrics.close)."""
        if getattr(self, "_closed", True):
            return
        self._closed = True
        thread = self._flush_thread
        if thread is not None:
            self._flush_stop.set()
            thread.join()
            self._flush_thread = None
        if self.trace_path:
            try:
                self.write_chrome_trace(self.trace_path)
            except Exception:
                if not _interpreter_alive():
                    return  # shutdown race: file machinery already torn down
                raise

    def __del__(self):  # best-effort backstop; close() is the real contract
        try:
            self.close()
        except Exception:
            pass


def _interpreter_alive() -> bool:
    import sys

    return not getattr(sys, "is_finalizing", lambda: False)()


def _quantile(sorted_xs: List[float], q: float) -> float:
    """Linear-interpolated quantile of an already-sorted list (numpy-free:
    the core must stay importable without any array library)."""
    if not sorted_xs:
        return 0.0
    if len(sorted_xs) == 1:
        return sorted_xs[0]
    pos = q * (len(sorted_xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(sorted_xs) - 1)
    frac = pos - lo
    return sorted_xs[lo] * (1.0 - frac) + sorted_xs[hi] * frac


def telemetry_env_setting() -> Optional[str]:
    """The ambient ``PERCEIVER_IO_TPU_TELEMETRY`` value, or None when unset/
    explicitly off ("", "0", "false")."""
    raw = os.environ.get(TELEMETRY_ENV, "").strip()
    if raw.lower() in ("", "0", "false"):
        return None
    return raw


def resolve_recorder(telemetry=None):
    """Resolve a telemetry knob to a recorder, plus whether the caller OWNS it.

    Returns ``(recorder, owned)``. ``owned`` is True when this call created
    the recorder (from ``True``/a path/the env) — the resolving surface is
    then responsible for ``close()`` (which writes the trace when a path was
    given). A recorder instance passed straight through stays caller-owned.

    Knob encoding (shared by ``ServingEngine(telemetry=...)``,
    ``TrainerConfig.telemetry`` and the env):
      * ``None``   — consult ``PERCEIVER_IO_TPU_TELEMETRY``; unset means off.
      * ``False``  — off, unconditionally (beats the env).
      * ``True``   — on, in-memory only.
      * ``str``    — on; Chrome trace written to that path at close.
      * recorder   — any object with the Recorder surface, used as-is.
    """
    if telemetry is None:
        telemetry = telemetry_env_setting()
        if telemetry is not None and telemetry.lower() in ("1", "true"):
            telemetry = True
    if telemetry is None or telemetry is False:
        return NULL_RECORDER, False
    if telemetry is True:
        return TelemetryRecorder(), True
    if isinstance(telemetry, str):
        return TelemetryRecorder(trace_path=telemetry), True
    return telemetry, False
