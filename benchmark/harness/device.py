"""The device a run is on: what JAX reports, its memory, its compilations, its peaks."""

from __future__ import annotations

import json
import os
import time

from benchmark.harness.manifest import BENCH_DIR


class NoAccelerator(RuntimeError):
    """The measurement path found no TPU, or fewer chips than the cell asks for."""


def describe_devices(chips: int, rehearse: bool) -> dict:
    import jax

    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    if rehearse:
        return info
    if info["platform"] != "tpu":
        raise NoAccelerator(f"JAX found {info['count']} {info['platform']} device(s) and no TPU; "
                            "the benchmark has no CPU fallback")
    if info["count"] < chips:
        raise NoAccelerator(f"the cell asks for {chips} chip(s), JAX found {info['count']}")
    return info


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device, as the allocator reports it."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()]
    return int(max(peaks))


def peaks_for(kind: str) -> dict:
    """The published peaks of ``kind``; a device the table does not hold is an error."""
    with open(os.path.join(BENCH_DIR, "rooflines", "peaks.json")) as f:
        table = json.load(f)["peaks"]
    if kind not in table:
        raise KeyError(f"no peaks on record for device kind {kind!r} (known: {sorted(table)}); "
                       "add it to benchmark/rooflines/peaks.json with its source")
    return table[kind]


def enable_caches() -> str:
    """The persistent compilation cache, at the fixed path the program's own
    ``enable_compile_cache`` gives (``JAX_COMPILATION_CACHE_DIR`` or
    ``<checkout>/.jax_cache``), holding every program however small or quick."""
    import jax

    from perceiver_io_tpu.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileMonitor:
    """Counts backend compilations and persistent-cache hits and misses as JAX reports
    them. ``between(t0, t1)`` counts those of a window, which must show none."""

    def __init__(self):
        import jax.monitoring

        self.compile_s: list = []
        self.compile_at: list = []  # time.time() when each compilation ended
        self.compile_of: list = []  # the function each was for
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name: str, duration: float, fun_name: str = "?", **_) -> None:
        if name.endswith("backend_compile_duration"):
            self.compile_s.append(duration)
            self.compile_at.append(time.time())
            self.compile_of.append(fun_name)

    def slowest(self, n: int = 6) -> list:
        """The compilations (or loads from the cache) that took longest: [name, seconds]."""
        order = sorted(range(len(self.compile_s)), key=lambda i: -self.compile_s[i])[:n]
        return [[self.compile_of[i], round(self.compile_s[i], 2)] for i in order]

    def _on_event(self, name: str, **_) -> None:
        if name.endswith("/cache_hits"):
            self.hits += 1
        elif name.endswith("/cache_misses"):
            self.misses += 1

    def between(self, t0: float, t1: float) -> int:
        """Compilations that ended in ``[t0, t1]`` (``time.time()`` clock), cache hits too."""
        return sum(1 for t in self.compile_at if t0 <= t <= t1)

    def since(self) -> dict:
        """Totals since the process started."""
        return {"compilations": len(self.compile_s), "compile_s": round(sum(self.compile_s), 3),
                "cache_hits": self.hits, "cache_misses": self.misses}
