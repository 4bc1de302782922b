"""What the host was doing when a run stalls. Three of PR 23's first 36 runs held one
stall of seconds with no compilation in it; this is the instrument that was missing.

A thread sleeps ``period`` over and over and books every beat that came late, with the
processor time the process used meanwhile. A stall of the measured loop (the window's
longest log interval or token gap) then reads one of three ways:

* the beats were on time: the process ran, and the loop's own thread was blocked, in
  the device's sync or the runtime under it;
* the beats were late and the process used no processor time: the whole process, or the
  machine, stood still (the host's doing, not the program's);
* the beats were late and the process used a processor's worth of time: some thread held
  the interpreter (a compilation, a collection, a C call that keeps the lock).

``pressure_ms`` is the time the kernel says tasks stood waiting for a processor, for
memory or for the disk (``/proc/pressure``), where the machine has it."""

from __future__ import annotations

import threading
import time

LATE_S = 0.02


class Heartbeat(threading.Thread):
    def __init__(self, period: float = 0.05):
        super().__init__(name="bench-heartbeat", daemon=True)
        self.period, self.late, self._halt = period, [], threading.Event()

    def run(self) -> None:
        while not self._halt.is_set():
            t0, cpu0 = time.perf_counter(), time.process_time()
            self._halt.wait(self.period)
            over = time.perf_counter() - t0 - self.period
            if over > LATE_S and not self._halt.is_set():
                self.late.append((time.time(), over, time.process_time() - cpu0))

    def stop(self) -> None:
        self._halt.set()
        self.join()

    def report(self, t_open: float, seconds: float) -> dict:
        """The latest beat inside ``[t_open, t_open + seconds]`` (wall clock) and over the
        whole run: how late, how far into the window, processor seconds used meanwhile."""
        row = lambda beat: {"late_ms": 1e3 * beat[1], "at_window_s": beat[0] - t_open, "process_cpu_s": beat[2]}
        inside = [b for b in self.late if t_open <= b[0] <= t_open + seconds + 1.0]
        by_lateness = lambda beats: max(beats, key=lambda b: b[1])
        return {"late_beats_in_window": len(inside),
                "latest_in_window": row(by_lateness(inside)) if inside else None,
                "latest_in_run": row(by_lateness(self.late)) if self.late else None}


def pressure_us() -> dict:
    """Total stalled microseconds so far by resource (``some``), or {} without PSI."""
    out = {}
    for resource in ("cpu", "memory", "io"):
        try:
            with open(f"/proc/pressure/{resource}") as f:
                some = next(line for line in f if line.startswith("some"))
            out[resource] = int(some.rsplit("total=", 1)[1])
        except (OSError, StopIteration, ValueError, IndexError):
            pass
    return out
