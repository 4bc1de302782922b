"""Starting and stopping the profiler around a few seconds of work: from inside the loop
(serving), or around a short fit of its own (training, whose loop the program owns)."""

from __future__ import annotations

import glob
import os
import shutil
import time

from benchmark.trace import reduce


class WindowTrace:
    """One profiler trace. The profiler is started and stopped by the thread that
    drives the device, so the traced work cannot begin before it is up: ``start()`` and
    ``finish()`` around a traced call (training), or ``poll(now)`` from a loop (serving),
    which traces ``seconds`` from ``start_after`` on."""

    def __init__(self, directory: str, start_after: float = 0.0, seconds: float = 0.0):
        self.directory = directory
        self.start_after, self.seconds = start_after, seconds
        self.started_at = self.stopped_at = self.start_s = self.stop_s = None

    def start(self) -> None:
        import jax.profiler

        shutil.rmtree(self.directory, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the harness's TraceAnnotations are TraceMe events
        options.host_tracer_level = 2
        t0 = time.perf_counter()
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self.started_at = time.perf_counter()
        self.start_s = self.started_at - t0

    def _stop(self) -> None:
        import jax.profiler

        self.stopped_at = time.perf_counter()
        jax.profiler.stop_trace()  # writes the trace out: seconds, in which the caller's loop stands still
        self.stop_s = time.perf_counter() - self.stopped_at

    def poll(self, since_open: float) -> None:
        if self.started_at is None and since_open >= self.start_after:
            self.start()
        elif (self.started_at is not None and self.stopped_at is None
              and time.perf_counter() >= self.started_at + self.seconds):
            self._stop()

    def finish(self) -> dict:
        """Stop if still running, and read the trace."""
        if self.started_at is None:
            raise RuntimeError("the window ended before the trace began")
        if self.stopped_at is None:
            self._stop()
        files = glob.glob(os.path.join(self.directory, "plugins", "profile", "*", "*.xplane.pb"))
        if not files:
            raise RuntimeError(f"the profiler wrote no trace under {self.directory}")
        return reduce.read_xplane(max(files, key=os.path.getmtime))
