"""Test harness configuration.

Tests run on CPU with 8 virtual XLA devices so multi-chip sharding paths are
exercised without TPU hardware (the reference could not test its NCCL paths in CI
at all — see SURVEY.md §4). A persistent compilation cache keeps re-runs fast.
"""

import os


def _xla_flag_known(name: str) -> bool:
    """XLA ABORTS the whole process (parse_flags_from_env.cc) on any unknown
    flag in XLA_FLAGS, so optional flags must be probed first. Registered
    flags embed their name string in the jaxlib binary; a byte scan of the
    extension .so is the only way to check without paying a subprocess
    backend init (~2s once per session, cheaper than a fatal abort)."""
    try:
        import glob
        import mmap

        import jaxlib

        root = os.path.dirname(jaxlib.__file__)
        sos = sorted(
            glob.glob(os.path.join(root, "**", "*.so"), recursive=True),
            key=os.path.getsize,
            reverse=True,
        )[:2]
        needle = name.encode()
        for path in sos:
            with open(path, "rb") as f, mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ) as m:
                if m.find(needle) != -1:
                    return True
        return False
    except Exception:
        return False  # cannot verify -> do not risk the abort


_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    _flags += " --xla_force_host_platform_device_count=8"
# Compile-speed flags: the suite is XLA:CPU COMPILE-bound (tiny shapes, dozens
# of distinct programs), and these cut cold-compile wall time ~45% (measured
# 41.1s -> 22.6s on a representative sharded train step). They reduce code
# quality of the compiled test programs, which is irrelevant here — numerics
# are IEEE-preserving and every test compares values produced under the same
# flags. Never set for benchmarks.
if "--xla_backend_optimization_level" not in _flags:
    _flags += " --xla_backend_optimization_level=0 --xla_llvm_disable_expensive_passes=true"
# 8 virtual device threads share ONE physical core on this host; XLA:CPU kills
# the whole process (F rendezvous.cc) if a collective participant is starved
# past 40s, which concurrent compiles/processes can trigger. Raise the fatal
# threshold; starvation then shows up as a warning + slow test, not an abort.
# Jaxlib builds that predate these flags reject them FATALLY, hence the probe.
if (
    "--xla_cpu_collective_call_terminate_timeout_seconds" not in _flags
    and _xla_flag_known("xla_cpu_collective_call_terminate_timeout_seconds")
    and _xla_flag_known("xla_cpu_collective_call_warn_stuck_timeout_seconds")
):
    _flags += (" --xla_cpu_collective_call_terminate_timeout_seconds=600"
               " --xla_cpu_collective_call_warn_stuck_timeout_seconds=120")
os.environ["XLA_FLAGS"] = _flags.strip()

import jax  # noqa: E402

# The suite runs on the CPU whatever JAX_PLATFORMS says.
jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: JAX_COMPILATION_CACHE_DIR when set, else a
# per-machine directory under <checkout>/.jax_cache (perceiver_io_tpu/
# compile_cache.py). The suite's programs are tiny, so cache all of them.
from perceiver_io_tpu.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

import signal  # noqa: E402
import threading  # noqa: E402

import pytest  # noqa: E402

# Per-test wall-clock budget (seconds) for the DEFAULT tier: a hang (stuck
# subprocess, livelocked collective) becomes a loud test FAILURE instead of
# stalling the whole tier until the outer 870s timeout kills it. Slow-tier
# tests (-m slow, explicitly opted into) are exempt.
# Override with PERCEIVER_TEST_TIMEOUT_S; 0 disables the guard entirely.
_PER_TEST_TIMEOUT_S = float(os.environ.get("PERCEIVER_TEST_TIMEOUT_S", "120"))


class PerTestTimeout(Exception):
    """Raised by the SIGALRM guard when a single test exceeds its budget."""


def _alarm_guard(item, phase):
    """Signal-based phase timeout: no extra dependency, main-thread only
    (SIGALRM cannot be delivered elsewhere), and skipped for the slow tier
    whose tests legitimately run long. The alarm interrupts blocking syscalls
    (subprocess waits, socket reads); a pure-native hang that never re-enters
    the interpreter (e.g. inside one long XLA call) only raises at the next
    bytecode boundary, so the outer tier timeout remains the last resort.
    Each phase (setup/call/teardown) gets its own budget — fixtures hang too."""
    timeout = _PER_TEST_TIMEOUT_S
    if (
        timeout <= 0
        or item.get_closest_marker("slow") is not None
        or not hasattr(signal, "SIGALRM")
        or threading.current_thread() is not threading.main_thread()
    ):
        yield
        return

    def _on_alarm(signum, frame):
        raise PerTestTimeout(
            f"{item.nodeid} [{phase}] exceeded the per-test timeout of {timeout:.0f}s "
            "(conftest guard; raise PERCEIVER_TEST_TIMEOUT_S or mark the test slow)"
        )

    previous = signal.signal(signal.SIGALRM, _on_alarm)
    signal.setitimer(signal.ITIMER_REAL, timeout)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_setup(item):
    yield from _alarm_guard(item, "setup")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    yield from _alarm_guard(item, "call")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_teardown(item):
    yield from _alarm_guard(item, "teardown")


def pytest_collection_modifyitems(config, items):
    """Deselect the slow tier by default, but never override an explicit ask:
    a user-passed -m expression or a ::node-id selection runs exactly what it
    names (an addopts marker filter would make a directly-addressed slow test
    silently vanish with 'no tests ran')."""
    args = config.invocation_params.args
    if config.option.markexpr or "-m" in args or any(a.startswith(("-m=", "--markexpr")) for a in args):
        return  # an explicit -m expression (even -m "") selects for itself
    if any("::" in a for a in args):
        return
    selected, deselected = [], []
    for item in items:
        (deselected if item.get_closest_marker("slow") else selected).append(item)
    if deselected:
        config.hook.pytest_deselected(items=deselected)
        items[:] = selected
        # file/dir path and -k selections still drop the slow tier; say so once
        # instead of leaving a silently shrunken (or empty) selection
        reporter = config.pluginmanager.get_plugin("terminalreporter")
        if reporter is not None:
            reporter.write_line(
                f"conftest: {len(deselected)} slow-tier tests deselected "
                '(select them with -m slow, -m "", or a ::node-id)'
            )


# Thread prefixes that are process-wide caches/pools, not per-test leaks:
# concurrent.futures keeps idle workers alive after an executor is collected,
# and orbax/tensorstore park IO threads between checkpoints. The telemetry
# flush thread (obs/core.py TelemetryRecorder, perceiver-telemetry-flush) is
# allowlisted because a recorder created from the ambient
# PERCEIVER_IO_TPU_TELEMETRY env can legitimately outlive one test while its
# owning surface is still open — close() still always joins it, and the
# telemetry tests assert that join directly. OUR other threads
# (perceiver-prefetch-*, perceiver-async-ckpt) are never on this list — they
# must ALWAYS join, including on exceptions mid-epoch.
_BENIGN_THREAD_PREFIXES = (
    "ThreadPoolExecutor",
    "asyncio_",
    "pydevd",
    "grpc",
    "tensorstore",
    "ocdbt",
    "perceiver-telemetry-flush",
)


@pytest.fixture(autouse=True)
def assert_no_leaked_threads():
    """Every test must leave no NEW live non-daemon threads behind: the
    prefetcher and async-checkpoint writer threads (data/prefetch.py,
    training/checkpoint.py) must always join — on normal completion, early
    break, and exceptions mid-epoch alike. A short grace window lets threads
    that are mid-join at teardown finish."""
    import time as _time

    before = set(threading.enumerate())

    yield

    def leaked():
        return [
            t
            for t in threading.enumerate()
            if t not in before
            and t.is_alive()
            and not t.daemon
            and not t.name.startswith(_BENIGN_THREAD_PREFIXES)
        ]

    deadline = _time.monotonic() + 5.0
    bad = leaked()
    while bad and _time.monotonic() < deadline:
        _time.sleep(0.05)
        bad = leaked()
    assert not bad, f"leaked non-daemon threads: {[t.name for t in bad]}"


@pytest.fixture(scope="module")
def x64():
    """Enable float64 for strict (bitwise / 1e-12) equivalence tests."""
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)
