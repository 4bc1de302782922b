"""Where JAX's persistent compilation cache lives — decided in one place.

A cold compile of the 455M train step takes minutes, and every entry point
that compiles (the CLIs, ``bench.py``, ``chip_smoke.py``, the serving replica
workers, the test suite) should find what an earlier process compiled. The
directory is part of the cache key, so it must not move:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself; this
    module then sets no path in code, so the cache can be placed from outside;
  * otherwise ``<checkout>/.jax_cache`` — with a per-machine subdirectory while
    JAX is held to the CPU, because XLA:CPU's compiled artifacts only replay
    on the machine that made them (tests/conftest.py history: replaying
    another host's entries aborts with SIGILL).
"""

from __future__ import annotations

import hashlib
import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _machine_key() -> str:
    """Identity of this machine, not of its CPU features: two hosts with equal
    cpuinfo flags produced incompatible XLA:CPU artifacts (the embedded target
    options differed), so the CPU cache never travels."""
    import jax

    ident = []
    try:
        with open("/etc/machine-id") as f:
            ident.append(f.read().strip())
    except OSError:
        import socket

        ident.append(socket.gethostname())
    try:
        with open("/proc/cpuinfo") as f:
            # unique lines only: the same key regardless of visible core count
            ident.extend(sorted({line for line in f if line.startswith(("flags", "model name"))}))
    except OSError:
        pass
    ident.append(jax.__version__)
    return hashlib.md5("".join(ident).encode()).hexdigest()[:10]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.
    Call before the first compile; calling again is harmless."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    if jax.config.jax_platforms == "cpu":
        path = os.path.join(path, f"cpu-{_machine_key()}")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
