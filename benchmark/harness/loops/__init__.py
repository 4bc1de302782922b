"""One driver per KIND of traffic mix (``train``, ``open_loop``), found by
the mix's ``kind``: ``benchmark/harness/loops/<kind>.py`` with a ``run(cell, env)``."""

from __future__ import annotations

import importlib


def driver_for(kind: str):
    try:
        return importlib.import_module(f"benchmark.harness.loops.{kind}")
    except ModuleNotFoundError as e:
        raise ValueError(f"no loop driver for traffic kind {kind!r}") from e
