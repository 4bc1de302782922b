"""Native (C) fast paths, loaded via ctypes with pure-Python fallbacks.

The TPU compute path is JAX/XLA/Pallas; the host-side runtime pieces that are
CPU-bound (per-batch dynamic masking for MLM training) have C implementations
here. Build once with::

    python -m perceiver_io_tpu.native.build

If the shared library is absent, callers fall back to the Python
implementations and a warning says so — no build step is required to use the
framework. The library is a build product (``*.so`` is git-ignored and kept
out of the chip tool's copy by ``.chiprunignore``): a machine that wants the
C path builds it there from ``wordmask.c``.

Reproducibility note: the C path uses its own (deterministic, seed-driven)
xorshift RNG stream, so seeded runs produce the same masking DISTRIBUTION but
not the same token-level draws as the numpy fallback. Which path is active is
logged once at load; pin ``use_native`` explicitly where bitwise run-to-run
reproducibility across differently-built environments matters.
"""

from __future__ import annotations

import ctypes
import logging
import os
from typing import Optional, Tuple

import numpy as np

logger = logging.getLogger(__name__)

_LIB_NAME = "libperceiver_native.so"
_lib: Optional[ctypes.CDLL] = None
_load_attempted = False


def _lib_path() -> str:
    return os.path.join(os.path.dirname(__file__), _LIB_NAME)


def load_library() -> Optional[ctypes.CDLL]:
    """The compiled library, or None when not built."""
    global _lib, _load_attempted
    if _load_attempted:
        return _lib
    _load_attempted = True
    path = _lib_path()
    if not os.path.exists(path):
        logger.warning(
            "perceiver_io_tpu native library not built (python -m perceiver_io_tpu.native.build); "
            "using the Python fallbacks, whose seeded masking draws differ from the C path's"
        )
        return None
    logger.info("perceiver_io_tpu native library loaded from %s", path)
    lib = ctypes.CDLL(path)
    lib.mask_words.restype = ctypes.c_long
    lib.mask_words.argtypes = [
        ctypes.POINTER(ctypes.c_int64),  # input_ids (in/out)
        ctypes.POINTER(ctypes.c_int64),  # word_ids
        ctypes.POINTER(ctypes.c_int64),  # labels (out)
        ctypes.c_long,                   # n
        ctypes.c_double,                 # mask_prob
        ctypes.c_int64,                  # mask_token_id
        ctypes.c_int64,                  # vocab_size
        ctypes.c_uint64,                 # seed
    ]
    _lib = lib
    return _lib


def native_available() -> bool:
    return load_library() is not None


def mask_words_native(
    input_ids: np.ndarray,
    word_ids: np.ndarray,
    mask_prob: float,
    mask_token_id: int,
    vocab_size: int,
    seed: int,
    ignore_index: int = -100,
) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """C whole-word masking. word_ids uses -1 for 'no word' (special tokens).
    Returns (masked_input_ids, labels) or None when the library isn't built."""
    lib = load_library()
    if lib is None:
        return None
    ids = np.ascontiguousarray(input_ids, dtype=np.int64).copy()
    wids = np.ascontiguousarray(word_ids, dtype=np.int64)
    if ids.shape != wids.shape:
        raise ValueError(f"input_ids and word_ids must have equal length: {ids.shape} vs {wids.shape}")
    labels = np.full_like(ids, ignore_index)
    lib.mask_words(
        ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        wids.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        labels.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        ids.shape[0],
        float(mask_prob),
        int(mask_token_id),
        int(vocab_size),
        int(seed) & (2**64 - 1),
    )
    return ids, labels
