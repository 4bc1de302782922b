"""The fused tick's work descriptor as ONE int32 array (docs/serving.md
"Unified ragged tick").

A tick's buffered work — scale resets, prefill chunk lanes, latent finish
lanes, fault poison, the decode flag — reaches the ``ragged_tick`` program as
a single array of int32 words, so a tick pays at most one host-to-device
transfer whatever it carries. This module is the only place that knows the
layout: the engine packs through ``views()`` on the host, the program reads
through ``unpack()`` on the device.

Word order: the header's five scalars, then every lane field flattened
row-major, at offsets fixed by the engine's own sizes (``lanes``, chunk
``cap``, ``pages_per_slot``, ``latents``). float32 and uint32 fields travel
by BIT PATTERN (a numpy ``view`` on the host, ``lax.bitcast_convert_type``
in the program: exact), bools as 0 / 1.
"""

from __future__ import annotations

from collections import namedtuple
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# idle-lane latent_start far beyond any position (no position is a latent);
# a model's chunk phase runs the carried lanes only, so nothing reads it
IDLE_LATENT_START = 2 ** 30

_I32, _U32, _F32 = np.int32, np.uint32, np.float32
# (name, shape in the engine's sizes, dtype the program sees), in word order
_FIELDS = (
    ("any_reset", (), bool),
    ("any_chunk", (), bool),
    ("any_finish", (), bool),
    ("poison", (), _I32),  # slot whose logits the poison phase NaNs; -1: none
    ("any_decode", (), bool),
    ("reset_ids", ("lanes*pages",), _I32),
    ("ch_ids", ("lanes", "cap"), _I32),
    ("ch_offset", ("lanes",), _I32),
    ("ch_count", ("lanes",), _I32),
    ("ch_latent_start", ("lanes",), _I32),
    ("ch_tables", ("lanes", "pages"), _I32),
    ("fin_active", ("lanes",), bool),
    ("fin_slot", ("lanes",), _I32),
    ("fin_tables", ("lanes", "pages"), _I32),
    ("fin_ids", ("lanes", "latents"), _I32),
    ("fin_n", ("lanes",), _I32),
    ("fin_rng", ("lanes", 2), _U32),
    ("fin_temp", ("lanes",), _F32),
    ("fin_tk", ("lanes",), _I32),
    ("fin_tp", ("lanes",), _F32),
    ("fin_ds", ("lanes",), bool),
    ("fin_pad", ("lanes",), _I32),
)

# what a chunk lane carries besides, for a model whose slots hold a recurrent
# state (models/core/serving_api.py): the slot whose state the lane carries on,
# and whether it starts from zero (the slot was just claimed). They follow the
# fields above, so a model without them keeps its layout word for word
_RECURRENT_FIELDS = (
    ("ch_slot", ("lanes",), _I32),
    ("ch_reset", ("lanes",), bool),
)

# the descriptor's fields by name, in word order (header first)
TickFields = namedtuple("TickFields", [name for name, _, _ in _FIELDS])
RecurrentTickFields = namedtuple("RecurrentTickFields", [name for name, _, _ in _FIELDS + _RECURRENT_FIELDS])


class TickDescriptorLayout:
    """Offsets, shapes and dtypes of one engine's descriptor."""

    def __init__(self, lanes: int, cap: int, pages_per_slot: int, latents: int,
                 recurrent: bool = False):
        sizes = {"lanes": lanes, "cap": cap, "pages": pages_per_slot,
                 "latents": latents, "lanes*pages": lanes * pages_per_slot}
        self._tuple = RecurrentTickFields if recurrent else TickFields
        # name -> (its words, shape, dtype the program sees)
        self.fields: Dict[str, Tuple[slice, tuple, type]] = {}
        offset = 0
        for name, dims, dtype in _FIELDS + (_RECURRENT_FIELDS if recurrent else ()):
            shape = tuple(sizes.get(d, d) for d in dims)
            words = int(np.prod(shape, dtype=np.int64))
            self.fields[name] = (slice(offset, offset + words), shape, dtype)
            offset += words
        self.words = offset

    def views(self, buf: np.ndarray) -> TickFields:
        """Writable numpy views of ``buf``'s fields, each in its own dtype
        and shape (scalars 0-d: assign through ``[...]``; bool fields are
        int32 words holding 0 / 1)."""
        return self._tuple(**{
            name: buf[words].view(np.int32 if dtype is bool else dtype).reshape(shape)
            for name, (words, shape, dtype) in self.fields.items()
        })

    def idle(self, any_decode: bool) -> np.ndarray:
        """A new host descriptor carrying no lane, reset or poison: trash
        tables, zero counts, neutral sampling encodings."""
        buf = np.zeros((self.words,), np.int32)
        v = self.views(buf)
        v.poison[...] = -1
        v.any_decode[...] = any_decode
        v.ch_latent_start[...] = IDLE_LATENT_START
        v.fin_temp[...] = 1.0
        v.fin_tp[...] = 1.0
        return buf

    def unpack(self, desc: jax.Array) -> TickFields:
        """The program's side: static slices of the device descriptor."""
        out = {}
        for name, (words, shape, dtype) in self.fields.items():
            field = desc[words].reshape(shape)
            if dtype is bool:
                field = field != 0
            elif dtype is not np.int32:
                field = jax.lax.bitcast_convert_type(field, jnp.dtype(dtype))
            out[name] = field
        return self._tuple(**out)
