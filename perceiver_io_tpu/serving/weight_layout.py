"""The layout in which a served model's weights reach the engine's programs.

An argument's layout is the compiler's choice, made from the leaf's shape alone:
on a TPU a matrix whose last dimension is no multiple of 128 while its rows are
is taken TRANSPOSED (nothing to pad that way), and a program that cannot read
it so copies it whole in front of its work, every run. A model names such
leaves (``ServingTraits.row_major_leaves``, models/core/serving_api.py (g)).

The layout is stated through the leaf's SHAPE, not through a ``Format``: an
executable compiled for a stated argument layout does not survive JAX's
persistent compilation cache on this runtime (read back, it expects the default
layout again and refuses the array: measured on the chip for a committed
array, for ``in_shardings`` and for an ahead-of-time compile alike, PERF.md 6,
PR 45), and every run after a checkout's first loads its programs from that
cache. So the engine keeps a named leaf with its rows split into tiles,
``(..., rows, cols) -> (..., rows / t, t, cols)`` with ``t`` the rows of one
native tile: the default layout of that shape IS row-major (``t`` is no
multiple of 128, so the last dimension stays minor) in the same tiles as the
row-major matrix, and every program views it as the matrix again on entry, a
bitcast. Done once, at construction; nothing is laid out again per call.
"""

from __future__ import annotations

import re
from typing import Sequence

import jax


def leaf_name(path) -> str:
    """A leaf's path of keys joined with "/": ``params/layers_0_in_proj``."""
    return "/".join(re.findall(r"\w+", jax.tree_util.keystr(path)))  # training/checkpoint.py names leaves so too


def missing_leaves(tree, names: Sequence[str]) -> list:
    """The ``names`` that are no leaf of ``tree``."""
    have = {leaf_name(path) for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]}
    return sorted(set(names) - have)


def _map_named(fn, tree, names: Sequence[str]):
    wanted = set(names)
    return jax.tree_util.tree_map_with_path(lambda path, leaf: fn(leaf) if leaf_name(path) in wanted else leaf, tree)


def split_rows(tree, names: Sequence[str]):
    """``tree`` with the leaves ``names`` lists kept as ``(..., rows / t, t,
    cols)``, ``t`` = 8 sublanes x the elements a 32-bit word packs (8 rows of
    float32, 16 of bfloat16, 32 of int8): one device-side transposition a
    leaf where the default layout was the transposed one. The other leaves, and
    names the tree no longer holds as leaves (a matrix the int8 transform
    replaced), are untouched. An abstract leaf (``jax.ShapeDtypeStruct``, for a
    compile without weights) gets the shape."""
    def split(leaf):
        t = 8 * max(4 // leaf.dtype.itemsize, 1)
        if leaf.ndim < 2 or leaf.shape[-2] % t:
            raise ValueError(f"a leaf of shape {leaf.shape} and dtype {leaf.dtype} cannot be stated row-major: "
                             f"its rows are no multiple of the {t} a tile holds")
        shape = (*leaf.shape[:-2], leaf.shape[-2] // t, t, leaf.shape[-1])
        if isinstance(leaf, jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct(shape, leaf.dtype, sharding=leaf.sharding)
        return leaf.reshape(shape)

    return _map_named(split, tree, names)


def merge_rows(tree, names: Sequence[str]):
    """Inverse of ``split_rows`` on the same names: the first thing a program
    does with its parameters, so the model sees the tree it initialised."""
    return _map_named(lambda leaf: leaf.reshape(*leaf.shape[:-3], -1, leaf.shape[-1]), tree, names)
