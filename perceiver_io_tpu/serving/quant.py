"""Weight-serving dtype transforms: bf16 cast and per-tensor int8 weights.

The serving half of ROADMAP item 3 (docs/serving.md "Quantized KV pages &
weight serving"): sessions per chip are HBM-bound, and after paged + int8 KV
the next biggest resident block is the PARAMETERS. ``ServingEngine(
weight_dtype=...)`` applies one of two transforms to the served params at
construction:

  * ``"bf16"`` — cast float32/float64 leaves to bfloat16. The cheap default:
    resident param HBM halves, matmuls promote back through flax's
    ``promote_dtype`` (bf16 kernel x f32 activations -> f32 accumulation),
    no dequant step in the compiled programs.
  * ``"int8"`` — PER-TENSOR symmetric int8: every float matmul-grade leaf
    (ndim >= 2: kernels, embeddings) is stored as ``{"q": int8, "s": scale}``
    with ``s = amax / 127`` in the leaf's original float dtype; 1-D leaves
    (biases, LayerNorm scales) stay full precision — they are a rounding
    error of the total bytes and per-tensor quantization would visibly hurt
    them. The engine's compiled programs DEQUANTIZE ON ENTRY
    (``dequantize_params`` is the first op of every params-consuming jit):
    the resident tree is int8 (~4x smaller than f32), the dequantized copy is
    a per-execution transient XLA schedules in and out of scratch.

Both transforms are applied ONCE at engine construction and are off by
default (``weight_dtype=None``) — off means the params object is passed
through UNTOUCHED (the f64 parity pins run through the identity path). This module is deliberately
jax-light and model-agnostic: it walks pytree leaves, never module code.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

WEIGHT_DTYPES = ("bf16", "int8")

# marker key so dequantize_params can recognize quantized leaves without a
# schema side-channel; no flax param is ever named this
_QKEY = "__int8_weight__"


def _is_float(leaf) -> bool:
    return hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating)


def tree_bytes(tree) -> int:
    """Total resident bytes of a (possibly quantized) param tree."""
    return sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(tree)
        if hasattr(leaf, "dtype")
    )


def cast_params_bf16(params):
    """bf16 weight serving: cast float leaves to bfloat16, leave the rest
    (int tables, rng keys) untouched."""
    return jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16) if _is_float(x) else x, params
    )


def quantize_params_int8(params):
    """Per-tensor symmetric int8 over matmul-grade float leaves (ndim >= 2);
    1-D float leaves are left in their original dtype. Returns a pytree in
    which each quantized leaf became ``{_QKEY: True-shaped marker...}`` —
    concretely a dict ``{"q": int8 array, "s": per-tensor scale}`` that
    ``dequantize_params`` folds back."""

    def q(x):
        if not _is_float(x) or x.ndim < 2:
            return x
        amax = jnp.max(jnp.abs(x))
        scale = (amax / 127.0).astype(x.dtype)
        safe = jnp.where(scale > 0, scale, 1.0)
        qx = jnp.clip(jnp.round(x / safe), -127, 127).astype(jnp.int8)
        return {_QKEY: qx, "s": scale}

    return jax.tree_util.tree_map(q, params)


def _is_qleaf(node) -> bool:
    return isinstance(node, dict) and _QKEY in node


def dequantize_params(params):
    """Fold int8 leaves back to ``q * s`` in the scale's dtype — the first op
    of every params-consuming compiled program on an int8-weight engine
    (identity on trees without quantized leaves)."""
    return jax.tree_util.tree_map(
        lambda n: n[_QKEY].astype(n["s"].dtype) * n["s"] if _is_qleaf(n) else n,
        params,
        is_leaf=_is_qleaf,
    )


def tree_layout_mismatch(a, b) -> bool:
    """True when two param trees differ in structure, any leaf shape, or any
    leaf dtype — the compatibility gate live param swaps run on: a
    mismatched tree would silently recompile every compiled program, so both
    ``ServingEngine.set_params`` (flip time) and ``ServingRouter.deploy``
    (operator time) refuse it through this ONE definition."""
    a_leaves, a_def = jax.tree_util.tree_flatten(a)
    b_leaves, b_def = jax.tree_util.tree_flatten(b)
    return a_def != b_def or any(
        getattr(x, "shape", None) != getattr(y, "shape", None)
        or getattr(x, "dtype", None) != getattr(y, "dtype", None)
        for x, y in zip(a_leaves, b_leaves)
    )


def serve_params(
    params, weight_dtype: Optional[str]
) -> Tuple[Any, Callable, int, int]:
    """Apply the weight-serving transform: returns ``(served_tree,
    dequant_fn, served_bytes, fp_bytes)``. ``dequant_fn`` is the identity for
    None/bf16 (nothing to fold at trace time) and ``dequantize_params`` for
    int8; engines call it on the params argument inside every jit."""
    fp_bytes = tree_bytes(params)
    if weight_dtype is None:
        return params, (lambda p: p), fp_bytes, fp_bytes
    if weight_dtype == "bf16":
        served = cast_params_bf16(params)
        return served, (lambda p: p), tree_bytes(served), fp_bytes
    if weight_dtype == "int8":
        served = quantize_params_int8(params)
        return served, dequantize_params, tree_bytes(served), fp_bytes
    raise ValueError(
        f"weight_dtype must be one of {WEIGHT_DTYPES} or None, got {weight_dtype!r}"
    )


def kv_bytes_per_token(num_channels: int, cache_dtype, kv_quant: Optional[str],
                       page_size: int, num_heads: int) -> Tuple[float, float]:
    """(fp_bytes, served_bytes) of ONE token's K+V rows — the serving-
    metrics/v9 ``bytes_per_token`` gauges. Quantized pages amortize the
    per-page-per-head f32 scale sidecars over the page's rows."""
    fp = 2 * num_channels * jnp.dtype(cache_dtype).itemsize
    if kv_quant is None:
        return float(fp), float(fp)
    # int8: one byte per channel; int4: two nibble-packed codes per byte
    code_bytes = 0.5 if kv_quant == "int4" else 1.0
    served = 2 * num_channels * code_bytes + 2 * num_heads * 4 / page_size
    return float(fp), float(served)
