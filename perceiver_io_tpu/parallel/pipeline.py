"""GPipe-style pipeline parallelism for the scanned self-attention stack.

The reference has NO pipeline parallelism (SURVEY.md §2.7: TP/PP/SP all absent —
its distribution story is Lightning DDP/FSDP); this module goes beyond it,
completing this framework's parallelism matrix (data / fsdp / tensor / seq /
pipe). The design follows the TPU-idiomatic recipe: the layer-stacked
(``nn.scan``) parameters are sharded over a ``pipe`` mesh axis — each device
holds ``num_layers / pipe`` contiguous layers — and the batch is split into
microbatches that flow through the stages inside one ``shard_map`` region,
activations hopping stage-to-stage over ICI with ``lax.ppermute``.

Schedule: plain GPipe. With P stages and M microbatches the loop runs
``T = M + P - 1`` ticks; stage ``s`` processes microbatch ``t - s`` at tick
``t`` (bubble fraction ``(P-1)/T``). Every stage executes the same program —
stage identity is ``lax.axis_index`` — so the whole schedule is a single
``lax.scan`` that XLA compiles once; there is no per-stage Python, no
data-dependent control flow, and the ppermute is the only communication until
the final one-shot ``psum`` that broadcasts the collected outputs from the last
stage.

Like ``fused_qkv`` and ``remat_policy`` this is a pure execution knob: the
parameter tree, checkpoints, and numerics (modulo dropout key derivation) are
identical to the non-pipelined model — correctness is pinned by equivalence
tests against the single-device forward/backward in
``tests/test_pipeline_parallel.py``.

Composition (v2): ``pipe`` composes with the ``data`` batch axis (microbatches
are per-data-shard) AND with ``fsdp`` — each stage's stacked params stay
ZeRO-3-sharded over the fsdp axis at rest and are all-gathered ONE LAYER AT A
TIME inside the stage's scan (under the remat boundary, so the backward pass
regathers instead of saving gathered layers); the all-gather's transpose is a
reduce-scatter, which is exactly ZeRO-3's gradient flow. The fsdp axis also
carries a batch shard (it is a data axis, parallel/mesh.py DATA_AXES), matching
the non-pipelined fsdp path. Without fsdp×pipe a pipeline cannot serve the
455M-class models PP exists for (the reference's flagship path is FSDP,
scripts/text/clm_fsdp.py:24-36). ``tensor``/``seq`` with ``pipe`` remain
rejected rather than silently resharded every tick.
"""

from __future__ import annotations

from functools import reduce
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from perceiver_io_tpu.parallel.mesh import DATA_AXES
from perceiver_io_tpu.parallel.ring_attention import _shard_map

_INCOMPATIBLE_AXES = ("tensor", "seq")


def pipeline_mesh_plan(pipe_axis: str = "pipe"):
    """(axis_size, batch_axes) when the ambient mesh pipelines, else None.

    Mirrors ``ring_attention``'s ambient-mesh discovery: modules call this at
    trace time under ``jax.sharding.set_mesh`` / jit-with-mesh context."""
    mesh = jax.sharding.get_abstract_mesh()
    if pipe_axis not in mesh.axis_names:
        return None
    size = mesh.shape[pipe_axis]
    if size <= 1:
        return None
    bad = [a for a in _INCOMPATIBLE_AXES if a != pipe_axis and a in mesh.axis_names and mesh.shape[a] > 1]
    if bad:
        raise ValueError(
            f"pipeline axis '{pipe_axis}' cannot combine with sharded {bad} axes "
            "(pipe composes with data/fsdp only)"
        )
    baxes = tuple(a for a in DATA_AXES if a in mesh.axis_names and mesh.shape[a] > 1)
    return size, baxes


def pipeline_layer_stack(
    layer_apply: Callable,
    stacked_params,
    x: jax.Array,
    gates: jax.Array,
    dropout_keys: Optional[jax.Array],
    *,
    num_stages: int,
    batch_axes=(),
    pipe_axis: str = "pipe",
    num_microbatches: Optional[int] = None,
    remat: bool = False,
    remat_policy=None,
    extra=(),
):
    """Run ``x`` through the stacked layers as a GPipe pipeline over ``pipe_axis``.

    layer_apply(params_one_layer, rng_or_None, h, gate, *extra_mb) -> h — one
    layer, pure. stacked_params: pytree with leading layer axis L
    (L % num_stages == 0). x: (B, N, D) with B divisible by num_microbatches
    (per data shard). gates: (L,) per-layer rope gate flags, scanned alongside
    the params. dropout_keys: (L,)-leading rng keys or None when deterministic.
    extra: batch-leading broadcast arrays (rope angles, pad masks, ...) —
    microbatched in lockstep with x and handed to every layer.
    """
    L = jax.tree.leaves(stacked_params)[0].shape[0]
    if L % num_stages:
        raise ValueError(f"num_layers ({L}) not divisible by pipeline stages ({num_stages})")
    M = num_microbatches or num_stages
    # local_fn reshapes the PER-DATA-SHARD batch, not the global one: with a >1
    # data axis the check must divide by the batch-axis mesh extent first, or
    # (e.g.) B=4, data=4, M=2 passes here and dies at trace time with an opaque
    # zero-sized reshape inside shard_map.
    mesh = jax.sharding.get_abstract_mesh()
    n_data_shards = 1
    for a in batch_axes:
        if a in mesh.axis_names:
            n_data_shards *= mesh.shape[a]
    local_batch, rem = divmod(x.shape[0], n_data_shards)
    if rem:
        raise ValueError(
            f"global batch {x.shape[0]} not divisible by the data-axis shard count ({n_data_shards})"
        )
    if local_batch % M:
        raise ValueError(
            f"per-data-shard batch {local_batch} (global {x.shape[0]} / {n_data_shards} shards) "
            f"not divisible by num_microbatches ({M})"
        )

    has_fsdp = mesh is not None and "fsdp" in mesh.axis_names and mesh.shape["fsdp"] > 1
    if has_fsdp:
        from perceiver_io_tpu.parallel.sharding import stacked_param_specs

        # per-leaf P(pipe, ..fsdp..): params enter the region still ZeRO-3
        # sharded; _gatherers reconstructs ONE layer at a time inside the scan.
        # min_fsdp_size=1 pins the region view to always-sharded: when the
        # at-rest param is replicated (below the train state's size floor) the
        # entry reshard is a free local slice, whereas the opposite mismatch
        # would all-gather a whole stage's params at region entry
        pspec = stacked_param_specs(stacked_params, mesh, pipe_axis, min_fsdp_size=1)
    else:
        pspec = jax.tree.map(lambda _: P(pipe_axis), stacked_params)
    # one all-gather closure per leaf (leaf-dim indices are per-LAYER, hence the
    # -1 offset from the stacked spec); a leaf with no fsdp dim passes through
    _gatherers = jax.tree.map(
        lambda spec: (
            lambda v, dims=tuple(i - 1 for i, a in enumerate(spec) if a == "fsdp"): reduce(
                lambda u, d: jax.lax.all_gather(u, "fsdp", axis=d, tiled=True), dims, v
            )
        ),
        pspec,
    )

    def layer_gathered(p, rng, h, gate, *ex):
        if has_fsdp:
            p = jax.tree.map(lambda v, g: g(v), p, _gatherers)
        return layer_apply(p, rng, h, gate, *ex)

    layer_fn = layer_gathered
    if remat:
        # gather INSIDE the checkpoint: the backward pass regathers the layer
        # (ZeRO-3 semantics) instead of saving the gathered full-size params
        layer_fn = jax.checkpoint(layer_gathered, policy=remat_policy)

    has_keys = dropout_keys is not None
    bspec = P(batch_axes if batch_axes else None)

    def local_fn(params_local, x_full, gates_local, keys_local, *extra_local):
        s = jax.lax.axis_index(pipe_axis)
        mb = x_full.shape[0] // M
        x_mbs = x_full.reshape(M, mb, *x_full.shape[1:])
        extra_mbs = tuple(a.reshape(M, mb, *a.shape[1:]) for a in extra_local)

        def stage(h, extra_mb, t):
            def one_layer(h, per_layer):
                p, gate, key = per_layer
                # decorrelate dropout across schedule ticks (one tick = one
                # microbatch through this stage)
                rng = jax.random.fold_in(key, t) if has_keys else None
                return layer_fn(p, rng, h, gate, *extra_mb), None

            h, _ = jax.lax.scan(one_layer, h, (params_local, gates_local, keys_local))
            return h

        T = M + num_stages - 1
        ys0 = jnp.zeros((M, mb, *x_full.shape[1:]), x_full.dtype)
        buf0 = jnp.zeros((mb, *x_full.shape[1:]), x_full.dtype)

        def tick(carry, t):
            buf, ys = carry
            # stage s works on microbatch m = t - s (clamped; out-of-range
            # ticks compute throwaway bubble work on a real microbatch's data)
            m_idx = jnp.clip(t - s, 0, M - 1)
            first = jax.lax.dynamic_index_in_dim(x_mbs, m_idx, keepdims=False)
            h = jnp.where(s == 0, first, buf)
            extra_mb = tuple(jax.lax.dynamic_index_in_dim(a, m_idx, keepdims=False) for a in extra_mbs)
            y = stage(h, extra_mb, t)
            # the last stage collects microbatch t-(P-1) once it is real
            out_idx = jnp.clip(t - (num_stages - 1), 0, M - 1)
            valid = (s == num_stages - 1) & (t >= num_stages - 1)
            cur = jax.lax.dynamic_index_in_dim(ys, out_idx, keepdims=False)
            ys = jax.lax.dynamic_update_index_in_dim(ys, jnp.where(valid, y, cur), out_idx, 0)
            buf = jax.lax.ppermute(y, pipe_axis, [(i, i + 1) for i in range(num_stages - 1)])
            return (buf, ys), None

        (_, ys), _ = jax.lax.scan(tick, (buf0, ys0), jnp.arange(T))
        # broadcast the collected outputs from the last stage to every stage
        ys = jax.lax.psum(jnp.where(s == num_stages - 1, ys, jnp.zeros_like(ys)), pipe_axis)
        return ys.reshape(x_full.shape)

    # keys ride the same leading layer axis as the params; when deterministic a
    # zeros dummy keeps the scanned (params, gates, keys) triple uniform and is
    # never touched (has_keys is a trace-time constant)
    keys_arg = dropout_keys if has_keys else jnp.zeros((L, 2), jnp.uint32)

    fn = _shard_map(
        local_fn,
        in_specs=(pspec, bspec, P(pipe_axis), P(pipe_axis)) + (bspec,) * len(extra),
        out_specs=bspec,
        mesh=None,
    )
    return fn(stacked_params, x, gates, keys_arg, *extra)
