"""High-level parallel-training API: shard a TrainState over a mesh and jit the
train step with explicit shardings. XLA SPMD inserts all collectives:

  - pure ``data`` mesh  ≙ reference DDP (gradient all-reduce over NCCL,
    scripts/trainer.yaml:14)
  - ``fsdp`` axis       ≙ reference FSDP/ZeRO-3 (scripts/text/clm_fsdp.py:24-36):
    params+moments sharded, per-layer all-gather / reduce-scatter
  - ``tensor`` axis     ≙ Megatron tensor parallelism (beyond the reference)
"""

from __future__ import annotations

from typing import Callable, Literal, Optional

import jax
from jax.sharding import Mesh

from perceiver_io_tpu.parallel.mesh import batch_sharding, replicated
from perceiver_io_tpu.parallel.sharding import (
    infer_param_shardings,
    replicated_shardings,
    state_shardings,
)

ParallelMode = Literal["dp", "fsdp"]


def _infer_state_shardings(state_or_shapes, mesh: Mesh, mode: ParallelMode, min_fsdp_size: int, pipeline_axis=None):
    """Sharding tree for a TrainState (concrete or jax.eval_shape result)."""
    if mode == "dp":
        param_sh = replicated_shardings(state_or_shapes.params, mesh)
    else:
        param_sh = infer_param_shardings(
            state_or_shapes.params, mesh, min_fsdp_size=min_fsdp_size, pipeline_axis=pipeline_axis
        )
    return state_shardings(state_or_shapes, param_sh, mesh)


def shard_train_state(state, mesh: Mesh, mode: ParallelMode = "fsdp", min_fsdp_size: int = 2**12,
                      pipeline_axis=None):
    """Place a host-resident TrainState onto the mesh; returns (sharded_state,
    sharding_tree) — the latter feeds jit in/out_shardings. ``pipeline_axis``:
    opt-in, must match the model's config (see infer_param_shardings; both
    default to None = no pipelining)."""
    state_sh = _infer_state_shardings(state, mesh, mode, min_fsdp_size, pipeline_axis)
    sharded = jax.tree.map(lambda x, s: jax.device_put(x, s), state, state_sh)
    return sharded, state_sh


def create_sharded_state(state_fn: Callable, mesh: Mesh, mode: ParallelMode = "fsdp", min_fsdp_size: int = 2**12,
                         pipeline_axis=None):
    """Materialize ``state_fn()`` (a zero-arg TrainState factory) directly onto
    the mesh: the factory is traced with ``jax.eval_shape`` to infer shardings,
    then jitted with ``out_shardings`` so every parameter and optimizer moment
    comes out sharded — no host-resident full copy, no replicate-then-reshard
    step (the device_put path in shard_train_state). Returns (state, shardings)."""
    state_shape = jax.eval_shape(state_fn)
    state_sh = _infer_state_shardings(state_shape, mesh, mode, min_fsdp_size, pipeline_axis)
    with jax.sharding.set_mesh(mesh):
        state = jax.jit(state_fn, out_shardings=state_sh)()
    return state, state_sh


def create_sharded_train_state(
    init_fn: Callable,
    tx,
    mesh: Mesh,
    mode: ParallelMode = "fsdp",
    min_fsdp_size: int = 2**12,
    rng=None,
    pipeline_axis=None,
):
    """create_sharded_state over ``TrainState.create(init_fn(), tx)`` where
    ``init_fn`` is a zero-arg closure returning the param tree."""
    from perceiver_io_tpu.training.trainer import TrainState

    return create_sharded_state(
        lambda: TrainState.create(init_fn(), tx, rng=rng), mesh, mode=mode, min_fsdp_size=min_fsdp_size,
        pipeline_axis=pipeline_axis,
    )


def make_batch_put(mesh: Optional[Mesh]) -> Callable:
    """The canonical host-batch -> device placement for the training hot loop:
    sharded over the mesh's data axes when a mesh is given, plain
    ``jax.device_put`` (local default device) otherwise. Shared by the fit
    loop's synchronous path and by ``DevicePrefetcher`` so the prefetched and
    unprefetched batches land with identical placement."""
    if mesh is None:
        return jax.device_put
    sharding = batch_sharding(mesh)
    return lambda batch: jax.device_put(batch, sharding)


def _with_mesh_context(fn: Callable, mesh: Mesh) -> Callable:
    """Run (and trace) the jitted ``fn`` under the ambient mesh so mesh-aware
    fast paths (e.g. the shard_map splash-attention wrapper) can see the axes.
    ``wrapped.lower`` lowers under the same mesh (``.compile().as_text()``
    then shows the collectives and kernels of the program that runs)."""

    def wrapped(*args, **kwargs):
        with jax.sharding.set_mesh(mesh):
            return fn(*args, **kwargs)

    def lower(*args, **kwargs):
        with jax.sharding.set_mesh(mesh):
            return fn.lower(*args, **kwargs)

    wrapped.lower = lower
    return wrapped


def make_sharded_train_step(train_step: Callable, mesh: Mesh, state_sh) -> Callable:
    """jit the (state, batch) -> (state, metrics) step with the batch sharded over
    the data axes, the state donated (in-place buffer reuse on device), and
    metrics replicated."""
    return _with_mesh_context(
        jax.jit(
            train_step,
            in_shardings=(state_sh, batch_sharding(mesh)),
            out_shardings=(state_sh, replicated(mesh)),
            donate_argnums=(0,),
        ),
        mesh,
    )


def make_sharded_eval_step(eval_step: Callable, mesh: Mesh, param_sh) -> Callable:
    return _with_mesh_context(
        jax.jit(
            eval_step,
            in_shardings=(param_sh, batch_sharding(mesh)),
            out_shardings=replicated(mesh),
        ),
        mesh,
    )
