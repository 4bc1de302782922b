"""Device-mesh construction and distributed initialization.

This replaces the reference's delegation to Lightning DDP/FSDP over NCCL
(reference scripts/trainer.yaml:14, scripts/text/clm_fsdp.py:24-36,
SURVEY.md §2.7): one ``jax.sharding.Mesh`` expresses data parallelism,
ZeRO-3-style parameter sharding, tensor parallelism, and sequence parallelism;
XLA SPMD inserts the collectives (all-reduce ≙ DDP, all-gather/reduce-scatter ≙
FSDP) over ICI within a slice and DCN across slices.

Canonical axis names:
  - ``data``    batch-sharding (DDP-equivalent)
  - ``fsdp``    parameter/optimizer sharding (FSDP/ZeRO-3-equivalent); params are
                sharded over it, and the batch is ALSO sharded over it (fsdp is a
                finer-grained data axis)
  - ``tensor``  Megatron-style head/width sharding
  - ``seq``     sequence/context parallelism for long inputs
  - ``pipe``    GPipe pipeline parallelism over the scanned layer stack
                (parallel/pipeline.py; layer-sharded params + microbatches)
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

DATA_AXES = ("data", "fsdp")  # axes the batch dimension is sharded over


def initialize_distributed(coordinator_address: Optional[str] = None, num_processes: Optional[int] = None, process_id: Optional[int] = None):
    """Multi-host bring-up (one JAX process per host). No-op when single-process.
    Replaces torch.distributed/NCCL process-group init, which Lightning performed
    for the reference."""
    if num_processes is not None and num_processes > 1:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )


def make_mesh(axes: Dict[str, int], devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a mesh with the given {axis_name: size}. Sizes must multiply to the
    device count (one axis may be -1 to infer). Axis order follows dict order;
    put the fastest-varying (most-communicating, e.g. ``tensor``) axis LAST so it
    maps onto adjacent ICI neighbours."""
    devices = list(devices if devices is not None else jax.devices())
    sizes = dict(axes)
    n = len(devices)
    infer = [k for k, v in sizes.items() if v == -1]
    if len(infer) > 1:
        raise ValueError("at most one mesh axis may be -1")
    if infer:
        known = int(np.prod([v for v in sizes.values() if v != -1]))
        if n % known:
            raise ValueError(f"device count {n} not divisible by {known}")
        sizes[infer[0]] = n // known
    total = int(np.prod(list(sizes.values())))
    if total != n:
        raise ValueError(f"mesh axes {sizes} require {total} devices, have {n}")
    dev_array = np.asarray(devices).reshape(*sizes.values())
    return Mesh(dev_array, tuple(sizes.keys()))


def data_mesh(num_devices: Optional[int] = None) -> Mesh:
    """Pure data-parallel mesh (the reference's default DDP strategy)."""
    devices = jax.devices()[: num_devices or len(jax.devices())]
    return make_mesh({"data": len(devices)}, devices)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Batch dim sharded over every data-like axis present in the mesh."""
    axes = tuple(a for a in DATA_AXES if a in mesh.axis_names)
    return NamedSharding(mesh, PartitionSpec(axes if axes else None))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def constrain_batch_sharded(x: jax.Array) -> jax.Array:
    """Pin dim 0 of an activation to the ambient mesh's data axes, leaving the
    other dims unconstrained. A propagation HINT, not a reshard: XLA's sharding
    propagation sometimes picks a channel-sharded layout for small norm/concat
    intermediates and then pays an 'involuntary full rematerialization'
    (replicate-then-reshard) to feed the next fsdp GEMM — observed on the
    Perceiver AR cross-attention q_norm/concat under data x fsdp meshes. No-op
    without an ambient mesh or without data axes (single device, pure
    tensor/seq meshes), so module code can call it unconditionally."""
    sizes = dict(jax.sharding.get_abstract_mesh().shape)
    axes = tuple(a for a in DATA_AXES if sizes.get(a, 1) > 1)
    if not axes:
        return x
    n_data = 1
    for a in axes:
        n_data *= sizes[a]
    if x.shape[0] % n_data:
        # a batch the data axes cannot divide (e.g. a ragged final eval batch)
        # must not FAIL the hint that exists only to speed up the common case —
        # propagation falls back to whatever XLA picks, as before the hint
        return x
    spec = PartitionSpec(axes, *([PartitionSpec.UNCONSTRAINED] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, spec)


def local_batch_to_global(batch, mesh: Mesh):
    """Multi-host data loading: each process holds its local shard of the batch
    (the jax-native replacement for the reference's ``split_dataset_by_node``,
    data/text/c4.py:76-79); assemble the logically-global array."""
    sharding = batch_sharding(mesh)
    return jax.tree.map(
        lambda x: jax.make_array_from_process_local_data(sharding, np.asarray(x)), batch
    )
