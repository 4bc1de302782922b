"""Worker for the 2-process distributed-CPU test (test_multiprocess.py).

Each process owns 4 virtual CPU devices; ``jax.distributed.initialize`` joins
them into one 8-device platform, a global ``data x fsdp`` mesh spans BOTH
processes, per-process data feeds the global batch via
``local_batch_to_global`` (the jax-native ``split_dataset_by_node``,
reference data/text/c4.py:76-79), and two fsdp-sharded train steps run with
XLA collectives crossing the process boundary — the multi-host leg of the
comm-backend claim (SURVEY.md §2.7) that single-process virtual meshes
cannot exercise.

Usage: multiprocess_worker.py <process_id> <num_processes> <port>
Prints one JSON line: {"proc": id, "losses": [loss0, loss1]}.
"""

import json
import os
import sys

proc_id, nprocs, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=4 "
    "--xla_backend_optimization_level=0 --xla_llvm_disable_expensive_passes=true "
    "--xla_cpu_collective_call_terminate_timeout_seconds=600"
)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from perceiver_io_tpu.compile_cache import enable_compile_cache  # noqa: E402
from perceiver_io_tpu.parallel.mesh import initialize_distributed  # noqa: E402

enable_compile_cache()

initialize_distributed(f"localhost:{port}", num_processes=nprocs, process_id=proc_id)
assert jax.process_count() == nprocs, jax.process_count()
assert jax.device_count() == 4 * nprocs, jax.device_count()

import numpy as np  # noqa: E402

from perceiver_io_tpu.models.core.config import CausalSequenceModelConfig  # noqa: E402
from perceiver_io_tpu.models.core.perceiver_ar import CausalSequenceModel  # noqa: E402
from perceiver_io_tpu.parallel.api import create_sharded_train_state, make_sharded_train_step  # noqa: E402
from perceiver_io_tpu.parallel.mesh import local_batch_to_global, make_mesh  # noqa: E402
from perceiver_io_tpu.training.trainer import build_optimizer, make_causal_lm_train_step  # noqa: E402

SEQ, GLOBAL_BATCH = 32, 8

config = CausalSequenceModelConfig(
    vocab_size=64, max_seq_len=SEQ, max_latents=16, num_channels=64,
    num_heads=4, num_self_attention_layers=2, cross_attention_dropout=0.0,
)
model = CausalSequenceModel(config=config, deterministic=True)
mesh = make_mesh({"data": 2, "fsdp": -1})

rng = jax.random.PRNGKey(0)
x0 = np.zeros((2, SEQ), np.int32)
tx = build_optimizer(1e-3)
state, state_sh = create_sharded_train_state(
    lambda: model.init(rng, x0, prefix_len=SEQ - config.max_latents),
    tx, mesh, min_fsdp_size=64,
)
step = make_sharded_train_step(make_causal_lm_train_step(model, tx, max_latents=config.max_latents), mesh, state_sh)

# the SAME deterministic global batch in every process; each contributes only
# the rows its addressable mesh slice owns (rows are laid out data-major, so
# process p owns the contiguous block [p*local : (p+1)*local])
data_rng = np.random.default_rng(42)
gx = data_rng.integers(0, config.vocab_size, (2, GLOBAL_BATCH, SEQ)).astype(np.int32)
losses = []
for it in range(2):
    local = GLOBAL_BATCH // nprocs
    rows = gx[it][proc_id * local : (proc_id + 1) * local]
    batch = local_batch_to_global({"input_ids": rows, "labels": np.roll(rows, -1, 1)}, mesh)
    state, metrics = step(state, batch)
    losses.append(float(metrics["loss"]))

print(json.dumps({"proc": proc_id, "losses": losses}), flush=True)
