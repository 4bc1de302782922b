"""Multi-PROCESS distributed training (the multi-host leg of SURVEY.md §2.7).

Everything else multi-device in this suite runs single-process virtual meshes;
here two OS processes (4 virtual CPU devices each) join through
``jax.distributed.initialize`` into one 8-device platform, per-process data
feeds the global batch (``local_batch_to_global`` — the jax-native
``split_dataset_by_node``, reference data/text/c4.py:76-79), and fsdp-sharded
train steps run XLA collectives ACROSS the process boundary (Gloo transport).

Assertions: both processes observe identical losses, and those losses match a
single-process run of the same global program — proving the per-process data
sharding assembles the same global batch and the cross-process collectives
compute the same reduction.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

_WORKER = os.path.join(os.path.dirname(__file__), "multiprocess_worker.py")
_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _single_process_reference():
    """The worker's exact program on this process's own 8-device platform."""
    import jax

    from perceiver_io_tpu.models.core.config import CausalSequenceModelConfig
    from perceiver_io_tpu.models.core.perceiver_ar import CausalSequenceModel
    from perceiver_io_tpu.parallel.api import create_sharded_train_state, make_sharded_train_step
    from perceiver_io_tpu.parallel.mesh import local_batch_to_global, make_mesh
    from perceiver_io_tpu.training.trainer import build_optimizer, make_causal_lm_train_step

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
    step = make_sharded_train_step(
        make_causal_lm_train_step(model, tx, max_latents=config.max_latents), mesh, state_sh
    )
    data_rng = np.random.default_rng(42)
    gx = data_rng.integers(0, config.vocab_size, (2, GLOBAL_BATCH, SEQ)).astype(np.int32)
    losses = []
    for it in range(2):
        batch = local_batch_to_global({"input_ids": gx[it], "labels": np.roll(gx[it], -1, 1)}, mesh)
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    return losses


@pytest.mark.slow
def test_two_process_fsdp_matches_single_process():
    port = _free_port()
    # the workers place their compile cache as the suite does (compile_cache.py)
    env = {**os.environ, "PYTHONPATH": _REPO, "JAX_PLATFORMS": "cpu"}
    procs = [
        subprocess.Popen(
            [sys.executable, _WORKER, str(i), "2", str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
        )
        for i in range(2)
    ]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=600)
        assert p.returncode == 0, f"worker failed:\n{err[-3000:]}"
        outs.append(json.loads(out.strip().splitlines()[-1]))

    by_proc = {o["proc"]: o["losses"] for o in outs}
    assert set(by_proc) == {0, 1}
    # replicated metrics: every process must see the SAME global loss
    np.testing.assert_array_equal(by_proc[0], by_proc[1])
    # and the distributed run must equal the single-process global program
    # (same batch, same init; only the process topology differs)
    ref = _single_process_reference()
    np.testing.assert_allclose(by_proc[0], ref, rtol=2e-5, atol=0)
    assert ref[1] < ref[0]  # it actually trains
