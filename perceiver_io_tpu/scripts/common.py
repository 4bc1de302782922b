"""Shared training-script machinery: optimizer flags, warm starts, runners.

Parity targets: the reference's CLI base + trainer defaults
(/root/reference/perceiver/scripts/cli.py, scripts/trainer.yaml) and the
``params=<ckpt or repo>`` warm-start dispatch (core/lightning.py:145-147); the
text classifier's encoder-only warm start from an MLM checkpoint
(text/classifier/lightning.py:31-36) becomes a param-subtree copy here.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from perceiver_io_tpu.compile_cache import enable_compile_cache
from perceiver_io_tpu.training.checkpoint import load_pytree
from perceiver_io_tpu.training.fit import Trainer, TrainerConfig
from perceiver_io_tpu.training.lrs import constant_with_warmup, cosine_with_warmup
from perceiver_io_tpu.training.trainer import TrainState, build_optimizer


@dataclass
class OptimizerFlags:
    lr: float = 1e-3
    weight_decay: float = 0.0
    warmup_steps: int = 500  # in optimizer-update units (not micro-batches)
    schedule: str = "cosine"  # "cosine" | "constant"
    min_fraction: float = 0.0
    max_grad_norm: Optional[float] = None
    accumulate_steps: int = 1  # micro-batches per optimizer update
    freeze_encoder: bool = False  # classifier fine-tuning: freeze encoder params


def build_tx(flags: OptimizerFlags, max_steps: int):
    # LR schedules advance once per OPTIMIZER UPDATE: with accumulation, k
    # micro-batches produce one update, so the horizon is max_steps / k
    # (warmup_steps is likewise in update units)
    updates = max(1, max_steps // max(1, flags.accumulate_steps))
    if flags.schedule == "cosine":
        schedule = cosine_with_warmup(flags.lr, updates, flags.warmup_steps, min_fraction=flags.min_fraction)
    elif flags.schedule == "constant":
        schedule = constant_with_warmup(flags.lr, flags.warmup_steps)
    else:
        raise ValueError(f"unknown schedule '{flags.schedule}'")
    freeze_filter = (lambda path: "encoder" in path) if flags.freeze_encoder else None
    return build_optimizer(
        schedule,
        weight_decay=flags.weight_decay,
        max_grad_norm=flags.max_grad_norm,
        freeze_filter=freeze_filter,
        accumulate_steps=flags.accumulate_steps,
    )


def load_encoder_params(checkpoint_dir: str, target_params):
    """Copy the encoder subtree out of a (TrainState or bare-params) checkpoint
    into another model's params — the reference's encoder-only warm start
    (text/classifier/lightning.py:31-36). Shapes must match; mismatches raise."""
    tree = load_pytree(checkpoint_dir)
    source = tree.get("params", tree)  # TrainState pytree or bare params
    encoder = source["params"]["encoder"]
    jax.tree.map(
        lambda a, b: (_ for _ in ()).throw(
            ValueError(f"encoder shape mismatch: {jnp.shape(a)} vs {jnp.shape(b)}")
        ) if jnp.shape(a) != jnp.shape(b) else None,
        encoder,
        target_params["params"]["encoder"],
    )
    target = dict(target_params)
    target["params"] = dict(target["params"])
    target["params"]["encoder"] = jax.tree.map(jnp.asarray, encoder)
    return target


def run_fit(
    trainer_cfg: TrainerConfig,
    state: TrainState,
    train_step: Callable,
    data_module,
    eval_step: Optional[Callable] = None,
    on_eval: Optional[Callable] = None,
    resume: bool = False,
) -> TrainState:
    """``resume=True`` continues a killed/finished run from
    ``<checkpoint_dir>/last``: the full TrainState (params, optimizer moments,
    step, rng) is restored, and — when the loader is stateful — the exact
    mid-epoch data position from ``last_iterator.json``, so training continues
    bit-exact from the next unseen batch (a stronger guarantee than the
    reference's Lightning restart, which replays the epoch)."""
    import json

    enable_compile_cache()
    trainer = Trainer(trainer_cfg)
    train_loader_fn = data_module.train_dataloader
    initial_best = None
    if resume and trainer_cfg.checkpoint_dir:
        last = os.path.join(trainer_cfg.checkpoint_dir, "last")
        if os.path.isdir(last):
            # a shape-only template — restoring must not materialize a second
            # full state (the factory form exists to avoid that memory peak)
            template = jax.eval_shape(state) if callable(state) else state
            if trainer_cfg.mesh_axes:
                # restore each array straight into its sharded device layout —
                # never materializing the full unsharded state on one host
                from perceiver_io_tpu.parallel.api import _infer_state_shardings
                from perceiver_io_tpu.parallel.mesh import make_mesh
                from perceiver_io_tpu.training.checkpoint import restore_checkpoint

                mesh = make_mesh(trainer_cfg.mesh_axes)
                state_sh = _infer_state_shardings(
                    template, mesh, trainer_cfg.parallel_mode, 2**12,
                    pipeline_axis=trainer_cfg.pipeline_axis,
                )
                state = restore_checkpoint(last, template, shardings=state_sh)
            else:
                state = Trainer.restore(last, template)
            it_path = os.path.join(trainer_cfg.checkpoint_dir, "last_iterator.json")
            if os.path.exists(it_path):
                loader = data_module.train_dataloader()
                if hasattr(loader, "load_state_dict"):
                    Trainer.restore_iterator(it_path, loader)
                    train_loader_fn = lambda: loader
            best_path = os.path.join(trainer_cfg.checkpoint_dir, "best_metric.json")
            if os.path.exists(best_path):
                with open(best_path) as f:
                    best_rec = json.load(f)
                # only comparable if the run monitors the same metric
                if best_rec.get("monitor") == trainer_cfg.monitor:
                    initial_best = float(best_rec["value"])
            print(json.dumps({"resumed_from_step": int(state.step), "best": initial_best}))
        else:
            print(json.dumps({"resume": "no checkpoint at " + last + "; starting fresh"}))
    return trainer.fit(
        state,
        train_step,
        train_loader_fn=train_loader_fn,
        eval_step=eval_step,
        eval_loader_fn=data_module.val_dataloader if eval_step else None,
        on_eval=on_eval,
        initial_best=initial_best,
    )
