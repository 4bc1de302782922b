"""Symbolic audio (MIDI) model training CLI (GiantMIDI-Piano).

Reference recipe: /root/reference/examples/training/sam/giantmidi/train.py —
134M Perceiver AR (max_seq_len=6144, max_latents=2048, 768 channels, 18 layers,
output_norm, no abs pos emb) -> published val_loss 1.944 (BASELINE.md).
"""

from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp

from perceiver_io_tpu.data.audio.datasets import GiantMidiPianoDataModule
from perceiver_io_tpu.models.audio.symbolic import SymbolicAudioModel, SymbolicAudioModelConfig
from perceiver_io_tpu.scripts.common import OptimizerFlags, build_tx, run_fit
from perceiver_io_tpu.training.fit import TrainerConfig
from perceiver_io_tpu.training.flops import PerceiverARFlops, detect_peak_flops
from perceiver_io_tpu.training.trainer import TrainState, make_causal_lm_eval_step, make_causal_lm_train_step
from perceiver_io_tpu.utils.cli import CLI

DATA_DEFAULTS = dict(
    dataset_dir=".cache/giantmidi", max_seq_len=6144, min_seq_len=2048, padding_side="left", batch_size=8
)
MODEL_DEFAULTS = dict(
    max_latents=2048,
    num_channels=768,
    num_heads=8,
    num_self_attention_layers=18,
    cross_attention_dropout=0.1,
    post_attention_dropout=0.1,
    residual_dropout=0.1,
    output_norm=True,
    output_bias=False,
    abs_pos_emb=False,
    activation_checkpointing=True,
)


def main(argv=None):
    cli = CLI(description="Train a Perceiver AR symbolic audio model", argv=argv)
    cli.add_group("data", GiantMidiPianoDataModule, DATA_DEFAULTS)
    cli.add_group("model", SymbolicAudioModelConfig, MODEL_DEFAULTS)
    cli.add_group("optimizer", OptimizerFlags, dict(lr=2e-4, warmup_steps=500, schedule="cosine", max_grad_norm=0.5))
    cli.add_group("trainer", TrainerConfig, dict(max_steps=100000, checkpoint_dir="ckpts/sam"))
    cli.add_bool_flag("resume", help="continue from <checkpoint_dir>/last (state + exact data position)")
    args = cli.parse()

    data = cli.build("data", args)
    data.prepare_data()
    data.setup()

    config = cli.build("model", args, link={"vocab_size": data.vocab_size, "max_seq_len": data.max_seq_len})
    trainer_cfg = cli.build("trainer", args)
    opt = cli.build("optimizer", args)

    model = SymbolicAudioModel(config=config, deterministic=False, dtype=jnp.bfloat16)
    eval_model = SymbolicAudioModel(config=config, deterministic=True, dtype=jnp.bfloat16)

    sample = jnp.zeros((2, config.max_seq_len), jnp.int32)
    params = jax.jit(model.init, static_argnames="prefix_len")(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(0)},
        sample,
        prefix_len=config.max_seq_len - config.max_latents,
    )
    print(json.dumps({"model_params": sum(p.size for p in jax.tree.leaves(params))}))

    tx = build_tx(opt, trainer_cfg.max_steps)
    state = TrainState.create(params, tx)

    flops = PerceiverARFlops(config, config.max_seq_len, config.cross_attention_dropout)
    trainer_cfg = dataclasses.replace(
        trainer_cfg,
        tokens_per_batch=flops.tokens_per_step(data.batch_size),
        flops_per_step=flops.train_flops_per_step(data.batch_size),
        # MFU is a device metric: reported on an accelerator the peak table
        # knows (an unknown TPU kind is an error), never on the CPU
        peak_flops=detect_peak_flops() if jax.default_backend() == "tpu" else None,
    )
    run_fit(
        trainer_cfg,
        state,
        make_causal_lm_train_step(model, tx, max_latents=config.max_latents),
        data,
        eval_step=make_causal_lm_eval_step(eval_model, max_latents=config.max_latents),
        resume=args.resume,
    )


if __name__ == "__main__":
    main()
