"""Causal language model training CLI (WikiText-103-raw, UTF-8 bytes).

Reference recipe: /root/reference/perceiver/scripts/text/clm.py (presets) and
examples/training/clm/train.py (30.7M model: max_seq_len=4096, max_latents=512,
num_channels=512, 8 layers, cross_attention_dropout=0.5 -> published val_loss
0.876, BASELINE.md).

Usage:
  python -m perceiver_io_tpu.scripts.text.clm --data.dataset_dir=.cache/wikitext \\
      --trainer.max_steps=20000 --trainer.mesh_axes=data=8
"""

from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp

from perceiver_io_tpu.data.text.datasets import WikiTextDataModule
from perceiver_io_tpu.data.text.common import Task
from perceiver_io_tpu.generation.generate import GenerationConfig
from perceiver_io_tpu.models.text.clm import CausalLanguageModel, CausalLanguageModelConfig
from perceiver_io_tpu.pipelines import TextGenerationPipeline
from perceiver_io_tpu.scripts.common import OptimizerFlags, build_tx, run_fit
from perceiver_io_tpu.training.fit import TrainerConfig
from perceiver_io_tpu.training.flops import PerceiverARFlops, detect_peak_flops
from perceiver_io_tpu.training.trainer import TrainState, make_causal_lm_eval_step, make_causal_lm_train_step
from perceiver_io_tpu.utils.cli import CLI

DATA_DEFAULTS = dict(
    dataset_dir=".cache/wikitext",
    tokenizer="bytes",
    max_seq_len=4096,
    task=Task.clm,
    padding_side="left",
    random_train_shift=True,
    batch_size=20,
)
MODEL_DEFAULTS = dict(
    max_latents=512,
    num_channels=512,
    num_self_attention_layers=8,
    cross_attention_dropout=0.5,
    post_attention_dropout=0.0,
)
OPT_DEFAULTS = dict(lr=2e-4, warmup_steps=200, schedule="cosine", max_grad_norm=0.5)


def main(argv=None):
    cli = CLI(description="Train a Perceiver AR causal language model", argv=argv)
    cli.add_group("data", WikiTextDataModule, DATA_DEFAULTS)
    cli.add_group("model", CausalLanguageModelConfig, MODEL_DEFAULTS)
    cli.add_group("optimizer", OptimizerFlags, OPT_DEFAULTS)
    cli.add_group("trainer", TrainerConfig, dict(max_steps=20000, checkpoint_dir="ckpts/clm"))
    cli.add_flag("sample_prompt", default="A man", help="prompt used for per-eval sample generation")
    cli.add_bool_flag("resume", help="continue from <checkpoint_dir>/last (state + exact data position)")
    args = cli.parse()

    data = cli.build("data", args)
    data.prepare_data()
    data.setup()

    config = cli.build(
        "model", args, link={"vocab_size": data.vocab_size, "max_seq_len": data.max_seq_len}
    )
    trainer_cfg = cli.build("trainer", args)
    opt = cli.build("optimizer", args)

    model = CausalLanguageModel(config=config, deterministic=False, dtype=jnp.bfloat16)
    eval_model = CausalLanguageModel(config=config, deterministic=True, dtype=jnp.bfloat16)

    sample = jnp.zeros((2, config.max_seq_len), jnp.int32)
    params = jax.jit(model.init, static_argnames="prefix_len")(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(0)},
        sample,
        prefix_len=config.max_seq_len - config.max_latents,
    )
    n_params = sum(p.size for p in jax.tree.leaves(params))
    print(json.dumps({"model_params": n_params}))

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

    def on_eval(state, metrics):
        # qualitative sample each eval (reference text/clm/lightning.py:54-92)
        pipe = TextGenerationPipeline(eval_model, state.params, tokenizer=data.tokenizer)
        text = pipe(args.sample_prompt, num_latents=1, config=GenerationConfig(max_new_tokens=128, do_sample=True, top_k=40))
        print(json.dumps({"sample": text[:200]}))

    run_fit(
        trainer_cfg,
        state,
        make_causal_lm_train_step(model, tx, max_latents=config.max_latents),
        data,
        eval_step=make_causal_lm_eval_step(eval_model, max_latents=config.max_latents),
        on_eval=on_eval,
        resume=args.resume,
    )


if __name__ == "__main__":
    main()
