"""Convergence-evidence runner: real learning curves per model family without
network access (VERDICT round-1 item 4; reference quality targets in
BASELINE.md / reference docs/training-examples.md:144-184).

Tasks (each writes convergence/<task>.json with the full eval history):

  digits_glyphs    the MNIST recipe (exact scripts/vision/image_classifier.py
                   architecture, 907K params) on generated 28x28 digits;
                   target: val_acc >= 0.98 (the reference's MNIST bar).
  digits_glyphs_hard  same recipe on the occlusion/heavy-warp/distractor tier —
                   the difficulty-calibration family: no bar, reported against
                   a linear-probe baseline (every digits task records one).
  digits_sklearn   a smaller Perceiver IO on the bundled real scikit-learn
                   digits (1,797 8x8 scans); target: val_acc >= 0.98.
  clm_markov       Perceiver AR byte CLM on an order-2 Markov corpus whose
                   conditional entropy is computed analytically — the one
                   corpus with an EXACT loss target; met when val CE is within
                   0.05 nats of the floor.
  clm_markov_sharded  the clm_markov recipe through the PRODUCTION execution
                   path: virtual data(2) x fsdp(4) mesh, bf16 compute,
                   dots-saveable remat, fused qkv — same analytic floor.
  clm_pysrc        Perceiver AR byte CLM on the installed site-packages'
                   python source (real text, no analytic floor): the curve +
                   final bits/byte are recorded.
  audio_markov     SymbolicAudioModel on a synthetic Markov 'MIDI-event'
                   corpus (data/audio/synthetic.py): ragged LEFT-padded
                   windows through the real audio collator, exercising the
                   pad-mask branch of the causal-LM step; target = the same
                   exact analytic entropy floor.

Usage:
  python -m perceiver_io_tpu.scripts.convergence --task digits_glyphs
  python -m perceiver_io_tpu.scripts.convergence --task all --out convergence
"""

from __future__ import annotations

import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np


def _fit(model, eval_model, data, steps, lr, make_train_step, make_eval_step,
         monitor, monitor_mode, init_fn, warmup_cap=500, mesh_axes=None, return_state=False,
         on_eval=None):
    import optax

    from perceiver_io_tpu.training.fit import Trainer, TrainerConfig
    from perceiver_io_tpu.training.trainer import TrainState

    tx = optax.chain(optax.clip_by_global_norm(1.0),
                     optax.adamw(optax.warmup_cosine_decay_schedule(0.0, lr, min(warmup_cap, steps // 4), steps)))
    if mesh_axes:
        # production path: params + moments initialize directly sharded on the
        # mesh (jitted factory with out_shardings — no host-resident full copy)
        state = lambda: TrainState.create(init_fn(), tx)
        shapes = jax.eval_shape(init_fn)
        n_params = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    else:
        params = jax.jit(init_fn)()
        n_params = sum(p.size for p in jax.tree.leaves(params))
        state = TrainState.create(params, tx)
    eval_every = max(steps // 12, 1)
    trainer = Trainer(TrainerConfig(
        max_steps=steps, eval_every=eval_every, log_every=eval_every,
        monitor=monitor, monitor_mode=monitor_mode, mesh_axes=mesh_axes or None,
    ))
    final = trainer.fit(state, make_train_step(model, tx), data.train_dataloader,
                        eval_step=make_eval_step(eval_model), eval_loader_fn=data.val_dataloader,
                        on_eval=on_eval)
    if return_state:
        return trainer.history, n_params, final
    return trainer.history, n_params


def _linear_probe_acc(splits, cap: int = 10_000) -> float:
    """Multinomial logistic regression on raw pixels — the trivial baseline
    that calibrates how hard a digit tier actually is (VERDICT r3 weak #3: a
    1.0 on easy data over-reads without a denominator)."""
    from sklearn.linear_model import LogisticRegression

    (tr_x, tr_y), (va_x, va_y) = splits
    tr = tr_x[:cap].reshape(min(len(tr_x), cap), -1).astype(np.float32) / 255.0
    va = va_x.reshape(len(va_x), -1).astype(np.float32) / 255.0
    clf = LogisticRegression(max_iter=300).fit(tr, tr_y[:cap])
    return float(clf.score(va, va_y))


def run_digits(source: str, steps: int, task_name: str = ""):
    from perceiver_io_tpu.data.vision.synthetic import SyntheticDigitsDataModule
    from perceiver_io_tpu.models.core.config import ClassificationDecoderConfig
    from perceiver_io_tpu.models.vision.image_classifier import (
        ImageClassifier,
        ImageClassifierConfig,
        ImageEncoderConfig,
    )
    from perceiver_io_tpu.training.trainer import make_classifier_eval_step, make_classifier_train_step

    if source in ("glyphs", "glyphs_hard"):
        data = SyntheticDigitsDataModule(source=source, n_train=20_000, n_val=2_000, batch_size=128)
        # the exact MNIST recipe architecture (scripts/vision/image_classifier.py)
        # for BOTH tiers, so easy-vs-hard accuracy differences are data-only
        enc_kw = dict(num_frequency_bands=32, num_cross_attention_layers=2, num_cross_attention_heads=1,
                      num_self_attention_blocks=3, num_self_attention_layers_per_block=3,
                      num_self_attention_heads=8, first_cross_attention_layer_shared=False,
                      first_self_attention_block_shared=False, dropout=0.1, init_scale=0.1)
        num_latents, num_latent_channels = 32, 128
    else:
        data = SyntheticDigitsDataModule(source="sklearn_digits", batch_size=64)
        enc_kw = dict(num_frequency_bands=12, num_cross_attention_layers=1, num_cross_attention_heads=1,
                      num_self_attention_blocks=2, num_self_attention_layers_per_block=2,
                      num_self_attention_heads=4, dropout=0.1, init_scale=0.1)
        num_latents, num_latent_channels = 16, 64
    data.setup()
    baseline_acc = _linear_probe_acc(data._load_splits())

    encoder = ImageEncoderConfig(image_shape=data.image_shape, **enc_kw)
    decoder = ClassificationDecoderConfig(num_classes=10, num_output_query_channels=128,
                                          num_cross_attention_heads=1, dropout=0.1, init_scale=0.1)
    config = ImageClassifierConfig(encoder=encoder, decoder=decoder,
                                   num_latents=num_latents, num_latent_channels=num_latent_channels)
    model = ImageClassifier(config=config, deterministic=False)
    eval_model = ImageClassifier(config=config, deterministic=True)

    sample = jnp.zeros((2, *data.image_shape))
    rngs = {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(0)}
    history, n_params = _fit(
        model, eval_model, data, steps, lr=1e-3,
        make_train_step=make_classifier_train_step, make_eval_step=make_classifier_eval_step,
        monitor="acc", monitor_mode="max", init_fn=lambda: model.init(rngs, sample),
    )
    accs = [h["val_acc"] for h in history if "val_acc" in h]
    achieved = max(accs) if accs else None
    if source == "glyphs_hard":
        # difficulty-calibration tier: no reference bar; MET means the model
        # beats the trivial baseline — the margin is the deliverable
        target = {"metric": "val_acc", "value": None,
                  "provenance": "difficulty-calibration tier (occlusion + heavy warps + "
                                "distractors); MET = model beats the linear-probe baseline"}
        met = bool(achieved is not None and achieved > baseline_acc)
    else:
        target = {"metric": "val_acc", "value": 0.98,
                  "provenance": "reference MNIST bar, docs/training-examples.md:144-150 (0.98160)"}
        met = bool(accs and max(accs) >= 0.98)
    return {
        "task": task_name or f"digits_{source}",
        "model_params": n_params,
        "target": target,
        "achieved": achieved,
        "baseline_val_acc": baseline_acc,
        "baseline": "multinomial logistic regression on raw pixels (10k train cap)",
        "met": met,
        "history": history,
    }


def run_clm(source: str, steps: int, task_name: str = "", profile: str = "", production: bool = False,
            size: str = ""):
    """``production=True`` (the ``clm_markov_sharded`` family) trains the SAME
    recipe through the flagship execution path instead of the single-device
    default: a virtual data(2) x fsdp(4) mesh (ZeRO-3 param/moment sharding,
    XLA-inserted collectives — the reference's clm_fsdp.py:24-36 regime), bf16
    compute with fp32 params/softmax, dots-saveable remat over the scanned
    layer stack, and single-GEMM fused qkv. Converging to the SAME analytic
    floor upgrades the 2-step loss-equality tests (test_training_parallel.py)
    to 'the sharded production path trains to the provable optimum'."""
    from perceiver_io_tpu.data.text.synthetic import SyntheticTextDataModule
    from perceiver_io_tpu.models.core.config import CausalSequenceModelConfig
    from perceiver_io_tpu.models.core.perceiver_ar import CausalSequenceModel
    from perceiver_io_tpu.training.trainer import make_causal_lm_eval_step, make_causal_lm_train_step

    # The corpus's entropy floor is a property of the DATA, so the loss target
    # stays exact regardless of model size — a cpu profile keeps runs on a
    # few CPU cores feasible.
    if not profile:
        profile = "tpu" if jax.default_backend() == "tpu" else "cpu"
    small = profile == "cpu"
    seq = 256 if small else 512
    if source == "markov":
        # single-pass corpus sized to the whole step budget: the vectorized
        # stationary-window sampler makes 25M fresh tokens cheap (~0.5s, 100MB),
        # and a never-repeating stream is the only regime where the analytic
        # floor is the training optimum too — a fixed small sample lets the
        # model push train CE below the floor by memorization while val CE
        # climbs (observed: train 0.90 vs floor 1.23 on a looped 1M corpus)
        # the 5m tier halves the batch: its SA stack is 11x the small recipe's
        # FLOPs and the corpus signal is strong enough that optimizer steps,
        # not tokens, bound convergence (measured 3.9 s/step at batch 8)
        batch = 8 if size == "5m" else 16
        # sharded eval consumes whole batches over the mesh's data axes, so the
        # production run sizes the val split to an exact batch multiple (192
        # windows = 12 full batches); the single-device profiles keep the
        # round number (ragged last batch is fine there)
        n_val = 192 * seq if production else (50_000 if small else 100_000)  # windows = n_val_tokens // seq
        data = SyntheticTextDataModule(source="markov", seq_len=seq, batch_size=batch,
                                       n_train_tokens=steps * batch * (seq + 1),
                                       n_val_tokens=n_val,
                                       vocab_size=32 if small else 64)
    else:
        data = SyntheticTextDataModule(source="python_source", seq_len=seq if small else 1024,
                                       batch_size=8,
                                       n_train_tokens=2_000_000 if small else 8_000_000,
                                       n_val_tokens=200_000 if small else 400_000)
    data.setup()

    knobs = dict(
        activation_checkpointing=True, remat_policy="dots_with_no_batch_dims_saveable",
        fused_qkv=True,
    ) if production else {}
    mesh_axes = {"data": 2, "fsdp": 4} if production else None
    dtype = jnp.bfloat16 if production else None
    if size == "5m":
        # production-SCALE tier (VERDICT r4 item 5): ~7.2M params with realistic
        # depth/width (8 layers x 256, heads 8) and the flagship's latent/prefix
        # proportion (latents = seq/2) — deep-stack scan x remat x fsdp
        # interactions only surface with a real layer count
        dims = dict(num_channels=256, num_heads=8, num_self_attention_layers=8)
    else:
        dims = dict(num_channels=128 if small else 256, num_heads=4 if small else 8,
                    num_self_attention_layers=2 if small else 4)
    config = CausalSequenceModelConfig(
        vocab_size=data.effective_vocab_size, max_seq_len=data.seq_len,
        max_latents=data.seq_len // 2, cross_attention_dropout=0.0,
        **dims, **knobs,
    )
    model = CausalSequenceModel(config=config, deterministic=False, dtype=dtype)
    eval_model = CausalSequenceModel(config=config, deterministic=True, dtype=dtype)

    x = jnp.zeros((2, data.seq_len), jnp.int32)
    rngs = {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(0)}
    # lr 2e-3 measured necessary to reach the markov floor: at 3e-4 the model
    # plateaus near the marginal entropy (bigram structure barely forms)
    history, n_params = _fit(
        model, eval_model, data, steps, lr=2e-3,
        make_train_step=lambda m, tx: make_causal_lm_train_step(m, tx, max_latents=config.max_latents),
        make_eval_step=lambda m: make_causal_lm_eval_step(m, max_latents=config.max_latents),
        monitor="loss", monitor_mode="min", warmup_cap=150,
        init_fn=lambda: model.init(rngs, x, prefix_len=data.seq_len - config.max_latents),
        mesh_axes=mesh_axes,
    )

    losses = [h["val_loss"] for h in history if "val_loss" in h]
    achieved = min(losses) if losses else None
    out = {
        "task": task_name or f"clm_{source}",
        "model_params": n_params,
        "achieved_val_ce_nats": achieved,
        "history": history,
    }
    out["profile"] = profile
    if production:
        out["execution_path"] = {
            "mesh": mesh_axes, "parallel_mode": "fsdp (ZeRO-3 param/moment sharding)",
            "dtype": "bfloat16 compute, float32 params + softmax/LN stats",
            "remat_policy": config.remat_policy, "fused_qkv": config.fused_qkv,
            "scanned_layers": True,
        }
    if source == "markov":
        floor = float(data.entropy_floor)
        out["target"] = {"metric": "val_loss", "value": floor, "tolerance_nats": 0.05,
                         "provenance": "analytic conditional entropy of the order-2 Markov corpus"}
        out["met"] = bool(achieved is not None and achieved <= floor + 0.05)
        out["entropy_floor_nats"] = floor
        out["gap_nats"] = None if achieved is None else achieved - floor
    else:
        out["target"] = {"metric": "val_loss", "value": None,
                         "provenance": "no analytic floor for real text; curve recorded"}
        out["bits_per_byte"] = None if achieved is None else achieved / float(np.log(2.0))
        out["met"] = achieved is not None
    return out


def run_audio_markov(steps: int, profile: str = ""):
    """The audio family's convergence run: same analytic floor as clm_markov,
    but through the SymbolicAudioModel alias, the GiantMIDI recipe's
    architecture knobs (output_norm, no abs pos emb — scripts/audio/symbolic.py
    MODEL_DEFAULTS), ragged left-padded windows, and pad-masked labels."""
    from perceiver_io_tpu.data.audio.synthetic import SyntheticMidiDataModule
    from perceiver_io_tpu.models.audio.symbolic import SymbolicAudioModel, SymbolicAudioModelConfig
    from perceiver_io_tpu.training.trainer import make_causal_lm_eval_step, make_causal_lm_train_step

    if not profile:
        profile = "tpu" if jax.default_backend() == "tpu" else "cpu"
    small = profile == "cpu"
    seq, latents, batch = (256, 128, 16) if small else (512, 256, 16)
    data = SyntheticMidiDataModule(
        seq_len=seq, max_latents=latents, batch_size=batch,
        # fresh chains per epoch; one epoch sized to the step budget
        n_train_chains=steps * batch, n_val_chains=256,
        vocab_size=32 if small else 64,
    )
    data.setup()

    config = SymbolicAudioModelConfig(
        vocab_size=data.model_vocab_size, max_seq_len=seq, max_latents=latents,
        num_channels=128 if small else 256, num_heads=4 if small else 8,
        num_self_attention_layers=2 if small else 4,
        cross_attention_dropout=0.0,
        output_norm=True, output_bias=False, abs_pos_emb=False,
    )
    model = SymbolicAudioModel(config=config, deterministic=False)
    eval_model = SymbolicAudioModel(config=config, deterministic=True)

    x = jnp.zeros((2, seq), jnp.int32)
    rngs = {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(0)}
    history, n_params = _fit(
        model, eval_model, data, steps, lr=2e-3,
        make_train_step=lambda m, tx: make_causal_lm_train_step(m, tx, max_latents=latents),
        make_eval_step=lambda m: make_causal_lm_eval_step(m, max_latents=latents),
        monitor="loss", monitor_mode="min", warmup_cap=150,
        init_fn=lambda: model.init(rngs, x, prefix_len=seq - latents),
    )

    losses = [h["val_loss"] for h in history if "val_loss" in h]
    achieved = min(losses) if losses else None
    floor = float(data.entropy_floor)
    return {
        "task": "audio_markov",
        "model_params": n_params,
        "profile": profile,
        "achieved_val_ce_nats": achieved,
        "target": {"metric": "val_loss", "value": floor, "tolerance_nats": 0.05,
                   "provenance": "analytic conditional entropy of the order-2 Markov event corpus "
                                 "(ragged left-padded windows, pad-masked labels)"},
        "met": bool(achieved is not None and achieved <= floor + 0.05),
        "entropy_floor_nats": floor,
        "gap_nats": None if achieved is None else achieved - floor,
        "history": history,
    }


def run_optical_flow_epe(steps: int):
    """Task-level optical-flow quality (VERDICT r4 item 7): the reference only
    converts official flow weights (vision/optical_flow/huggingface.py) and its
    quality evidence is Sintel-visual; with zero egress the substitute is
    frame pairs under ANALYTICALLY-known rigid motion (data/vision/synthetic.py
    make_flow_pair): train a small OpticalFlow model on patch-sized pairs, then
    report endpoint error through the FULL pipeline — patching, model forward,
    flow_scale_factor rescale, border-weighted blending
    (data/vision/optical_flow.py:107-144) — on LARGER unseen images, against
    the zero-flow trivial baseline (EPE = mean true displacement)."""
    import optax

    from perceiver_io_tpu.data.vision.optical_flow import OpticalFlowProcessor
    from perceiver_io_tpu.data.vision.synthetic import SyntheticFlowDataModule, make_flow_pair
    from perceiver_io_tpu.models.vision.optical_flow import (
        OpticalFlow,
        OpticalFlowConfig,
        OpticalFlowDecoderConfig,
        OpticalFlowEncoderConfig,
    )
    from perceiver_io_tpu.training.trainer import _apply_updates

    shape, scale = (32, 48), 20
    # displacement bound: the 27-channel inputs carry 3x3 neighborhoods, so
    # gradient-level correspondence cues live within ~1px; motions much beyond
    # that need the official model's scale (41M, 24 layers) to resolve through
    # attention alone. Sub-2px rigid motion keeps the task learnable at probe
    # scale while still exercising every pipeline stage end-to-end.
    max_shift, max_rot = 1.25, 1.5
    data = SyntheticFlowDataModule(image_shape=shape, batch_size=16, flow_scale_factor=scale,
                                   max_shift=max_shift, max_rot_deg=max_rot)
    data.setup()

    # Probe-scale trainability (diagnosed via a pixelwise-MLP control that DID
    # learn this data, then bisected on the perceiver):
    #   * encoder init_scale 0.25 — at the 0.02 default the 54->hidden content
    #     projection lands ~1% of the feature variance next to the O(1) Fourier
    #     position channels, starving every input-dependent path of gradient;
    #   * decoder rescale_factor 1.0 — the official head divides by 100
    #     (huggingface flow-model convention), so from a 0.02-scale init the
    #     kernel must grow ~100x before outputs reach target scale;
    #   * cross_attention_residual=True + widening 4 — the official 41M config
    #     runs residual-free (per-pixel evidence reaches the output only
    #     through attention weights over latent values), a route that needs the
    #     official scale to train; the residual (also a reference decoder
    #     option) gives dense query features a direct path to the flow head.
    # With all three, train MSE drops ~10x below the zero-flow floor within
    # 300 steps; with any one missing it sits AT the floor for 600+ steps.
    enc = OpticalFlowEncoderConfig(
        image_shape=shape, num_patch_input_channels=27, num_patch_hidden_channels=32,
        num_frequency_bands=16, num_cross_attention_heads=1, num_self_attention_heads=4,
        num_self_attention_layers_per_block=4, num_self_attention_blocks=1,
        init_scale=0.25,
    )
    dec = OpticalFlowDecoderConfig(
        image_shape=shape, num_cross_attention_qk_channels=64,
        num_cross_attention_v_channels=64, num_cross_attention_heads=1,
        cross_attention_residual=True, cross_attention_widening_factor=4,
        rescale_factor=1.0,
    )
    cfg = OpticalFlowConfig(encoder=enc, decoder=dec, num_latents=128, num_latent_channels=64)
    model = OpticalFlow(config=cfg, deterministic=False)
    eval_model = OpticalFlow(config=cfg, deterministic=True)

    def make_train_step(m, tx):
        def step(state, batch):
            rng = jax.random.fold_in(state.rng, state.step)

            def loss_fn(p):
                pred = m.apply(p, batch["x"], rngs={"dropout": rng})
                loss = jnp.mean((pred - batch["flow"] / scale) ** 2)
                return loss, {"loss": loss}

            (_, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(state.params)
            return _apply_updates(state, tx, grads), metrics

        return step

    def make_eval_step(m):
        def eval_step(params, batch):
            pred = m.apply(params, batch["x"])
            return {
                "loss": jnp.mean((pred - batch["flow"] / scale) ** 2),
                "epe": jnp.mean(jnp.linalg.norm(pred * scale - batch["flow"], axis=-1)),
            }

        return eval_step

    rngs = {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(0)}
    sample = jnp.zeros((2, 2, 27, *shape), jnp.float32)
    # the judged full-pipeline EPE must come from the monitor-BEST params, not
    # whatever the cosine tail left behind — track them via the eval hook
    best = {"loss": float("inf"), "params": None}

    def track_best(state, val):
        if float(val["loss"]) < best["loss"]:
            best["loss"] = float(val["loss"])
            # COPY: the trainer's jitted step donates the state buffers, so a
            # bare reference is dead (Array deleted) by the next train step
            best["params"] = jax.tree.map(jnp.copy, state.params)

    history, n_params, state = _fit(
        model, eval_model, data, steps, lr=2e-3,
        make_train_step=make_train_step, make_eval_step=make_eval_step,
        monitor="loss", monitor_mode="min", init_fn=lambda: model.init(rngs, sample),
        # the loss surface opens slowly here (tiny early gradient norms while
        # attention warms up); a long warmup just delays that — 150 measured
        # sufficient on the single-batch overfit diagnostic
        warmup_cap=150, return_state=True, on_eval=track_best,
    )
    eval_params = best["params"] if best["params"] is not None else state.params

    # full-pipeline EPE on UNSEEN, larger-than-patch images: patch grid of 4
    # overlapping patches per pair, border-weighted blending — the path a user
    # of pipelines.py("optical-flow") runs
    proc = OpticalFlowProcessor(patch_size=shape, patch_min_overlap=8, flow_scale_factor=scale)
    rng = np.random.default_rng(12345)
    eval_shape = (48, 72)
    pairs, truths = [], []
    for _ in range(8):
        f1, f2, flow = make_flow_pair(rng, eval_shape, max_shift=max_shift, max_rot_deg=max_rot)
        pairs.append((f1, f2))
        truths.append(flow)
    truths = np.stack(truths)
    apply = jax.jit(lambda xx: eval_model.apply(eval_params, xx))
    pred = proc.process(lambda xx: apply(jnp.asarray(xx)), pairs, batch_size=4)
    epe = float(np.linalg.norm(pred - truths, axis=-1).mean())
    zero_epe = float(np.linalg.norm(truths, axis=-1).mean())

    epes = [h["val_epe"] for h in history if "val_epe" in h]
    return {
        "task": "optical_flow_epe",
        "model_params": n_params,
        "target": {"metric": "val_epe", "value": None,
                   "provenance": f"analytic rigid-motion flow (shift <={max_shift}px, rot "
                                 f"<={max_rot}deg — see displacement-bound note in "
                                 "run_optical_flow_epe); MET = full-pipeline EPE < 0.5 x the "
                                 "zero-flow baseline on unseen larger-than-patch images "
                                 "(4-patch grid, blended)"},
        "achieved": epe,
        "full_pipeline_epe_px": epe,
        "zero_flow_baseline_epe_px": zero_epe,
        "patch_level_val_epe_best": min(epes) if epes else None,
        "met": bool(epe < 0.5 * zero_epe),
        "history": history,
    }


TASKS = {
    "digits_glyphs": lambda steps: run_digits("glyphs", steps or 3000, "digits_glyphs"),
    "digits_glyphs_hard": lambda steps: run_digits("glyphs_hard", steps or 3000, "digits_glyphs_hard"),
    "digits_sklearn": lambda steps: run_digits("sklearn_digits", steps or 2000, "digits_sklearn"),
    "clm_markov": lambda steps: run_clm("markov", steps or 2000, "clm_markov"),
    "clm_markov_sharded": lambda steps: run_clm("markov", steps or 4000, "clm_markov_sharded",
                                                profile="cpu", production=True),
    "clm_markov_5m": lambda steps: run_clm("markov", steps or 3000, "clm_markov_5m",
                                           profile="cpu", production=True, size="5m"),
    "clm_pysrc": lambda steps: run_clm("python_source", steps or 2000, "clm_pysrc"),
    "audio_markov": lambda steps: run_audio_markov(steps or 2500),
    "optical_flow_epe": lambda steps: run_optical_flow_epe(steps or 2500),
}


def _spark(values, width=44):
    """ASCII curve: min..max scaled to 8 glyph levels."""
    if not values:
        return ""
    glyphs = "▁▂▃▄▅▆▇█"
    if len(values) > width:
        idx = np.linspace(0, len(values) - 1, width).round().astype(int)
        values = [values[i] for i in idx]
    lo, hi = min(values), max(values)
    span = (hi - lo) or 1.0
    return "".join(glyphs[int((v - lo) / span * 7)] for v in values)


def render(out_dir: str, md_path: str = "CONVERGENCE.md") -> None:
    """Regenerate CONVERGENCE.md from the recorded convergence/<task>.json files."""
    sections = []
    for name in TASKS:
        path = os.path.join(out_dir, f"{name}.json")
        if not os.path.exists(path):
            continue
        with open(path) as f:
            r = json.load(f)
        hist = r.get("history", [])
        metric = r["target"]["metric"]  # producers always write val_acc / val_loss
        curve = [h[metric] for h in hist if metric in h]
        lines = [f"## {r['task']}", ""]
        lines.append(f"- model params: {r['model_params']:,}" + (f" (profile: {r['profile']})" if r.get("profile") else ""))
        tgt = r["target"]
        if tgt["value"] is not None:
            lines.append(f"- target: {tgt['metric']} {'>=' if 'acc' in tgt['metric'] else '<='} {tgt['value']:.5g}"
                         + (f" (+{tgt['tolerance_nats']} nats tolerance)" if "tolerance_nats" in tgt else "")
                         + f" — {tgt['provenance']}")
        else:
            lines.append(f"- target: none ({tgt['provenance']})")
        ach = r.get("achieved", r.get("achieved_val_ce_nats"))
        ach_s = "n/a (no eval points recorded)" if ach is None else f"{ach:.5g}"
        lines.append(f"- achieved: {ach_s} — **{'MET' if r.get('met') else 'NOT MET'}**")
        if r.get("baseline_val_acc") is not None:
            lines.append(f"- trivial baseline: {r['baseline_val_acc']:.5g} ({r.get('baseline', 'linear probe')})")
        if r.get("zero_flow_baseline_epe_px") is not None:
            lines.append(f"- full-pipeline EPE: {r['full_pipeline_epe_px']:.4g} px vs zero-flow "
                         f"baseline {r['zero_flow_baseline_epe_px']:.4g} px "
                         f"(patch-level best val EPE {r['patch_level_val_epe_best']:.4g} px)")
        if r.get("execution_path"):
            ep = r["execution_path"]
            lines.append(f"- execution path: mesh {ep['mesh']}, {ep['parallel_mode']}; {ep['dtype']}; "
                         f"remat {ep['remat_policy']}; fused_qkv {ep['fused_qkv']}")
        if r.get("entropy_floor_nats") is not None:
            lines.append(f"- analytic floor: {r['entropy_floor_nats']:.5g} nats; gap: {r['gap_nats']:.4g} nats")
        if r.get("bits_per_byte") is not None:
            lines.append(f"- bits/byte: {r['bits_per_byte']:.4g}")
        if curve:
            lines.append(f"- eval curve ({len(curve)} points, first {curve[0]:.4g} → best "
                         f"{(max if 'acc' in metric else min)(curve):.4g}): `{_spark(curve)}`")
        sections.append("\n".join(lines))

    doc = [
        "# Convergence evidence",
        "",
        "Real learning curves per model family, trained in-image with zero egress",
        "(VERDICT round-1 item 4). Data sources and the analytic-loss-target",
        "methodology live in `perceiver_io_tpu/data/{vision,text}/synthetic.py`;",
        "rerun any curve with `python -m perceiver_io_tpu.scripts.convergence",
        "--task <name>` and regenerate this file with `--render`. Add",
        "`--supervise` for the 8-virtual-device production tasks",
        "(`clm_markov_sharded`, `clm_markov_5m`): XLA:CPU's multi-device",
        "rendezvous can wedge probabilistically at launch on constrained hosts,",
        "and the wrapper kills a silent child and relaunches, up to 3 attempts.",
        "",
        "The `clm_markov` run is the strongest correctness statement: its corpus",
        "has an analytically computed conditional entropy, so the validation CE",
        "target is exact — converging to it proves model, loss, optimizer, data",
        "pipeline and eval loop end-to-end with no dataset noise excuse.",
        "",
        *sections,
        "",
    ]
    with open(md_path, "w") as f:
        f.write("\n".join(doc))
    print(f"wrote {md_path}")


def _supervise(argv) -> int:
    """Relaunch-until-progress wrapper for the 8-virtual-device production
    tasks: XLA:CPU's multi-device collective rendezvous can deadlock
    PROBABILISTICALLY at launch on constrained hosts (observed 3/3 on the
    7.2M clm_markov_5m long run while 12-step probes and a direct loop ran
    clean — an unisolated thread-scheduling race, NOTES.md round 5). A wedged
    launch emits NOTHING and burns no CPU, so 'no output for the stall window'
    (1200 s default; env override PERCEIVER_IO_TPU_SUPERVISE_STALL_S) is a
    reliable wedge signal; the child is killed and relaunched, up to 3
    attempts. Fast non-wedge failures (child exits on its own) are returned
    as-is, not retried."""
    import subprocess
    import sys as _sys
    import time as _time

    child_argv = [a for a in argv if a != "--supervise"]
    cmd = [_sys.executable, "-u", "-m", "perceiver_io_tpu.scripts.convergence", *child_argv]
    for attempt in (1, 2, 3):
        # binary pipe: a nonblocking TEXT stream raises TypeError when no
        # data is buffered (codecs can't concat the raw layer's None)
        proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        os.set_blocking(proc.stdout.fileno(), False)

        def _drain():
            chunk = proc.stdout.read()
            if chunk:
                print(chunk.decode(errors="replace"), end="", flush=True)
                return True
            return False

        last_output = _time.time()
        # first eval can legitimately take ~10 min on this host; env override
        # exists for the self-test (tests/test_cli_trainer.py)
        stall_s = float(os.environ.get("PERCEIVER_IO_TPU_SUPERVISE_STALL_S", "1200"))
        wedged = False
        while True:
            if _drain():
                last_output = _time.time()
            if proc.poll() is not None:
                _drain()
                break
            if _time.time() - last_output > stall_s:
                print(f"[supervise] no output for {stall_s:.0f}s — killing wedged attempt {attempt}",
                      flush=True)
                proc.kill()
                proc.wait()
                _drain()  # flush whatever the child had buffered before it wedged
                wedged = True
                break
            _time.sleep(2.0)
        if not wedged:
            return proc.returncode
    print("[supervise] 3 attempts all wedged", flush=True)
    return 1


def main(argv=None):
    # allow_abbrev=False: _supervise forwards argv minus the LITERAL
    # "--supervise"; an abbreviated form (--su) surviving into the child
    # would recurse the wrapper indefinitely
    ap = argparse.ArgumentParser(description=__doc__, allow_abbrev=False)
    ap.add_argument("--task", default="all", choices=[*TASKS, "all"])
    ap.add_argument("--steps", type=int, default=0, help="0 = per-task default")
    ap.add_argument("--out", default="convergence")
    ap.add_argument("--render", action="store_true", help="regenerate CONVERGENCE.md from recorded results")
    ap.add_argument("--supervise", action="store_true",
                    help="relaunch-until-progress wrapper for the 8-device production tasks "
                         "(XLA:CPU launch-race mitigation; see _supervise)")
    args = ap.parse_args(argv)

    if args.supervise:
        import sys as _sys

        raise SystemExit(_supervise(argv if argv is not None else _sys.argv[1:]))

    # scratch out dirs keep their rendered markdown beside them; only the
    # default artifact dir regenerates the repo-root CONVERGENCE.md
    md_path = "CONVERGENCE.md" if args.out == "convergence" else os.path.join(args.out, "CONVERGENCE.md")
    if args.render:
        render(args.out, md_path)
        return

    os.makedirs(args.out, exist_ok=True)
    names = list(TASKS) if args.task == "all" else [args.task]
    for prod_task in ("clm_markov_sharded", "clm_markov_5m"):
        if prod_task in names and jax.device_count() != 8:
            msg = (f"{prod_task} needs exactly 8 devices for its data(2) x fsdp(4) "
                   f"mesh (have {jax.device_count()}); run with JAX_PLATFORMS=cpu "
                   "XLA_FLAGS=--xla_force_host_platform_device_count=8")
            if args.task == "all":
                names.remove(prod_task)
                print(f"skipping {prod_task}: {msg}")
            else:
                raise SystemExit(msg)
    for name in names:
        result = TASKS[name](args.steps)
        path = os.path.join(args.out, f"{name}.json")
        with open(path, "w") as f:
            json.dump(result, f, indent=1)
        print(json.dumps({k: v for k, v in result.items() if k != "history"}))
        render(args.out, md_path)


if __name__ == "__main__":
    main()
