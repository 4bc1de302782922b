"""Benchmark tasks: Perceiver AR training and decode, Perceiver IO optical flow,
on one TPU chip.

    python bench.py                  the headline task (``clm``), in this process
    python bench.py --task <name>    one task, in this process

One process, no children: a chip belongs to one process at a time. Without a
TPU the script fails — a CPU run yields no rate. Each task prints one JSON
record that names the device it ran on:

  {"metric": ..., "value": ..., "unit": ..., "vs_baseline": ..., "device": {...}}

The headline is the reference's published flagship — the 455M C4 Perceiver AR
(examples/training/clm/train_fsdp.sh: 20 layers x 1280, heads 10, seq 1024,
latents 512, xlnet 32k vocab, bf16, remat) — as a jitted train step.
vs_baseline is measured MFU against the BASELINE.json north star of 40% MFU
(the reference publishes no throughput numbers to compare against directly).

Tasks:
  ``clm``           the 455M flagship train step (the headline)
  ``clm_30m``       the 30.7M WikiText CLM config (seq 4096)
  ``clm_8k``        long-context: the Perceiver AR paper's 8k regime
                    (seq 8192, 1024 latents) trained on ONE chip via
                    latent compression + dots-saveable remat
  ``optical_flow``  Perceiver IO optical-flow inference at the official
                    deepmind/optical-flow-perceiver dims (41M params) on
                    Sintel-resolution 436x1024 frame pairs — the second
                    BASELINE.json north star. vs_baseline measures
                    against a fixed A100-equivalent per-chip target
                    derived in ``_OF_TARGET_FPS_PER_CHIP`` below.
  ``decode``        cached autoregressive decode (batch 8, 2048-token
                    prompt, 512 new tokens) through ``generate()`` with
                    the full decode stack (chunked greedy decode via
                    the multi-query fused kernel). vs_baseline is the
                    CHUNKING win over the single-token loop; the
                    fused-kernel on/off ratio is the record's
                    ``kernel_speedup`` field.

These are the tasks the first benchmark PR (ROADMAP queue 1 item 1) turns into
cells; ``chip_smoke.py`` is the quick proof that the main path runs at all.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp


def _bench_clm_config(config, batch_size, n_steps, metric):
    from perceiver_io_tpu.models.core.perceiver_ar import CausalSequenceModel
    from perceiver_io_tpu.training.flops import PerceiverARFlops, detect_peak_flops, mfu
    from perceiver_io_tpu.training.trainer import TrainState, build_optimizer, make_causal_lm_train_step
    model = CausalSequenceModel(config=config, deterministic=False, dtype=jnp.bfloat16)

    rng = jax.random.PRNGKey(0)
    x = jax.random.randint(rng, (batch_size, config.max_seq_len), 0, config.vocab_size)
    batch = {"input_ids": x, "labels": jnp.roll(x, -1, axis=1)}

    prefix_len = config.max_seq_len - config.max_latents
    params = jax.jit(model.init, static_argnames="prefix_len")(
        {"params": rng, "dropout": rng}, x, prefix_len=prefix_len
    )
    tx = build_optimizer(1e-3, max_grad_norm=1.0)
    state = TrainState.create(params, tx)
    step = jax.jit(make_causal_lm_train_step(model, tx, max_latents=config.max_latents), donate_argnums=(0,))

    for _ in range(2):  # warmup / compile
        state, metrics = step(state, batch)
    jax.block_until_ready(metrics["loss"])

    dt = float("inf")
    for _ in range(3):  # best of 3 windows
        t0 = time.perf_counter()
        for _ in range(n_steps):
            state, metrics = step(state, batch)
        jax.block_until_ready(metrics["loss"])  # steps are state-dependent: this waits for all of them
        dt = min(dt, time.perf_counter() - t0)

    flops_model = PerceiverARFlops(config=config, seq_len=config.max_seq_len, prefix_dropout=config.cross_attention_dropout)
    tokens_per_sec = flops_model.tokens_per_step(batch_size) * n_steps / dt
    measured_mfu = mfu(tokens_per_sec, flops_model, batch_size, detect_peak_flops())

    return {
        "metric": metric,
        "value": round(tokens_per_sec, 1),
        "unit": "latent_tokens/s",
        "vs_baseline": round(measured_mfu / 0.40, 4),
    }


def bench_clm_455m():
    """The reference's published flagship (455M C4, train_fsdp.sh) on one chip."""
    from perceiver_io_tpu.models.core.config import flagship_455m_config

    return _bench_clm_config(flagship_455m_config(), batch_size=16, n_steps=5,
                             metric="perceiver_ar_clm_455m_train_tokens_per_sec_per_chip")


def bench_clm_30m():
    from perceiver_io_tpu.models.core.config import CausalSequenceModelConfig

    config = CausalSequenceModelConfig(
        vocab_size=262, max_seq_len=4096, max_latents=512, num_channels=512,
        num_heads=8, num_self_attention_layers=8, cross_attention_dropout=0.5,
        # single-GEMM qkv: +15% on this config's small per-layer GEMMs (scripts/
        # ablate.py on v5e: 142.2k -> 163.8k tok/s; no effect on the 455M config
        # whose GEMMs already saturate the MXU — see NOTES.md ablation table)
        fused_qkv=True,
    )
    return _bench_clm_config(config, batch_size=8, n_steps=10,
                             metric="perceiver_ar_clm_30m_train_tokens_per_sec_per_chip")


def clm_8k_bench_config(scan_unroll: int = 1):
    """The Perceiver AR paper's 8k long-context regime on the 30M-class
    architecture. Shared by the bench task and scripts/xla_cost_proxy.py so the
    measured workload and the FLOPs-accounting workload cannot drift."""
    from perceiver_io_tpu.models.core.config import CausalSequenceModelConfig

    return CausalSequenceModelConfig(
        vocab_size=262, max_seq_len=8192, max_latents=1024, num_channels=512,
        num_heads=8, num_self_attention_layers=8, cross_attention_dropout=0.5,
        activation_checkpointing=True, remat_policy="dots_with_no_batch_dims_saveable",
        fused_qkv=True, scan_unroll=scan_unroll,
    )


def decode_bench_config(scan_unroll: int = 1):
    """The decode-serving 30M-class shape (NOTES.md); shared with the proxy."""
    from perceiver_io_tpu.models.core.config import CausalSequenceModelConfig

    return CausalSequenceModelConfig(
        vocab_size=262, max_seq_len=4096, max_latents=512, num_channels=512,
        num_heads=8, num_self_attention_layers=8, scan_unroll=scan_unroll,
    )


def bench_clm_8k():
    """Long-context single-chip training: the Perceiver AR paper's 8k regime
    (seq 8192, 1024 latents) on the 30M-class architecture — latent compression
    is what keeps 8k-context training feasible on ONE chip (NOTES.md measured
    139k latent tokens/s / 15.6% MFU); contexts beyond one chip's HBM use ring
    attention (sequence_parallel_axis) instead."""
    return _bench_clm_config(clm_8k_bench_config(), batch_size=4, n_steps=5,
                             metric="perceiver_ar_clm_8k_longcontext_train_tokens_per_sec_per_chip")


# Fixed external target for the optical-flow task (BASELINE.json north star:
# "Perceiver IO optical-flow inference matching A100 frames/sec on v5e-8").
# The compiled forward costs 11.449 TFLOP per Sintel frame pair (XLA
# cost_analysis of the 41M model on all six 368x496 patches with the 24-layer
# SA scan UNROLLED — scripts/xla_cost_proxy.py; the round-2 figure of 4.659
# TFLOP came from a rolled scan, whose body cost_analysis counts only once,
# so it understated the workload and overstated the A100 target). An A100
# (312 TFLOP/s dense bf16 peak) running that workload at the suite-wide 40%-MFU
# north star sustains 312e12 * 0.40 / 11.449e12 = 10.9 frame-pairs/s; matching
# it across a v5e-8 slice means each chip must deliver 10.9 / 8 = 1.36
# frame-pairs/s. vs_baseline = measured fps / this target.
_OF_FLOPS_PER_FRAME_PAIR = 11.449e12
_OF_TARGET_FPS_PER_CHIP = 312e12 * 0.40 / _OF_FLOPS_PER_FRAME_PAIR / 8


def bench_optical_flow():
    from perceiver_io_tpu.data.vision.optical_flow import OpticalFlowProcessor
    from perceiver_io_tpu.models.vision.optical_flow import OpticalFlow, official_41m_config

    # official deepmind/optical-flow-perceiver dims (reference
    # vision/optical_flow/huggingface.py; 41M params)
    cfg = official_41m_config()
    model = OpticalFlow(config=cfg, dtype=jnp.bfloat16)

    rng = jax.random.PRNGKey(0)
    proc = OpticalFlowProcessor(patch_size=(368, 496))
    n_patches = len(proc.compute_patch_grid_indices((436, 1024)))  # Sintel-resolution frame pair
    x = jax.random.normal(rng, (n_patches, 2, 27, 368, 496), jnp.bfloat16)
    params = jax.jit(model.init)(rng, x[:1])
    apply = jax.jit(lambda p, xx: model.apply(p, xx))
    jax.block_until_ready(apply(params, x))  # warmup / compile

    best = float("inf")
    n_pairs = 3
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n_pairs):
            o = apply(params, x)
        jax.block_until_ready(o)
        best = min(best, time.perf_counter() - t0)

    fps = n_pairs / best
    return {
        "metric": "perceiver_io_optical_flow_sintel_frames_per_sec_per_chip",
        "value": round(fps, 3),
        "unit": "frame_pairs/s",
        "vs_baseline": round(fps / _OF_TARGET_FPS_PER_CHIP, 4),  # vs the fixed A100-derived target above
    }


def measure_generate(model, params, x, new_tokens, gcfg, rng, kernel: bool = True):
    """The ONE decode timing harness, shared by ``bench_decode`` and
    scripts/decode_sweep.py so the two cannot measure differently: kernel
    toggle via the kill-switch env var + ``jax.clear_caches()`` (kernel
    selection is a trace-time decision), a warmup call that also yields the
    speculation stats (greedy is deterministic, so stats are identical every
    run), then best-of-3 timed windows ending in ``block_until_ready``.
    Returns (new_tokens_per_s, stats); the caller's env-var state is restored
    on exit."""
    from perceiver_io_tpu.generation.generate import generate

    b = x.shape[0]
    prior = os.environ.get("PERCEIVER_IO_TPU_DISABLE_DECODE_KERNEL")
    os.environ["PERCEIVER_IO_TPU_DISABLE_DECODE_KERNEL"] = "" if kernel else "1"
    jax.clear_caches()
    try:
        out, stats = generate(model, params, x, num_latents=1, rng=rng, config=gcfg, return_stats=True)
        jax.block_until_ready(out)  # warmup / compile
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            out = generate(model, params, x, num_latents=1, rng=rng, config=gcfg)
            jax.block_until_ready(out)
            best = min(best, time.perf_counter() - t0)
    finally:
        if prior is None:
            os.environ.pop("PERCEIVER_IO_TPU_DISABLE_DECODE_KERNEL", None)
        else:
            os.environ["PERCEIVER_IO_TPU_DISABLE_DECODE_KERNEL"] = prior
        jax.clear_caches()
    return b * new_tokens / best, stats


def bench_decode():
    """Cached autoregressive decode through the public ``generate()`` loop:
    batch 8, 2048-token prompt, 512 greedy tokens on the 30M-class config
    (seq 4096 window, the decode-serving shape from NOTES.md). The value is
    end-to-end new-tokens/s (prefill included) with the full decode stack on:
    chunked greedy decode (decode_chunk=8, Jacobi self-speculation through the
    multi-query fused decode kernel). vs_baseline is the CHUNKING win — the
    ratio over the same loop decoding one token per iteration (the round-1
    methodology) — since per-iteration overhead, not FLOPs, dominates decode on
    this platform (NOTES.md). The record also carries the single-token rate and
    the kernel-disabled chunked rate (the kernel's contribution)."""
    from perceiver_io_tpu.generation.generate import GenerationConfig
    from perceiver_io_tpu.models.core.perceiver_ar import CausalSequenceModel

    config = decode_bench_config()
    model = CausalSequenceModel(config=config, dtype=jnp.bfloat16)
    b, prompt_len, new_tokens = 8, 2048, 512
    rng = jax.random.PRNGKey(0)
    x = jax.random.randint(rng, (b, prompt_len), 0, config.vocab_size)
    params = jax.jit(model.init, static_argnames="prefix_len")(rng, x, prefix_len=prompt_len - config.max_latents)

    chunked = GenerationConfig(max_new_tokens=new_tokens, decode_chunk=8)
    single = GenerationConfig(max_new_tokens=new_tokens)

    if os.environ.get("PERCEIVER_IO_TPU_DISABLE_DECODE_KERNEL", "") not in ("", "0", "false"):
        sys.exit("unset PERCEIVER_IO_TPU_DISABLE_DECODE_KERNEL before benchmarking: "
                 "the fused measurement would silently run with the kernel off")
    chunked_tps, chunk_stats = measure_generate(model, params, x, new_tokens, chunked, rng, kernel=True)
    single_tps, _ = measure_generate(model, params, x, new_tokens, single, rng, kernel=True)
    xla_tps, _ = measure_generate(model, params, x, new_tokens, chunked, rng, kernel=False)

    return {
        "metric": "perceiver_ar_decode_new_tokens_per_sec_per_chip",
        "value": round(chunked_tps, 1),
        "unit": "tokens/s",
        "vs_baseline": round(chunked_tps / single_tps, 4),
        "single_token_tps": round(single_tps, 1),
        "kernel_off_chunked_tps": round(xla_tps, 1),
        "kernel_speedup": round(chunked_tps / xla_tps, 4),
        # speculation quality on this (untrained) model: chunk-phase tokens per
        # multi-query iteration, in [1, decode_chunk]
        "accept_rate": round(
            chunk_stats["chunked_tokens"] / max(chunk_stats["chunk_iterations"], 1), 3
        ),
        "tail_steps": chunk_stats["tail_steps"],
    }


BENCHES = {"clm": bench_clm_455m, "clm_30m": bench_clm_30m, "clm_8k": bench_clm_8k,
           "optical_flow": bench_optical_flow, "decode": bench_decode}

def main(argv=None) -> int:
    import argparse

    from perceiver_io_tpu.compile_cache import enable_compile_cache

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--task", choices=sorted(BENCHES), default="clm")
    args = parser.parse_args(argv)
    enable_compile_cache()
    first = jax.devices()[0]
    device = {"platform": first.platform, "kind": first.device_kind, "count": len(jax.devices())}
    if first.platform != "tpu":
        print(json.dumps({"error": "bench.py measures on a TPU only; JAX found none", "device": device}))
        return 1
    print(json.dumps({**BENCHES[args.task](), "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
