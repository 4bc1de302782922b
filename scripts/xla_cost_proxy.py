"""XLA-cost-model counts for the bench tasks, taken without an accelerator.

Counts what can be counted on the CPU: the compiled per-step FLOPs and
bytes-accessed of every bench task (XLA cost analysis of the lowered program;
the HLO arithmetic is backend-invariant up to fusion details, so the CPU
backend's count proxies the TPU program), cross-checked against the analytic
FLOPs model bench.py derives MFU from, plus the throughput each task would
need to sustain the BASELINE.json 40%-MFU north star on one v5e chip
(197 TFLOP/s bf16 peak — training/flops.py TPU_PEAK_FLOPS). These are counts
and targets, never measurements: a rate comes only from a chip run.

Everything is lowered from ABSTRACT inputs (jax.eval_shape /
ShapeDtypeStruct): no parameters are materialized, nothing executes, so the
455M flagship costs compile time only.

Usage:  JAX_PLATFORMS=cpu python scripts/xla_cost_proxy.py [--out COUNTS.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import jax
import jax.numpy as jnp

jax.config.update("jax_platforms", "cpu")  # counts are taken on the CPU backend

V5E_PEAK_FLOPS = 197e12
TARGET_MFU = 0.40


def _cost(lowered) -> dict:
    cost = lowered.compile().cost_analysis()
    return {"flops": float(cost.get("flops", float("nan"))),
            "bytes_accessed": float(cost.get("bytes accessed", float("nan")))}


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _train_task(config, batch_size):
    from perceiver_io_tpu.models.core.perceiver_ar import CausalSequenceModel
    from perceiver_io_tpu.training.flops import PerceiverARFlops
    from perceiver_io_tpu.training.trainer import TrainState, build_optimizer, make_causal_lm_train_step

    model = CausalSequenceModel(config=config, deterministic=False, dtype=jnp.bfloat16)
    tx = build_optimizer(1e-3, max_grad_norm=1.0)
    prefix_len = config.max_seq_len - config.max_latents
    x = _sds((batch_size, config.max_seq_len), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(0)},
                           jnp.zeros((batch_size, config.max_seq_len), jnp.int32), prefix_len=prefix_len)
    )
    state = jax.eval_shape(lambda p: TrainState.create(p, tx), params)
    step = make_causal_lm_train_step(model, tx, max_latents=config.max_latents)
    batch = {"input_ids": x, "labels": x}
    lowered = jax.jit(step, donate_argnums=(0,)).lower(state, batch)
    cost = _cost(lowered)

    fm = PerceiverARFlops(config=config, seq_len=config.max_seq_len,
                          prefix_dropout=config.cross_attention_dropout)
    analytic = fm.train_flops_per_step(batch_size)
    tokens = fm.tokens_per_step(batch_size)
    return {
        **cost,
        "tokens_per_step": tokens,
        "analytic_flops_per_step": float(analytic),
        "xla_vs_analytic": round(cost["flops"] / analytic, 4),
        "implied_latent_tokens_per_s_at_40pct_mfu": round(
            TARGET_MFU * V5E_PEAK_FLOPS / cost["flops"] * tokens, 1
        ),
    }


def task_clm():
    from perceiver_io_tpu.models.core.config import flagship_455m_config

    return _train_task(flagship_455m_config(), batch_size=16)


def task_clm_8k():
    from bench import clm_8k_bench_config

    # scan_unroll: unrolled for COUNTING, not speed — XLA cost_analysis counts
    # a rolled scan body once, silently dividing the SA-stack FLOPs by
    # num_layers (pinned by tests/test_cost_proxy.py)
    return _train_task(clm_8k_bench_config(scan_unroll=8), batch_size=4)


def task_optical_flow():
    from perceiver_io_tpu.models.vision.optical_flow import OpticalFlow, official_41m_config

    cfg = official_41m_config(scan_unroll=24)  # counting, not speed — see task_clm_8k note
    model = OpticalFlow(config=cfg, dtype=jnp.bfloat16)
    x = _sds((6, 2, 27, 368, 496), jnp.bfloat16)  # all six Sintel patches, one frame pair
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 2, 27, 368, 496), jnp.bfloat16))
    )
    lowered = jax.jit(lambda p, xx: model.apply(p, xx)).lower(params, x)
    cost = _cost(lowered)
    return {
        **cost,
        "frame_pairs_per_forward": 1,
        "implied_frame_pairs_per_s_at_40pct_mfu": round(TARGET_MFU * V5E_PEAK_FLOPS / cost["flops"], 3),
    }


def task_decode():
    from bench import decode_bench_config
    from perceiver_io_tpu.models.core.perceiver_ar import CausalSequenceModel

    config = decode_bench_config(scan_unroll=8)  # counting, not speed — see task_clm_8k note
    model = CausalSequenceModel(config=config, dtype=jnp.bfloat16)
    b = 8
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((b, 2048), jnp.int32),
                           prefix_len=2048 - config.max_latents)
    )
    cache = jax.eval_shape(lambda: model.init_cache(batch_size=b, dtype=jnp.bfloat16))

    out = {}
    for name, n in (("single_token_step", 1), ("chunk8_block", 8)):
        tok = _sds((b, n), jnp.int32)
        lowered = jax.jit(
            lambda p, t, c: model.apply(p, t, c, method=CausalSequenceModel.decode_block)
        ).lower(params, tok, cache)
        cost = _cost(lowered)
        out[name] = {
            **cost,
            "new_tokens": b * n,
            "implied_new_tokens_per_s_at_40pct_mfu": round(
                TARGET_MFU * V5E_PEAK_FLOPS / cost["flops"] * b * n, 1
            ),
        }
    # the FLOPs ratio a perfectly-accepted 8-chunk saves per token vs 8 single steps
    out["chunk8_vs_8_singles_flops"] = round(
        out["chunk8_block"]["flops"] / (8 * out["single_token_step"]["flops"]), 4
    )
    return out


TASKS = {"clm": task_clm, "clm_8k": task_clm_8k,
         "optical_flow": task_optical_flow, "decode": task_decode}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=os.path.join(_REPO, "COST_COUNTS.json"))
    args = ap.parse_args(argv)

    results = {}
    for name, fn in TASKS.items():
        t0 = time.time()
        results[name] = fn()
        results[name]["compile_seconds"] = round(time.time() - t0, 1)
        print(f"[proxy] {name}: {json.dumps(results[name])}", flush=True)

    artifact = {
        "method": (
            "XLA cost_analysis of each bench task's compiled program, lowered "
            "from abstract inputs on the CPU backend (HLO arithmetic is backend-"
            "invariant up to fusion details); implied throughputs assume one v5e chip "
            "(197 TFLOP/s bf16 peak) at the BASELINE.json 40%-MFU north star. Counts "
            "and targets, never measurements."
        ),
        "peak_flops_assumed": V5E_PEAK_FLOPS,
        "target_mfu": TARGET_MFU,
        "generated_by": "scripts/xla_cost_proxy.py",
        "tasks": results,
    }
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=1)
        f.write("\n")
    print(f"[proxy] wrote {args.out}")


if __name__ == "__main__":
    main()
