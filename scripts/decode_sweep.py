"""Decode-speculation sweep (VERDICT r4 item 3: measure or revert).

On the real chip, measures end-to-end new-tokens/s AND chunk-phase acceptance
rate for the full decode-stack grid:

  decode_chunk in {1, 4, 8, 16}  x  fused kernel {on, off}
  + the draft-seeding A/B at the default chunk (seed_drafts_from_prompt on/off)

on the serving shape bench.py's decode task uses (batch 8, 2048-token prompt,
512 new tokens, 30M-class config — shared factory ``decode_bench_config``).
decode_chunk=16 exceeds the fused kernel's n_q <= 8 bound, so its "kernel on"
cell records the automatic XLA fallback (the gate's behavior, worth pinning).

Writes DECODE_SWEEP.json at the repo root. Run by hand on the chip (one
process; the chip tool runs it like any other command). Every committed token is greedy-exact regardless of configuration (float64
equivalence tests in tests/test_chunked_decode.py); this sweep only decides
which speculation knobs PAY — any cell that doesn't beats its complexity out
of the default path next round.
"""

from __future__ import annotations

import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import jax
import jax.numpy as jnp


def main():
    from bench import decode_bench_config
    from perceiver_io_tpu.generation.generate import GenerationConfig, generate
    from perceiver_io_tpu.models.core.perceiver_ar import CausalSequenceModel

    if jax.default_backend() != "tpu" and "--allow-cpu" not in sys.argv:
        sys.exit("decode_sweep needs the TPU backend (pass --allow-cpu to force, e.g. for smoke tests)")

    config = decode_bench_config()
    model = CausalSequenceModel(config=config, dtype=jnp.bfloat16)
    b, prompt_len, new_tokens = 8, 2048, 512
    if "--smoke" in sys.argv:  # tiny shapes for plumbing tests off-chip
        b, prompt_len, new_tokens = 2, 64, 16
        import dataclasses

        config = dataclasses.replace(config, max_seq_len=128, max_latents=32,
                                     num_channels=64, num_heads=2, num_self_attention_layers=2)
        model = CausalSequenceModel(config=config)
    rng = jax.random.PRNGKey(0)
    x = jax.random.randint(rng, (b, prompt_len), 0, config.vocab_size)
    params = jax.jit(model.init, static_argnames="prefix_len")(
        rng, x, prefix_len=prompt_len - config.max_latents
    )

    from bench import measure_generate  # the one shared timing harness (bench.py)

    def measure(chunk: int, kernel: bool, seed: bool) -> dict:
        gcfg = GenerationConfig(max_new_tokens=new_tokens, decode_chunk=chunk,
                                seed_drafts_from_prompt=seed)
        tps, stats = measure_generate(model, params, x, new_tokens, gcfg, rng, kernel=kernel)
        rec = {"decode_chunk": chunk, "kernel": kernel, "seed_drafts_from_prompt": seed,
               "new_tokens_per_s": round(tps, 1)}
        if chunk > 1:
            rec["accept_rate"] = round(
                float(stats["chunked_tokens"]) / max(float(stats["chunk_iterations"]), 1.0), 3
            )
            rec["tail_steps"] = int(stats["tail_steps"])
        return rec

    grid = [(1, True, True), (1, False, True)]
    for chunk in (4, 8, 16):
        grid += [(chunk, True, True), (chunk, False, True)]
    grid.append((8, True, False))  # the draft-seeding A/B arm

    records = []
    for chunk, kernel, seed in grid:
        t0 = time.time()
        rec = measure(chunk, kernel, seed)
        rec["measure_seconds"] = round(time.time() - t0, 1)
        records.append(rec)
        print(json.dumps(rec), flush=True)

    base = next(r for r in records if r["decode_chunk"] == 1 and not r["kernel"])
    out_path = os.path.join(_REPO, "DECODE_SWEEP.json")
    tmp = out_path + ".tmp"  # atomic: a kill mid-write must not leave a
    with open(tmp, "w") as f:  # corrupt artifact that gates the watcher forever
        json.dump({
            "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "backend": jax.default_backend(),
            "shape": {"batch": b, "prompt_len": prompt_len, "new_tokens": new_tokens},
            "baseline_single_token_no_kernel_tps": base["new_tokens_per_s"],
            "records": records,
        }, f, indent=1)
        f.write("\n")
    os.replace(tmp, out_path)
    print(f"wrote {out_path}")


if __name__ == "__main__":
    main()
