"""Model-level comparison on the chip, at the configuration's own widths: the served
path's logits (prompt prefilled in chunks through the paged cache, then decode steps
through the two kernels) against the plain float32 reference, position by position.

    python3 -m benchmark.families.falcon_h1.logits_check [--seed N] [--prompts 255,256,257,1024]

Not part of a benchmark run (the cell's ``correct`` compares what the timed path served);
this is the guide's logits comparison, for the builder: it prints, per prompt length, the
widest gap between the program's and the reference's logits over the prompt's last
position and ``--new`` decoded positions, beside the spread of the reference's logits
there. The weights are the cell's (made from the seed, in the served type); the pool is a
small one (2 slots), since only one request is in it at a time."""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", default="serve-falcon-h1-chat")
    parser.add_argument("--seed", type=int, default=3200000101)
    parser.add_argument("--prompts", default="255,256,257,1024")
    parser.add_argument("--new", type=int, default=8)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.families import falcon_h1 as family
    from benchmark.families.falcon_h1 import reference
    from benchmark.harness import device, manifest

    cell = manifest.resolve_cell(args.workload)
    device.enable_caches()
    info = device.describe_devices(cell["chips"], args.rehearse)
    if args.rehearse:
        cell = manifest.rehearsal_cell(cell)
    config, engine = cell["config"], cell["settings"]["engine"]
    sizes, page, chunk = config["sizes"], engine["kv_page_size"], engine["prefill_chunk_tokens"]
    dtype = jnp.dtype(config["compute_dtype"])
    weights = family.make_weights(sizes, args.seed, dtype)
    model = family.build_model(config, deterministic=True)
    params = family.to_program_params(weights)
    pages_per_slot = -(-sizes["serving_context_tokens"] // page)
    kind = type(model)
    prefill = jax.jit(lambda p, c, ids, off, n, reset, table: model.apply(
        p, ids, off, n, reset, 1, table, c, method=kind.prefill_chunk_paged), donate_argnums=(1,))
    first = jax.jit(lambda p, c: model.apply(p, c.last_hidden[1:2], method=kind._head))
    decode = jax.jit(lambda p, c, ids: model.apply(p, ids, c, method=kind.decode_step_paged), donate_argnums=(1,))
    rng = np.random.default_rng([args.seed, 7])
    rows = []
    for n in [int(x) for x in args.prompts.split(",")]:
        n = min(n, sizes["serving_context_tokens"] - args.new)
        tokens = rng.integers(1, sizes["vocab_size"], size=n + args.new).astype(np.int32)
        cache = model.init_paged_cache(2, pages_per_slot + 1, page, dtype)
        table = jnp.arange(1, pages_per_slot + 1, dtype=jnp.int32)
        for off in range(0, n, chunk):
            count = min(chunk, n - off)
            ids = np.zeros((chunk,), np.int32)
            ids[:count] = tokens[off: off + count]
            cache = prefill(params, cache, jnp.asarray(ids), off, count, off == 0, table)
        got = [np.asarray(first(params, cache)[0], np.float32)]
        cache = cache.install_slot(1, table, n)
        for t in range(n, n + args.new - 1):
            step = np.zeros((2, 1), np.int32)
            step[1, 0] = tokens[t]
            logits, cache = decode(params, cache, jnp.asarray(step))
            got.append(np.asarray(logits[1, 0], np.float32))
        want = np.asarray(reference.score_served(weights, sizes, tokens[:n], tokens[n:]), np.float32)
        gap = np.abs(np.stack(got) - want)
        row = {"prompt_tokens": n, "positions": len(got), "chunks": -(-n // chunk),
               "logit_gap_max": float(gap.max()), "logit_gap_mean": float(gap.mean()),
               "reference_logit_std": float(want.std()), "reference_logit_absmax": float(np.abs(want).max()),
               "argmax_agree": int((np.stack(got).argmax(-1) == want.argmax(-1)).sum())}
        rows.append(row)
        print(json.dumps({"logits_check": row}), flush=True)
    print(json.dumps({"workload": args.workload, "device": info, "seed": args.seed, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
