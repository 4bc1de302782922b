"""The plain reference against the program's model, at a toy size in float32 on the CPU:
the training pass row by row, and what the serving engine emits (short prompts through
prefill + install, long ones through chunks and the finish, a shared prefix, and requests
that slide the window)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families.perceiver_ar import program
from benchmark.families.perceiver_ar import reference as ref
from benchmark.families.perceiver_ar import weights as ref_weights
from benchmark.harness import check
from benchmark.tests.conftest import TOY


def _setup(abs_pos_emb: bool, seed: int = 5):
    sizes = dict(TOY, abs_pos_emb=abs_pos_emb)
    config = {"sizes": sizes, "execution": {}, "compute_dtype": "float32"}
    weights = ref_weights.make_weights(sizes, seed)
    model = program.build_model(config, deterministic=True)
    params = program.to_program_params(weights)
    program.check_param_tree(model, params)
    return sizes, weights, model, params


@pytest.mark.parametrize("abs_pos_emb", [False, True])
def test_training_pass_agrees(abs_pos_emb):
    sizes, weights, model, params = _setup(abs_pos_emb, seed=2**31 + 7)
    with jax.default_matmul_precision("highest"):
        rows = np.random.default_rng(0).integers(0, sizes["vocab_size"], size=(2, 64)).astype(np.int32)
        got = model.apply(params, jnp.asarray(rows), prefix_len=48)
        want = jnp.stack([ref.train_logits(weights, sizes, jnp.asarray(r)) for r in rows])
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * float(jnp.max(jnp.abs(want)))


def test_weights_round_trip_and_count():
    sizes, weights, _, params = _setup(True)
    back = program.from_program_params(params)
    assert jax.tree.structure(back) == jax.tree.structure(weights)
    assert all((a == b).all() for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(weights)))
    assert ref_weights.count_parameters(sizes) == sum(x.size for x in jax.tree.leaves(weights))


@pytest.mark.parametrize("abs_pos_emb", [False, True])
def test_served_tokens_are_the_references_greedy_choice(abs_pos_emb):
    from perceiver_io_tpu.serving import ServingEngine

    sizes, weights, model, params = _setup(abs_pos_emb)
    engine = ServingEngine(model, params, num_slots=3, kv_page_size=8, prefill_chunk_tokens=8, prefix_cache=True)
    rng = np.random.default_rng(1)
    preamble = rng.integers(1, 97, size=32)
    # (prompt length, new tokens): under the 16 latents, at them, over them, full window,
    # and three whose prompt + answer pass the window of 64
    specs = [(5, 6), (12, 30), (16, 10), (17, 8), (40, 12), (60, 20), (64, 9), (48, 30), (50, 5), (7, 45)]
    prompts = []
    for i, (n, _) in enumerate(specs):
        p = rng.integers(1, 97, size=n)
        if i in (4, 5, 7, 8):
            p[:32] = preamble[: min(32, n)]
        prompts.append(p.astype(np.int32))
    handles = [engine.submit(p, max_new_tokens=new) for p, (_, new) in zip(prompts, specs)]
    engine.run_until_drained()
    engine.close()
    with jax.default_matmul_precision("highest"):
        for handle, prompt, (n, new) in zip(handles, prompts, specs):
            tokens = np.asarray(handle.output_ids)
            assert handle.ok and len(tokens) == new
            logits = np.asarray(ref.score_served(weights, sizes, prompt, tokens, pad_to=32))
            deficit = check.token_deficits(logits, tokens)
            assert deficit.max() < 1e-4, (n, new, deficit.max())


def test_lower_precision_moves_the_reference():
    sizes, weights, _, _ = _setup(False)
    tokens = jnp.asarray(np.random.default_rng(3).integers(0, 97, size=64).astype(np.int32))
    exact = ref.train_logits(weights, sizes, tokens)
    gaps = {p: float(jnp.max(jnp.abs(ref.train_logits(weights, sizes, tokens, p) - exact))) for p in ("bfloat16", "int8")}
    assert 0 < gaps["bfloat16"] < gaps["int8"]


def test_worst_leaf_gap_is_relative_to_the_larger_of_leaf_and_median():
    want = {"a": np.array(1.0), "b": np.array([2.0, 1e-9]), "c": np.array(3.0)}
    got = {"a": np.array(1.1), "b": np.array([2.0, 2e-9]), "c": np.array(3.0)}
    gap, leaf = check.worst_leaf_gap(got, want)
    # median leaf norm is 1.5: "a" is off by 0.1 / 1.5; the all-but-zero leaf b[1] does not count
    assert leaf == "a" and gap == pytest.approx(0.1 / 1.5)
