"""The layout a model states for its weights (ISSUE 45; ``serving_api.py`` (g)), on the
CPU at toy sizes. The layout itself is the TPU compiler's (``tests/test_aot_tpu_compile.py``
reads it from the compiled tick); pinned here is the mechanism around it.

* an engine over a model that states a leaf serves the tokens of an engine over the same
  model with the statement removed, greedy and sampled, with ONE tick program each;
* the stated leaves, and nothing else of the tree, are kept with their rows split into
  tiles, at construction and after ``set_params`` (which compiles nothing), and every
  program is handed the model's own matrices back;
* a model that states nothing (Perceiver AR, LFM2, whose convolution layers also hold a
  leaf named ``in_proj``) is handed its tree as it came: the same objects;
* a stated name that is no leaf of the tree, or a leaf whose rows no tile divides, is
  refused at construction.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.generation.generate import GenerationConfig
from perceiver_io_tpu.models.core.falcon_h1 import FalconH1ForCausalLM
from perceiver_io_tpu.serving import ServingEngine
from perceiver_io_tpu.serving.weight_layout import merge_rows, split_rows
from tests import falcon_h1_toy, lfm2_moe_toy
from tests.test_ragged_tick import PS, _make_model

FALCON_ENGINE = dict(num_slots=3, kv_page_size=8, prefill_chunk_tokens=8, num_kv_pages=40)
ENGINES = {"perceiver_ar": dict(num_slots=3, kv_page_size=PS, prefill_chunk_tokens=4, max_prefill_slots=2),
           "lfm2_moe": FALCON_ENGINE}
STATED = ("params/layers_0_in_proj", "params/layers_1_in_proj")
SAMPLING = {"greedy": dict(max_new_tokens=7),
            "sampled": dict(max_new_tokens=7, do_sample=True, temperature=0.8, top_k=20)}


class _Unstated(FalconH1ForCausalLM):
    """Falcon-H1 with the statement removed: the engine's behaviour before ISSUE 45."""

    def serving_traits(self):
        return dataclasses.replace(super().serving_traits(), row_major_leaves=())


class _Misnamed(FalconH1ForCausalLM):
    def serving_traits(self):
        return dataclasses.replace(super().serving_traits(), row_major_leaves=(*STATED, "params/layers_2_in_proj"))


def _as(cls, model):
    return cls(config=model.config, deterministic=model.deterministic, dtype=model.dtype, param_dtype=model.param_dtype)


@pytest.fixture(scope="module")
def falcon():
    return falcon_h1_toy.build()


def _serve(engine, sampling):
    """Five requests of mixed lengths, two of them joining after three ticks."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, falcon_h1_toy.SIZES["vocab_size"], size=n).astype(np.int32) for n in (5, 8, 23, 17, 31)]
    submit = lambda i: engine.submit(prompts[i], config=GenerationConfig(**SAMPLING[sampling]), rng=jax.random.PRNGKey(i))
    handles = [submit(i) for i in range(3)]
    for _ in range(3):
        engine.step()
    handles += [submit(i) for i in (3, 4)]
    engine.run_until_drained(max_steps=400)
    assert all(h.ok for h in handles)
    return [h.result().tolist() for h in handles]


def _split(engine, params):
    """Names of the served leaves that are not the caller's own arrays."""
    return sorted(f"params/{k}" for k, v in engine.params["params"].items() if v is not params["params"][k])


@pytest.mark.parametrize("sampling", sorted(SAMPLING))
def test_a_stated_layout_serves_the_tokens_of_the_unstated_model(falcon, sampling):
    model, params, _ = falcon
    assert model.serving_traits().row_major_leaves == STATED
    stated = ServingEngine(model, params, **FALCON_ENGINE)
    unstated = ServingEngine(_as(_Unstated, model), params, **FALCON_ENGINE)
    assert _serve(stated, sampling) == _serve(unstated, sampling)
    assert stated.decode_compilations == 1 and unstated.decode_compilations == 1
    assert stated.total_compilations == unstated.total_compilations
    # the statement removed, the engine is handed the tree as it came
    assert unstated.params is params


def test_the_stated_leaves_alone_are_kept_in_row_tiles_and_the_tick_sees_the_models_matrices(falcon):
    model, params, _ = falcon
    engine = ServingEngine(model, params, **FALCON_ENGINE)
    assert _split(engine, params) == sorted(STATED)
    hidden, cols = params["params"]["layers_0_in_proj"].shape
    for name in STATED:
        leaf, given = engine.params["params"][name.split("/")[1]], params["params"][name.split("/")[1]]
        assert leaf.shape == (hidden // 8, 8, cols)  # float32: 8 rows a tile
        assert np.array_equal(np.asarray(leaf).reshape(hidden, cols), np.asarray(given))
    # the entry hook of every program undoes it: shapes and values of the tree the model initialised
    seen = engine._dequant_params(engine.params)
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(lambda a, b: a.shape == b.shape and bool((a == b).all()), seen, params))
    # lower_tick() lowers the program the running engine compiles: the tick's argument has the split shape
    arg = engine.lower_tick().in_avals[0][0]["params"]["layers_0_in_proj"]
    assert arg.shape == (hidden // 8, 8, cols)


def test_set_params_lays_the_new_tree_out_the_same_and_compiles_nothing(falcon):
    model, params, _ = falcon
    engine = ServingEngine(model, params, **FALCON_ENGINE)
    before = _serve(engine, "greedy")
    compiled = engine.total_compilations
    fresh = jax.tree_util.tree_map(lambda x: x + 0, params)
    engine.set_params(fresh)
    assert _split(engine, fresh) == sorted(STATED)
    assert _serve(engine, "greedy") == before and engine.total_compilations == compiled


@pytest.mark.parametrize("weight_dtype", ["bf16", "int8"])
def test_the_statement_holds_for_the_leaves_the_weight_transform_left_in_place(falcon, weight_dtype):
    """bf16 casts the leaf and the cast leaf is split (16 rows a tile); int8 replaces a
    matrix by its quantized form, which the tick dequantizes on entry: nothing of it is stated."""
    model, params, _ = falcon
    engine = ServingEngine(model, params, **FALCON_ENGINE, weight_dtype=weight_dtype)
    leaf = engine.params["params"]["layers_0_in_proj"]
    assert (leaf.shape[1:] == (16, 196) and leaf.dtype == jnp.bfloat16) if weight_dtype == "bf16" else isinstance(leaf, dict)
    handle = engine.submit(np.arange(1, 20, dtype=np.int32), max_new_tokens=5)
    engine.run_until_drained(max_steps=100)
    assert handle.ok and engine.decode_compilations == 1


@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_a_model_that_states_nothing_is_handed_its_tree_as_it_came(kind):
    model, params = _make_model() if kind == "perceiver_ar" else lfm2_moe_toy.build()[:2]
    assert model.serving_traits().row_major_leaves == ()
    engine = ServingEngine(model, params, **ENGINES[kind])
    args = engine._ragged_args(True, engine._forced_none, engine._use_forced_none)
    assert engine.params is params and args[0] is params
    assert engine._dequant_params(params) is params
    handle = engine.submit(np.arange(1, 12, dtype=np.int32), max_new_tokens=4)
    engine.run_until_drained(max_steps=100)
    assert handle.ok and engine.decode_compilations == 1


def test_a_stated_name_that_is_no_leaf_is_refused_at_construction(falcon):
    model, params, _ = falcon
    with pytest.raises(ValueError, match=r"row_major_leaves names \['params/layers_2_in_proj'\].*no leaves"):
        ServingEngine(_as(_Misnamed, model), params, **FALCON_ENGINE)


def test_split_rows_takes_abstract_leaves_and_refuses_rows_no_tile_divides():
    device = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    tree = {"params": {"w": jax.ShapeDtypeStruct((2, 32, 12), jnp.bfloat16, sharding=device),
                       "v": jax.ShapeDtypeStruct((32, 12), jnp.bfloat16, sharding=device)}}
    kept = split_rows(tree, ["params/w"])
    assert kept["params"]["w"].shape == (2, 2, 16, 12) and kept["params"]["w"].sharding == device
    assert kept["params"]["v"] is tree["params"]["v"]
    w = jnp.arange(2 * 32 * 12, dtype=jnp.bfloat16).reshape(2, 32, 12)
    assert jnp.array_equal(merge_rows(split_rows({"w": w}, ["w"]), ["w"])["w"], w)
    with pytest.raises(ValueError, match="cannot be stated row-major"):
        split_rows({"w": jnp.zeros((24, 12), jnp.bfloat16)}, ["w"])
