"""Nemotron-H on the CPU at a toy size: the program's model against the plain reference,
prefill in chunks then decode through the paged cache against the reference's full forward
pass, the two halves of the experts plus the shared expert counted once against the uncut
layer, a chunk riding the decode step against the chunk then the step, the ungated kernels (interpreted) against the reference's expert layer at a width
that is no multiple of 128, and four wrong models that the comparison has to fail."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families.nemotron_h import reference, weights as family_weights
from perceiver_io_tpu.models.core.falcon_h1 import gated_group_rms_norm, rope_half
from perceiver_io_tpu.models.core.nemotron_h import NemotronHForCausalLM
from perceiver_io_tpu.ops import moe
from tests.nemotron_h_toy import SIZES, build
from tests.test_lfm2_moe import _decode, _prefill  # the two steps driven by hand, whatever the model

# float32 rounding through seven layers, the logits of order 4: every product is at
# ``highest`` on both sides, so what is left is the order of the sums (the chunked scan
# against the recurrence, a sorted grouped product against a sum over every expert)
TOL = 5e-5


@pytest.fixture(scope="module")
def toy():
    return build()


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(3), (29,), 1, SIZES["vocab_size"])


def test_model_forward_matches_the_reference(toy, tokens):
    model, params, weights = toy
    want = np.asarray(reference.forward(weights, SIZES, tokens))
    got = model.apply(params, tokens[None])[0]
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(np.asarray(got), want, atol=TOL, rtol=0)


@pytest.mark.parametrize("precision,least", [("float8", 100 * TOL), ("int8", 100 * TOL), ("bfloat16", 20 * TOL)])
def test_reference_controls_move_the_logits(toy, tokens, precision, least):
    _, _, weights = toy
    sound = np.asarray(reference.forward(weights, SIZES, tokens))
    control = np.asarray(reference.forward(weights, SIZES, tokens, precision))
    assert np.abs(control - sound).max() > least


# ------------------------------------------------ wrong models the comparison has to fail
class _Rotary(NemotronHForCausalLM):
    def _qkv(self, p, x):
        cfg = self.config
        q, k, v = super()._qkv(p, x)
        pos = jnp.arange(x.shape[0])
        k = rope_half(k.reshape(x.shape[0], cfg.num_key_value_heads, cfg.head_dim), pos, 10000.0)
        return rope_half(q, pos, 10000.0), k.reshape(x.shape[0], -1), v


class _Gated(NemotronHForCausalLM):
    def _experts(self, p, x, valid=None):
        cfg = self.config
        half = p["experts_up"].shape[-1] // 2
        weights = moe.ExpertWeights(p["router"], p["expert_bias"], p["experts_up"], p["experts_down"][:, :half])
        routed, load = moe.expert_layer(x, weights, cfg.experts_held, cfg.num_experts_per_tok,
                                        cfg.routed_scaling_factor, valid=valid, norm_eps=1e-20)
        shared = jnp.square(jax.nn.relu(self._mm(x, p["shared_up"])))
        return routed + self._mm(shared, p["shared_down"]), load


class _WholeVectorNorm(NemotronHForCausalLM):
    def _mixer_out(self, p, y, xs, z):
        cfg = self.config
        y = y + p["D"].astype(jnp.float32)[:, None] * xs
        y = gated_group_rms_norm(y.reshape(*y.shape[:-2], cfg.mamba_inner), z, p["mixer_norm"], 1,
                                 cfg.layer_norm_epsilon)
        return self._mm(y, p["out_proj"])


def _no_bias(params):
    return {"params": {k: jnp.zeros_like(v) if k.endswith("expert_bias") else v for k, v in params["params"].items()}}


WRONG = {"a dropped e_score_correction_bias": (NemotronHForCausalLM, _no_bias),
         "a gated expert": (_Gated, lambda p: p),
         "a rotary embedding": (_Rotary, lambda p: p),
         "a whole-vector output norm": (_WholeVectorNorm, lambda p: p)}


@pytest.mark.parametrize("fault", sorted(WRONG))
def test_a_wrong_model_fails_the_comparison(toy, tokens, fault):
    """Each is a model one could have written from the catalog's summary alone; the
    comparison that passes the program above (TOL) fails it by two orders or more."""
    model, params, weights = toy
    cls, change = WRONG[fault]
    wrong = cls(config=model.config, dtype=jnp.float32, param_dtype=jnp.float32)
    want = np.asarray(reference.forward(weights, SIZES, tokens))
    got = np.asarray(wrong.apply(change(params), tokens[None])[0])
    assert np.abs(got - want).max() > 100 * TOL


# ------------------------------------------- the two steps the engine's tick is built from
@pytest.mark.parametrize("chunk", [8, 24, 32])
def test_prefill_in_chunks_then_decode_equals_the_references_logits(toy, tokens, chunk):
    """Through the cache's three kinds of state, against the REFERENCE's full forward pass:
    a chunk boundary inside the convolution's reach and at the scan's chunk (8), a chunk
    with padding rows (24, 32), then one token a step. Float32; TOL's reason is above."""
    model, params, weights = toy
    ids = np.asarray(tokens)
    full = np.asarray(reference.forward(weights, SIZES, tokens))
    prompt = 21
    cache = model.init_paged_cache(3, 16, 8, jnp.float32)
    table = jnp.zeros((cache.pages_per_slot,), jnp.int32).at[:6].set(jnp.arange(3, 9))
    cache, first = _prefill(model, params, cache, ids[:prompt], 1, table, chunk)
    cache, rest = _decode(model, params, cache, 3, 1, ids[prompt:])
    got = np.stack([np.asarray(first)] + [np.asarray(r) for r in rest])
    # position i's logits predict token i + 1: the prompt's last position, then every decoded one
    np.testing.assert_allclose(got, full[prompt - 1:], atol=TOL, rtol=0)
    assert int(cache.length[1]) == len(ids) and not bool(cache.active[0])
    # pages for the ``*`` layers only, a state for the ``M`` layers only, counters for the ``E`` layers
    pattern = SIZES["hybrid_override_pattern"]
    assert cache.kp.shape[0] == pattern.count("*") and cache.ssm_state.shape[0] == pattern.count("M")
    assert cache.ssm_state.dtype == jnp.float32
    counts = np.asarray(cache.expert_counts)
    assert counts.shape == (2, pattern.count("E"), SIZES["router_experts"])
    top_k = SIZES["num_experts_per_tok"]
    assert (counts[1].sum(axis=-1) == prompt * top_k).all() and (counts[0].sum(axis=-1) == (len(ids) - prompt) * top_k).all()
    # the idle slots' state was left alone
    assert not np.asarray(cache.ssm_state[:, 0]).any() and np.asarray(cache.ssm_state[:, 1]).any()


def test_a_reused_slot_serves_its_second_request_as_if_fresh(toy, tokens):
    model, params, weights = toy
    ids = np.asarray(tokens)
    second = np.asarray(jax.random.randint(jax.random.PRNGKey(9), (17,), 1, SIZES["vocab_size"]))
    full = np.asarray(reference.forward(weights, SIZES, jnp.asarray(second)))
    cache = model.init_paged_cache(2, 16, 8, jnp.float32)
    table = jnp.zeros((cache.pages_per_slot,), jnp.int32).at[:4].set(jnp.arange(1, 5))
    cache, _ = _prefill(model, params, cache, ids[:21], 0, table, 8)
    cache, _ = _decode(model, params, cache, 2, 0, ids[21:])
    cache = cache.release_slot(0)  # the first request's state and pages are left as they lie
    assert float(jnp.abs(cache.ssm_state[:, 0]).max()) > 0
    cache, first = _prefill(model, params, cache, second[:11], 0, table, 8)
    cache, rest = _decode(model, params, cache, 2, 0, second[11:])
    got = np.stack([np.asarray(first)] + [np.asarray(r) for r in rest])
    np.testing.assert_allclose(got, full[10:], atol=TOL, rtol=0)


# ---------------------------------------- a chunk lane riding the decode step
# (the chunk's first position, its rows, its cap, slots that decode beside it)
RIDING = {"a later chunk that carries the state and the tail": (8, 8, 8, 2), "a chunk shorter than its cap": (16, 5, 8, 2),
          "a chunk of two scan chunks": (8, 13, 16, 2), "a first chunk (reset)": (0, 8, 8, 2), "no slot active": (8, 8, 8, 0)}
LEAVES = ("kp", "vp", "ssm_state", "conv_state", "last_hidden", "length", "active", "page_table", "expert_counts")


def _leaf(cache, name):
    value = np.asarray(getattr(cache, name))
    return value[:, 1:] if name in ("kp", "vp") else value  # page 0 is the trash page: what lands there is never read


@pytest.mark.parametrize("case", sorted(RIDING))
def test_a_chunk_riding_the_decode_step_equals_the_chunk_then_the_step(toy, tokens, case):
    """``decode_rows_with_chunk_paged`` (serving_api.py (h)) against ``prefill_chunk_paged`` then
    ``decode_rows_paged`` on the same cache: two slots mid-decode (or none), the third mid-prefill;
    the rows and EVERY leaf of the cache, and with ``live`` False the chunk alone."""
    model, params, _ = toy
    offset, count, cap, decoding = RIDING[case]
    ids = np.asarray(tokens)
    cache = model.init_paged_cache(3, 16, 8, jnp.float32)
    tables = [jnp.zeros((cache.pages_per_slot,), jnp.int32).at[:4].set(jnp.arange(1 + 4 * i, 5 + 4 * i)) for i in range(3)]
    for slot, n in zip(range(decoding), (13, 6)):
        cache, _ = _prefill(model, params, cache, ids[slot: slot + n], slot, tables[slot], 8)
    late = np.asarray(jax.random.randint(jax.random.PRNGKey(17), (24,), 1, SIZES["vocab_size"]))
    if offset:  # the chunks before this one leave a state and a tail behind
        for done in range(0, offset, 8):
            cache = model.apply(params, jnp.asarray(late[done: done + 8]), done, 8, done == 0, 2, tables[2], cache,
                                method=type(model).prefill_chunk_paged)
        assert float(jnp.abs(cache.ssm_state[:, 2]).max()) > 0.01 and float(jnp.abs(cache.conv_state[:, 2]).max()) > 0.01
    else:  # a state and a tail that a first chunk must NOT read
        cache = cache.replace(ssm_state=cache.ssm_state.at[:, 2].set(0.3), conv_state=cache.conv_state.at[:, 2].set(0.7))
    rows = np.zeros((cap,), np.int32)
    rows[:count] = late[offset: offset + count]
    lane = (jnp.asarray(rows), offset, count, offset == 0, 2, tables[2])
    step = jnp.asarray([[int(ids[20])], [int(ids[21])], [0]], jnp.int32)
    apart = model.apply(params, *lane, cache, method=type(model).prefill_chunk_paged)
    want_rows, want = model.apply(params, step, apart, method=type(model).decode_rows_paged)
    got_rows, got = model.apply(params, step, cache, *lane, method=type(model).decode_rows_with_chunk_paged)
    # and with the decode rows switched off (a lane past the first, a tick that only carries lanes): the chunk alone
    _, alone = model.apply(params, step, cache, *lane, jnp.asarray(False), method=type(model).decode_rows_with_chunk_paged)
    live = np.asarray(cache.active)
    assert live.sum() == decoding and np.abs(np.asarray(want_rows)[live]).max(initial=1.0) > 0.1
    np.testing.assert_allclose(np.asarray(got_rows)[live], np.asarray(want_rows)[live], atol=TOL, rtol=0)
    for name in LEAVES:
        exact = np.asarray(getattr(cache, name)).dtype.kind in "bi"
        for merged, plain in ((got, want), (alone, apart)):
            np.testing.assert_allclose(_leaf(merged, name), _leaf(plain, name), atol=0 if exact else TOL, rtol=0, err_msg=name)
    # both rows of the counters moved by their own group's assignments alone
    moved = np.asarray(got.expert_counts - cache.expert_counts).sum(axis=-1)
    top_k = SIZES["num_experts_per_tok"]
    assert (moved[0] == decoding * top_k).all() and (moved[1] == count * top_k).all()
    assert float(jnp.abs(got.last_hidden[2] - cache.last_hidden[2]).max()) > 0.01
    # the chunk moved its own slot's state and the decode step the decoding slots', nobody else's
    changed = np.abs(np.asarray(got.ssm_state - cache.ssm_state)).max(axis=(0, 2, 3, 4)) > 0
    assert changed.tolist() == [decoding > 0, decoding > 1, True]


# ------------------------------------------------------------------ the expert layer
EXPERTS, TOP_K, WIDTH = 16, 6, 200  # 200 columns: laid out at 256, the padding zero


@pytest.fixture(scope="module")
def expert_case():
    """One ``E`` layer with EVERY one of its 16 experts (the uncut layer), 6 a token, at a width
    that is no multiple of 128, and 37 rows; (the reference's weights, its sizes, x)."""
    sizes = {**SIZES, "hidden_size": 128, "router_experts": EXPERTS, "n_routed_experts": EXPERTS,
             "num_experts_per_tok": TOP_K, "moe_intermediate_size": WIDTH, "moe_shared_expert_intermediate_size": 256,
             "num_hidden_layers": 1, "hybrid_override_pattern": "E"}
    w = family_weights.make_weights(sizes, 3, jnp.float32)["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(5), (37, sizes["hidden_size"]))
    return w, sizes, x


def _held(w, first, count, shared=True):
    return moe.ExpertWeights(w["router"], w["expert_bias"], w["experts_up"][first:first + count],
                             w["experts_down"][first:first + count],
                             *((w["shared_up"], w["shared_down"]) if shared else ()))


def _layer(x, w, first, count, shared=True, **kernel):
    return moe.expert_layer(x, _held(w, first, count, shared), (first, count), TOP_K, 2.5, form="relu2",
                            norm_eps=1e-20, **kernel)


def test_the_stacks_are_laid_out_once_at_the_lanes_multiple(expert_case):
    w, sizes, _ = expert_case
    assert moe.pad_width(WIDTH) == family_weights.pad_to_lanes(WIDTH) == 256 and moe.pad_width(1856) == 1920
    assert w["experts_up"].shape == (EXPERTS, 128, 256) and w["experts_down"].shape == (EXPERTS, 256, 128)
    assert not np.asarray(w["experts_up"][:, :, WIDTH:]).any() and not np.asarray(w["experts_down"][:, WIDTH:]).any()
    assert np.asarray(w["experts_up"][:, :, WIDTH - 1]).any() and np.asarray(w["experts_down"][:, WIDTH - 1]).any()
    # the parameters counted are the published ones
    per_expert = 2 * 128 * WIDTH
    assert family_weights.count_parameters(sizes) == (
        2 * sizes["vocab_size"] * 128 + 128 + 128 + 128 * EXPERTS + EXPERTS + EXPERTS * per_expert + 2 * 128 * 256)


def test_the_two_halves_and_the_shared_expert_once_add_up_to_the_uncut_layer(expert_case):
    """The deployment's two chips: each routes over all 16 experts and computes its own
    eight's part; BOTH compute the shared expert in full. The halves' routed parts plus
    the shared expert counted ONCE equal the reference's uncut layer; each half with its
    shared expert equals the reference's own share."""
    w, sizes, x = expert_case
    whole = np.asarray(reference.expert_layer(w, sizes, x, held=(0, EXPERTS)))
    shared = np.asarray(reference.relu2_mlp(x, w["shared_up"], w["shared_down"]))
    total, loads = shared.copy(), []
    for first in (0, 8):
        held = {**w, "experts_up": w["experts_up"][first:first + 8], "experts_down": w["experts_down"][first:first + 8]}
        routed, load = _layer(x, w, first, 8, shared=False)
        np.testing.assert_allclose(np.asarray(routed), np.asarray(reference.expert_layer(held, sizes, x, held=(first, 8), shared=False)),
                                   atol=TOL, rtol=0)
        with_shared, _ = _layer(x, w, first, 8)
        np.testing.assert_allclose(np.asarray(with_shared), np.asarray(routed) + shared, atol=TOL, rtol=0)
        total += np.asarray(routed)
        loads.append(np.asarray(load))
    np.testing.assert_allclose(total, whole, atol=TOL, rtol=0)
    assert np.abs(whole).max() > 0.5 and np.abs(shared).max() > 0.1 and np.abs(total - shared).max() > 0.1
    # both halves count the same assignments: all 16 experts', 6 a row, about half of them held by each
    assert np.array_equal(loads[0], loads[1]) and loads[0].sum() == TOP_K * len(x)
    assert 0 < loads[0][:8].sum() < TOP_K * len(x)


def test_the_routers_weights_are_scores_over_their_sum_times_the_scaling_factor(expert_case):
    w, _, x = expert_case
    chosen, picked = moe.route(x, w["router"], w["expert_bias"], TOP_K, 2.5, norm_eps=1e-20)
    plain, _ = moe.route(x, w["router"], jnp.zeros_like(w["expert_bias"]), TOP_K, 2.5, norm_eps=1e-20)
    assert (np.sort(np.asarray(chosen), axis=-1) != np.sort(np.asarray(plain), axis=-1)).any()
    scores = jax.nn.sigmoid(jnp.dot(x, w["router"], precision=jax.lax.Precision.HIGHEST))
    own = jnp.take_along_axis(scores, chosen, axis=-1)
    np.testing.assert_allclose(np.asarray(picked), np.asarray(2.5 * own / own.sum(axis=-1, keepdims=True)), atol=1e-6, rtol=0)
    np.testing.assert_allclose(np.asarray(picked.sum(axis=-1)), 2.5, atol=1e-5, rtol=0)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, TOL), (jnp.bfloat16, 0.05)])
def test_ungated_kernel_equals_ragged_dot_at_a_padded_width(dtype, tol):
    groups, k, n = 6, 128, 256
    keys = jax.random.split(jax.random.PRNGKey(2), 2)
    key = jnp.asarray(np.random.default_rng(0).choice([0, 1, 3, 4, 5, 6], size=41, p=[.3, .2, .2, .1, .1, .1]))
    layout = moe.group_layout(key, groups, 32 // jnp.dtype(dtype).itemsize)
    lhs = jax.random.normal(keys[0], (layout.rows, k)).astype(dtype)
    up = (jax.random.normal(keys[1], (groups, k, n)) * k ** -0.5).astype(dtype)
    visited = np.asarray(jnp.arange(layout.rows) < layout.n_tiles * layout.tile_rows)
    want = moe.grouped_matmul(lhs, up, layout, jnp.float32, "relu2")
    got = moe.grouped_matmul(lhs, up, layout, jnp.float32, "relu2", use_kernel=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got)[visited], np.asarray(want)[visited], atol=tol, rtol=0)
    plain = np.square(np.maximum(np.asarray(moe.grouped_matmul(lhs, up, layout, jnp.float32)), 0))
    np.testing.assert_allclose(np.asarray(want)[visited], plain[visited], atol=tol, rtol=0)


def test_expert_layer_through_the_ungated_kernels_equals_the_reference_row_by_row(expert_case):
    """The Pallas products in interpret mode over the stacks as laid out (256 columns for a
    published 200), the half-share and the shared expert, against the reference's layer."""
    w, sizes, x = expert_case
    held = {**w, "experts_up": w["experts_up"][:8], "experts_down": w["experts_down"][:8]}
    want = np.asarray(reference.expert_layer(held, sizes, x, held=(0, 8)))
    got, _ = _layer(x, w, 0, 8, use_kernel=True, interpret=True)
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(np.asarray(got), want, atol=TOL, rtol=0)
    with pytest.raises(ValueError, match="form"):
        moe.expert_layer(x, _held(w, 0, 8), (0, 8), TOP_K, form="gelu")
