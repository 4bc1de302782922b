"""LFM2 mixture-of-experts on the CPU at a toy size: the plain reference's layers against the
published implementation, the program's model against the reference, prefill in chunks
then decode through the paged cache against the reference's full forward pass, the expert
layer's shares against the uncut layer, and the two kernels (interpreted) against their
XLA forms."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families.lfm2_moe import reference
from perceiver_io_tpu.ops import moe
from perceiver_io_tpu.ops import paged_decode_kernel as paged
from tests.lfm2_moe_toy import SIZES, build

# float32 rounding through five layers, the logits of order 10: every product is at
# ``highest`` on both sides, so what is left is the order of the sums
TOL = 5e-5
HF_TOL = 1e-5  # one layer, or a stack of four, against the published code: float32 rounding alone


@pytest.fixture(scope="module")
def toy():
    return build()


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(3), (29,), 1, SIZES["vocab_size"])


# --------------------------------------------- (i) the reference against transformers
DENSE = {**SIZES, "num_hidden_layers": 4, "num_dense_layers": 4,
         "layer_types": ["conv", "full_attention", "conv", "full_attention"]}


@pytest.fixture(scope="module")
def published():
    """(torch, the published modules, their config at the toy widths, every layer dense)."""
    torch = pytest.importorskip("torch")
    try:
        from transformers.models.lfm2 import modeling_lfm2
        from transformers.models.lfm2.configuration_lfm2 import Lfm2Config
    except ImportError as e:
        pytest.skip(f"transformers has no lfm2: {e}")
    keys = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "max_position_embeddings", "norm_eps", "rope_theta", "conv_L_cache", "layer_types")
    config = Lfm2Config(**{k: DENSE[k] for k in keys}, conv_bias=False, block_auto_adjust_ff_dim=False,
                        tie_word_embeddings=True)
    config._attn_implementation = "eager"
    return torch, modeling_lfm2, config


@pytest.fixture(scope="module")
def dense_weights():
    from benchmark.families.lfm2_moe import weights

    return weights.make_weights(DENSE, 11, jnp.float32)


def _t(torch, a):
    return torch.tensor(np.asarray(a, np.float32))


def _layer_state(torch, w, prefix=""):
    """One layer of the benchmark's weights under the published names."""
    state = {prefix + "operator_norm.weight": _t(torch, w["operator_norm"]),
             prefix + "ffn_norm.weight": _t(torch, w["ffn_norm"])}
    for name in ("w1", "w3", "w2"):
        state[prefix + f"feed_forward.{name}.weight"] = _t(torch, w[name]).T
    if "in_proj" in w:
        state[prefix + "conv.in_proj.weight"] = _t(torch, w["in_proj"]).T
        state[prefix + "conv.out_proj.weight"] = _t(torch, w["out_proj"]).T
        state[prefix + "conv.conv.weight"] = _t(torch, w["conv"]).T[:, None, :]
    else:
        for ours, theirs in (("q_proj", "q_proj"), ("k_proj", "k_proj"), ("v_proj", "v_proj"), ("o_proj", "out_proj")):
            state[prefix + f"self_attn.{theirs}.weight"] = _t(torch, w[ours]).T
        state[prefix + "self_attn.q_layernorm.weight"] = _t(torch, w["q_layernorm"])
        state[prefix + "self_attn.k_layernorm.weight"] = _t(torch, w["k_layernorm"])
    return {k: v.contiguous() for k, v in state.items()}


def _load(module, state, prefix):
    own = {k[len(prefix):]: v for k, v in state.items() if k.startswith(prefix)}
    report = module.load_state_dict(own, strict=True)
    assert not report.missing_keys and not report.unexpected_keys
    return module.float().eval()


@pytest.fixture(scope="module")
def rows():
    return np.asarray(jax.random.normal(jax.random.PRNGKey(21), (23, SIZES["hidden_size"])), np.float32)


def test_reference_short_conv_matches_the_published_layer(published, dense_weights, rows):
    torch, lfm2, config = published
    w = dense_weights["layers"][0]
    theirs = _load(lfm2.Lfm2ShortConv(config, 0), _layer_state(torch, w), "conv.")
    with torch.no_grad():
        want = theirs.slow_forward(torch.tensor(rows)[None])[0].numpy()
    got = np.asarray(reference.short_conv(w, DENSE, jnp.asarray(rows)))
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(got, want, atol=HF_TOL, rtol=0)


def test_reference_attention_matches_the_published_layer(published, dense_weights, rows):
    torch, lfm2, config = published
    w = dense_weights["layers"][1]
    theirs = _load(lfm2.Lfm2Attention(config, 1), _layer_state(torch, w), "self_attn.")
    n = len(rows)
    x = torch.tensor(rows)[None]
    cos_sin = lfm2.Lfm2RotaryEmbedding(config)(x, torch.arange(n)[None])
    mask = torch.full((n, n), float("-inf")).triu(1)[None, None]
    with torch.no_grad():
        want = theirs(x, position_embeddings=cos_sin, attention_mask=mask)[0][0].numpy()
    got = np.asarray(reference.attention(w, DENSE, jnp.asarray(rows), jnp.arange(n)))
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, atol=HF_TOL, rtol=0)


def test_reference_dense_mlp_matches_the_published_layer(published, dense_weights, rows):
    torch, lfm2, config = published
    w = dense_weights["layers"][0]
    theirs = _load(lfm2.Lfm2MLP(config), _layer_state(torch, w), "feed_forward.")
    with torch.no_grad():
        want = theirs(torch.tensor(rows)).numpy()
    got = np.asarray(reference.gated_mlp(jnp.asarray(rows), w["w1"], w["w3"], w["w2"]))
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, atol=HF_TOL, rtol=0)


def test_reference_dense_stack_matches_the_published_model(published, dense_weights, tokens):
    """Embedding, four layers of both kinds, ``embedding_norm`` LAST, the tied head."""
    torch, lfm2, config = published
    theirs = lfm2.Lfm2ForCausalLM(config).float().eval()
    state = {"model.embed_tokens.weight": _t(torch, dense_weights["embed_tokens"]),
             "lm_head.weight": _t(torch, dense_weights["embed_tokens"]),
             "model.embedding_norm.weight": _t(torch, dense_weights["embedding_norm"])}
    for i, w in enumerate(dense_weights["layers"]):
        state.update(_layer_state(torch, w, f"model.layers.{i}."))
    report = theirs.load_state_dict(state, strict=False)
    assert not report.unexpected_keys and not [k for k in report.missing_keys if "inv_freq" not in k]
    with torch.no_grad():
        want = theirs(torch.tensor(np.asarray(tokens))[None].long()).logits[0].numpy()
    got = np.asarray(reference.forward(dense_weights, DENSE, tokens))
    assert np.abs(want).max() > 0.5
    # the logits reach 10 here: a stack's float32 rounding, relative to that
    np.testing.assert_allclose(got, want, atol=HF_TOL * max(1.0, np.abs(want).max()), rtol=0)


# ------------------------------------------------- (ii) the program against the reference
def test_model_forward_matches_the_reference(toy, tokens):
    model, params, weights = toy
    want = reference.forward(weights, SIZES, tokens)
    got = model.apply(params, tokens[None])[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL, rtol=0)


@pytest.mark.parametrize("precision,least", [("float8", 100 * TOL), ("int8", 100 * TOL), ("bfloat16", 20 * TOL)])
def test_reference_controls_move_the_logits(toy, tokens, precision, least):
    _, _, weights = toy
    sound = np.asarray(reference.forward(weights, SIZES, tokens))
    control = np.asarray(reference.forward(weights, SIZES, tokens, precision))
    assert np.abs(control - sound).max() > least


def _prefill(model, params, cache, ids, slot, table, chunk):
    n, done = len(ids), 0
    while done < n:
        count = min(chunk, n - done)
        rows = np.zeros((chunk,), np.int32)
        rows[:count] = ids[done: done + count]
        cache = model.apply(params, jnp.asarray(rows), done, count, done == 0, slot, table, cache,
                            method=type(model).prefill_chunk_paged)
        done += count
    first = model.apply(params, cache.last_hidden[jnp.array([slot])], method=type(model)._head)[0]
    return cache.install_slot(slot, table, n), first


def _decode(model, params, cache, slots, slot, ids):
    out = []
    for token in ids:
        batch = np.zeros((slots, 1), np.int32)
        batch[slot, 0] = token
        logits, cache = model.apply(params, jnp.asarray(batch), cache, method=type(model).decode_step_paged)
        out.append(logits[slot, 0])
    return cache, out


@pytest.mark.parametrize("chunk", [8, 24, 32])
def test_prefill_in_chunks_then_decode_equals_the_references_logits(toy, tokens, chunk):
    """The two steps the engine's tick is built from, through the paged cache, against the
    REFERENCE's full forward pass: a chunk boundary inside the convolution's reach (8), a
    chunk with padding rows (24, 32), then one token a step."""
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
    # the counters: the prompt's rows in the chunk lanes' block, a decoded token a step in the other,
    # every expert layer, ``num_experts_per_tok`` assignments a token; the idle slots count nowhere
    counts = np.asarray(cache.expert_counts)
    layers, top_k = SIZES["num_hidden_layers"] - SIZES["num_dense_layers"], SIZES["num_experts_per_tok"]
    assert counts.shape == (2, layers, SIZES["num_experts"])
    assert (counts[1].sum(axis=-1) == prompt * top_k).all() and (counts[0].sum(axis=-1) == (len(ids) - prompt) * top_k).all()


def test_a_reused_slot_serves_its_second_request_as_if_fresh(toy, tokens):
    model, params, weights = toy
    ids = np.asarray(tokens)
    second = np.asarray(jax.random.randint(jax.random.PRNGKey(9), (17,), 1, SIZES["vocab_size"]))
    full = np.asarray(reference.forward(weights, SIZES, jnp.asarray(second)))
    cache = model.init_paged_cache(2, 16, 8, jnp.float32)
    table = jnp.zeros((cache.pages_per_slot,), jnp.int32).at[:4].set(jnp.arange(1, 5))
    cache, _ = _prefill(model, params, cache, ids[:21], 0, table, 8)
    cache, _ = _decode(model, params, cache, 2, 0, ids[21:])
    cache = cache.release_slot(0)  # the first request's columns and pages are left as they lie
    assert float(jnp.abs(cache.conv_state[:, 0]).max()) > 0
    cache, first = _prefill(model, params, cache, second[:11], 0, table, 8)
    cache, rest = _decode(model, params, cache, 2, 0, second[11:])
    got = np.stack([np.asarray(first)] + [np.asarray(r) for r in rest])
    np.testing.assert_allclose(got, full[10:], atol=TOL, rtol=0)


# ---------------------------------------- (ii b) a chunk lane riding the decode step
# (the chunk's first position, its rows, its cap, slots that decode beside it)
RIDING = {"a full chunk": (8, 8, 8, 2), "a chunk shorter than its cap": (16, 5, 8, 2),
          "a first chunk (reset)": (0, 8, 8, 2), "no slot active": (8, 8, 8, 0)}


@pytest.mark.parametrize("case", sorted(RIDING))
def test_a_chunk_riding_the_decode_step_equals_the_chunk_then_the_step(toy, tokens, case):
    """``decode_rows_with_chunk_paged`` (serving_api.py (h)) against ``prefill_chunk_paged`` then
    ``decode_rows_paged`` on the same cache: two slots mid-decode (or none), the third mid-prefill."""
    model, params, _ = toy
    offset, count, cap, decoding = RIDING[case]
    ids = np.asarray(tokens)
    cache = model.init_paged_cache(3, 16, 8, jnp.float32)
    tables = [jnp.zeros((cache.pages_per_slot,), jnp.int32).at[:4].set(jnp.arange(1 + 4 * i, 5 + 4 * i)) for i in range(3)]
    for slot, n in zip(range(decoding), (13, 6)):
        cache, _ = _prefill(model, params, cache, ids[slot: slot + n], slot, tables[slot], 8)
    late = np.asarray(jax.random.randint(jax.random.PRNGKey(17), (24,), 1, SIZES["vocab_size"]))
    if offset:  # the chunks before this one, and columns a first chunk must NOT read otherwise
        for done in range(0, offset, 8):
            cache = model.apply(params, jnp.asarray(late[done: done + 8]), done, 8, done == 0, 2, tables[2], cache,
                                method=type(model).prefill_chunk_paged)
    else:
        cache = cache.replace(conv_state=cache.conv_state.at[:, 2].set(0.7))
    rows = np.zeros((cap,), np.int32)
    rows[:count] = late[offset: offset + count]
    step = jnp.asarray([[int(ids[20])], [int(ids[21])], [0]], jnp.int32)
    apart = model.apply(params, jnp.asarray(rows), offset, count, offset == 0, 2, tables[2], cache,
                        method=type(model).prefill_chunk_paged)
    want_rows, want = model.apply(params, step, apart, method=type(model).decode_rows_paged)
    got_rows, got = model.apply(params, step, cache, jnp.asarray(rows), offset, count, offset == 0, 2, tables[2],
                                method=type(model).decode_rows_with_chunk_paged)
    # and with the decode rows switched off (a lane past the first, a tick that only carries lanes): the chunk alone
    _, alone = model.apply(params, step, cache, jnp.asarray(rows), offset, count, offset == 0, 2, tables[2], jnp.asarray(False),
                           method=type(model).decode_rows_with_chunk_paged)
    for name in ("conv_state", "last_hidden", "length", "active", "expert_counts"):
        np.testing.assert_allclose(np.asarray(getattr(alone, name)), np.asarray(getattr(apart, name)), atol=TOL, rtol=0, err_msg=name)
    np.testing.assert_allclose(np.asarray(alone.kp)[:, 1:], np.asarray(apart.kp)[:, 1:], atol=TOL, rtol=0)
    live = np.asarray(cache.active)
    assert live.sum() == decoding and np.abs(np.asarray(want_rows)[live]).max(initial=1.0) > 0.1
    np.testing.assert_allclose(np.asarray(got_rows)[live], np.asarray(want_rows)[live], atol=TOL, rtol=0)
    for name in ("kp", "vp", "conv_state", "last_hidden"):
        a, b = np.asarray(getattr(got, name)), np.asarray(getattr(want, name))
        if name in ("kp", "vp"):  # page 0 is the trash page: what lands there is never read
            a, b = a[:, 1:], b[:, 1:]
        np.testing.assert_allclose(a, b, atol=TOL, rtol=0, err_msg=name)
    for name in ("length", "active", "page_table", "expert_counts"):
        np.testing.assert_array_equal(np.asarray(getattr(got, name)), np.asarray(getattr(want, name)), err_msg=name)
    # both rows of the counters moved by their own group's assignments alone
    moved = np.asarray(got.expert_counts - cache.expert_counts).sum(axis=-1)
    assert (moved[0] == decoding * SIZES["num_experts_per_tok"]).all() and (moved[1] == count * SIZES["num_experts_per_tok"]).all()
    assert float(jnp.abs(got.last_hidden[2] - cache.last_hidden[2]).max()) > 0.01


# ------------------------------------------------------------- (iii) the share test
@pytest.fixture(scope="module")
def expert_case():
    """One expert layer of 32 experts, 4 a token, and 37 rows; (reference weights, sizes, x)."""
    sizes = {**SIZES, "num_experts": 32, "num_experts_per_tok": 4, "num_hidden_layers": 2, "num_dense_layers": 1,
             "layer_types": ["conv", "conv"]}
    from benchmark.families.lfm2_moe import weights

    w = weights.make_weights(sizes, 3, jnp.float32)["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(5), (37, sizes["hidden_size"]))
    return w, sizes, x


def _held(w, first, count):
    return moe.ExpertWeights(w["router"], w["expert_bias"], w["experts_w13"][first:first + count],
                             w["experts_w2"][first:first + count])


def test_the_shares_of_four_chips_add_up_to_the_uncut_layer(expert_case):
    """``held`` = (0, 8), (8, 8), (16, 8), (24, 8): each routes over all 32 experts and
    computes its own eight's part; the four parts add up to the reference's whole layer,
    and each equals the reference's own share."""
    w, sizes, x = expert_case
    whole = np.asarray(reference.expert_layer(w, sizes, x))
    total, loads = np.zeros_like(whole), []
    for first in (0, 8, 16, 24):
        part, load = moe.expert_layer(x, _held(w, first, 8), (first, 8), 4)
        np.testing.assert_allclose(np.asarray(part), np.asarray(reference.expert_layer(w, sizes, x, held=(first, 8))),
                                   atol=TOL, rtol=0)
        total += np.asarray(part)
        loads.append(np.asarray(load))
    np.testing.assert_allclose(total, whole, atol=TOL, rtol=0)
    assert np.abs(whole).max() > 0.1
    # every share counts the same assignments: all 32 experts', 4 a row
    assert all(np.array_equal(load, loads[0]) for load in loads) and loads[0].sum() == 4 * len(x)
    uncut, _ = moe.expert_layer(x, _held(w, 0, 32), (0, 32), 4)
    np.testing.assert_allclose(np.asarray(uncut), whole, atol=TOL, rtol=0)


def test_the_selection_bias_changes_choices_and_never_weights(expert_case):
    w, sizes, x = expert_case
    chosen, picked = moe.route(x, w["router"], w["expert_bias"], 4)
    plain, _ = moe.route(x, w["router"], jnp.zeros_like(w["expert_bias"]), 4)
    assert (np.sort(np.asarray(chosen), axis=-1) != np.sort(np.asarray(plain), axis=-1)).any()
    # the weights are the chosen experts' SCORES over their sum: the bias is nowhere in them
    scores = jax.nn.sigmoid(jnp.dot(x, w["router"], precision=jax.lax.Precision.HIGHEST))
    own = jnp.take_along_axis(scores, chosen, axis=-1)
    np.testing.assert_allclose(np.asarray(picked), np.asarray(own / (own.sum(axis=-1, keepdims=True) + 1e-6)),
                               atol=1e-6, rtol=0)
    # and a bias large enough decides the choice alone
    forced = jnp.zeros((32,)).at[jnp.array([3, 9, 20, 31])].set(10.0)
    only, _ = moe.route(x, w["router"], forced, 4)
    assert (np.sort(np.asarray(only), axis=-1) == np.array([3, 9, 20, 31])).all()


def test_rows_that_are_no_tokens_are_routed_nowhere(expert_case):
    w, sizes, x = expert_case
    valid = jnp.arange(len(x)) < 20
    part, load = moe.expert_layer(x, _held(w, 0, 32), (0, 32), 4, valid=valid)
    want = np.asarray(reference.expert_layer(w, sizes, x))
    np.testing.assert_allclose(np.asarray(part)[:20], want[:20], atol=TOL, rtol=0)
    assert not np.asarray(part)[20:].any() and int(load.sum()) == 4 * 20


# ---------------------------------------------------------------- (iv) the kernels
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, TOL), (jnp.bfloat16, 0.05)])
def test_grouped_kernel_equals_ragged_dot_with_an_expert_that_receives_no_row(dtype, tol):
    groups, k, n = 6, 128, 256
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    # 41 assignments over six experts; expert 2 receives none, and 5 rows belong to no held expert
    key = jnp.asarray(np.random.default_rng(0).choice([0, 1, 3, 4, 5, 6], size=41, p=[.3, .2, .2, .1, .1, .1]))
    layout = moe.group_layout(key, groups, 32 // jnp.dtype(dtype).itemsize)
    assert int(layout.sizes[2]) == 0 and int(layout.sizes.sum()) == int((key < groups).sum())
    lhs = jax.random.normal(keys[0], (layout.rows, k)).astype(dtype)
    w13 = (jax.random.normal(keys[1], (groups, k, 2 * n)) * k ** -0.5).astype(dtype)
    w2 = (jax.random.normal(keys[2], (groups, n, k)) * n ** -0.5).astype(dtype)
    visited = np.asarray(jnp.arange(layout.rows) < layout.n_tiles * layout.tile_rows)
    for weights, form, rows in ((w13, "gated_silu", lhs), (w2, None, jax.random.normal(keys[3], (layout.rows, n)).astype(dtype))):
        want = moe.grouped_matmul(rows, weights, layout, jnp.float32, form)
        got = moe.grouped_matmul(rows, weights, layout, jnp.float32, form, use_kernel=True, interpret=True)
        np.testing.assert_allclose(np.asarray(got)[visited], np.asarray(want)[visited], atol=tol, rtol=0)
    # the tile table names expert 2 nowhere among the tiles that hold rows
    assert 2 not in np.asarray(layout.tile_group)[: int(layout.n_tiles)].tolist()


def test_expert_layer_through_the_kernel_equals_the_reference(expert_case):
    w, sizes, x = expert_case
    got, _ = moe.expert_layer(x, _held(w, 0, 32), (0, 32), 4, use_kernel=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(reference.expert_layer(w, sizes, x)), atol=TOL, rtol=0)


@pytest.mark.parametrize("hq,hkv,d", [(8, 2, 64), (32, 8, 64), (8, 4, 32), (4, 2, 128)])
def test_grouped_query_paged_decode_of_narrow_heads_equals_plain_attention(hq, hkv, d):
    """Heads narrower than a lane tile (64, 32) leave the kernel as whole pool rows and are picked out
    after it; heads of 128 take the path they took."""
    layers, pages, ps, slots = 2, 13, 8, 3
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    kp, vp = (jax.random.normal(k, (layers, pages, ps, hkv * d)) for k in ks[:2])
    q = jax.random.normal(ks[2], (slots, hq, d)) * d ** -0.5
    table = jnp.array([[3, 5, 7, 0], [1, 2, 0, 0], [9, 10, 11, 12]], jnp.int32)
    length = jnp.array([19, 0, 32], jnp.int32)
    xla = paged.paged_gqa_reference_attention(q, kp, vp, table, length, 1)
    kernel = paged.fused_paged_decode_attention_gqa(q, kp, vp, table, length, 1, interpret=True)
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(xla), atol=TOL, rtol=0)
    assert not np.asarray(kernel[1]).any()  # a slot of length 0 is skipped
    n = int(length[2])
    keys, values = (t[1][table[2]].reshape(-1, hkv, d)[:n] for t in (kp, vp))
    for head in (0, hq // 2, hq - 1):  # query head j reads K/V head j // n_rep
        prob = jax.nn.softmax(keys[:, head // (hq // hkv)] @ q[2, head])
        np.testing.assert_allclose(np.asarray(kernel[2, head]), np.asarray(prob @ values[:, head // (hq // hkv)]),
                                   atol=TOL, rtol=0)
