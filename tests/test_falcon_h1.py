"""Falcon-H1's hybrid block on the CPU at a toy size: the plain reference against the
published implementation, the program's model against the reference, the chunked scan
against the recurrence, prefill in chunks then decode through the cache against the
full forward pass, and the two new kernels (interpreted) against their XLA forms."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families.falcon_h1 import reference
from perceiver_io_tpu.ops import paged_decode_kernel as paged
from perceiver_io_tpu.ops import ssm
from tests.falcon_h1_toy import SIZES, build

TOL = 5e-5  # float32 rounding through two layers; the logits are of order 1


@pytest.fixture(scope="module")
def toy():
    return build()


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(3), (29,), 1, SIZES["vocab_size"])


def test_reference_matches_the_published_implementation(toy, tokens):
    torch = pytest.importorskip("torch")
    try:
        from transformers import FalconH1Config, FalconH1ForCausalLM
    except ImportError as e:
        pytest.skip(f"transformers has no falcon_h1: {e}")
    _, _, weights = toy
    published = {k: v for k, v in SIZES.items() if k not in ("serving_context_tokens", "embedding_init_std")}
    config = FalconH1Config(**published, mamba_expand=1, mamba_conv_bias=True, mamba_proj_bias=False,
                            mamba_rms_norm=True, mamba_norm_before_gate=False, attention_bias=False, mlp_bias=False,
                            projectors_bias=False, tie_word_embeddings=False, hidden_act="silu")
    config._attn_implementation = "eager"
    theirs = FalconH1ForCausalLM(config).float().eval()

    def t(a):
        return torch.tensor(np.asarray(a, np.float32))

    state = {"model.embed_tokens.weight": t(weights["embed_tokens"]), "lm_head.weight": t(weights["lm_head"]).T,
             "model.final_layernorm.weight": t(weights["final_layernorm"])}
    for i, w in enumerate(weights["layers"]):
        p = f"model.layers.{i}."
        state[p + "input_layernorm.weight"] = t(w["input_layernorm"])
        state[p + "pre_ff_layernorm.weight"] = t(w["pre_ff_layernorm"])
        for name in ("q_proj", "k_proj", "v_proj", "o_proj"):
            state[p + f"self_attn.{name}.weight"] = t(w[name]).T
        for name in ("gate_proj", "up_proj", "down_proj"):
            state[p + f"feed_forward.{name}.weight"] = t(w[name]).T
        state[p + "mamba.in_proj.weight"], state[p + "mamba.out_proj.weight"] = t(w["in_proj"]).T, t(w["out_proj"]).T
        state[p + "mamba.conv1d.weight"] = t(w["conv_weight"]).T[:, None, :]
        state[p + "mamba.conv1d.bias"], state[p + "mamba.norm.weight"] = t(w["conv_bias"]), t(w["mixer_norm"])
        state[p + "mamba.dt_bias"], state[p + "mamba.A_log"], state[p + "mamba.D"] = t(w["dt_bias"]), t(w["A_log"]), t(w["D"])
    report = theirs.load_state_dict({k: v.contiguous() for k, v in state.items()}, strict=False)
    assert not report.missing_keys and not report.unexpected_keys
    with torch.no_grad():
        want = theirs(torch.tensor(np.asarray(tokens))[None].long()).logits[0].numpy()
    got = np.asarray(reference.forward(weights, SIZES, tokens))
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_model_forward_matches_the_reference(toy, tokens):
    model, params, weights = toy
    want = reference.forward(weights, SIZES, tokens)
    got = model.apply(params, tokens[None])[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL, rtol=0)


@pytest.mark.parametrize("precision,least", [("float8", 100 * TOL), ("int8", 100 * TOL), ("bf16state", 2 * TOL)])
def test_reference_controls_move_the_logits(toy, tokens, precision, least):
    """Rounding the matrix products' operands moves the logits far past float32 rounding;
    rounding the recurrent state to bfloat16 after every step moves them too, but little
    (the state decays, so the rounding does not pile up)."""
    _, _, weights = toy
    sound = np.asarray(reference.forward(weights, SIZES, tokens))
    control = np.asarray(reference.forward(weights, SIZES, tokens, precision))
    assert np.abs(control - sound).max() > least


@pytest.mark.parametrize("rows,chunk", [(19, 4), (19, 8), (7, 8), (33, 32)])
def test_chunked_scan_equals_the_recurrence(rows, chunk):
    h, p, g, n = 4, 16, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(rows), 6)
    x, b, c = (jax.random.normal(k, s) for k, s in zip(ks, [(rows, h, p), (rows, g, n), (rows, g, n)]))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (rows, h)))
    a = -jnp.exp(jax.random.normal(ks[4], (h,)))
    before = jax.random.normal(ks[5], (h, p, n))
    y_want, s_want = ssm.ssm_recurrence(x, dt, a, b, c, before)
    y_got, s_got = ssm.ssd_chunk_scan(x, dt, a, b, c, before, chunk)
    np.testing.assert_allclose(np.asarray(y_got), np.asarray(y_want), atol=TOL, rtol=0)
    np.testing.assert_allclose(np.asarray(s_got), np.asarray(s_want), atol=TOL, rtol=0)


def test_a_row_with_zero_dt_leaves_the_state_alone():
    h, p, g, n = 4, 16, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x, b, c = (jax.random.normal(k, s) for k, s in zip(ks, [(16, h, p), (16, g, n), (16, g, n)]))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (16, h))) * (jnp.arange(16) < 11)[:, None]
    a, before = -jnp.exp(jax.random.normal(ks[4], (h,))), jax.random.normal(ks[5], (h, p, n))
    _, padded = ssm.ssd_chunk_scan(x, dt, a, b, c, before, 8)
    _, short = ssm.ssm_recurrence(x[:11], dt[:11], a, b[:11], c[:11], before)
    np.testing.assert_allclose(np.asarray(padded), np.asarray(short), atol=TOL, rtol=0)


ACTIVE = {"mixed": [True, False, True, True, False], "none": [False] * 5, "all": [True] * 5,
          "only-last": [False] * 4 + [True], "all-but-last": [True] * 4 + [False]}


@pytest.mark.parametrize("active", sorted(ACTIVE))
@pytest.mark.parametrize("g", [1, 2, 4])  # 4 heads in g groups: one, two and four head blocks a slot
def test_ssm_decode_update_kernel_equals_its_xla_form(g, active):
    layers, slots, h, p, n = 2, 5, 4, 16, 16
    assert h // ssm._head_block(h, g) == g
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    state = jax.random.normal(ks[0], (layers, slots, h, p, n))
    x, b, c = (jax.random.normal(k, s) for k, s in zip(ks[1:], [(slots, h, p), (slots, g, n), (slots, g, n)]))
    dt, a = jax.nn.softplus(jax.random.normal(ks[4], (slots, h))), -jnp.exp(jax.random.normal(ks[5], (h,)))
    active = jnp.asarray(ACTIVE[active])
    s_want, y_want = ssm.ssm_decode_update_xla(state, 1, x, dt, a, b, c, active)
    s_got, y_got = ssm.ssm_decode_update(state, 1, x, dt, a, b, c, active, interpret=True)
    np.testing.assert_allclose(np.asarray(s_got), np.asarray(s_want), atol=TOL, rtol=0)
    np.testing.assert_allclose(np.asarray(y_got), np.asarray(y_want), atol=TOL, rtol=0)
    # the other layer and the slots that do not decode are untouched, bit for bit
    idle = ~np.asarray(active)
    assert np.array_equal(np.asarray(s_got[0]), np.asarray(state[0]))
    assert np.array_equal(np.asarray(s_got[1])[idle], np.asarray(state[1])[idle])
    assert not np.asarray(y_got)[idle].any()


@pytest.mark.parametrize("blocks", [1, 2, 4])
@pytest.mark.parametrize("n_live", [0, 1, 3, 15, 16])  # of S = 16 slots
def test_ssm_decode_update_fetches_a_block_only_for_a_slot_that_decodes(n_live, blocks):
    """A COUNT of what the kernel's pipeline will move, never a time: walk the grid
    (S, blocks) in its order through the kernel's own index map (``ssm._step_block``) with
    ``live_list``'s output, and count the steps whose state block differs from the step
    before; only those fetch a block and write one back. Every (decoding slot, head block)
    is held exactly once, and the steps past the live list add at most ONE block, the one
    they rest on. On the parent's maps ``(live[w], j)`` the same walk counts ``S x blocks``
    whenever a slot is idle and there is more than one head block, whatever ``n_live`` is:
    ``j`` went on alternating over the idle slot's blocks."""
    slots = 16
    active = np.zeros(slots, bool)
    active[np.random.default_rng(n_live).permutation(slots)[:n_live]] = True
    live, count = ssm.live_list(jnp.asarray(active))
    assert int(count[0]) == n_live
    w, j = np.repeat(np.arange(slots), blocks), np.tile(np.arange(blocks), slots)
    slot, block = (np.asarray(v) for v in ssm._step_block(w, j, live, count))

    def fetched(held):  # the steps whose block differs from the step before
        return [held[0]] + [now for before, now in zip(held, held[1:]) if now != before]

    moved = fetched(list(zip(slot.tolist(), block.tolist())))
    decoding = [(s, k) for s in np.flatnonzero(active).tolist() for k in range(blocks)]
    assert moved[:len(decoding)] == decoding  # in slot order, each block once
    rest = moved[len(decoding):]
    assert len(rest) == (n_live < slots) and all(not active[s] and k == 0 for s, k in rest)
    if blocks > 1 and n_live < slots:  # what the parent's maps would have moved
        assert len(fetched(list(zip(np.asarray(live)[w].tolist(), j.tolist())))) == slots * blocks


def test_grouped_query_paged_decode_equals_plain_attention():
    layers, pages, ps, hkv, d, hq, slots = 2, 13, 8, 2, 16, 4, 3
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    kp, vp = (jax.random.normal(k, (layers, pages, ps, hkv * d)) for k in ks[:2])
    q = jax.random.normal(ks[2], (slots, hq, d))
    table = jnp.array([[3, 5, 7, 0], [1, 2, 0, 0], [9, 10, 11, 12]], jnp.int32)
    length = jnp.array([19, 0, 32], jnp.int32)
    xla = paged.paged_gqa_reference_attention(q, kp, vp, table, length, 1)
    kernel = paged.fused_paged_decode_attention_gqa(q, kp, vp, table, length, 1, interpret=True)
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(xla), atol=TOL, rtol=0)
    assert not np.asarray(kernel[1]).any()  # a slot of length 0 is skipped
    for slot in (0, 2):
        n = int(length[slot])
        keys = kp[1][table[slot]].reshape(-1, hkv, d)[:n]
        values = vp[1][table[slot]].reshape(-1, hkv, d)[:n]
        for head in range(hq):  # query head j reads K/V head j // n_rep
            prob = jax.nn.softmax(keys[:, head // (hq // hkv)] @ q[slot, head])
            np.testing.assert_allclose(np.asarray(kernel[slot, head]), np.asarray(prob @ values[:, head // (hq // hkv)]),
                                       atol=TOL, rtol=0)


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
def test_prefill_in_chunks_then_decode_equals_the_full_forward(toy, tokens, chunk):
    model, params, _ = toy
    ids = np.asarray(tokens)
    full = np.asarray(model.apply(params, tokens[None])[0])
    prompt = 21
    cache = model.init_paged_cache(3, 16, 8, jnp.float32)
    table = jnp.zeros((cache.pages_per_slot,), jnp.int32).at[:6].set(jnp.arange(3, 9))
    cache, first = _prefill(model, params, cache, ids[:prompt], 1, table, chunk)
    cache, rest = _decode(model, params, cache, 3, 1, ids[prompt:])
    got = np.stack([np.asarray(first)] + [np.asarray(r) for r in rest])
    # position i's logits predict token i + 1: the prompt's last position, then every decoded one
    np.testing.assert_allclose(got, full[prompt - 1:], atol=TOL, rtol=0)
    assert int(cache.length[1]) == len(ids) and not bool(cache.active[0])


def test_a_reused_slot_serves_its_second_request_as_if_fresh(toy, tokens):
    model, params, _ = toy
    ids = np.asarray(tokens)
    second = np.asarray(jax.random.randint(jax.random.PRNGKey(9), (17,), 1, SIZES["vocab_size"]))
    full = np.asarray(model.apply(params, jnp.asarray(second)[None])[0])
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
