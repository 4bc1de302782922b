"""The head runs once a tick (ISSUE 42): between two ticks a slot carries its last hidden
row, never its logits. Pinned here, on the CPU at toy sizes, for both served families:

* the tick program holds exactly ONE matmul against the head's weights, and no array of
  the vocabulary's width is an argument, a result, a ``cond`` operand or a loop carry of it
  (the sampler's own ``cond`` over the logits, inside ``tick.sample``, is where they die);
* a prompt whose last chunk and finish ride a tick that also decodes other slots gets its
  first token from THAT tick, and every served token, greedy and sampled, is what the
  model's own logits path (``prefill`` / ``decode_step``, ``decode_step_paged``) gives
  under the engine's rng chain; for a model whose chunk rows ride its decode pass
  (``serving_api.py`` (h), ISSUE 46: LFM2) from the NEXT tick, the same tokens, and the
  branch such a tick takes holds one pair of grouped products an expert layer;
* ``decode_step_paged`` is the head of ``decode_rows_paged``'s rows;
* a poisoned row trips ``finite`` for exactly its slot, at a small page and on the default engine.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jex_core

from benchmark.families.falcon_h1 import reference as falcon_reference
from benchmark.families.lfm2_moe import reference as lfm2_reference
from perceiver_io_tpu.generation.generate import GenerationConfig
from perceiver_io_tpu.generation.sampling import process_logits_batched, sample_token_batched
from perceiver_io_tpu.models.core.perceiver_ar import CausalSequenceModel
from perceiver_io_tpu.reliability import armed
from perceiver_io_tpu.serving import ServingEngine
from perceiver_io_tpu.serving.engine import TICK_SCOPES, RequestStatus
from tests import falcon_h1_toy, lfm2_moe_toy
from tests.test_falcon_h1 import _prefill as _falcon_prefill
from tests.test_lfm2_moe import _prefill as _lfm2_prefill
from tests.test_paging import _reference_tokens
from tests.test_ragged_tick import LATENTS, PS, VOCAB, WINDOW, _make_model

FALCON_ENGINE = dict(num_slots=3, kv_page_size=8, prefill_chunk_tokens=8, num_kv_pages=40)
AR_PAGED = dict(num_slots=3, kv_page_size=PS, prefill_chunk_tokens=4, max_prefill_slots=2)
AR_DENSE = dict(num_slots=3)  # the default-constructed engine: one page a window, one-shot admission
ENGINES = {"falcon_h1": FALCON_ENGINE, "lfm2_moe": FALCON_ENGINE, "perceiver_ar_paged": AR_PAGED,
           "perceiver_ar_dense": AR_DENSE}


@pytest.fixture(scope="module")
def models():
    falcon, falcon_params, falcon_weights = falcon_h1_toy.build()
    ar, ar_params = _make_model()
    return {"falcon_h1": (falcon, falcon_params, falcon_weights), "perceiver_ar": (ar, ar_params, None),
            "lfm2_moe": lfm2_moe_toy.build()}


def _engine(models, kind, **more):
    model, params, _ = models[kind if kind in models else "perceiver_ar"]
    return ServingEngine(model, params, **ENGINES[kind], **more)


# ---------------------------------------------------------------- (a) the compiled tick
def _tick_args(engine):
    return engine._jit_ragged_tick, engine._ragged_args(True, engine._forced_none, engine._use_forced_none)


def _sub_jaxprs(eqn):
    """(jaxpr, the eqn's operands its inputs stand for) of every jaxpr an equation calls."""
    p, name = eqn.params, eqn.primitive.name
    if name == "cond":
        return [(b.jaxpr, eqn.invars[1:]) for b in p["branches"]]
    if name == "while":
        nc, nb = p["cond_nconsts"], p["body_nconsts"]
        return [(p["body_jaxpr"].jaxpr, eqn.invars[nc:]),
                (p["cond_jaxpr"].jaxpr, list(eqn.invars[:nc]) + list(eqn.invars[nc + nb:]))]
    out = []
    for value in p.values():  # scan, jit, custom_jvp_call, ...: inputs map by position where they can
        inner = getattr(value, "jaxpr", value)
        if isinstance(inner, jex_core.Jaxpr):
            out.append((inner, eqn.invars if len(inner.invars) == len(eqn.invars) else [None] * len(inner.invars)))
    return out


def _riding(eqn):
    """What crosses a ``cond`` (its operands) or rides a loop (its carries and inputs, the
    loop's constants aside), and what comes out."""
    p, name = eqn.params, eqn.primitive.name
    skip = {"cond": 1, "while": p.get("cond_nconsts", 0) + p.get("body_nconsts", 0), "scan": p.get("num_consts", 0)}
    return list(eqn.invars[skip[name]:]) + list(eqn.outvars) if name in skip else []


def _walk(jaxpr, weights, vocab, found, stack=""):
    """Collect the head matmuls and every vocabulary-wide array that crosses a ``cond``
    or rides a loop, weights aside (``weights``: the variables that ARE the tick's
    parameters, followed into every sub-jaxpr by position). The sampler's own ``cond``
    over the logits, under ``tick.sample``, is where they are meant to die."""
    for eqn in jaxpr.eqns:
        where = f"{stack}/{eqn.source_info.name_stack}/{eqn.primitive.name}"
        if eqn.primitive.name == "dot_general" and eqn.outvars[0].aval.shape[-1:] == (vocab,):
            found["dots"].append(where)
        if TICK_SCOPES["sample"] not in where:
            found["crossing"] += [(where, v.aval) for v in _riding(eqn) if not isinstance(v, jex_core.Literal)
                                  and v not in weights and vocab in getattr(v.aval, "shape", ())]
        for inner, operands in _sub_jaxprs(eqn):
            inner_weights = {iv for iv, ov in zip(inner.invars, operands)
                             if ov is not None and not isinstance(ov, jex_core.Literal) and ov in weights}
            _walk(inner, inner_weights, vocab, found, where)


@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_the_tick_holds_one_head_matmul_and_carries_nothing_of_the_vocabularys_width(models, kind):
    engine = _engine(models, kind)
    vocab, hidden = engine._traits.vocab_size, engine._traits.hidden_size
    assert engine._state.next_hidden.shape == (3, hidden) and hidden != vocab
    tick, args = _tick_args(engine)
    # arguments and results: the parameters aside (args[0]: the head's weights are there)
    lowered = tick.lower(*args)
    carried_in = jax.tree.leaves(lowered.args_info[0][1:])
    assert carried_in and not [a for a in carried_in if vocab in a._aval.shape]
    assert not [o for o in jax.tree.leaves(lowered.out_info) if vocab in o.shape]
    # the text: one dot whose result is (slots, vocab), in the program's own body
    dots = [line for line in lowered.as_text().splitlines()
            if "stablehlo.dot_general" in line and f"x{vocab}x" in line.rsplit("->", 1)[-1]]
    assert len(dots) == 1, dots
    assert f"-> tensor<3x{vocab}x" in dots[0]
    # the jaxpr: the same one matmul, under tick.decode's ``head`` scope, outside every
    # loop; and nothing vocabulary-wide crosses a cond or rides a loop
    top = jax.make_jaxpr(tick)(*args).jaxpr.eqns
    assert len(top) == 1
    body = top[0].params["jaxpr"].jaxpr
    n_weights = len(jax.tree.leaves(args[0]))
    found = {"dots": [], "crossing": []}
    _walk(body, set(body.invars[:n_weights]), vocab, found)
    assert len(found["dots"]) == 1 and not found["crossing"], found
    assert f"{TICK_SCOPES['decode']}/" in found["dots"][0] and "/head/" in found["dots"][0]
    assert "while" not in found["dots"][0] and "scan" not in found["dots"][0]
    assert TICK_SCOPES["finish_lanes"] not in found["dots"][0]


GROUPED = "ragged_dot_general"  # ``ops/moe.py``'s grouped products as the CPU lowers them


def _count(jaxpr, primitive, loops=False):
    """Equations of ``primitive`` in a jaxpr and everything it calls, loop bodies aside."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == primitive
        if loops or eqn.primitive.name not in ("while", "scan"):
            n += sum(_count(inner, primitive, loops) for inner, _ in _sub_jaxprs(eqn))
    return n


def test_a_tick_with_a_riding_chunk_lane_runs_each_expert_layer_once(models):
    """ISSUE 46: for a model whose chunk rows ride its decode pass the rows phase is two
    ``cond``s of which a tick takes one or neither: the model's loop over the carried chunk
    lanes with the decode step riding the first, or the decode step alone. Each holds one
    pair of grouped products an expert layer (``ragged_dot`` on the CPU), the first inside
    its loop over the lanes: a tick with ONE chunk lane and decoding slots reads each expert
    layer once. The head's one matmul lies in front of both."""
    engine = _engine(models, "lfm2_moe")
    assert engine._traits.chunk_rides_decode
    tick, args = _tick_args(engine)
    body = jax.make_jaxpr(tick)(*args).jaxpr.eqns[0].params["jaxpr"].jaxpr
    taken = lambda e: e.params["branches"][1].jaxpr  # a cond's index 1 is the branch of a true predicate
    rows = [e for e in body.eqns if e.primitive.name == "cond" and _count(taken(e), GROUPED, loops=True)]
    assert len(rows) == 2
    assert all(_count(e.params["branches"][0].jaxpr, GROUPED, loops=True) == 0 for e in rows)
    pairs = len(engine.model.config.expert_layers)
    lanes_ridden, decode_alone = map(taken, rows)
    assert _count(lanes_ridden, GROUPED) == 0 and _count(lanes_ridden, GROUPED, loops=True) == 2 * pairs
    assert _count(decode_alone, GROUPED) == _count(decode_alone, GROUPED, loops=True) == 2 * pairs
    names = [str(e.source_info.name_stack) for e in rows]
    assert "tick." not in names[0] and TICK_SCOPES["decode"] in names[1]
    vocab = engine._traits.vocab_size
    found = {"dots": [], "crossing": []}
    _walk(body, set(), vocab, found)
    assert len(found["dots"]) == 1 and all(found["dots"][0] not in str(e.source_info.name_stack) for e in rows)
    sampler = body.eqns[:body.eqns.index(rows[0])]
    assert any(e.primitive.name == "cond" and _count(taken(e), "dot_general", loops=True) for e in sampler)


# ------------------------------------------- (b) the finish tick samples; tokens by hand
def _sample_chain(step_logits, first_logits, n_new, rng, sampling):
    """The engine's documented chain (``decode_body``), by hand for one request: each step
    splits the request's key, samples with the second half and keeps the first."""
    temperature, top_k, do_sample = sampling
    logits, out = first_logits, []
    for _ in range(n_new):
        keys = jax.random.split(rng)
        processed = process_logits_batched(logits, jnp.asarray([temperature], jnp.float32),
                                           jnp.asarray([top_k], jnp.int32), jnp.ones((1,), jnp.float32))
        tok = sample_token_batched(keys[1][None], processed, jnp.asarray([do_sample])).astype(jnp.int32)
        rng = keys[0]
        out.append(int(tok[0]))
        logits = step_logits(tok)
    return out


def _perceiver_by_hand(model, params, prompt, n_new, rng, sampling):
    """Through the model's LOGITS methods (``prefill`` / ``decode_step``) on generate()'s
    canonical left-padded form: no engine, no carried row."""
    n = len(prompt)
    ids, pad = np.zeros((1, WINDOW), np.int32), np.ones((1, WINDOW), bool)
    ids[0, WINDOW - n:], pad[0, WINDOW - n:] = prompt, False
    cache = model.init_cache(batch_size=1, dtype=jnp.float32)
    logits, cache = model.apply(params, jnp.asarray(ids), WINDOW - LATENTS, cache, pad_mask=jnp.asarray(pad),
                                method=CausalSequenceModel.prefill)
    box = [cache]

    def step(tok):
        step_logits, box[0] = model.apply(params, tok[:, None], box[0], method=CausalSequenceModel.decode_step)
        return step_logits[:, -1]

    return _sample_chain(step, logits[:, -1], n_new, rng, sampling)


def _falcon_by_hand(model, params, prompt, n_new, rng, sampling):
    """Through ``prefill_chunk_paged``, ``_head`` of the prompt's last row and
    ``decode_step_paged`` (the benchmark's ``logits_check`` path) on a cache of one slot."""
    ps = FALCON_ENGINE["kv_page_size"]
    cache = model.init_paged_cache(1, 16, ps, jnp.float32)
    pages = -(-(len(prompt) + n_new) // ps)
    table = np.zeros((cache.pages_per_slot,), np.int32)
    table[:pages] = 1 + np.arange(pages)
    cache, first = _falcon_prefill(model, params, cache, np.asarray(prompt), 0, jnp.asarray(table),
                                   FALCON_ENGINE["prefill_chunk_tokens"])
    box = [cache]

    def step(tok):
        step_logits, box[0] = model.apply(params, tok[:, None], box[0], method=type(model).decode_step_paged)
        return step_logits[:, 0]

    return _sample_chain(step, first[None], n_new, rng, sampling)


def _lfm2_by_hand(model, params, prompt, n_new, rng, sampling):
    """Through ``prefill_chunk_paged``, ``_head`` of the prompt's last row and
    ``decode_step_paged`` on a cache of one slot: no engine, no riding lane."""
    ps = FALCON_ENGINE["kv_page_size"]
    cache = model.init_paged_cache(1, 16, ps, jnp.float32)
    pages = -(-(len(prompt) + n_new) // ps)
    table = np.zeros((cache.pages_per_slot,), np.int32)
    table[:pages] = 1 + np.arange(pages)
    cache, first = _lfm2_prefill(model, params, cache, np.asarray(prompt), 0, jnp.asarray(table),
                                 FALCON_ENGINE["prefill_chunk_tokens"])
    box = [cache]

    def step(tok):
        step_logits, box[0] = model.apply(params, tok[:, None], box[0], method=type(model).decode_step_paged)
        return step_logits[:, 0]

    return _sample_chain(step, first[None], n_new, rng, sampling)


GREEDY, SAMPLED = (1.0, 0, False), (0.8, 20, True)
# (family, engine, the decoding neighbour's prompt, the late prompt: chunk lanes, then a
# finish that rides a decoding tick)
LATE = {
    "falcon_h1": ("falcon_h1", [7, 3, 11, 2, 5], list(range(20, 37))),       # 17 tokens: chunks of 8, 8, 1
    "perceiver_ar_paged": ("perceiver_ar", [5, 6, 7], list(range(3, 12))),   # 9 tokens: 3 by a chunk lane, 6 latents
}


@pytest.mark.parametrize("sampling", [GREEDY, SAMPLED], ids=["greedy", "sampled"])
@pytest.mark.parametrize("kind", sorted(LATE))
def test_a_finish_riding_a_decoding_tick_is_sampled_in_that_tick(models, kind, sampling):
    family, neighbour_prompt, late_prompt = LATE[kind]
    model, params, weights = models[family]
    engine = _engine(models, kind)
    config = GenerationConfig(max_new_tokens=6, do_sample=sampling[2], temperature=sampling[0],
                              top_k=sampling[1] or None)
    neighbour = engine.submit(neighbour_prompt, max_new_tokens=24)
    while len(neighbour.output_ids) < 2:
        engine.step()
    late = engine.submit(late_prompt, config=config, rng=jax.random.PRNGKey(11))
    seen = []
    for _ in range(8):
        before = (late.admitted_at, len(late.output_ids), len(neighbour.output_ids))
        engine.step()
        seen.append((before, (late.admitted_at, len(late.output_ids), len(neighbour.output_ids))))
        if late.output_ids:
            break
    (ready_before, late_before, neighbour_before), (ready_after, late_after, neighbour_after) = seen[-1]
    # the tick that ended the prompt (decode-ready stamped inside it) also decoded the
    # neighbour AND harvested the late request's first token: no tick between
    assert ready_before is None and ready_after is not None
    assert (late_before, late_after) == (0, 1) and neighbour_after == neighbour_before + 1
    assert len(seen) >= 2  # chunk lanes rode earlier ticks, which decoded the neighbour too
    assert all(after[2] == before[2] + 1 for before, after in seen)
    engine.run_until_drained(max_steps=200)
    assert late.ok and neighbour.ok and engine.decode_compilations == 1
    by_hand = _falcon_by_hand if family == "falcon_h1" else _perceiver_by_hand
    assert late.result().tolist() == by_hand(model, params, late_prompt, 6, jax.random.PRNGKey(11), sampling)
    assert neighbour.result().tolist() == by_hand(model, params, neighbour_prompt, 24, jax.random.PRNGKey(0), GREEDY)
    if sampling is GREEDY and family == "perceiver_ar":
        assert late.result().tolist() == _reference_tokens(model, params, late_prompt, GenerationConfig(max_new_tokens=6))
    if sampling is GREEDY and family == "falcon_h1":
        tokens = np.asarray(late.output_ids)
        scored = falcon_reference.score_served(weights, falcon_h1_toy.SIZES, late_prompt, tokens, pad_to=16)
        assert np.array_equal(np.asarray(scored).argmax(axis=-1), tokens)


@pytest.mark.parametrize("sampling", [GREEDY, SAMPLED], ids=["greedy", "sampled"])
def test_a_finish_riding_a_decoding_tick_of_a_model_whose_chunks_ride_is_sampled_in_the_next_tick(models, sampling):
    """ISSUE 46, the LFM2 twin of the test above: the tick that ends the prompt decodes the
    neighbour and installs the late slot's row AFTER its sampler ran, so the late request's
    first token is the next tick's; its chunk lanes rode the decode step in every tick;
    and the tokens are still the model's own logits path's."""
    model, params, weights = models["lfm2_moe"]
    neighbour_prompt, late_prompt = [7, 3, 11, 2, 5], list(range(20, 37))  # 17 tokens: chunks of 8, 8, 1
    engine = _engine(models, "lfm2_moe")
    config = GenerationConfig(max_new_tokens=6, do_sample=sampling[2], temperature=sampling[0],
                              top_k=sampling[1] or None)
    neighbour = engine.submit(neighbour_prompt, max_new_tokens=24)
    while len(neighbour.output_ids) < 2:
        engine.step()
    riding_before = engine.metrics.snapshot()["ragged_tick"]["riding_chunk_lanes"]
    late = engine.submit(late_prompt, config=config, rng=jax.random.PRNGKey(11))
    seen = []
    for _ in range(8):
        before = (late.admitted_at, len(late.output_ids), len(neighbour.output_ids))
        engine.step()
        seen.append((before, (late.admitted_at, len(late.output_ids), len(neighbour.output_ids))))
        if late.output_ids:
            break
    assert len(seen) == 4  # three chunk lanes, each riding a tick that decoded the neighbour, then one tick more
    (ready_before, _, _), (ready_after, late_after, _) = seen[-2]
    assert ready_before is None and ready_after is not None and late_after == 0  # decode-ready, not yet sampled
    assert seen[-1][0][0] == ready_after and seen[-1][1][1] == 1 and late.first_token_at > late.admitted_at
    assert all(after[2] == before[2] + 1 for before, after in seen)  # the neighbour gained a token in every tick
    assert engine.metrics.snapshot()["ragged_tick"]["riding_chunk_lanes"] == riding_before + 3
    engine.run_until_drained(max_steps=200)
    assert late.ok and neighbour.ok and engine.decode_compilations == 1
    assert late.result().tolist() == _lfm2_by_hand(model, params, late_prompt, 6, jax.random.PRNGKey(11), sampling)
    assert neighbour.result().tolist() == _lfm2_by_hand(model, params, neighbour_prompt, 24, jax.random.PRNGKey(0), GREEDY)
    if sampling is GREEDY:
        tokens = np.asarray(late.output_ids)
        scored = lfm2_reference.score_served(weights, lfm2_moe_toy.SIZES, late_prompt, tokens, pad_to=16)
        assert np.array_equal(np.asarray(scored).argmax(axis=-1), tokens)


# --------------------------------------------------- (d) logits = the head of the rows
@pytest.mark.parametrize("kind", sorted(LATE))
def test_decode_step_paged_is_the_head_of_the_rows_methods_rows(models, kind):
    family, neighbour_prompt, late_prompt = LATE[kind]
    model, params, _ = models[family]
    engine = _engine(models, kind)
    handles = [engine.submit(p, max_new_tokens=12) for p in (neighbour_prompt, late_prompt)]
    while not all(h.output_ids for h in handles):
        engine.step()
    cache, ids = engine._cache, jnp.asarray([[4], [9], [1]], jnp.int32)
    logits, after = model.apply(params, ids, cache, method=type(model).decode_step_paged)
    rows, after_rows = model.apply(params, ids, cache, method=type(model).decode_rows_paged)
    assert rows.shape == (3, engine._traits.hidden_size) and rows.dtype == engine._state.next_hidden.dtype
    assert logits.shape == (3, 1, engine._traits.vocab_size)
    np.testing.assert_array_equal(np.asarray(logits[:, 0]),
                                  np.asarray(model.apply(params, rows, method=type(model)._head)))
    for a, b in zip(jax.tree.leaves(after), jax.tree.leaves(after_rows)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------- (c) a poisoned row, contained
@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_a_poisoned_row_fails_exactly_its_slot(models, kind):
    prompts = [[5, 6, 7], [9, 8, 7, 6], [2, 4]]

    def run(poison):
        engine = _engine(models, kind)
        handles = [engine.submit(p, max_new_tokens=10) for p in prompts]
        while not all(len(h.output_ids) >= 2 for h in handles):
            engine.step()
        if poison:
            with armed("serving.nan", slot=handles[1].slot):
                engine.step()
            assert handles[1].status is RequestStatus.FAILED and handles[1].finish_reason == "nonfinite_logits"
            assert np.isfinite(np.asarray(engine._state.next_hidden)).all()  # the row went with the slot
        engine.run_until_drained(max_steps=200)
        return handles

    clean, poisoned = run(False), run(True)
    assert [h.ok for h in poisoned] == [True, False, True]
    for i in (0, 2):  # the slot-mates never saw it: token for token the clean run
        assert poisoned[i].result().tolist() == clean[i].result().tolist()
    assert len(poisoned[1].output_ids) < 10
    assert poisoned[1].output_ids == clean[1].output_ids[:len(poisoned[1].output_ids)]
