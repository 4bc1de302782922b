"""Unified ragged tick: one fused program per steady-state tick (ISSUE 19).

The contract: on a paged engine every steady-state tick — prefill chunks,
latent finishes, fault poison, batched decode, quantized-page scale resets —
dispatches as ONE compiled program whose lanes are a host-built fixed-shape
work descriptor, and the emitted token streams are ``generate()``'s in
float64 (near-tie argmax flips cannot mask a real bug; int8 / int4 pools
under the same tick: tests/test_kv_quant.py). The compile-count invariant is
"the tick program compiles exactly once, ever" and the serving-metrics
``ragged_tick`` block pins programs-per-tick at 1.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu.generation.generate import GenerationConfig
from perceiver_io_tpu.models.core.config import CausalSequenceModelConfig
from perceiver_io_tpu.models.core.perceiver_ar import CausalSequenceModel
from perceiver_io_tpu.obs.core import TelemetryRecorder
from perceiver_io_tpu.ops.paged_decode_kernel import PagedKVCache
from perceiver_io_tpu.reliability import armed
from perceiver_io_tpu.serving import ServingEngine
from perceiver_io_tpu.serving.metrics import SCHEMA, load_metrics_jsonl
from perceiver_io_tpu.serving.tick_descriptor import TickFields
from tests.test_paging import _reference_tokens  # the same toy model: window 12 over 6 latents

VOCAB = 262
WINDOW = 12
LATENTS = 6
PS = 4


def _make_model(param_dtype=jnp.float32):
    config = CausalSequenceModelConfig(
        vocab_size=VOCAB, max_seq_len=WINDOW, max_latents=LATENTS,
        num_channels=16, num_heads=2, num_self_attention_layers=2,
        cross_attention_dropout=0.0,
    )
    model = CausalSequenceModel(config=config, param_dtype=param_dtype)
    rng = jax.random.PRNGKey(0)
    prompt = jax.random.randint(rng, (1, 8), 0, VOCAB)
    params = jax.jit(model.init, static_argnames="prefix_len")(rng, prompt, prefix_len=2)
    return model, params


@pytest.fixture(scope="module")
def setup():
    return _make_model()


@pytest.fixture(scope="module")
def setup64(x64):
    return _make_model(param_dtype=jnp.float64)


# prompts chosen to straddle the prefill ladder rungs AND the page grid:
# shorter than the latent floor (classic path), mid-ladder, partial tail
# page (9 = 2 pages + 1 row), and the full window (ring-wrap territory once
# decode appends roll the oldest page)
CHURN_PROMPTS = [[5, 6, 7], [2] * 5, list(range(3, 12)), [9] * WINDOW,
                 [41, 40, 39, 38], list(range(60, 67))]
CHURN_NEW = [6, 3, 5, 8, 4, 7]


def _run_churn(model, params, **engine_kw):
    engine = ServingEngine(model, params, num_slots=3, kv_page_size=PS,
                           **engine_kw)
    assert engine.ragged
    handles = []
    for i, (p, m) in enumerate(zip(CHURN_PROMPTS, CHURN_NEW)):
        handles.append(engine.submit(p, max_new_tokens=m,
                                     rng=jax.random.PRNGKey(i)))
        engine.step()
    engine.run_until_drained(max_steps=400)
    assert all(h.done for h in handles)
    assert [len(h.output_ids) for h in handles] == CHURN_NEW
    return [h.result().tolist() for h in handles], engine


CHURN_ADMISSION = {"one_shot": {}, "chunked": {"prefill_chunk_tokens": 4, "max_prefill_slots": 2}}
_CHURN_RUNS: dict = {}  # admission -> the churn's tokens (one engine life a mode)


@pytest.mark.parametrize("admission", sorted(CHURN_ADMISSION))
@pytest.mark.parametrize("request_no", range(len(CHURN_PROMPTS)))
def test_ragged_tick_f64_matches_generate(setup64, request_no, admission):
    """The headline parity: fused-tick tokens == ``generate()``'s in float64,
    across ladder-straddling lengths, ring wraps, partial tail pages,
    interleaved admissions — with and without chunked admission."""
    model, params = setup64
    if admission not in _CHURN_RUNS:
        _CHURN_RUNS[admission], _ = _run_churn(model, params, **CHURN_ADMISSION[admission])
    expected = _reference_tokens(model, params, CHURN_PROMPTS[request_no],
                                 GenerationConfig(max_new_tokens=CHURN_NEW[request_no]))
    assert _CHURN_RUNS[admission][request_no] == expected


def test_ragged_tick_sampled_rng_chain_identical(setup):
    """Sampling: the per-slot rng split chain is part of the fused decode
    phase, and a finish lane installs its request's key — sampled streams
    match the default engine's (one page a window) seed for seed, on both
    admission paths."""
    model, params = setup

    def run(**pool):
        engine = ServingEngine(model, params, num_slots=2, **pool)
        handles = [
            engine.submit(p, max_new_tokens=6, do_sample=True, temperature=0.8,
                          top_k=20, rng=jax.random.PRNGKey(7 + i))
            for i, p in enumerate(([5, 6, 7], list(range(3, 12))))
        ]
        engine.run_until_drained(max_steps=200)
        return [h.result().tolist() for h in handles]

    assert run(kv_page_size=PS) == run()


def test_ragged_tick_one_program_ever(setup):
    """THE perf invariant: steady-state churn — mixed admissions, chunked
    prefill, evictions — compiles the fused tick program exactly once, the
    watchdog budget of 1 holds, and the v11 metrics pin programs-per-tick
    at 1 for decode-carrying ticks."""
    model, params = setup
    toks, engine = _run_churn(model, params,
                              prefill_chunk_tokens=4, max_prefill_slots=2)
    assert engine._jit_ragged_tick._cache_size() == 1
    assert engine.decode_compilations == 1  # the property pins the fused jit
    if engine.watchdog is not None:
        engine.watchdog.check()  # ragged_tick budget=1 holds after churn
    # no per-phase program exists beside it: the tick is the one dispatcher
    assert not hasattr(engine, "_jit_decode") and not hasattr(engine, "_jit_chunk_kv")
    snap = engine.metrics.snapshot()
    assert snap["ragged_tick"]["enabled"] is True
    assert snap["ragged_tick"]["ticks"] > 0
    assert snap["ragged_tick"]["programs_per_tick"]["p50"] == 1.0
    assert snap["ragged_tick"]["descriptor_build_s"]["p95"] >= 0.0
    # pages all home, slots clear — the descriptor leaked nothing
    assert engine._pool.pages_in_use == 0
    assert all(p is None for p in engine._slot_pages)
    assert not engine._tick_chunks and not engine._tick_finishes


def test_ragged_preempt_and_quarantine_drop_buffered_lanes(setup):
    """An admission evicted the same tick it buffered descriptor lanes must
    take those lanes with it (its pages return to the pool mid-tick): churn
    with deadline-expired work stays deterministic and drains whole."""
    model, params = setup

    def run():
        engine = ServingEngine(model, params, num_slots=2, kv_page_size=PS,
                               prefill_chunk_tokens=4, max_prefill_slots=2,
                               default_deadline_s=60.0)
        handles = [engine.submit(p, max_new_tokens=4, rng=jax.random.PRNGKey(i))
                   for i, p in enumerate(CHURN_PROMPTS[:4])]
        engine.run_until_drained(max_steps=300)
        return [h.result().tolist() for h in handles], engine

    toks1, e1 = run()
    toks2, _ = run()
    assert toks1 == toks2
    assert e1._pool.pages_in_use == 0
    # exercise _drop_tick_work directly: buffered lanes for a slot vanish
    e1._tick_chunks.append((1, None, 0, 0, 0, None))
    e1._tick_finishes.append((1, None, None, 0, None, None))
    e1._tick_resets.append((0, None))
    e1._tick_poison = 1
    e1._drop_tick_work(1)
    assert not e1._tick_chunks and not e1._tick_finishes
    assert e1._tick_resets and e1._tick_poison is None
    e1._drop_tick_work(0)
    assert not e1._tick_resets


# --------------------------------------------------------------- descriptor
def _seventeen_arrays(engine, any_decode):
    """The descriptor as the seventeen-array signature built it (the loops
    the packed layout replaced, kept as the reference): numpy arrays in
    their own dtypes plus the five scalars, by ``TickFields`` name."""
    lanes, cap = engine._ragged_lanes, engine._ragged_chunk_cap
    P = engine._pages_per_slot
    reset_ids = np.zeros((lanes * P,), np.int32)
    for i, (_slot, ids_row) in enumerate(engine._tick_resets):
        reset_ids[i * P:(i + 1) * P] = ids_row
    ch_ids = np.zeros((lanes, cap), np.int32)
    ch_offset = np.zeros((lanes,), np.int32)
    ch_count = np.zeros((lanes,), np.int32)
    ch_lstart = np.full((lanes,), 2 ** 30, np.int32)
    ch_tables = np.zeros((lanes, P), np.int32)
    for i, (_slot, ids, off, c, lstart, trow) in enumerate(engine._tick_chunks):
        ch_ids[i], ch_offset[i], ch_count[i] = ids, off, c
        ch_lstart[i], ch_tables[i] = lstart, trow
    fin_active = np.zeros((lanes,), bool)
    fin_slot = np.zeros((lanes,), np.int32)
    fin_tables = np.zeros((lanes, P), np.int32)
    fin_ids = np.zeros((lanes, engine._latents), np.int32)
    fin_n = np.zeros((lanes,), np.int32)
    fin_rng = np.zeros((lanes, 2), np.uint32)
    fin_temp = np.ones((lanes,), np.float32)
    fin_tk = np.zeros((lanes,), np.int32)
    fin_tp = np.ones((lanes,), np.float32)
    fin_ds = np.zeros((lanes,), bool)
    fin_pad = np.zeros((lanes,), np.int32)
    for i, (slot, trow, ids_latent, n, rng, sampling) in enumerate(engine._tick_finishes):
        fin_active[i], fin_slot[i], fin_tables[i] = True, slot, trow
        fin_ids[i], fin_n[i], fin_rng[i] = ids_latent, n, rng
        fin_temp[i], fin_tk[i], fin_tp[i], fin_ds[i], fin_pad[i] = sampling
    poison = -1 if engine._tick_poison is None else int(engine._tick_poison)
    return TickFields(
        any_reset=np.bool_(bool(engine._tick_resets)),
        any_chunk=np.bool_(bool(engine._tick_chunks)),
        any_finish=np.bool_(bool(engine._tick_finishes)), poison=np.int32(poison),
        any_decode=np.bool_(any_decode), reset_ids=reset_ids, ch_ids=ch_ids,
        ch_offset=ch_offset, ch_count=ch_count, ch_latent_start=ch_lstart,
        ch_tables=ch_tables, fin_active=fin_active, fin_slot=fin_slot,
        fin_tables=fin_tables, fin_ids=fin_ids, fin_n=fin_n, fin_rng=fin_rng,
        fin_temp=fin_temp, fin_tk=fin_tk, fin_tp=fin_tp, fin_ds=fin_ds, fin_pad=fin_pad,
    )


def _buffer_tick(engine, kind, rs):
    """Fill the engine's tick buffers, as admission would, with one tick of
    ``kind``; returns the tick's ``any_decode``."""
    P, cap, L = engine._pages_per_slot, engine._ragged_chunk_cap, engine._latents
    table = lambda: rs.randint(1, 7, size=(P,)).astype(np.int32)
    if kind in ("chunk_lanes", "prefill_only"):
        for slot in (2, 0):
            engine._tick_chunks.append(
                (slot, rs.randint(0, VOCAB, size=(cap,)).astype(np.int32),
                 int(rs.randint(0, WINDOW)), int(rs.randint(1, cap + 1)),
                 int(rs.randint(0, WINDOW)), table()))
    if kind == "finish_lanes":
        # a sampled and a greedy request: full-range rng words, temperature
        # and top_p that are no round binary fractions
        for slot, sampling in ((1, (0.7300000190734863, 40, 0.9100000262260437, True, 3)),
                               (2, (1.0, 0, 1.0, False, 0))):
            rng = rs.randint(0, 2 ** 32, size=(2,), dtype=np.uint64).astype(np.uint32)
            engine._tick_finishes.append(
                (slot, table(), rs.randint(0, VOCAB, size=(L,)).astype(np.int32),
                 int(rs.randint(L, WINDOW + 1)), rng, sampling))
    if kind == "resets_int8":
        for slot in (0, 2):
            engine._tick_resets.append((slot, table()))
    if kind == "poison":
        engine._tick_poison = 1
    return kind != "prefill_only"


@pytest.mark.parametrize("kind", ["idle", "chunk_lanes", "finish_lanes",
                                  "resets_int8", "poison", "prefill_only"])
def test_descriptor_pack_unpack_round_trip(setup, kind, monkeypatch):
    """One int32 array carries what the seventeen arrays and five scalars
    carried: packed on the host, unpacked in a program, every field comes
    back in its dtype and shape, BIT-exact (float32 sampling parameters and
    uint32 rng words travel by bit pattern through the int32 view)."""
    model, params = setup
    engine = ServingEngine(model, params, num_slots=3, kv_page_size=PS,
                           prefill_chunk_tokens=4, max_prefill_slots=2,
                           kv_quant="int8" if kind == "resets_int8" else None)
    layout = engine._desc_layout
    assert tuple(layout.fields) == TickFields._fields
    any_decode = _buffer_tick(engine, kind, np.random.RandomState(31))
    want = _seventeen_arrays(engine, any_decode)
    sent, device_put = [], jax.device_put
    monkeypatch.setattr(jax, "device_put",
                        lambda x, *a, **kw: sent.append(x) or device_put(x, *a, **kw))
    for round_no in range(2):
        args = engine._ragged_args(any_decode, engine._forced_none,
                                   engine._use_forced_none)
        # the runtime may read what it is handed after device_put returns:
        # every packed tick sends a host array of its own, never the template
        assert len(sent) == (kind != "idle") * (round_no + 1)
        owned = [engine._desc_idle_host] + sent
        assert not any(np.shares_memory(x, y) for i, x in enumerate(owned)
                       for y in owned[:i])
        assert len(args) == 6 and args[0] is engine.params
        desc = args[3]
        assert isinstance(desc, jax.Array) and desc.dtype == jnp.int32
        assert desc.shape == (layout.words,)
        # only the tick that carries nothing but decode rides the resident one
        assert (desc is engine._desc_decode_only) == (kind == "idle")
        got = jax.jit(layout.unpack)(desc)
        for name, expected in want._asdict().items():
            value = np.asarray(getattr(got, name))
            assert value.dtype == expected.dtype and value.shape == expected.shape, name
            assert value.tobytes() == expected.tobytes(), name
    # a packed tick leaves nothing behind in the template
    engine._tick_chunks.clear(), engine._tick_finishes.clear(), engine._tick_resets.clear()
    engine._tick_poison = None
    again = engine._ragged_args(False, engine._forced_none, engine._use_forced_none)[3]
    assert np.array_equal(np.asarray(again), layout.idle(any_decode=False))


class _Transfers:
    """Counts the engine's explicit host-to-device calls (``jax.device_put``,
    ``jnp.asarray``, ``jnp.array``) while installed; the implicit ones (a
    numpy array or a Python scalar handed to a jit) are the transfer guard's
    to refuse."""

    def __init__(self, monkeypatch):
        self.calls = []
        for module, name in ((jax, "device_put"), (jnp, "asarray"), (jnp, "array")):
            monkeypatch.setattr(module, name, self._counting(name, getattr(module, name)))

    def _counting(self, name, fn):
        def counted(*args, **kwargs):
            self.calls.append(name)
            return fn(*args, **kwargs)
        return counted


def test_descriptor_transfers_zero_on_decode_only_one_on_lane_ticks(setup, monkeypatch):
    """The tick's host-to-device traffic: NOTHING on a decode-only tick (the
    resident descriptor: refused outright by the guard otherwise), exactly
    ONE explicit transfer on a tick with a lane, and never an implicit one."""
    model, params = setup
    engine = ServingEngine(model, params, num_slots=3, kv_page_size=PS,
                           prefill_chunk_tokens=4, max_prefill_slots=2)
    first = engine.submit(list(range(3, 12)), max_new_tokens=12)
    for _ in range(4):  # chunks, the finish, then decode: everything compiled
        engine.step()
    assert first.slot is not None and not engine._prefilling

    transfers = _Transfers(monkeypatch)
    with jax.transfer_guard_host_to_device("disallow_explicit"):
        assert engine.step_dispatch()  # decode-only
    assert transfers.calls == []
    engine.step_harvest()

    engine.submit(list(range(20, 28)), max_new_tokens=2)  # split admission: chunk lanes
    kinds = []
    while engine._prefilling or not kinds:
        del transfers.calls[:]
        with jax.transfer_guard_host_to_device("disallow"):
            assert engine.step_dispatch()
        kinds.append((engine._tick_chunk_items, engine._tick_finish_items))
        assert transfers.calls == ["device_put"], kinds
        engine.step_harvest()
    assert any(ch for ch, _ in kinds) and any(fin for _, fin in kinds)
    assert engine.decode_compilations == 1


def test_descriptor_counters_over_every_tick_kind(setup):
    """A run that mixes every kind of tick — resets (int8 pool), chunk
    lanes, finish lanes, poison, prefill-only, decode-only — compiles the
    tick once, leaves the watchdog silent, and the snapshot's two keys read
    what the run did (counted here at the dispatcher's door)."""
    model, params = setup
    engine = ServingEngine(model, params, num_slots=3, kv_page_size=PS,
                           prefill_chunk_tokens=4, max_prefill_slots=2,
                           kv_quant="int8", telemetry=TelemetryRecorder())
    seen = []  # per fused dispatch: (resets, chunks, finishes, poison, any_decode)
    pack = engine._ragged_args

    def watched(any_decode, forced, use_forced):
        seen.append((len(engine._tick_resets), len(engine._tick_chunks),
                     len(engine._tick_finishes), engine._tick_poison is not None,
                     bool(any_decode)))
        return pack(any_decode, forced, use_forced)

    engine._ragged_args = watched
    handles = [engine.submit(list(range(3, 12)), max_new_tokens=6)]
    engine.step()  # the first chunk with nothing decoding: prefill-only
    for prompt in ([9] * WINDOW, list(range(60, 67))):
        handles.append(engine.submit(prompt, max_new_tokens=5))
        engine.step()
    with armed("serving.nan", slot=handles[0].slot):
        engine.step()
    engine.run_until_drained(max_steps=200)
    assert all(h.done for h in handles) and handles[0].status.value == "failed"

    carried = [any(work[:4]) or not work[4] for work in seen]
    for column, what in enumerate(("resets", "chunks", "finishes", "poison")):
        assert any(work[column] for work in seen), f"no tick carried {what}"
    assert any(not work[4] for work in seen), "no prefill-only tick"
    assert not all(carried), "no decode-only tick"
    assert engine.decode_compilations == 1
    assert engine.watchdog.check() == [] and engine.watchdog.violations == []
    block = engine.metrics.snapshot()["ragged_tick"]
    assert block["resident_descriptor_tick_pct"] == pytest.approx(
        100.0 * carried.count(False) / len(carried))
    p50, p95 = np.percentile([int(c) for c in carried], [50, 95])
    assert block["descriptor_transfers"] == {"p50": round(float(p50), 6),
                                             "p95": round(float(p95), 6)}
    assert block["ticks"] >= len(seen)
    engine.close()


# ------------------------------------------------- lanes carried, lanes compiled
@pytest.mark.parametrize("live", [0, 1, 2, 8])
def test_chunk_phase_runs_once_a_carried_lane(setup, live, monkeypatch):
    """The model's chunk phase pays for the lanes the tick CARRIES (packed from
    lane 0), not for the lanes the descriptor has room for: with jit off, count
    the lane body's ``write_rows`` calls for ``live`` lanes of 8 compiled; the
    jitted loop (a trip count read from the descriptor) writes the same pages."""
    model, params = setup
    engine = ServingEngine(model, params, num_slots=8, kv_page_size=PS, prefill_chunk_tokens=4)
    assert engine._ragged_lanes == 8  # the default: as many lanes as slots
    rs = np.random.RandomState(5)
    P, cap = engine._pages_per_slot, engine._ragged_chunk_cap
    counts = [int(rs.randint(1, cap + 1)) for _ in range(live)]
    for slot, count in enumerate(counts):
        engine._tick_chunks.append(
            (slot, rs.randint(0, VOCAB, size=(cap,)).astype(np.int32), PS * int(rs.randint(0, 2)),
             count, int(rs.randint(0, WINDOW)),
             rs.randint(1, engine._pool.num_pages, size=(P,)).astype(np.int32)))
    desc = engine._ragged_args(False, engine._forced_none, engine._use_forced_none)[3]
    lanes = engine._desc_layout.unpack(desc)
    assert int(np.sum(np.asarray(lanes.ch_count) > 0)) == live
    jitted = jax.jit(model.serving_chunk_phase)(engine.params, engine._cache, lanes)
    seen, write_rows = [], PagedKVCache.write_rows
    monkeypatch.setattr(PagedKVCache, "write_rows",
                        lambda self, trow, offset, count, k, v:
                        seen.append(int(count)) or write_rows(self, trow, offset, count, k, v))
    with jax.disable_jit():
        eager = model.serving_chunk_phase(engine.params, engine._cache, lanes)
    assert seen == counts  # one call a carried lane, in lane order; none for an idle one
    # every page but the trash page (0: garbage by design) holds the same rows
    for a, b in ((eager.ca.kp, jitted.ca.kp), (eager.ca.vp, jitted.ca.vp)):
        np.testing.assert_allclose(np.asarray(a)[1:], np.asarray(b)[1:], rtol=1e-6, atol=1e-7)
    if not live:
        assert np.array_equal(np.asarray(eager.ca.kp), np.asarray(engine._cache.ca.kp))
    engine.close()


# one mixed run: one-shot admissions (under LATENTS tokens), split ones, and a
# prompt that forks the first page of an earlier one out of the prefix cache
# (both end inside the window: a ring that wraps neither donates nor forks)
LANE_PROMPTS = [[5, 6, 7], list(range(3, 12)), [9] * WINDOW, [8] * PS + list(range(30, 36)), [2] * 5,
                list(range(60, 67)), [8] * PS + list(range(20, 26)), list(range(3, 11)) + [77, 78]]
LANE_NEW = [6, 5, 8, 2, 3, 7, 2, 6]
LANE_SLOTS = 4
_LANE_RUNS: dict = {}  # max_prefill_slots -> (tokens, lanes, decode_compilations, snapshot)


def _run_lanes(model, params, max_prefill_slots):
    if max_prefill_slots not in _LANE_RUNS:
        # pages to spare, so the cached page is still there when its sibling comes
        engine = ServingEngine(model, params, num_slots=LANE_SLOTS, kv_page_size=PS, prefill_chunk_tokens=4,
                               prefix_cache=True, num_kv_pages=8 * LANE_SLOTS,
                               max_prefill_slots=max_prefill_slots)
        handles = [engine.submit(p, max_new_tokens=m) for p, m in zip(LANE_PROMPTS[:6], LANE_NEW)]
        for _ in range(4):
            engine.step()
        handles += [engine.submit(p, max_new_tokens=m) for p, m in zip(LANE_PROMPTS[6:], LANE_NEW[6:])]
        engine.run_until_drained(max_steps=400)
        assert all(h.ok for h in handles)
        _LANE_RUNS[max_prefill_slots] = ([h.result().tolist() for h in handles], engine._ragged_lanes,
                                         engine.decode_compilations, engine.metrics.snapshot())
        engine.close()
    return _LANE_RUNS[max_prefill_slots]


@pytest.mark.parametrize("request_no", range(len(LANE_PROMPTS)))
def test_tokens_do_not_depend_on_the_compiled_lane_count(setup64, request_no):
    """2 compiled lanes (``max_prefill_slots=1``) against ``num_slots`` of them
    (the default): the same float64 tokens, ``generate()``'s, request by
    request; each engine compiled its one tick program once."""
    model, params = setup64
    narrow, wide = _run_lanes(model, params, 1), _run_lanes(model, params, None)
    assert (narrow[1], wide[1]) == (2, LANE_SLOTS)
    assert narrow[2] == wide[2] == 1
    for _, _, _, snap in (narrow, wide):
        assert snap["prefix_cache"]["hits"] >= 1 and snap["chunked_prefill"]["chunked_admissions"] >= 4
    assert wide[3]["ragged_tick"]["chunk_lanes"]["p95"] > 1  # some tick carried several lanes
    expected = _reference_tokens(model, params, LANE_PROMPTS[request_no],
                                 GenerationConfig(max_new_tokens=LANE_NEW[request_no]))
    assert narrow[0][request_no] == wide[0][request_no] == expected


@pytest.mark.parametrize("max_prefill_slots, lanes", [(1, 2), (2, 3), (None, 3)])
def test_snapshot_reads_lanes_compiled_and_chunk_lanes_carried(setup, max_prefill_slots, lanes):
    """``ragged_tick.lanes`` is the tick program's compiled lane count and
    ``chunk_lanes`` the chunk lanes a tick carried, over the ticks that carried
    one (the counts the dispatcher booked): how far apart the two are is
    readable without a trace."""
    model, params = setup
    engine = ServingEngine(model, params, num_slots=3, kv_page_size=PS, prefill_chunk_tokens=4,
                           max_prefill_slots=max_prefill_slots)
    block = engine.metrics.snapshot()["ragged_tick"]
    assert block["lanes"] == engine._ragged_lanes == lanes
    assert block["chunk_lanes"] == {"mean": 0.0, "p50": 0.0, "p95": 0.0}  # no tick carried one yet
    booked, book = [], engine.metrics.record_tick_dispatch

    def watched(programs, chunk_items, *rest):
        booked.append(chunk_items)
        return book(programs, chunk_items, *rest)

    engine.metrics.record_tick_dispatch = watched
    for prompt in (list(range(3, 12)), [9] * WINDOW, [5, 6, 7], list(range(60, 67))):
        engine.submit(prompt, max_new_tokens=5)
    engine.run_until_drained(max_steps=200)
    carried = [n for n in booked if n]
    assert carried and 0 in booked and max(carried) <= lanes
    block = engine.metrics.snapshot()["ragged_tick"]
    p50, p95 = np.percentile(carried, [50, 95])
    assert block["chunk_lanes"] == {"mean": round(sum(carried) / len(carried), 6),
                                    "p50": round(float(p50), 6), "p95": round(float(p95), 6)}
    assert block["lanes"] == lanes
    engine.close()


def test_ring_active_flag_equals_slot_state_active_after_every_step(setup):
    """``cache.sa.active`` is what tells the dense decode kernel which slots'
    rings to read (a slot it skips comes back zeros), and the engine harvests
    ``state.active`` slots: the two must be equal after every ``step()`` —
    through one-shot installs, chunked admissions (a slot in the middle of its
    prefill is neither), finish lanes, finishes, a deadline eviction and a
    quarantine. The snapshot reports the slots a tick decoded beside the pool's
    size."""
    model, params = setup
    engine = ServingEngine(model, params, num_slots=3, kv_page_size=PS,
                           prefill_chunk_tokens=4, max_prefill_slots=2)
    seen = set()

    def step():
        engine.step()
        flag, active = np.asarray(engine._cache.sa.active), np.asarray(engine._state.active)
        np.testing.assert_array_equal(flag, active)
        decoding = {slot for slot, _ in engine.scheduler.occupied()} - set(engine._prefilling)
        assert set(np.flatnonzero(flag).tolist()) == decoding
        seen.add((int(flag.sum()), len(engine._prefilling)))

    handles = []
    for i, (prompt, new) in enumerate(zip(CHURN_PROMPTS, CHURN_NEW)):
        handles.append(engine.submit(prompt, max_new_tokens=new, rng=jax.random.PRNGKey(i)))
        step()
    late = engine.submit([9] * WINDOW, max_new_tokens=50, deadline_s=1e-3)
    victim = engine.submit(list(range(3, 12)), max_new_tokens=8)
    while victim.slot is None or victim.slot in engine._prefilling:
        step()
    with armed("serving.nan", slot=victim.slot):
        step()
    for _ in range(200):
        if engine.scheduler.queue_depth == 0 and not list(engine.scheduler.occupied()):
            break
        step()
    assert all(h.done for h in handles) and [len(h.output_ids) for h in handles] == CHURN_NEW
    assert victim.status.value == "failed" and late.status.value == "timed_out"
    assert not np.asarray(engine._cache.sa.active).any()
    # some step left slots decoding beside a slot in the middle of its prefill, some a free slot
    assert any(n and prefilling for n, prefilling in seen) and any(n < 3 and not prefilling for n, prefilling in seen)
    snap = engine.metrics.snapshot()
    assert snap["chunked_prefill"]["chunked_admissions"] >= 1 and engine.prefill_compilations >= 1  # both paths
    block = snap["ragged_tick"]
    assert block["slots"] == 3
    assert 0 < block["decoding_slots"]["mean"] <= block["decoding_slots"]["p95"] <= 3
    assert {k: block["decoding_slots"][k] for k in ("p50", "p95")} == block["decode_items"]
    engine.close()


def test_engine_tokens_with_the_dense_kernel_reading_active_slots_only(monkeypatch):
    """The chip's path at a toy size: the tick's self-attention through
    ``fused_decode_attention`` (interpret mode; a ring of 128 rows is the
    smallest it takes) with ``live = 0`` for every slot that holds no installed
    request. Tokens equal the XLA formulation's (which computes every slot),
    request for request, through both admission paths, a free slot, a slot in
    the middle of its prefill and a slot used twice; and the kernel really was
    handed a live length a slot."""
    import perceiver_io_tpu.ops.decode_kernel as dk

    window, latents = 160, 128
    config = CausalSequenceModelConfig(
        vocab_size=VOCAB, max_seq_len=window, max_latents=latents, num_channels=16,
        num_heads=2, num_self_attention_layers=2, cross_attention_dropout=0.0,
    )
    model = CausalSequenceModel(config=config)
    rng = jax.random.PRNGKey(0)
    params = jax.jit(model.init, static_argnames="prefix_len")(
        rng, jax.random.randint(rng, (1, window), 0, VOCAB), prefix_len=window - latents)
    prompts = [list(range(5, 45)), list(range(1, 151)), [7, 3, 9] * 20, list(range(100, 240))]
    lives = []

    def run(kernel):
        if kernel:
            real = dk.fused_decode_attention_auto

            def interpreted(*args, live=None, **kw):
                lives.append(live)
                return real(*args, live=live, **kw, interpret=True)

            monkeypatch.setattr(dk, "decode_kernel_supported", lambda n_q, cap, *a, **kw: (n_q, cap) == (1, latents))
            monkeypatch.setattr(dk, "fused_decode_attention_auto", interpreted)
        engine = ServingEngine(model, params, num_slots=3, kv_page_size=16, prefill_chunk_tokens=32)
        handles = []
        for prompt in prompts:  # the fourth waits for a slot
            handles.append(engine.submit(prompt, max_new_tokens=4))
            engine.step()
        engine.run_until_drained(max_steps=100)
        assert all(h.ok for h in handles) and engine.decode_compilations == 1
        engine.close()
        return [h.result().tolist() for h in handles]

    plain = run(kernel=False)
    assert run(kernel=True) == plain
    assert lives and all(live is not None and live.shape == (3,) for live in lives)  # the layer loop's call


# -------------------------------------------------------------------- chaos
def test_chaos_ragged_tick_churn_scenario():
    """The ragged_tick_churn scenario is registered (the matrix smoke in
    test_reliability covers it in CI) and green standalone: quarantine +
    preemption inside the fused tick, survivors f64-identical to the
    same engine run uncontended, free list whole at drain."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chaos_check_ragged_under_test",
        os.path.join(os.path.dirname(__file__), "..", "scripts", "chaos_check.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert "ragged_tick_churn" in mod.CHECKS
    result = mod.main(["--checks", "ragged_tick_churn"])
    assert result["all_ok"], result["checks"]["ragged_tick_churn"]


# -------------------------------------------------------------- serve_bench
def test_serve_bench_ragged_arm_smoke(tmp_path):
    """CI satellite: ``serve_bench --ragged`` writes the ragged_tick section
    — tokens/s + inter-token p95 under the fused tick, its one program a
    tick, and the int4 sessions-at-fixed-HBM comparison with its
    >= 1.8x-vs-fp acceptance — into the BENCH_serving.json artifact."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "serve_bench_ragged_under_test",
        os.path.join(os.path.dirname(__file__), "..", "scripts", "serve_bench.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    out = tmp_path / "SERVE_BENCH.json"
    profile_out = tmp_path / "BENCH_serving.json"
    result = mod.main([
        "--preset", "tiny", "--slots", "2", "--requests", "3",
        "--ragged", "--ragged-repeats", "2", "--no-baseline",
        "--out", str(out), "--profile-out", str(profile_out),
    ])
    block = result["ragged_tick"]
    # the structural headline: ONE program per steady ragged tick
    assert block["ragged_arm"]["programs_per_tick"]["p50"] == 1.0
    assert block["ragged_arm"]["tick_compilations"] == 1
    assert block["ragged_arm"]["descriptor_build_s"]["p95"] >= 0.0
    cap = block["int4_capacity"]
    for arm in ("fp", "int8", "int4"):
        assert cap[f"{arm}_arm"]["pool_bytes"] <= cap["pool_byte_budget"]
    assert cap["int4_arm"]["kv_quant"]["mode"] == "int4"
    assert cap["int4_vs_fp_sessions_ratio"] >= 1.8  # the acceptance floor
    assert cap["int4_vs_int8_sessions_ratio"] > 1.0
    assert cap["meets_1p8x_fp"] is True
    # quality is REPORTED, never silently dropped
    assert cap["quality"]["greedy_token_agreement_vs_fp"] is not None
    assert cap["quality"]["compared_tokens"] > 0
    on_disk = json.loads(profile_out.read_text())
    assert on_disk["ragged_tick"]["ragged_arm"]["programs_per_tick"]["p50"] == 1.0
    assert (tmp_path / "BENCH_serving.manifest.json").exists()


def test_schema_v11_and_reader_normalizes_pre_v11(tmp_path):
    """The writer stamps serving-metrics/v12; the reader backfills
    ragged_tick: None onto pre-v11 snapshots (and routers truthfully
    report None — 'not recorded' stays indistinguishable from 'no tick
    dispatcher exists', the schema's long-standing discipline)."""
    assert SCHEMA == "serving-metrics/v13"
    path = tmp_path / "old.jsonl"
    path.write_text(json.dumps({
        "event": "snapshot", "schema": "serving-metrics/v10",
        "requests_submitted": 1,
    }) + "\n")
    snaps = load_metrics_jsonl(str(path))["snapshots"]
    assert snaps[0]["ragged_tick"] is None
