"""``trace/experts.py`` and the rooflines it reads by: an operation's scope by its path,
the decode step's grouped products against the work the tick's counters name on a trace
small enough to compute by hand, and what the readers say where there is nothing to read."""

import pytest

from benchmark.harness import layers
from benchmark.rooflines import lfm2_paged_gqa_decode, moe_grouped_matmul
from benchmark.trace import experts

TICK = "jit(ragged_tick)/cond/branch_1_fun/"
DECODE = TICK + "tick.decode/M.decode_rows_paged/M._ffn/"
SIZES = {"hidden_size": 2048, "moe_intermediate_size": 1792, "num_hidden_layers": 13, "num_dense_layers": 1,
         "num_experts": 32, "num_experts_per_tok": 4, "num_key_value_heads": 8, "head_dim": 64,
         "layer_types": ["conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv",
                         "full_attention", "conv", "conv", "conv"]}
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
READERS = ["moe_grouped_matmul_roofline", "tick_program.moe_share_pct", "tick_program.short_conv_share_pct",
           "experts.touched_per_step", "experts.load_max_over_mean", "lfm2_paged_gqa_decode_roofline"]


@pytest.mark.parametrize("op_name,scope", [
    (DECODE + "moe/experts/grouped_gated_matmul", "tick.decode/moe/experts"),
    (DECODE + "moe/route/jit(take_along_axis)/gather", "tick.decode/moe/route"),
    (TICK + "tick.chunk_lanes/while/body/M.prefill_chunk_paged/short_conv/dot_general", "tick.chunk_lanes/short_conv"),
    (TICK + "tick.decode/M.decode_rows_paged/attention/fused_paged_decode_attention_gqa", "tick.decode/attention"),
    (TICK + "tick.decode/M._head/head/dot_general", "tick.decode/head"),
    (TICK + "tick.sample/sort", "tick.sample"),
    ("jit(release)/scatter", "(unscoped)"),
])
def test_an_operation_goes_to_the_innermost_scope_of_its_path(op_name, scope):
    assert experts.scope_of(op_name) == scope


def test_moe_holds_both_of_its_parts():
    assert experts._under("tick.decode/moe/experts", "moe") and experts._under("tick.decode/moe/route", "moe")
    assert experts._under("tick.decode/moe/experts", "moe/experts")
    assert not experts._under("tick.decode/moe/route", "moe/experts") and not experts._under("tick.decode/mlp", "moe")


def test_the_rooflines_count_the_work():
    assert moe_grouped_matmul.expert_bytes(SIZES) == 3 * 2048 * 1792 * 2 == 22_020_096
    assert moe_grouped_matmul.expert_layers(SIZES) == 12
    # a decode step of 34 slots: 12 layers x 31 experts touched, 34 x 4 assignments a layer: the weights bound it
    touched, assignments = 12 * 31, 12 * 34 * 4
    seconds = moe_grouped_matmul.seconds_at_roofline(SIZES, PEAKS, touched, assignments)
    assert seconds == pytest.approx((touched * 22_020_096 + assignments * 2 * 2048 * 2) / 819e9)
    assert moe_grouped_matmul.flops(SIZES, assignments) / 197e12 < seconds
    # past the ridge (240 rows an expert at these peaks) the operations bound a call
    assert 235 < moe_grouped_matmul.ridge_rows(SIZES, PEAKS) < 245
    assert moe_grouped_matmul.seconds_at_roofline(SIZES, PEAKS, 32, 32 * 400) == pytest.approx(
        moe_grouped_matmul.flops(SIZES, 32 * 400) / 197e12)
    # the attention layers alone hold pages: 3 of 13
    assert lfm2_paged_gqa_decode.attention_layers(SIZES) == 3
    assert lfm2_paged_gqa_decode.bytes_per_tick(SIZES, 1000) == 2 * 3 * 1000 * 512 * 2


def test_decode_roofline_on_a_trace_computed_by_hand():
    """Two ticks, seconds for milliseconds. Tick 7 decodes 30 slots, its counters say 31
    experts a layer; its program [1, 2] spends 0.5 under the decode step's moe/experts and
    0.1 under a chunk lane's (not the decode step's: left out). Tick 8 decodes nothing."""
    ops = [["custom-call.1", 1.0, 0.3, DECODE + "moe/experts/grouped_gated_matmul"],
           ["custom-call.2", 1.3, 0.2, DECODE + "moe/experts/grouped_matmul"],
           ["fusion.3", 1.5, 0.1, DECODE + "moe/route/dot_general"],
           ["custom-call.4", 1.6, 0.1, TICK + "tick.chunk_lanes/while/body/M._ffn/moe/experts/grouped_matmul"],
           ["conditional.5", 1.0, 0.9, TICK + "tick.decode/cond"],  # a container
           ["custom-call.6", 3.0, 0.4, TICK + "tick.chunk_lanes/while/body/M._ffn/moe/experts/grouped_matmul"]]
    programs = [["jit_ragged_tick(3)", 1.0, 1.0], ["jit_ragged_tick(3)", 3.0, 0.5]]
    rows = [{"tick": 7, "program_start_s": 1.0, "record": {"decoding": 30}},
            {"tick": 8, "program_start_s": 3.0, "record": {"decoding": 0}}]
    harvests = {7: (12 * 30 * 4 + 1024 * 12, 31.0)}
    got = experts.decode_roofline_pct(ops, programs, rows, harvests, SIZES, PEAKS)
    want = 100.0 * moe_grouped_matmul.seconds_at_roofline(SIZES, PEAKS, 31.0 * 12, 30 * 4 * 12) / 0.5
    assert got == pytest.approx(want)
    # a tick whose counters the trace does not carry is left out; none at all reads nothing
    assert experts.decode_roofline_pct(ops, programs, rows, {}, SIZES, PEAKS) is None


@pytest.mark.parametrize("metric", READERS)
def test_a_reader_that_finds_nothing_returns_none(metric):
    """The parent's program has no such span or counter, and a run that is not traced has no trace."""
    read = layers.load_reader(metric)
    assert read({"snapshot": {"schema": "serving-metrics/v13", "experts": None}, "trace": None, "ticks": [],
                 "sizes": SIZES, "peaks": PEAKS, "program_name": "ragged_tick"}) is None
    assert read({}) is None


def test_the_counter_readers_read_the_snapshots_block():
    ctx = {"snapshot": {"experts": {"touched_per_step": {"mean": 30.5, "p50": 31.0, "p95": 32.0},
                                     "load_max_over_mean": 1.42}}}
    assert layers.load_reader("experts.touched_per_step")(ctx) == 30.5
    assert layers.load_reader("experts.load_max_over_mean")(ctx) == 1.42
