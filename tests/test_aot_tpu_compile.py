"""The main path's Pallas kernels compile for a TPU v5e at real widths.

No chip is attached here: the installed TPU compiler compiles for a DESCRIBED
``v5e:2x2`` topology (shapes only, nothing runs). That refuses what interpret
mode cannot see — more SMEM or VMEM than a kernel may use, an op Mosaic does
not lower, a slice off the tiling — which is how the int8 scale sidecars
(SMEM), the int4 nibble unpack (``arith.shrui`` on i8 vectors) and the dense
decode kernel at the flagship's width (16 MiB scoped VMEM) were found. The
kernel functions are compiled directly: their ``*_supported`` gates ask
``jax.default_backend()``, which is the CPU here. A compile that passes is
not a chip run; ``chip_smoke.py`` is.
"""

import collections
import os

import jax
import jax.numpy as jnp
import pytest

from perceiver_io_tpu.ops import decode_kernel as dk
from perceiver_io_tpu.ops import moe
from perceiver_io_tpu.ops import paged_decode_kernel as pdk
from perceiver_io_tpu.ops import ragged_paged_kernel as rpk
from perceiver_io_tpu.ops import ssm
from perceiver_io_tpu.ops.flash import splash_mha

FLAGSHIP = (10, 128)  # heads x head width: 455M C4 Perceiver AR
GIANTMIDI = (8, 96)  # 134M GiantMIDI Perceiver AR, 2048 latents
WIKITEXT = (8, 64)  # 30.7M WikiText Perceiver AR, window 4096
POOL_PAGES = 2048  # the (N, H) scale sidecars outgrew SMEM from about 1k pages


@pytest.fixture(scope="module")
def v5e():
    """One device of a described v5e:2x2, with the persistent compilation cache
    off: such a compile is written to the cache but cannot be read back without
    a chip, and every later run would warn about the unreadable entry."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _pool_args(sds, rows, heads, width, window, page, pool):
    hd = heads * width
    pages_per_slot = -(-window // page)
    kp = {
        "fp": sds((POOL_PAGES, page, hd), jnp.bfloat16),
        "int8": sds((POOL_PAGES, page, hd), jnp.int8),
        "int4": sds((POOL_PAGES, page, hd // 2), jnp.uint8),
    }[pool]
    scale = None if pool == "fp" else sds((POOL_PAGES, heads), jnp.float32)
    return dict(
        q=sds((rows, heads, 1, width), jnp.bfloat16), kp=kp, vp=kp,
        page_table=sds((rows, pages_per_slot), jnp.int32),
        start=sds((rows,), jnp.int32), live=sds((rows,), jnp.int32),
        rope_k=sds((rows, pages_per_slot * page, width // 2), jnp.float32),
        k_scale=scale, v_scale=scale,
    )


def _paged(sds, heads_width, window, page, pool):
    args = _pool_args(sds, 16, *heads_width, window, page, pool)
    return jax.jit(lambda a: pdk.fused_paged_decode_attention(**a, window=window)).lower(args)


def _ragged(sds, heads_width, window, page, pool):
    args = _pool_args(sds, 16, *heads_width, window, page, pool)
    args["causal_bound"] = sds((16,), jnp.int32)
    qbits = 4 if pool == "int4" else 8
    return jax.jit(
        lambda a: rpk.fused_ragged_paged_attention(**a, window=window, qbits=qbits)
    ).lower(args)


def _dense(sds, heads_width, batch, capacity, n_q):
    heads, width = heads_width
    kv = sds((batch, capacity, heads * width), jnp.bfloat16)
    return jax.jit(
        lambda q, k, v, ang, pos, pad, live: dk.fused_decode_attention(q, k, v, ang, pos, pad, live=live)
    ).lower(
        sds((batch, heads, n_q, width), jnp.bfloat16), kv, kv,
        sds((batch, capacity, width // 2), jnp.float32), sds((batch,), jnp.int32),
        sds((batch, capacity), jnp.bool_), sds((batch,), jnp.int32),
    )


def _dense_stacked(sds, heads_width, layers, batch, capacity):
    """The serving pool's self-attention ring: the kernel indexes the layer of
    the stacked buffers itself (``layer`` traced, as inside the layer loop) and
    is told which slots to read (``live``: the capacity or 0)."""
    heads, width = heads_width
    kv = sds((layers, batch, capacity, heads * width), jnp.bfloat16)
    return jax.jit(
        lambda q, k, v, ang, pad, live, layer: dk.fused_decode_attention(
            q, k, v, ang, capacity - 1, pad, live=live, layer=layer)
    ).lower(
        sds((batch, heads, 1, width), jnp.bfloat16), kv, kv,
        sds((batch, capacity, width // 2), jnp.float32), sds((batch, capacity), jnp.bool_),
        sds((batch,), jnp.int32), sds((), jnp.int32),
    )


def _splash_fwd_bwd(sds, heads_width, n_q, n_k):
    heads, width = heads_width
    q = sds((2, heads, n_q, width), jnp.bfloat16)
    kv = sds((2, heads, n_k, width), jnp.bfloat16)
    loss = lambda q, k, v: splash_mha(q, k, v, causal=True).astype(jnp.float32).sum()
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv)


def _gqa(sds, q_heads, kv_heads, width, layers, slots, pages, page, window):
    """The hybrid decoder's grouped-query paged decode: the pool stacks the
    layers under one page table, eight pages of a slot a grid step."""
    pool = sds((layers, pages, page, kv_heads * width), jnp.bfloat16)
    return jax.jit(lambda q, kp, vp, table, length: pdk.fused_paged_decode_attention_gqa(
        q, kp, vp, table, length, layers - 1)).lower(
        sds((slots, q_heads, width), jnp.bfloat16), pool, pool,
        sds((slots, -(-window // page)), jnp.int32), sds((slots,), jnp.int32))


def _ssm_update(sds, heads, groups, head_dim, state, layers, slots):
    """The decode step's state update over the whole pool's float32 state,
    aliased in place (2.15 GB at the hybrid cell's 128 slots)."""
    f32 = jnp.float32
    return jax.jit(
        lambda s, x, dt, a, b, c, active: ssm.ssm_decode_update(s, layers - 1, x, dt, a, b, c, active),
        donate_argnums=(0,),
    ).lower(
        sds((layers, slots, heads, head_dim, state), f32), sds((slots, heads, head_dim), f32),
        sds((slots, heads), f32), sds((heads,), f32), sds((slots, groups, state), f32),
        sds((slots, groups, state), f32), sds((slots,), jnp.bool_))


def _experts(sds, experts, hidden, width, top_k, rows):
    """A routed expert layer's two grouped products, through ``expert_layer``: the layout
    from the router's choices (a dynamic grid bound), the gated product over the stacked
    gate-and-up matrices, the down product."""
    bf16 = jnp.bfloat16
    weights = moe.ExpertWeights(sds((hidden, experts), bf16), sds((experts,), bf16),
                                sds((experts, hidden, 2 * width), bf16), sds((experts, width, hidden), bf16))
    return jax.jit(lambda x, w, valid: moe.expert_layer(x, w, (0, experts), top_k, valid=valid, use_kernel=True)).lower(
        sds((rows, hidden), bf16), weights, sds((rows,), jnp.bool_))


def _experts_share(sds, held, routed, hidden, width, shared, top_k, rows):
    """An ungated expert layer on a SHARE of its experts, through ``expert_layer``: the router
    over all ``routed`` experts, the ``relu(.)^2`` product and the down product over the
    ``held`` stacks as laid out (``width`` a multiple of the lanes), the shared expert dense."""
    bf16 = jnp.bfloat16
    weights = moe.ExpertWeights(sds((hidden, routed), bf16), sds((routed,), bf16), sds((held, hidden, width), bf16),
                                sds((held, width, hidden), bf16), sds((hidden, shared), bf16), sds((shared, hidden), bf16))
    return jax.jit(lambda x, w, valid: moe.expert_layer(x, w, (0, held), top_k, 2.5, valid=valid, use_kernel=True,
                                                        form="relu2", norm_eps=1e-20)).lower(
        sds((rows, hidden), bf16), weights, sds((rows,), jnp.bool_))


CASES = {
    # Nemotron-3-Nano-30B-A3B's widths at the serving cell's sizes: 32 query heads over 2 K/V
    # heads of 128 (the pool's row is 256 wide), 2 attention layers, 128 slots, 2049 pages of 64
    # under a 1024-token row; 64 mixer heads of 64 x state 128 in 8 groups, 6 layers; 64 held of
    # 128 experts of 2688 x 1856 laid out at 1920, 6 a token, beside the shared 3712, a decode
    # step's 128 rows and a chunk lane's 256
    "gqa-paged-32over2x128-l2-b128-w1024": (_gqa, 32, 2, 128, 2, 128, 2049, 64, 1024),
    "ssm-update-64x64x128-g8-l6-b128": (_ssm_update, 64, 8, 64, 128, 6, 128),
    "experts-relu2-64of128x2688x1920-top6-rows128": (_experts_share, 64, 128, 2688, moe.pad_width(1856), 3712, 6, 128),
    "experts-relu2-64of128x2688x1920-top6-rows256": (_experts_share, 64, 128, 2688, moe.pad_width(1856), 3712, 6, 256),
    # LFM2-8B-A1B's widths at the serving cell's sizes: 32 query heads over 8 K/V
    # heads of 64 (the pool's row is 512 wide; a slot's result leaves the kernel at
    # that width), 3 attention layers, 128 slots, 2049 pages of 64 under a 1024-token
    # row; 32 experts of 2048 x 1792, 4 a token, a decode step's 128 rows and a
    # chunk lane's 256
    "gqa-paged-32over8x64-l3-b128-w1024": (_gqa, 32, 8, 64, 3, 128, 2049, 64, 1024),
    "experts-32x2048x1792-top4-rows128": (_experts, 32, 2048, 1792, 4, 128),
    "experts-32x2048x1792-top4-rows256": (_experts, 32, 2048, 1792, 4, 256),
    # Falcon-H1-34B's widths at the serving cell's sizes: 20 query heads over 4
    # K/V heads of 128, 128 slots, 3072 pages of 64 under a 1536-token row; 32
    # mixer heads of 128 x state 256 in 2 groups, 4 layers
    "gqa-paged-20over4x128-l4-b128-w1536": (_gqa, 20, 4, 128, 4, 128, 3072, 64, 1536),
    "ssm-update-32x128x256-g2-l4-b128": (_ssm_update, 32, 2, 128, 256, 4, 128),
    "paged-fp-10x128-w1024": (_paged, FLAGSHIP, 1024, 64, "fp"),
    "paged-fp-10x128-w512": (_paged, FLAGSHIP, 512, 64, "fp"),
    "paged-int8-10x128-w1024": (_paged, FLAGSHIP, 1024, 64, "int8"),
    "paged-int8-10x128-w512": (_paged, FLAGSHIP, 512, 32, "int8"),
    "paged-fp-8x64-w4096": (_paged, WIKITEXT, 4096, 128, "fp"),
    "paged-int8-8x64-w4096": (_paged, WIKITEXT, 4096, 128, "int8"),
    "ragged-fp-10x128-w1024": (_ragged, FLAGSHIP, 1024, 64, "fp"),
    "ragged-int8-10x128-w1024": (_ragged, FLAGSHIP, 1024, 64, "int8"),
    "ragged-int4-10x128-w1024": (_ragged, FLAGSHIP, 1024, 128, "int4"),
    "ragged-int4-8x64-w4096": (_ragged, WIKITEXT, 4096, 64, "int4"),
    # the flagship's self-attention cache under generate(): batch 8, capacity
    # 512 — with the (1280, 1280) rotate-half constant in the kernel a 512-row
    # block was refused (16.71M of 16M scoped VMEM); without it ``_kv_block``
    # picks 512 at every published width (tests/test_decode_kernel.py pins it)
    "dense-10x128-b8-cap512": (_dense, FLAGSHIP, 8, 512, 1),
    "dense-10x128-b8-cap1024-q8": (_dense, FLAGSHIP, 8, 1024, 8),
    # the 512-row block's largest measured need: eight queries at batch 64
    "dense-10x128-b64-cap2048-q8": (_dense, FLAGSHIP, 64, 2048, 8),
    "dense-8x96-b8-cap2048": (_dense, GIANTMIDI, 8, 2048, 1),
    # the paged pool's stacked self-attention ring: 455M at the online cell's
    # 64 slots, and the 30.7M configuration (8 layers, 16 slots)
    "dense-stacked-10x128-l20-b64-cap512": (_dense_stacked, FLAGSHIP, 20, 64, 512),
    "dense-stacked-8x64-l8-b16-cap512": (_dense_stacked, WIKITEXT, 8, 16, 512),
    "splash-fwd-bwd-512x1024x128": (_splash_fwd_bwd, FLAGSHIP, 512, 1024),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(v5e, case):
    build, *shape = CASES[case]
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
    compiled = build(sds, *shape).compile()  # raises what the chip's compiler would raise
    assert "tpu_custom_call" in compiled.as_text()


def _compiled_tick(v5e, cell_name):
    """A serving cell's WHOLE tick, compiled: the published widths of the cell's
    configuration under the cell's engine settings, abstract weights that lie on the
    described v5e, lowered through the engine's own arguments and jit (the pool's
    gigabytes of zeros are built on the CPU)."""
    from benchmark.harness import manifest
    from perceiver_io_tpu.serving import ServingEngine

    cell = manifest.resolve_cell(cell_name)
    config = cell["config"]
    family = manifest.load_family(config["family"])
    weights = jax.eval_shape(lambda: family.build_weights(config["sizes"], jax.random.PRNGKey(0), jnp.bfloat16))
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=v5e)
    params = jax.tree_util.tree_map(sds, family.to_program_params(weights))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")  # the kernels' gates ask it; the engine is built on the CPU
        engine = ServingEngine(family.build_model(config, deterministic=True), params, **cell["settings"]["engine"],
                               telemetry=False)
        args = engine._ragged_args(True, engine._forced_none, engine._use_forced_none)
        # the parameters are the shapes the engine keeps; the pool's arrays become shapes on the v5e
        shapes = jax.tree_util.tree_map(lambda x: x if isinstance(x, jax.ShapeDtypeStruct) else sds(x), args)
        return engine._jit_ragged_tick.lower(*shapes).compile()


@pytest.fixture(scope="module")
def falcon_tick_text(v5e):
    """The compiled text of ``serve-falcon-h1-chat``'s WHOLE tick at the published widths of
    ``benchmark/configs/falcon-h1-34b-4l.json`` (about 40 s; the pool is 3.8 GB of zeros)."""
    return _compiled_tick(v5e, "serve-falcon-h1-chat").as_text()


# hidden x (z 4096 | xBC 5120 | dt 32): the one matrix with a ragged column count, as the model holds it and as
# the engine hands it to the tick (its rows in tiles of 16: serving/weight_layout.py)
IN_PROJ, IN_PROJ_KEPT = "bf16[5120,9248]", "bf16[320,16,9248]"


@pytest.mark.parametrize("holds", ["no copy of in_proj", "in_proj arrives row-major", "the kernels are named"])
def test_falcon_tick_is_handed_in_proj_as_both_branches_read_it(falcon_tick_text, holds):
    """ISSUE 45: taken as the compiler chooses, ``in_proj`` arrives transposed (9,248
    columns pad nothing that way) and the chunk branch copied each layer's 94.7 MB three
    times in front of its loop, twelve copies a lane tick; stated row-major
    (``ServingTraits.row_major_leaves``) no computation of the tick copies it."""
    lines = falcon_tick_text.splitlines()
    if holds == "no copy of in_proj":
        copies = [line.strip()[:160] for line in lines if " copy(" in line and (IN_PROJ in line or IN_PROJ_KEPT in line)]
        assert not copies, copies
    elif holds == "in_proj arrives row-major":
        entry = [line for line in lines if "_in_proj" in line and " parameter(" in line and "params___" in line]
        assert len(entry) == 4 and all(IN_PROJ_KEPT + "{2,1,0:T(8,128)(2,1)}" in line for line in entry), \
            [line[:160] for line in entry]
        # and both branches read it as the matrix without moving it
        assert any(IN_PROJ + "{1,0:T(8,128)(2,1)} bitcast(" in line for line in lines)
    else:
        assert "ssm_decode_update" in falcon_tick_text and "fused_paged_decode_attention_gqa" in falcon_tick_text


def _by_computation(lines):
    """(the computation a line of a compiled module's text lies in, the line)."""
    at = None
    for line in lines:
        if line.endswith("{") and " (" in line and not line.startswith(" "):
            at = line.split(" (")[0]
        yield at, line


def _kernels_by_computation(lines, kernels):
    """{computation: {kernel: its custom calls there}}, over the computations that hold any."""
    held = {}
    for at, line in _by_computation(lines):
        for kernel in kernels:
            if "custom-call(" in line and line.lstrip().startswith(f"%{kernel}."):
                held.setdefault(at, dict.fromkeys(kernels, 0))[kernel] += 1
    return held


@pytest.fixture(scope="module")
def lfm2_tick(v5e):
    """``serve-lfm2-moe-assist``'s WHOLE tick at the published widths of
    ``benchmark/configs/lfm2-8b-a1b-13l.json``: (the compiled text, its memory account); about a minute."""
    compiled = _compiled_tick(v5e, "serve-lfm2-moe-assist")
    return compiled.as_text(), compiled.memory_analysis()


# the page pools (3 attention layers x 2,049 pages of 64 x 8 heads of 64) and an expert layer's two stacks
LFM2_BIG = ("bf16[3,2049,64,512]", "bf16[32,2048,3584]", "bf16[32,1792,2048]")
LFM2_EXPERT_LAYERS = 12


@pytest.mark.parametrize("holds", ["one stream of the experts a branch", "no copy of the pools or the stacks",
                                   "the kernels are named", "the temporaries stay small"])
def test_lfm2_tick_reads_each_expert_layer_once_whatever_it_carries(lfm2_tick, holds):
    """ISSUE 46: a tick that carried a chunk lane and decoded ran the model's chunk phase and
    then its decode step, two loops over the layers, 24 pairs of grouped products. The
    model states that its chunk rows ride its decode pass (``serving_api.py`` (h)): every
    computation of the compiled tick that holds grouped products holds ONE pair an expert
    layer, and nothing is copied in front of the new branches."""
    text, memory = lfm2_tick
    lines = text.splitlines()
    if holds == "one stream of the experts a branch":
        held = _kernels_by_computation(lines, ("grouped_gated_matmul", "grouped_matmul"))
        # the loop over the carried lanes, the decode step riding the first; the decode step alone
        assert len(held) == 2 and all(set(n.values()) == {LFM2_EXPERT_LAYERS} for n in held.values()), held
    elif holds == "no copy of the pools or the stacks":
        copies = [line.strip()[:160] for line in lines if " copy(" in line and any(f"= {big}" in line for big in LFM2_BIG)]
        assert not copies, copies
        assert any(big in text for big in LFM2_BIG)
    elif holds == "the kernels are named":
        assert "fused_paged_decode_attention_gqa" in text and "ragged-dot" not in text
    else:
        # PR 44's tick: 10.03 GB of arguments + 0.16 GB of temporaries; the cell's peak on the chip is 10.06 GB
        assert memory.argument_size_in_bytes < 10.05e9 and memory.temp_size_in_bytes < 0.3e9, memory


@pytest.fixture(scope="module")
def nemotron_tick(v5e):
    """``serve-nemotron3-nano-agent``'s WHOLE tick at the published widths of
    ``benchmark/configs/nemotron3-nano-30b-a3b-14l.json``: (the compiled text, its memory account); about half a minute."""
    compiled = _compiled_tick(v5e, "serve-nemotron3-nano-agent")
    return compiled.as_text(), compiled.memory_analysis()


@pytest.mark.parametrize("holds", ["the routed products are the grouped kernels", "no copy of in_proj or of an expert stack",
                                   "it fits beside the harness's weights"])
def test_nemotron_tick_streams_the_held_experts_through_the_grouped_kernels(nemotron_tick, holds):
    """ISSUE 49: at the published 1856 columns the grouped kernels were refused and the products fell to
    ``ragged_dot``; laid out at 1920 where the weights are made they are the Pallas kernels, the stacks
    arrive as laid out, and ``in_proj`` (10,304 columns) arrives row-major as the model states."""
    text, memory = nemotron_tick
    lines = text.splitlines()
    if holds == "the routed products are the grouped kernels":
        assert "ragged-dot" not in text
        for kernel in ("grouped_relu2_matmul", "grouped_matmul", "ssm_decode_update", "fused_paged_decode_attention_gqa"):
            assert kernel in text, kernel
        assert "grouped_gated_matmul" not in text
    elif holds == "no copy of in_proj or of an expert stack":
        big = ("bf16[2688,10304]", "bf16[168,16,10304]", "bf16[64,2688,1920]", "bf16[64,1920,2688]")
        copies = [line.strip()[:160] for line in lines if " copy(" in line and any(b in line for b in big)]
        assert not copies, copies
        entry = [line for line in lines if "_experts_" in line and " parameter(" in line and "params___" in line]
        assert len(entry) == 12 and all("{2,1,0:T(8,128)(2,1)}" in line for line in entry), [line[:160] for line in entry]
    else:
        # 11.35 GB of arguments (weights 9.44 as laid out, state 1.61, pages 0.27) + 0.15 GB of temporaries
        assert memory.argument_size_in_bytes < 11.4e9 and memory.temp_size_in_bytes < 0.3e9, memory


# the float32 recurrent state (6 ``M`` layers x 128 slots x 64 heads x 64 x 128: 1.61 GB), the two page pools (2 ``*`` layers x
# 2,049 pages of 64 x 2 heads of 128) and the convolution columns' pool (28 MB)
NEMOTRON_POOLS, NEMOTRON_CONV = ("f32[6,128,64,64,128]", "bf16[2,2049,64,256]"), "bf16[6,128,18432]"
NEMOTRON_EXPERT_LAYERS = 6


@pytest.mark.parametrize("holds", ["one stream of the experts a branch", "no copy of the pools"])
def test_nemotron_tick_reads_each_held_expert_layer_once_whatever_it_carries(nemotron_tick, holds):
    """ISSUE 50: in the plain order a tick that carried a chunk lane and decoded streamed all 64 held experts of six
    layers for the chunk's rows and 45 of them again for the decode rows. The model states that its chunk rows ride its
    decode pass (``serving_api.py`` (h)): every computation of the compiled tick that holds grouped products holds ONE
    pair an ``E`` layer, and the new branch keeps no pool apart by copying it (1.61 GB of state: 4 ms a lane tick)."""
    text, _ = nemotron_tick
    lines = text.splitlines()
    if holds == "one stream of the experts a branch":
        held = _kernels_by_computation(lines, ("grouped_relu2_matmul", "grouped_matmul"))
        # the loop over the carried lanes, the decode step riding the first; the decode step alone
        assert len(held) == 2 and all(set(n.values()) == {NEMOTRON_EXPERT_LAYERS} for n in held.values()), held
        updates = _kernels_by_computation(lines, ("ssm_decode_update",))
        assert set(updates) == set(held) and all(n == {"ssm_decode_update": 6} for n in updates.values()), updates
    else:
        copies = [line.strip()[:160] for line in lines if " copy(" in line and any(f"= {pool}" in line for pool in NEMOTRON_POOLS)]
        assert not copies, copies
        assert all(pool in text for pool in NEMOTRON_POOLS)
        # the columns' pool is re-laid around the decode rows' update (slots to the minor dimension and back: PERF.md 7.18 c),
        # as in the parent's decode step: TWO of these a tick, into that layout in whichever branch of the first ``cond``
        # the tick takes (the lanes' loop | nothing), and back at the tick's exit
        relaid = collections.Counter(at for at, line in _by_computation(lines) if " copy(" in line and f"= {NEMOTRON_CONV}" in line)
        assert sum(relaid.values()) <= 3 and set(relaid.values()) == {1}, relaid
