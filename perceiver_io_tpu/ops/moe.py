"""A routed expert layer that is told which experts it holds.

``route`` scores a token over ALL the experts the router was trained with,
chooses ``top_k`` and weights them; ``expert_layer`` routes and computes the
part of ``sum_i w_i E_i(x)`` that the experts HELD here give: the chip's share
of an expert-parallel layer, run without its exchange. An expert's FORM is data
(``FORMS``): ``E(x) = (silu(x W_1) * x W_3) W_2`` (``"gated_silu"``) or ``E(x) =
relu(x W_1)^2 W_2`` (``"relu2"``, two matrices and not three). Nothing is
computed for an expert that is not held, nothing stands in for the chips that
hold the others, and no token is dropped: there is no capacity. A SHARED expert
(``ExpertWeights.shared_w1`` / ``shared_w2``, the same form, dense) is added in
full for every row: every chip of a deployment computes it alike.

The assignments that fell to held experts are laid out expert by expert, each
expert's rows starting on a tile of ``tile_rows`` rows (``group_layout``: one
sort of the assignments' expert indices; a tile then belongs to ONE expert),
and go through two grouped products over the experts' stacked weights: ``w13``
``(held, hidden, 2 x width)``, gate beside up, for ``silu(rows W_1) * rows
W_3`` in one pass (``(held, hidden, width)`` and ``relu(rows W_1)^2`` for the
ungated form: the activation is the first product's epilogue either way), then
``w2`` ``(held, width, hidden)``. A width that is no multiple of 128 lanes is
laid out ONCE, where the weights are made, with zero columns in ``w13`` and
zero rows in ``w2`` up to the next multiple: exact for both forms (``silu(0) *
0 = relu(0)^2 = 0``), and what ``pad_width`` names. On one TPU device
these are Pallas kernels whose grid walks the tiles that hold rows (a dynamic
bound) and names each tile's expert through a prefetched table: an expert's
matrices are read once a call, and the matrices of an expert that received no
row are not read. At a decode step's handful of rows an expert the products
are bound by that weight stream. Elsewhere (the CPU suite, a sharded trace)
they are ``jax.lax.ragged_dot`` over the same layout.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from perceiver_io_tpu.ops.flash import single_device_trace

_HIGHEST = jax.lax.Precision.HIGHEST
# the weight blocks a grid step holds, double-buffered: gate and up at
# 2048 x 896 bfloat16 are 14.7 MB, a whole down matrix as much
_WEIGHT_BLOCKS_BYTES = 24 * 2**20
_VMEM_LIMIT = 48 * 2**20


# an expert's form: the first product's stack and its epilogue
FORMS = ("gated_silu", "relu2")
LANES = 128


def pad_width(width: int) -> int:
    """The expert width as the stacks are laid out: the next multiple of the
    128 lanes (1856 -> 1920), the added columns of ``w13`` and rows of ``w2``
    zero."""
    return -(-width // LANES) * LANES


class ExpertWeights(NamedTuple):
    """One expert layer's weights as they lie here: ``router`` (hidden,
    experts) and ``bias`` (experts,) over ALL the experts; ``w13`` (held,
    hidden, 2 x width), each held expert's gate matrix beside its up matrix
    (``(held, hidden, width)``, the one first matrix, for an ungated form);
    ``w2`` (held, width, hidden); ``shared_w1`` (hidden, shared width) /
    ``shared_w2`` (shared width, hidden): the shared expert's, or None."""

    router: jax.Array
    bias: jax.Array
    w13: jax.Array
    w2: jax.Array
    shared_w1: Optional[jax.Array] = None
    shared_w2: Optional[jax.Array] = None


def route(
    x: jax.Array, router: jax.Array, bias: jax.Array, top_k: int, scale: float = 1.0, renormalize: bool = True,
    norm_eps: float = 1e-6,
) -> Tuple[jax.Array, jax.Array]:
    """x (T, hidden), router (hidden, experts), bias (experts,) -> (chosen (T,
    top_k) int32, weights (T, top_k) float32). Scores are ``sigmoid(x W_r)``,
    the product in x's dtype accumulated in float32; the choice goes by ``score
    + bias`` (the router's load-balancing correction: it moves the CHOICE
    only), the weights by the score alone, over the chosen's sum plus
    ``norm_eps`` if ``renormalize``, times ``scale``. Sigmoid, top-k and the
    normalisation in float32."""
    f32 = jnp.float32
    logits = jnp.dot(x, router.astype(x.dtype), precision=_HIGHEST if x.dtype == f32 else None,
                     preferred_element_type=f32)
    s = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(s + bias.astype(f32), top_k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if renormalize:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + norm_eps)
    return chosen.astype(jnp.int32), picked * scale


def grouped_kernel_supported(fan_in: int, fan_out: int) -> bool:
    """The Pallas form on one TPU device, for lane-aligned expert matrices;
    anything else (the CPU suite, a sharded trace) takes ``ragged_dot``."""
    if jax.default_backend() != "tpu" or not single_device_trace():
        return False
    return fan_in % 128 == 0 and fan_out % 128 == 0


class GroupLayout(NamedTuple):
    """Where ``group_layout`` put the assignments: ``dest`` (A,) each
    assignment's row, ``rows`` (static) for one that belongs to no group;
    ``sizes`` (groups,) assignments a group; ``tiles`` (groups,) tiles a group;
    ``tile_group`` (rows // tile_rows,) the group of each tile, in order, the
    tiles past ``n_tiles`` () naming the last group. int32."""

    dest: jax.Array
    sizes: jax.Array
    tiles: jax.Array
    tile_group: jax.Array
    n_tiles: jax.Array
    rows: int
    tile_rows: int


def group_layout(key: jax.Array, groups: int, tile_rows: int) -> GroupLayout:
    """key (A,): each assignment's group, ``groups`` for none. Group ``g``'s
    assignments get consecutive rows, in their own order, from the first row of
    a tile on: ``sum_{j < g} ceil(sizes[j] / tile_rows)`` tiles in. ``rows`` is
    the most that can take: ``A + groups * (tile_rows - 1)`` in whole tiles."""
    a = key.shape[0]
    rows = -(-(a + groups * (tile_rows - 1)) // tile_rows) * tile_rows
    sizes = jnp.sum((key[:, None] == jnp.arange(groups)[None, :]).astype(jnp.int32), axis=0)
    tiles = (sizes + tile_rows - 1) // tile_rows
    ends, first = jnp.cumsum(tiles), jnp.cumsum(sizes) - sizes
    start = (ends - tiles) * tile_rows
    # by group, each group's in the assignments' own order, those of no group last: an
    # assignment's rank in its group is its place in that order less where its group begins
    order = jnp.argsort(key, stable=True)
    group = jnp.minimum(key[order], groups - 1)
    dest = jnp.where(key[order] < groups, start[group] + jnp.arange(a) - first[group], rows)
    dest = jnp.zeros((a,), jnp.int32).at[order].set(dest.astype(jnp.int32))
    tile_group = jnp.sum((ends[None, :] <= jnp.arange(rows // tile_rows)[:, None]).astype(jnp.int32), axis=1)
    return GroupLayout(dest, sizes, tiles, jnp.minimum(tile_group, groups - 1), ends[-1], rows, tile_rows)


def _epilogue(products, form: Optional[str]):
    """What a first product leaves behind, from its float32 accumulators: the
    form's activation (``None``: the product itself, a second product)."""
    if form == "gated_silu":
        return jax.nn.silu(products[0]) * products[1]
    return jnp.square(jax.nn.relu(products[0])) if form == "relu2" else products[0]


def _grouped_kernel(form: Optional[str], precision):
    def kernel(tile_group_ref, lhs_ref, *refs):
        del tile_group_ref  # the index maps read it
        out_ref = refs[-1]
        products = [jax.lax.dot_general(lhs_ref[...], w[...], (((1,), (0,)), ((), ())), precision=precision,
                                        preferred_element_type=jnp.float32) for w in refs[:-1]]
        out_ref[...] = _epilogue(products, form).astype(out_ref.dtype)

    return kernel


def _tile_cols(fan_in: int, fan_out: int, operands: int, itemsize: int) -> int:
    """Whole matrices where their double-buffered blocks fit (a block that spans
    the fan-out is one contiguous copy), else halves of the fan-out."""
    cols = fan_out
    while 2 * operands * fan_in * cols * itemsize > _WEIGHT_BLOCKS_BYTES and cols % 256 == 0:
        cols //= 2
    return cols


def grouped_matmul(
    lhs: jax.Array, weights: jax.Array, layout: GroupLayout, out_dtype, form: Optional[str] = None,
    use_kernel: bool = False, interpret: bool = False,
) -> jax.Array:
    """lhs (layout.rows, K) laid out by ``group_layout``; weights (groups, K,
    N), or (groups, K, 2 x N) for the gated form ``silu(lhs W[..., :N]) * lhs
    W[..., N:]``; ``form``: the epilogue over the float32 accumulators (one of
    ``FORMS``, or None for the product itself). Returns (layout.rows, N) in
    ``out_dtype``: each tile's rows times its group's matrix, accumulated in
    float32. Tiles past ``layout.n_tiles`` are not visited by the kernel: their
    rows hold nothing meant."""
    exact = lhs.dtype == jnp.float32
    gated = form == "gated_silu"
    _, k, n = weights.shape
    n = n // 2 if gated else n
    if not use_kernel:
        padded = layout.tiles * layout.tile_rows
        out = jax.lax.ragged_dot(lhs, weights, padded, precision=_HIGHEST if exact else None,
                                 preferred_element_type=jnp.float32)
        return _epilogue([out[:, :n], out[:, n:]] if gated else [out], form).astype(out_dtype)
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tm = layout.tile_rows
    operands = 2 if gated else 1
    tn = _tile_cols(k, n, operands, weights.dtype.itemsize)
    # the gated form reads one stack twice: a column block of the gate half, and the
    # block as far into the up half
    weight_specs = [pl.BlockSpec((None, k, tn), lambda j, t, group, half=half: (group[t], 0, half * (n // tn) + j))
                    for half in range(operands)]
    return pl.pallas_call(
        _grouped_kernel(form, _HIGHEST if exact else None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            # an expert's tiles are consecutive, so its block is fetched once a column tile;
            # with no assignment at all one tile of zero rows runs: the bound stays positive
            grid=(n // tn, jnp.maximum(layout.n_tiles, 1)),
            in_specs=[pl.BlockSpec((tm, k), lambda j, t, group: (t, 0))] + weight_specs,
            out_specs=pl.BlockSpec((tm, tn), lambda j, t, group: (t, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((layout.rows, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name={"gated_silu": "grouped_gated_matmul", "relu2": "grouped_relu2_matmul"}.get(form, "grouped_matmul"),
    )(layout.tile_group, lhs, *([weights] * operands))


def expert_layer(
    x: jax.Array, weights: ExpertWeights, held: Tuple[int, int], top_k: int, scale: float = 1.0,
    renormalize: bool = True, valid: Optional[jax.Array] = None, use_kernel: bool = False,
    interpret: bool = False, load_groups: Optional[jax.Array] = None, form: str = "gated_silu",
    norm_eps: float = 1e-6,
) -> Tuple[jax.Array, jax.Array]:
    """The held experts' part of the routed sum, plus the shared expert where
    ``weights`` holds one. x (T, hidden) in the dtype the products run in;
    ``held`` = (first, count): ``weights.w13`` / ``w2`` are experts ``first ..
    first + count - 1`` of the ``weights.router``'s; ``form``: one of ``FORMS``;
    valid (T,): rows that are tokens (the others are routed nowhere and
    counted nowhere). Returns (y (T, hidden) in x's dtype, load (experts,)
    int32: the valid assignments each of ALL the experts received, held here
    or not). ``load_groups`` (G, T) bool: the load comes back apart for each
    group of rows, (G, experts): one call, and so one read of an expert's
    matrices, for rows whose assignments are counted in different books.
    Products accumulate in float32, by ``grouped_matmul`` (``use_kernel``: its
    Pallas form)."""
    t, dtype = x.shape[0], x.dtype
    first, count = held
    if weights.w13.shape[0] != count or weights.w2.shape[0] != count:
        raise ValueError(f"held {held} names {count} experts, the stacks hold {weights.w13.shape[0]}")
    if form not in FORMS:
        raise ValueError(f"an expert's form is one of {FORMS}, got {form!r}")
    with jax.named_scope("route"):
        chosen, picked = route(x, weights.router, weights.bias, top_k, scale, renormalize, norm_eps)
        counted = jnp.ones((t,), bool) if valid is None else valid
        assigned = (chosen[..., None] == jnp.arange(weights.router.shape[1])) & counted[:, None, None]
        if load_groups is None:
            load = jnp.sum(assigned, axis=(0, 1), dtype=jnp.int32)
        else:
            load = jnp.sum(assigned[None] & load_groups[:, :, None, None], axis=(1, 2), dtype=jnp.int32)
    with jax.named_scope("experts"):
        local = chosen - first
        here = (local >= 0) & (local < count) & counted[:, None]
        # a row an assignment to a held expert, expert by expert in whole tiles; the
        # rows in between read the zero row appended to x
        layout = group_layout(jnp.where(here, local, count).reshape(-1), count, 32 // dtype.itemsize)
        token = jnp.full((layout.rows,), t, jnp.int32).at[layout.dest].set(
            jnp.arange(t * top_k, dtype=jnp.int32) // top_k, mode="drop")
        rows = jnp.take(x, token, axis=0, mode="fill", fill_value=0)
        hidden = grouped_matmul(rows, weights.w13.astype(dtype), layout, dtype, form, use_kernel, interpret)
        out = grouped_matmul(hidden, weights.w2.astype(dtype), layout, jnp.float32, None, use_kernel, interpret)
        back = jnp.take(out, jnp.minimum(layout.dest, layout.rows - 1), axis=0).reshape(t, top_k, -1)
        y = jnp.sum(jnp.where(here[..., None], back * picked[..., None], 0.0), axis=1)
    if weights.shared_w1 is not None:
        with jax.named_scope("shared"):
            precision = _HIGHEST if dtype == jnp.float32 else None
            w1, w2 = weights.shared_w1.astype(dtype), weights.shared_w2.astype(dtype)
            first_products = jnp.split(jnp.dot(x, w1, precision=precision, preferred_element_type=jnp.float32),
                                       2 if form == "gated_silu" else 1, axis=-1)
            y = y + jnp.dot(_epilogue(first_products, form).astype(dtype), w2, precision=precision,
                            preferred_element_type=jnp.float32)
    return y.astype(dtype), load
