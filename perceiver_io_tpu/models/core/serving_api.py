"""What ``ServingEngine`` asks of a model it serves from its page pool.

The engine owns slots, pages, the tick loop and the descriptor; it knows no
architecture. A model class answers five questions, by methods the engine
calls on the (unbound) module, and may make three statements more, (f), (g)
and (h):

(a) its cache: ``init_paged_cache(num_slots, num_pages, page_size, dtype,
    kv_quant)`` returns ONE pytree holding everything a slot keeps between
    ticks (donated to the tick program like the pool it contains), with
    ``release_slot(slot)`` and ``quarantine_slot(slot, table_row)``;
(b) a request's reservation: ``serving_pages(prompt_tokens, max_new_tokens,
    page_size, bucket)`` pages, claimed whole at admission;
(c) its chunk step, ``serving_chunk_phase(params, cache, lanes)``, and what
    ends a prompt, ``serving_finish_phase(params, cache, state, lanes,
    install_state)`` — the tick program's two prefill phases, traced into the
    one fused program. The engine packs a tick's lanes FROM LANE 0 (the
    carried chunk lanes are the first ``sum(ch_count > 0)`` rows, the carried
    finish lanes the first ``sum(fin_active)``; a carried chunk lane's count
    is at least 1), and a phase runs the carried lanes only: the compiled lane
    count sizes the descriptor and the phases' static shapes, never the work.
    A finish lane hands ``install_state`` the slot's ROW: the last hidden row
    of its prompt, ``(hidden_size,)``, as the head would receive it. The
    phase runs no head;
(d) its decode step: ``decode_rows_paged(ids (B, 1), cache)`` under
    ``model.apply`` returns ``(rows (B, hidden_size), cache)``: every slot's
    new last hidden row, again as the head would receive it
    (``decode_step_paged`` is the head of those rows, for callers that want
    logits);
(e) its head over rows: ``_head(rows (B, hidden_size))`` under
    ``model.apply`` returns logits ``(B, vocab_size)``, row by row.

Between two ticks a slot carries its row, never its logits: the tick's decode
phase begins with ONE pass of (e) over every slot's row, samples from it, runs
(d) on the sampled tokens and stores the new rows. The tick's phases run in
one of two orders, by what the model states in (h). A model that states
nothing: chunk lanes, finish lanes, then the decode phase, so a slot whose
prompt ended in this tick's finish lane is sampled in the same tick, through
the same pass of the head. A model that states (h): the head and the sampler
first, over the slots that decoded at the tick's entry, then the rows, then
the finish lanes, so such a slot is sampled by the NEXT tick's one pass of the
head (the path a finish takes under either order when no slot decodes).

A model with routed expert layers MAY also count, on the device, what only
the device knows, which experts its tokens were routed to:

(f) ``ServingTraits.expert_counters`` = (expert layers, experts), with
    ``take_expert_counts(taken)`` on its cache: ``(counts (2, layers, experts)
    int32, cache)``, the assignments each expert received in the decode steps
    (row 0) and in the chunk lanes (row 1) since the counters were last taken;
    they start again from zero where the traced flag ``taken`` holds. The tick
    program appends them to its token output, so they reach the host in the
    tick's ONE readback of the tokens (no copy of their own), and the engine
    books them (``EngineMetrics.record_expert_counts``, the tick's record). A
    model without the field counts nothing and its tick returns what it did.
    The counters run over ALL the router's experts. A model that holds a SHARE
    of them (the chip's part of an expert-parallel layer) also states
    ``ServingTraits.experts_held`` = (first, count), and the book then tells
    the two things apart: the matrices a decode step READ are the held experts
    that received a row (``touched_per_step`` counts those, of ``count``), and
    of a decode step's assignments only those that fell to held experts were
    computed here (``held_assignment_pct``; the others are some other chip's,
    computed nowhere in a one-chip cell). ``assignments`` and
    ``load_max_over_mean`` stay over all the router's experts: the router's
    balance is the model's, whoever holds the experts.

A model MAY also say in which layout the tick must RECEIVE a weight:

(g) ``ServingTraits.row_major_leaves``: leaves of the parameter tree, each by
    its path of keys joined with "/" (``"params/layers_0_in_proj"``). An
    argument's layout is the compiler's choice, made from the leaf's shape
    alone and before any program exists; where a branch of the tick cannot
    read that choice, the branch copies the whole weight in front of its
    work, every time it runs. The engine keeps a named leaf on the device
    row-major (last dimension minor), laid out ONCE, at construction and at
    ``set_params``, and hands the model its own matrix back at the entry of
    every program it builds over the parameters (``serving/weight_layout.py``
    says how, and why not through a ``Format``), so it is the layout of the
    ARGUMENT and nothing is laid out again per call. A name that is no leaf
    of the tree is refused at construction. A statement, not a rule over
    shapes: a model names the matrices whose compiled tick was read and found
    to copy. A model that names none is handed its weights as it always was.

A model MAY also say that its chunk rows ride its decode pass:

(h) ``ServingTraits.chunk_rides_decode``. Where a layer's weights are read
    once a CALL and both the decode step and a chunk lane are bound by that
    read (routed experts at a handful of rows an expert), a tick that carries
    a chunk lane AND decodes pays the stream twice. Such a model answers, in
    place of (c)'s chunk phase, with ``serving_ride_phase(params, cache,
    lanes, ids (B, 1), decodes)``: ``(rows (B, hidden_size), cache)``, its
    loop over the carried chunk lanes, each lane ONE loop over the layers in
    which the decode step rides the first lane where ``decodes`` (a traced
    flag: the tick decodes): what the lanes' chunk step then (d) return for
    slots that are disjoint (they are: a prefilling slot does not decode).
    The engine then runs the tick's other order (above): a tick that carries
    a chunk lane runs this phase, a tick that only decodes runs (d) alone.
    The engine enters no scope around the phase; the model names the decode
    rows' work and what the two groups share ``TICK_DECODE_SCOPE/...`` and
    the chunk rows' own work ``TICK_CHUNK_SCOPE/...``, as the tick's other
    phases are named. A statement by the model, not a rule over shapes: a
    finish costs its request one tick, which only a second weight stream
    saved pays for.
    Two models state it, each from its own configuration (it has expert
    layers): LFM2 and Nemotron-H. What runs ONCE over the rows of both groups
    is the layer whose weights a call reads once: LFM2's feed-forward,
    Nemotron-H's ``E`` layer (its held share, its shared expert). Every other
    operator runs for the two groups APART, because they share nothing: a
    convolution's decode update and its chunk's, a recurrence's
    ``ssm_decode_update`` over every slot and its ``ssd_chunk_scan`` over one
    slot's state, the paged decode attention and a chunk's attention over its
    slot's pages; their projections are read at 128 and 256 rows and are not
    bound by the weight read. The lanes' loop itself asks nothing of the
    layers: it is ONE function, ``Lfm2MoeForCausalLM.serving_ride_phase``,
    which Nemotron-H takes by reference and which calls the class's own
    ``decode_rows_with_chunk_paged``.

``serving_traits()`` says, in plain data, what else differs: which prompts
take the split admission, what the descriptor's lanes carry, and which of four
engine options (``prefix_cache``, ``kv_quant``, ``handle_preemption``,
``journal``) the model does not carry yet, each with the piece that is missing.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

# the tick program's scope names (``serving/engine.py`` ``TICK_SCOPES``) a model's
# merged pass (h) names its two groups' operations by
TICK_DECODE_SCOPE, TICK_CHUNK_SCOPE = "tick.decode", "tick.chunk_lanes"


@dataclass(frozen=True)
class ServingTraits:
    vocab_size: int
    # the width of the row a slot carries between ticks: the head's input
    hidden_size: int
    # the most tokens a slot holds: longer prompts are rejected at submit, and
    # a slot's page-table row has ceil(window / page_size) entries
    window: int
    # ids a finish lane carries: the tail of the prompt that is prefilled at
    # the finish, not by chunk lanes (Perceiver AR's latents). 0: none, the
    # last chunk lane ends the prompt
    finish_ids: int
    # bytes of recurrent state one slot holds beside its pages (0: none). With
    # such a state, a claimed slot's first chunk lane carries ``reset`` (the
    # state is zeroed inside the tick) and every chunk lane names its slot
    recurrent_bytes_per_slot: int = 0
    # (f): (expert layers, experts) of the assignment counters the model's cache
    # keeps on the device and the tick returns with its tokens; None: the model
    # has no routed experts, counts nothing and pays nothing
    expert_counters: Optional[Tuple[int, int]] = None
    # (f): (first, count) of the router's experts whose matrices lie here; None:
    # every expert the counters run over is held
    experts_held: Optional[Tuple[int, int]] = None
    # (g): parameter leaves ("/"-joined key paths) the tick must receive
    # row-major; (): the compiler lays every argument out as it chooses
    row_major_leaves: Tuple[str, ...] = ()
    # (h): the decode step rides the first carried chunk lane, in the model's
    # ``serving_ride_phase``, and a finished prompt's first token is the next
    # tick's; False: chunk lanes, finish lanes, then the decode step
    chunk_rides_decode: bool = False
    # engine option -> why this model cannot be served with it yet. (What the
    # grouped-query paged decode kernel takes is no option: full-precision
    # pages whose row ``kv_heads * head_dim`` is lane-aligned: heads of 128, or
    # of 64 where the row is a multiple of 128, ``paged_gqa_decode_supported``.)
    unsupported: Dict[str, str] = field(default_factory=dict)

    @property
    def recurrent_state(self) -> bool:
        return self.recurrent_bytes_per_slot > 0

    @property
    def split_from(self) -> int:
        """Prompts of at least this many tokens take the split admission (chunk
        lanes then a finish lane inside the tick): the finish consumes
        ``finish_ids`` tokens, so shorter ones take the one-shot prefill +
        install programs; with no finish ids, every prompt is split."""
        return max(self.finish_ids, 1)

    @property
    def prefill_floor(self) -> int:
        """The smallest rung of the one-shot prefill ladder: a rung holds the
        finish ids; a model that never takes the one-shot path has one rung."""
        return self.finish_ids or self.window

    def latent_start(self, prompt_tokens: int) -> int:
        """The first position a chunk lane treats as a latent (Perceiver AR's
        boundary); far past any position for a model that has none."""
        return prompt_tokens - self.finish_ids if self.finish_ids else 2 ** 30
