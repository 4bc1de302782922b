"""Share of the decode steps' expert assignments that fell to experts HELD on this chip
(the ones computed here), from the ``experts`` block of the engine's snapshot; None for a
program whose book does not know which experts are held."""

from benchmark.trace import experts


def read(ctx):
    return experts.snapshot_experts(ctx, "held_assignment_pct")
