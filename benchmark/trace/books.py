"""The program's own books in a traced run: its recorder's ``summary()`` (``ctx["obs"]``)
and its ``EngineMetrics.snapshot()`` (``ctx["snapshot"]``), as the per-layer readers of
the serving cells open them.

A reader returns ``None`` where there is nothing to read: no recorder or snapshot in the
context (the unit tests' stub), or a program older than what the reader reads, which says
so itself (a recorder summary without ``schema`` predates the declared tick phases; a
snapshot under ``serving-metrics/v13`` predates the request-life stamps). Where the
program IS new enough and the span or key is missing, it was renamed: that raises, naming
it, so that a traced run fails instead of printing a thinner line."""

from __future__ import annotations

REQUEST_LIFE_SCHEMA = 13  # serving-metrics/v13: first_token_s, prefix_hit_tokens, ...


def phase(ctx: dict, name: str):
    """The recorder's aggregate of one span or observed interval."""
    obs = ctx.get("obs")
    if not obs or "schema" not in obs:
        return None
    if name not in obs["phases"]:
        raise KeyError(f"the program's recorder has no phase {name!r} (renamed?); it has {sorted(obs['phases'])}")
    return obs["phases"][name]


def mean_ms(ctx: dict, name: str):
    """A phase's mean in milliseconds over the recorder's life. The host gap
    (``serving.host_gap``: sync's return to the next dispatch's return) and its four
    parts (``serving.host_gap.harvest``, ``serving.between_steps``,
    ``serving.host_gap.schedule``, ``serving.host_gap.dispatch``) are booked by the
    engine for the same ticks, so the parts' means add up to the gap's."""
    booked = phase(ctx, name)
    if booked is None or not booked["count"]:
        return None
    return 1e3 * booked["total_s"] / booked["count"]


def snapshot_value(ctx: dict, *path: str, since: int | None = REQUEST_LIFE_SCHEMA):
    """One value of the engine's snapshot; ``since`` is the schema version that brought it
    (``None``: every version has it)."""
    snapshot = ctx.get("snapshot")
    if not snapshot:
        return None
    if since is not None and int(snapshot["schema"].rsplit("/v", 1)[1]) < since:
        return None
    value = snapshot
    for key in path:
        if key not in value:
            raise KeyError(f"the engine's snapshot ({snapshot['schema']}) has no {'.'.join(path)!r} (renamed?)")
        value = value[key]
    return value
