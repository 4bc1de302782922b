"""Prompt tokens served from shared prefix pages over the prompt tokens of every
decode-ready admission, both counted by the engine over its whole life (warm-up included)."""

from benchmark.trace import books


def read(ctx):
    saved = books.snapshot_value(ctx, "prefix_hit_tokens")
    admitted = books.snapshot_value(ctx, "prompt_tokens_admitted")
    if saved is None or not admitted:
        return None
    return 100.0 * saved / admitted
