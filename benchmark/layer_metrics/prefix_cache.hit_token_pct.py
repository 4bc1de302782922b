"""Prompt tokens served from shared pages (the engine's prefix-hit records) over the
prompt tokens of all admitted requests of the run."""


def read(ctx):
    total = ctx.get("prompt_tokens_admitted")
    if not total or ctx.get("prefix_hit_tokens") is None:
        return None
    return 100.0 * ctx["prefix_hit_tokens"] / total
