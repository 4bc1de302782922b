"""Mean share of the pool's slots that a tick decodes (each has its recurrent state read
and written whole), from the ``recurrent_state`` block of the engine's snapshot."""


def read(ctx):
    block = (ctx.get("snapshot") or {}).get("recurrent_state")
    if not block or not block.get("slots"):
        return None
    return 100.0 * block["decoding_slots"]["mean"] / block["slots"]
