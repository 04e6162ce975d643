"""Device-idle time inside `engine/decode_finish` (the per-slot loop once
the tokens are back: append, stop checks, finish, release), per engine
step of the traced window (device_trace gaps, placed by the program's
spans)."""
from ._program import idle_ms_per_step


def read(ctx):
    return idle_ms_per_step(ctx, "engine/decode_finish")
