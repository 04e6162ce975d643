"""Device-idle time inside `engine/decode_prepare` (the per-slot
`ensure_writable` loop, victims, copy-on-write arguments and the uploads of
tables, tokens and positions), per engine step of the traced window
(device_trace gaps, placed by the program's spans)."""
from ._program import idle_ms_per_step


def read(ctx):
    return idle_ms_per_step(ctx, "engine/decode_prepare")
