"""Device-idle time inside `engine/admit` (the queue's head admitted:
prefix hashing, `share_prefix`, `ensure_writable`), per engine step of the
traced window (device_trace gaps, placed by the program's spans)."""
from ._program import idle_ms_per_step


def read(ctx):
    return idle_ms_per_step(ctx, "engine/admit")
