"""Device-idle time inside `engine/prefill_host` (a prefill chunk's host
side: chunk ids, copy-on-write arguments, uploads, bookkeeping after the
executable), per engine step of the traced window (device_trace gaps,
placed by the program's spans)."""
from ._program import idle_ms_per_step


def read(ctx):
    return idle_ms_per_step(ctx, "engine/prefill_host")
