"""Device-idle time inside `engine/prefill_call` and `engine/decode_call`
(launch latency before the device starts, read-back latency after it
ends), per engine step of the traced window (device_trace gaps, placed by
the program's spans)."""
from ._program import idle_ms_per_step


def read(ctx):
    return idle_ms_per_step(ctx, "engine/prefill_call", "engine/decode_call")
