"""Device-idle time inside `engine/collect` (the step's one wait and one
read-back: what is left of it once the device has finished is read-back
latency), per engine step of the traced window (device_trace gaps, placed
by the program's spans). A program without the span reads as nothing."""
from ._program import idle_ms_per_step


def read(ctx):
    return idle_ms_per_step(ctx, "engine/collect")
