"""Device-idle time inside `engine/fetch`, the second half of
`engine/collect`: the device has finished and the host reads the step's
tokens and flags back (`jax.device_get`), which packing them into one
array would shorten; per engine step of the traced window (device_trace
gaps, placed by the program's spans). With `idle_in_wait` it sums to
`idle_in_collect`. A program without the span reads as nothing."""
from ._program import idle_ms_per_step


def read(ctx):
    return idle_ms_per_step(ctx, "engine/fetch")
