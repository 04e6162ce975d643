"""Device-idle time inside `engine/wait`, the first half of
`engine/collect`: the host has asked for the step's results and blocks
until the device has finished. Idle there is launch latency that outlasted
the call span, and the time from the device's last op to the host's waking;
per engine step of the traced window (device_trace gaps, placed by the
program's spans). With `idle_in_fetch` it sums to `idle_in_collect` less
what the span layer takes between and after the two (0.04-0.07 ms a step
while a profile is taken). A program without the span reads as nothing."""
from ._program import idle_ms_per_step


def read(ctx):
    return idle_ms_per_step(ctx, "engine/wait")
