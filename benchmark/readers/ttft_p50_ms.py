"""Median time to first token over every request of the run, from when
it was due (host_clock)."""
from ..drive_serve import percentile


def read(ctx):
    t = ctx["facts"].get("ttft_ms")
    return percentile(t, 50) if t else None
