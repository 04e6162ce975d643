"""Nearest-rank 90th percentile of the time the requests submitted in the
whole window spent in the engine's queue: their first `request/queue`
span (program_counter: the engine's own record)."""
from ._program import window_requests


def read(ctx):
    waits = sorted(s.t1 - s.t0 for s in window_requests(ctx).values())
    if not waits:
        return None
    return 1e3 * waits[max(0, -(-90 * len(waits) // 100) - 1)]
