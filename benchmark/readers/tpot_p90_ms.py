"""90th percentile (nearest rank) over requests of the mean gap between
a request's tokens (host_clock). The tail the issue asked for: with 37
requests in a window it is the 4th highest, set by which short answers
share their steps with long prompts' chunks, so it repeats run to run
(to 0.2 ms) and swings 5 % seed to seed; recorded on every traced line
beside the bounded `tpot_mean_ms` (PERF.md section 6, PR 24)."""
from ..drive_serve import percentile


def read(ctx):
    t = ctx["facts"].get("tpot_ms")
    return percentile(t, 90) if t else None
