"""Whether the readers of the whole window (`queue_wait_p90_ms`,
`stalled_share`) read a whole window: 100 where the program's span ring
has pushed nothing out (`trace.evicted()`) or what it holds begins before
the window does (`trace.oldest()`), else the share of the window that it
still holds. A guard, like `device_runs_paired_share`; nothing from a
program whose ring does not say (program_counter)."""
from .stalled_share import window


def read(ctx):
    try:
        from paddle_tpu.monitor import trace
        lost, oldest = trace.evicted(), trace.oldest()
    except (ImportError, AttributeError):
        return None
    w = window(ctx)
    if w is None:
        return None
    if not lost or oldest is None or oldest <= w[0]:
        return 100.0
    return 100.0 * max(0.0, w[1] - oldest) / (w[1] - w[0])
