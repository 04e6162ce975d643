"""The program's own spans, for the readers: `paddle_tpu.monitor.trace`
keeps every finished span of the process in a bounded ring, on
`time.perf_counter()` like `ctx["host_window"]` and the drivers' stamps.
A program that has no such ring (an older commit) gives no spans, and a
reader that finds none returns None, as the others do."""
from __future__ import annotations

from ..trace import _union, overlap


def program_spans(ctx, prefix: str, window=None) -> list:
    """Spans (`name t0 t1 span_id parent_id trace_id attrs`, seconds on
    perf_counter) whose name starts with `prefix`, wholly inside `window`:
    by default the traced part of the run's window."""
    try:
        from paddle_tpu.monitor import trace
        read = trace.spans
    except (ImportError, AttributeError):
        return []
    window = window or ctx.get("host_window")
    if not window or window[0] is None or window[1] is None:
        return []
    return read(window[0], window[1], prefix)


def steps_in_trace(ctx) -> int:
    """`engine/step` spans inside the traced part of the window."""
    return len(program_spans(ctx, "engine/step"))


def idle_inside(ctx, spans):
    """Nanoseconds of device 0's idle time that lie inside `spans`: their
    length less its overlap with `Trace.busy_intervals(0)`. The spans go
    onto the trace's clock by the one offset between the start of
    `bench/traced_window` there and the host stamp taken right after it
    was entered. None without a device plane."""
    tr = ctx.get("trace")
    if tr is None or not tr.ops or not spans:
        return None
    shift = tr.t0 - ctx["host_window"][0] * 1e9
    runs = _union([(s.t0 * 1e9 + shift, s.t1 * 1e9 + shift) for s in spans])
    return sum(b - a for a, b in runs) - overlap(runs, tr.busy_intervals(0))


def idle_ms_per_step(ctx, *names: str):
    """Device-idle milliseconds inside the engine phases `names`, per
    engine step of the traced part of the window."""
    steps = steps_in_trace(ctx)
    spans = [s for n in names for s in program_spans(ctx, n)]
    idle = idle_inside(ctx, spans)
    if idle is None or not steps:
        return None
    return idle / 1e6 / steps


def window_requests(ctx) -> dict:
    """{request id: its first `request/queue` span} for the requests that
    were submitted inside the whole window and have left the queue."""
    f = ctx["facts"]
    if "t_open" not in f or "t_close" not in f:
        return {}
    out = {}
    for s in program_spans(ctx, "request/queue",
                           (f["t_open"], float("inf"))):
        if s.t0 <= f["t_close"] and not s.attrs.get("requeue"):
            out.setdefault(s.trace_id, s)
    return out
