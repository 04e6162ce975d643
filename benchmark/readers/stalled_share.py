"""Share of the run that was a stall: 100 x the sum of `excess_s` over the
program's `host/stall` records (a step that took far longer than its calls
have been taking; `paddle_tpu/monitor/trace.py::stall`) / the window. The
whole window where the driver's facts give it (serving: `t_open`,
`t_close`), else the traced part (training). 0.0 in a clean run; nothing
from a program that seals no such record (program_counter)."""
from ._program import program_spans


def window(ctx):
    """(start, end) on perf_counter of what a whole-window reader reads,
    or None."""
    f = ctx["facts"]
    w = (f["t_open"], f["t_close"]) if "t_open" in f and "t_close" in f \
        else ctx.get("host_window")
    if not w or w[0] is None or w[1] is None or w[1] <= w[0]:
        return None
    return tuple(w)


def read(ctx):
    try:
        from paddle_tpu.monitor import trace
        trace.stall
    except (ImportError, AttributeError):
        return None
    w = window(ctx)
    if w is None:
        return None
    excess = sum(s.attrs.get("excess_s", 0.0)
                 for s in program_spans(ctx, "host/stall", w))
    return 100.0 * excess / (w[1] - w[0])
