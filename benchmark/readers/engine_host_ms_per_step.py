"""Host time of `engine.step()` less the device time inside it, per step,
over the traced window (host_clock less device_trace)."""
from ..trace import _union, overlap
from ._common import traced_steps


def read(ctx):
    tr, steps = ctx["trace"], traced_steps(ctx)
    if tr is None or not tr.ops or not steps:
        return None
    spans = tr.span_runs("engine_step")
    if not spans:
        return None
    inside = overlap(_union(spans), tr.busy_intervals(0))
    host = sum(b - a for a, b in spans)
    return (host - inside) / 1e6 / len(spans)
