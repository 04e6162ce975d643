"""How late the generator sent: 99th percentile of (sent - due)
(host_clock). One thread both sends and steps the engine, so a request
that comes due during a step is sent when the step returns."""
from ..drive_serve import percentile


def read(ctx):
    late = ctx["facts"].get("lateness_ms")
    return percentile(late, 99) if late else None
