"""Share of a decode run's device time spent in the latent attention's
ops (the selector beside `mla_decode_roofline_share`): whether the latent
kernel weighs in the step what the count of its bytes says. It falls as
the kernel nears its roofline (device_trace)."""
from ._decode_ops import decode_runs, op_seconds_per_run


def read(ctx):
    sel = ctx["cell"].selector("mla_decode_roofline_share")
    timed = op_seconds_per_run(ctx, sel.get("op_pattern", "mla_decode"))
    if timed is None:
        return None
    runs = decode_runs(ctx)
    whole = sum(b - a for a, b in runs) / 1e9 / len(runs)
    return 100.0 * timed[0] / whole
