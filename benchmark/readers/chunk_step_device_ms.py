"""Device time of one run of the prefill-chunk executable, mean over the
traced window (device_trace)."""
from ._common import mean, serve_module_runs


def read(ctx):
    runs = serve_module_runs(ctx).get("chunk")
    return None if not runs else 1e3 * mean(runs)
