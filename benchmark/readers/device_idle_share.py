"""1 - union of the device-busy intervals over the traced window, mean
over the chips (device_trace)."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.ops or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)
