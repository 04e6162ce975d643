"""Device time of one run of the jitted train step, mean over the traced
window (device_trace)."""


def step_runs(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.modules:
        return []
    sel = ctx["cell"].selector("train_step_device_ms")
    runs = tr.module_runs(sel.get("module_pattern", "."))
    if not runs:
        tot = {}
        for n, a, b in tr.module_runs("."):
            tot[n] = tot.get(n, 0) + b - a
        if not tot:
            return []
        top = max(tot, key=tot.get)
        runs = [r for r in tr.module_runs(".") if r[0] == top]
    return runs


def read(ctx):
    runs = step_runs(ctx)
    if not runs:
        return None
    return sum(b - a for _, a, b in runs) / len(runs) / 1e6
