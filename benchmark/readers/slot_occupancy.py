"""Mean share of the engine's slots that hold a decoding request, per
step, over the whole window (program_counter)."""


def read(ctx):
    f = ctx["facts"]
    steps = [s for s in f.get("steps", []) if s[1] <= f["t_close"]]
    if not steps:
        return None
    return 100.0 * sum(s[2] for s in steps) / len(steps) / f["max_slots"]
