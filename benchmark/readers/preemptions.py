"""Pool-pressure preemptions, engine stats differenced over the run
(program_counter)."""


def read(ctx):
    f = ctx["facts"]
    if "counters" not in f:
        return None
    c0, c1 = f["counters"]
    return float(c1["preemptions"] - c0["preemptions"])
