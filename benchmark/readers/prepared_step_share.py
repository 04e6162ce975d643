"""Share of the traced window's `engine/step` spans that ran at least one
executable (an `engine/prefill_call` or `engine/decode_call` span lies
inside them) whose `plan` says the step was launched from a plan made
while the step before it was on the device (`prepared`; `rebuilt`: that
plan was thrown away for something it could not foresee and the step built
before its launch; `sync`: nothing was on the device to prepare under).
Spans without the attribute (a program from before the prepared step) read
as nothing, not as 0 (program_counter: the engine's own record of how it
came by each step)."""
from ._program import program_spans


def read(ctx):
    steps = [s for s in program_spans(ctx, "engine/step")
             if "plan" in s.attrs]
    calls = sorted((s.t0, s.t1) for name in ("engine/prefill_call",
                                             "engine/decode_call")
                   for s in program_spans(ctx, name))
    plans, i = [], 0
    for s in sorted(steps, key=lambda s: s.t0):
        while i < len(calls) and calls[i][0] < s.t0:
            i += 1
        if i < len(calls) and calls[i][1] <= s.t1:
            plans.append(s.attrs["plan"])
    if not plans:
        return None
    return 100.0 * sum(p == "prepared" for p in plans) / len(plans)
