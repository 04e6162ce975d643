"""Helpers shared by the readers: the traced part of the window on the
host's clock, and the runs of the serving executables. A reader that
finds nothing to read returns None."""
from __future__ import annotations


def traced_steps(ctx) -> list:
    """The engine steps (host records) wholly inside the traced window."""
    hw = ctx.get("host_window")
    steps = ctx["facts"].get("steps", [])
    if not hw or hw[1] is None:
        return []
    return [s for s in steps if s[0] >= hw[0] and s[1] <= hw[1]]


def serve_module_runs(ctx) -> dict:
    """{"decode": [seconds], "chunk": [seconds]}: the runs of the two
    serving executables in the trace. Both are called `jit_fn`; inside
    one `engine_step` span the engine advances every pending prefill by
    one chunk and then decodes once, so in a step that ran several the
    LAST is the decode executable and the others are chunks.
    The selector (data beside the reader) says which executables count
    at all."""
    tr = ctx["trace"]
    if tr is None or not tr.modules:
        return {}
    sel = ctx["cell"].selector("decode_step_device_ms")
    runs = tr.module_runs(sel.get("module_pattern", "."))
    steps = []
    for a, b in tr.span_runs("engine_step"):
        inside = [r for r in runs if r[1] >= a and r[2] <= b]
        if inside:
            steps.append(inside)
    if not steps:
        return {}
    # learn the decode executable's name from the steps that ran more
    # than one executable (chunks, then decode); a step that ran one
    # alone may be either, so runs are then told apart by that name
    votes = {}
    for inside in steps:
        if len(inside) > 1:
            votes[inside[-1][0]] = votes.get(inside[-1][0], 0) + 1
    if not votes:
        for inside in steps:
            votes[inside[0][0]] = votes.get(inside[0][0], 0) + 1
    decode = max(votes, key=votes.get)
    out = {"decode": [], "chunk": []}
    for inside in steps:
        for n, a, b in inside:
            out["decode" if n == decode else "chunk"].append((b - a) / 1e9)
    return out


def mean(xs):
    return sum(xs) / len(xs) if xs else None
