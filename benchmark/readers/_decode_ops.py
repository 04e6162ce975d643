"""Helpers of the readers that look INSIDE the decode executable: its runs
in the trace as intervals, the device time of one kernel's ops within them,
and the program's `engine/decode_*` spans of the traced part of the window.
`_common.serve_module_runs` tells the two serving executables apart but
keeps only their durations; this keeps where each run lies, by the same
rule (inside one `engine_step` span the last executable is the decode)."""
from __future__ import annotations

import re

from . import _program


def decode_runs(ctx) -> list:
    """[(start_ns, end_ns)] of the decode executable's runs in the trace."""
    tr = ctx.get("trace")
    if tr is None or not tr.modules:
        return []
    sel = ctx["cell"].selector("decode_step_device_ms")
    runs = tr.module_runs(sel.get("module_pattern", "."))
    steps = []
    for a, b in tr.span_runs("engine_step"):
        inside = [r for r in runs if r[1] >= a and r[2] <= b]
        if inside:
            steps.append(inside)
    if not steps:
        return []
    votes = {}
    for inside in steps:
        if len(inside) > 1:
            votes[inside[-1][0]] = votes.get(inside[-1][0], 0) + 1
    if not votes:
        for inside in steps:
            votes[inside[0][0]] = votes.get(inside[0][0], 0) + 1
    decode = max(votes, key=votes.get)
    return [(a, b) for inside in steps for n, a, b in inside if n == decode]


def op_seconds_per_run(ctx, pattern: str):
    """(mean device seconds a decode run spends in ops whose name matches,
    runs) or None where the trace has no such op inside a decode run."""
    runs = decode_runs(ctx)
    tr = ctx.get("trace")
    if not runs or not tr.ops:
        return None
    rx = re.compile(pattern)
    ops = sorted((a, b) for n, a, b in tr.ops[0] if rx.search(n))
    total, i = 0, 0
    for lo, hi in sorted(runs):
        while i < len(ops) and ops[i][1] <= lo:
            i += 1
        j = i
        while j < len(ops) and ops[j][0] < hi:
            total += min(ops[j][1], hi) - max(ops[j][0], lo)
            j += 1
    if not total:
        return None
    return total / 1e9 / len(runs), len(runs)


def span_attr_means(ctx, name: str, *attrs: str):
    """Mean of each attribute over the traced window's `name` spans that
    carry all of them, or None where none does (a program without them)."""
    rows = [[s.attrs[a] for a in attrs]
            for s in _program.program_spans(ctx, name)
            if all(a in s.attrs for a in attrs)]
    if not rows:
        return None
    return [sum(r[i] for r in rows) / len(rows) for i in range(len(attrs))]
