"""Reduction of a profiler trace (`.xplane.pb`, read with
`jax.profiler.ProfileData`) to what the per-layer readers use: device
operations and executables with their device time, the busy union, and
the idle gaps attributed to what the host was doing. Host spans are the
`TraceAnnotation`s the benchmark itself wraps around its calls into the
program (`Recorder.span`), so they sit on the trace's own clock.
"""
from __future__ import annotations

import glob
import os
import re
import time
from contextlib import contextmanager

SPAN_PREFIX = "bench/"
WINDOW_SPAN = SPAN_PREFIX + "traced_window"
SHORT_GAP_NS = 2000
_SUFFIX = re.compile(r"[.\-_]?\d+$")


class Recorder:
    """Host-side spans of one run. A span is also written
    into the profiler's trace while one is being taken."""

    def __init__(self):
        self.spans = {}          # name -> [(t0, t1)]
        self.tracing = False

    @contextmanager
    def span(self, name: str):
        ann = None
        if self.tracing:
            import jax
            ann = jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.spans.setdefault(name, []).append((t0, t1))

    def total(self, name: str) -> float:
        return sum(t1 - t0 for t0, t1 in self.spans.get(name, []))


def _union(intervals):
    """Sorted, merged copy of [(start, end)]."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def overlap(xs, ys) -> int:
    """Total length in which an interval of xs and one of ys both run;
    both lists sorted and merged (`_union`)."""
    i = j = tot = 0
    while i < len(xs) and j < len(ys):
        lo, hi = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if hi > lo:
            tot += hi - lo
        if xs[i][1] <= ys[j][1]:
            i += 1
        else:
            j += 1
    return tot


_HLO = re.compile(r"^%?([^\s=]+)\s*=\s*\(?([a-z]+\d*)\[([\d,]*)\]")


def op_name(text: str) -> str:
    """A short stable name for a device op. On the TPU an op's event name
    is its whole HLO line (`%fusion.775.remat = bf16[8,2047,1024]{...}
    fusion(...)`): keep the instruction's name without its number and the
    first result's type and dimensions (`fusion.remat_bf16_8_2047_1024_`)."""
    m = _HLO.match(text)
    if not m:
        return _SUFFIX.sub("", text)[:96]
    name = re.sub(r"\.\d+", "", m.group(1))
    return f"{name}_{m.group(2)}_{m.group(3).replace(',', '_')}_"[:96]


class Trace:
    """ops / modules: per device plane, lists of (name, start_ns, end_ns).
    spans: {benchmark span name: [(start_ns, end_ns)]} from host planes."""

    def __init__(self, planes):
        self.ops, self.modules, self.spans = [], [], {}
        for plane in planes:
            if re.match(r"/device:TPU:\d+$", plane.name):
                ops, mods = [], []
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        for e in line.events:
                            ops.append((op_name(e.name), e.start_ns,
                                        e.start_ns + e.duration_ns))
                    elif line.name == "XLA Modules":
                        for e in line.events:
                            mods.append((e.name, e.start_ns,
                                         e.start_ns + e.duration_ns))
                self.ops.append(ops)
                self.modules.append(mods)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(SPAN_PREFIX):
                            self.spans.setdefault(
                                e.name[len(SPAN_PREFIX):], []).append(
                                    (e.start_ns, e.start_ns + e.duration_ns))
        win = self.spans.get(WINDOW_SPAN[len(SPAN_PREFIX):])
        if win:
            self.t0, self.t1 = win[0]
        else:
            ev = [x for dev in self.ops + self.modules for x in dev]
            self.t0 = min((e[1] for e in ev), default=0)
            self.t1 = max((e[2] for e in ev), default=0)

    # -- everything below is clipped to the traced window

    def _clip(self, events):
        return [(n, max(a, self.t0), min(b, self.t1)) for n, a, b in events
                if b > self.t0 and a < self.t1]

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def busy_intervals(self, dev: int = 0):
        src = self.ops[dev] if self.ops[dev] else self.modules[dev]
        return _union([(a, b) for _, a, b in self._clip(src)])

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.ops:
            return 0.0
        return sum(sum(b - a for a, b in self.busy_intervals(d))
                   for d in range(len(self.ops))) / len(self.ops) / 1e9

    def device_ops(self, top: int = 10) -> list:
        tot = {}
        for n, a, b in self._clip(self.ops[0] if self.ops else []):
            tot[n] = tot.get(n, 0.0) + (b - a) / 1e9
        return sorted(([n, s] for n, s in tot.items()),
                      key=lambda x: -x[1])[:top]

    def op_seconds(self, pattern: str, dev: int = 0) -> tuple:
        """(total device seconds, count) of ops whose name matches."""
        rx = re.compile(pattern)
        hit = [(b - a) for n, a, b in self._clip(self.ops[dev])
               if rx.search(n)]
        return sum(hit) / 1e9, len(hit)

    def module_runs(self, pattern: str = ".", dev: int = 0) -> list:
        """[(name, start_ns, end_ns)] of executables wholly inside the
        window whose name matches."""
        rx = re.compile(pattern)
        return [(n, a, b) for n, a, b in self.modules[dev]
                if a >= self.t0 and b <= self.t1 and rx.search(n)]

    def span_runs(self, name: str) -> list:
        return [(a, b) for a, b in self.spans.get(name, [])
                if a >= self.t0 and b <= self.t1]

    def idle_gaps(self, top: int = 10) -> list:
        """Idle time of device 0 by the benchmark span the host was in
        (the innermost one covering the gap's middle); gaps under 2 us
        are lumped together."""
        if not self.ops:
            return []
        busy = self.busy_intervals(0)
        edges = [self.t0] + [x for iv in busy for x in iv] + [self.t1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        spans = sorted(((a, b, n) for n, runs in self.spans.items()
                        if n != WINDOW_SPAN[len(SPAN_PREFIX):]
                        for a, b in runs), key=lambda s: s[0])
        tot = {}
        for a, b in gaps:
            if b - a < SHORT_GAP_NS:
                who = f"_between_ops__each_under_{SHORT_GAP_NS}_ns_"
            else:
                mid = (a + b) // 2
                cover = [s for s in spans if s[0] <= mid <= s[1]]
                who = min(cover, key=lambda s: s[1] - s[0])[2] if cover \
                    else "_outside_benchmark_spans_"
            tot[who] = tot.get(who, 0.0) + (b - a) / 1e9
        return sorted(([n, s] for n, s in tot.items()),
                      key=lambda x: -x[1])[:top]


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    return Trace(ProfileData.from_file(max(paths, key=os.path.getmtime))
                 .planes)
