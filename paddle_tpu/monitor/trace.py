"""Span layer — the program's one record of what ran when, and why.

The monitor's registry answers "what are the aggregates doing" and a
device profile answers "what did the chip run"; this module answers what
neither can: *which request, engine step or train step was slow, and which
phase ate the time*. A **span** is one interval of host work: a name,
``t0``/``t1`` on ``time.perf_counter()``, its own id, the id of the span
that caused it (``parent_id``) and the id its request or train step shares
(``trace_id``: the request id, the step number). Sites make ONE call:

* ``with span(name, **attrs):`` — a scoped phase of the calling thread.
  It is ALSO a ``jax.profiler.TraceAnnotation("paddle/<name>")`` entered
  and left with it, so whenever anyone takes a profile
  (``jax.profiler.start_trace``, ``paddle.profiler.Profiler``, the
  benchmark) the program's phases lie in the ``.xplane.pb`` beside the
  device ops, on one clock. With no profile being taken an annotation is
  a flag check: that is its off switch, and there is no other.
* ``record(name, t0, t1, **attrs)`` — an interval already timed.
* ``start_trace(name, key=...)`` — one causal unit (a serving request from
  ``submit()`` to finish, one ``TrainStep`` call) whose phases open and
  close across calls and threads: ``tr.span("queue")`` ... ``.end()``.
  Such spans overlap each other, so they cannot be annotations (a
  ``TraceAnnotation`` nests per thread); used as a context manager the
  trace's ROOT span is scoped, and is one.

Every finished span goes to ONE process-wide bounded ring (the flight
recorder: ``RING_CAPACITY`` spans, on by default, no knob) that
``spans(t0, t1, prefix)`` reads; to the profiler's recorder while a
``Profiler`` records; and — for spans of a trace — to the JSONL sink when
``enable()`` turned one on. ``ring(False)`` exists so the ring's cost can
be measured, not as a mode. In the ring and as an annotation a span of a
trace is ``<trace>/<span>`` (``request/queue``, ``train_step/dispatch``);
inside its trace, and so in the sink, it keeps the short name.

The sink (opt-in: ``enable()`` / ``PADDLE_TRACE``) is the Dapper-style
part, scaled down to one process. Sampling is head-based and governs the
sink only: the keep/drop decision is made when the trace STARTS
(``PADDLE_TRACE_SAMPLE``, a probability in [0, 1], default 1.0 — a
deterministic credit accumulator, not a PRNG, so a 0.1 sample really
keeps every 10th trace). Unsampled traces still buffer their spans in
memory (bounded) so a WARN fired mid-trace can **escalate** them to
sampled — the trace you need post-mortem is by construction the one the
sampler would have dropped. Records are schema-v1 ``run.trace.jsonl``
through the same buffered :class:`~paddle_tpu.monitor.sink.JsonlSink`
(per-process ``.procN`` suffix under the launcher env contract), timed on
``perf_counter`` and exported against a wall-clock anchor taken once at
tracer start, so they line up with the monitor's ``ts`` fields.
"""
from __future__ import annotations

import atexit
import itertools
import os
import threading
import time
from collections import deque, namedtuple
from typing import Dict, Optional

from jax.profiler import TraceAnnotation

from .sink import JsonlSink

__all__ = ["TRACE_SCHEMA_VERSION", "RING_CAPACITY", "Span", "SpanRecord",
           "Tracer", "span", "record", "spans", "ring", "start_trace",
           "enable", "disable", "enabled", "get", "current_trace_id",
           "escalate"]

TRACE_SCHEMA_VERSION = 1
RING_CAPACITY = 32768
ANNOTATION_PREFIX = "paddle/"

# the sink session, when one is enabled (sampling, JSONL, escalation)
_active: Optional["Tracer"] = None

_lock = threading.Lock()

# ---- the flight recorder: every finished span of the process
_ring: deque = deque(maxlen=RING_CAPACITY)
_ring_on = True
_span_ids = itertools.count(1)
_tls = threading.local()          # .stack: this thread's open scoped spans
# set by paddle_tpu.profiler while a Profiler records: (name, t0, t1) -> None
_profiler_emit = None

SpanRecord = namedtuple("SpanRecord", ["name", "t0", "t1", "span_id",
                                       "parent_id", "trace_id", "attrs"])


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


class Span:
    """One interval of host work. Scoped (``with span(...)``): stamped,
    annotated and linked to the enclosing scoped span of its thread on
    entry, sealed on exit. Unscoped (``trace.span(...)``): opened by its
    owner and sealed by ``end()``, possibly steps later and on another
    thread. ``event()`` attaches a point annotation (bounded — a runaway
    event stream degrades to a drop counter, never unbounded memory)."""

    MAX_EVENTS = 256

    __slots__ = ("name", "t0", "t1", "span_id", "parent_id", "key",
                 "attrs", "trace", "kind", "adopt_kind", "events",
                 "events_dropped", "_ann")

    def __init__(self, name: str, adopt_kind: Optional[str] = None,
                 **attrs):
        self.name = name
        self.attrs = attrs
        self.t0 = self.t1 = None
        self.span_id = next(_span_ids)
        self.parent_id = None
        self.key = None               # what readers of the ring call trace_id
        self.trace = None             # the trace it is a child of, if any
        self.kind = "phase"
        self.adopt_kind = adopt_kind
        self.events = None
        self.events_dropped = 0

    def _under(self, up: "Span"):
        """Link to the span that caused this one."""
        self.parent_id = up.span_id
        self.key = up.key
        self.trace = up if isinstance(up, _Trace) else up.trace

    # ------------------------------------------------------------- scoped

    def __enter__(self):
        try:
            st = _tls.stack
        except AttributeError:
            st = _tls.stack = []
        if st:
            self._under(st[-1])
        st.append(self)
        self._ann = ann = TraceAnnotation(ANNOTATION_PREFIX + self.name)
        ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(None, None, None)
        st = _tls.stack
        if st and st[-1] is self:
            st.pop()
        self.end(t1)
        return False

    # ------------------------------------------------------------ content

    def set(self, **attrs):
        self.attrs.update(attrs)
        return self

    def event(self, name: str, t: Optional[float] = None, **fields):
        if self.events is None:
            self.events = []
        elif len(self.events) >= self.MAX_EVENTS:
            self.events_dropped += 1
            return
        ev = {"name": name, "t": time.perf_counter() if t is None else t}
        if fields:
            ev.update(fields)
        self.events.append(ev)

    def end(self, t1: Optional[float] = None):
        if self.t1 is not None:
            return  # idempotent: a double end keeps the first boundary
        self.t1 = time.perf_counter() if t1 is None else t1
        if _ring_on:
            # plain tuples of plain values: the collector stops tracking
            # them, so a full ring costs later collections nothing
            _ring.append((self.name, self.t0, self.t1, self.span_id,
                          self.parent_id, self.key, self.attrs))
        emit = _profiler_emit
        if emit is not None:
            emit(self.name, self.t0, self.t1)
        tr = self.trace
        if tr is not None:
            if tr.tracer is not None:
                tr._seal(self)
        elif self.adopt_kind is not None and _active is not None:
            # observed OUTSIDE any trace (the DeviceLoader's wait/fetch/H2D
            # run before the step trace opens; a checkpoint save lands
            # between steps): buffered (bounded, cross-thread) until the
            # next trace of that kind starts and adopts it — the step
            # waterfall then shows the feed work that preceded the dispatch,
            # and an unrelated request trace in between cannot steal it
            _active._floating.append(self)

    @property
    def dur_s(self) -> float:
        return (self.t1 if self.t1 is not None
                else time.perf_counter()) - self.t0


class _Trace(Span):
    """One causal unit, which IS its root span (``<name>/<root>``) plus
    what a trace adds: children opened by name, and — with a sink session
    (``tracer``) — the buffer that holds them until ``end()`` decides
    (sampling) whether they reach the sink. Without a session the trace
    only names its spans and hands them its ``key``. As a context manager
    the root is a scoped span of the calling thread."""

    MAX_SPANS = 512

    __slots__ = ("tracer", "trace_id", "trace_name", "sampled", "escalated",
                 "_sealed", "_dropped")

    def __init__(self, tracer: Optional["Tracer"], trace_id: Optional[str],
                 name: str, kind: str, sampled: bool, attrs: dict,
                 key=None, root: str = "call"):
        Span.__init__(self, f"{name}/{root}", **attrs)
        self.t0 = time.perf_counter()
        self.key = key
        self.kind = kind
        self.tracer = tracer
        self.trace_id = trace_id       # the sink's id; None without a sink
        self.trace_name = name
        self.sampled = sampled
        self.escalated = None
        self._sealed = []              # finished children, for the sink
        self._dropped = 0

    # -------------------------------------------------------------- building

    def span(self, name: str, kind: str = "phase", parent: Optional[Span]
             = None, t0: Optional[float] = None, **attrs) -> Span:
        """Open a child span (default parent: the root)."""
        sp = Span(f"{self.trace_name}/{name}", **attrs)
        sp._under(self)
        if parent is not None:
            sp.parent_id = parent.span_id
        sp.kind = kind
        sp.t0 = time.perf_counter() if t0 is None else t0
        return sp

    def record(self, name: str, t0: float, t1: float, kind: str = "phase",
               parent: Optional[Span] = None, **attrs) -> Span:
        """A completed span in one call (both boundaries already known)."""
        sp = self.span(name, kind=kind, parent=parent, t0=t0, **attrs)
        sp.end(t1)
        return sp

    def _seal(self, span: Span):
        if len(self._sealed) >= self.MAX_SPANS:
            self._dropped += 1
            return
        self._sealed.append(span)

    def _adopt(self, span: Span):
        """Take a finished span recorded outside any trace (a loader wait
        before the step opened) as a child of the root."""
        span.trace, span.parent_id = self, self.span_id
        self._seal(span)

    # ------------------------------------------------------------- lifecycle

    def __enter__(self):
        st = _stack()
        if not (st and st[-1] is self):
            st.append(self)
        self._ann = ann = TraceAnnotation(ANNOTATION_PREFIX + self.name)
        ann.__enter__()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(None, None, None)
        self.end(t1)
        return False

    def escalate(self, reason: str = "warn"):
        """Force-sample this trace (always-sample-on-WARN): the spans are
        already buffered, so escalation any time before ``end()`` loses
        nothing. Without a sink session there is nothing to keep."""
        if self.tracer is None:
            return
        if not self.sampled:
            self.sampled = True
            self.tracer._escalated += 1
            # per-reason tally: a dump then says WHICH tripwire class
            # (health_nan, straggler, deadline...) is forcing sampling
            rs = self.tracer._escalate_reasons
            rs[reason] = rs.get(reason, 0) + 1
        if self.escalated is None:
            self.escalated = reason

    def end(self, t1: Optional[float] = None, **attrs):
        if self.t1 is not None:
            return
        if attrs:
            self.attrs.update(attrs)
        st = getattr(_tls, "stack", None)
        if st and st[-1] is self:
            st.pop()
        Span.end(self, t1)
        if self.tracer is not None:
            self.tracer._finish_trace(self)


class Tracer:
    """One enabled sink session (JSONL + sampling + escalation state)."""

    def __init__(self, path: Optional[str] = None, *,
                 sample: Optional[float] = None, flush_every: int = 32):
        if sample is None:
            try:
                sample = float(os.environ.get("PADDLE_TRACE_SAMPLE", "")
                               or 1.0)
            except ValueError:
                sample = 1.0
        self.sample = min(max(float(sample), 0.0), 1.0)
        self.sink = JsonlSink(path, flush_every) if path else None
        self.path = self.sink.path if self.sink else None
        self._wall0 = time.time()
        self._mono0 = time.perf_counter()
        self._ids = itertools.count(1)
        self._slock = threading.Lock()
        # head-sampling credit: starts at 1.0 so the FIRST trace is always
        # kept (a short run with sample=0.1 still yields one trace);
        # sample=0.0 means "escalations only" and keeps nothing up front
        self._credit = 1.0 if self.sample > 0 else 0.0
        self._open: dict = {}          # id(trace) -> trace
        self._floating = deque(maxlen=64)
        self._recent = deque(maxlen=8)   # newest sampled trace ids
        self._last_trace_id: Optional[str] = None
        self.traces_started = 0
        self.traces_sampled = 0
        self.spans_written = 0
        self._escalated = 0
        self._escalate_reasons: Dict[str, int] = {}
        self._via_monitor = False
        if self.sink is not None:
            self.sink.write({"v": TRACE_SCHEMA_VERSION, "kind": "trace_meta",
                             "ts": self._wall0, "pid": os.getpid(),
                             "proc": int(os.environ.get("PADDLE_TRAINER_ID",
                                                        "0") or 0),
                             "sample": self.sample})

    # --------------------------------------------------------------- clocks

    def wall(self, mono: float) -> float:
        return self._wall0 + (mono - self._mono0)

    # --------------------------------------------------------------- traces

    def start_trace(self, name: str, kind: str = "trace",
                    current: bool = True, key=None, root: str = "call",
                    **attrs) -> _Trace:
        """Open a trace. ``current=True`` makes its root the innermost
        scoped span of this thread (step traces; WARN tagging reads it);
        serving request traces pass False — many are open at once and none
        is "the" current one. Pending floating spans (loader waits recorded
        before any trace existed) are adopted as children of the new root.
        """
        with self._slock:
            self._credit += self.sample
            sampled = self._credit >= 1.0
            if sampled:
                self._credit -= 1.0
            n = next(self._ids)
        tid = f"{os.getpid():x}-{n:x}"
        tr = _Trace(self, tid, name, kind, sampled, attrs, key, root)
        with self._slock:
            # the open-trace map is read by OTHER threads (escalate from
            # the aggregator's WARN path, snapshot_info from dump) — every
            # access goes through the lock
            self._open[id(tr)] = tr
        self._last_trace_id = tid
        self.traces_started += 1
        if current:
            _stack().append(tr)
        if self._floating:
            # adopt only the floats addressed to this trace KIND: loader/
            # ckpt spans are step-trace context — a serving request trace
            # starting in between must not steal them
            with self._slock:
                keep, mine = deque(maxlen=self._floating.maxlen), []
                for sp in self._floating:
                    (mine if sp.adopt_kind == kind else keep).append(sp)
                self._floating = keep
            for sp in mine:
                tr._adopt(sp)
        return tr

    def _finish_trace(self, tr: _Trace):
        with self._slock:
            self._open.pop(id(tr), None)
        spans = [tr] + tr._sealed
        tr._sealed = []        # the children point back at their trace
        if not tr.sampled:
            return
        self.traces_sampled += 1
        self._recent.append(tr.trace_id)
        # the sink numbers a trace's spans from its root (0) in creation
        # order, whatever ids the process-wide counter handed them
        local = {tr.span_id: 0}
        for i, g in enumerate(sorted(s.span_id for s in spans[1:]), 1):
            local[g] = i
        prefix = tr.trace_name + "/"
        # children sealed before an escalation/late root-end keep insertion
        # order; export sorts by start so waterfalls render stably
        spans.sort(key=lambda s: (s.t0, local[s.span_id]))
        for sp in spans:
            if sp is tr:
                parent, name = None, tr.trace_name
            else:
                parent = local.get(sp.parent_id, 0)
                name = sp.name[len(prefix):] if sp.name.startswith(prefix) \
                    else sp.name
            rec = {"v": TRACE_SCHEMA_VERSION, "kind": "span",
                   "trace": tr.trace_id, "span": local[sp.span_id],
                   "parent": parent, "name": name,
                   "span_kind": sp.kind, "ts": self.wall(sp.t0),
                   "dur_s": round((sp.t1 if sp.t1 is not None else sp.t0)
                                  - sp.t0, 9)}
            if sp.attrs:
                rec["attrs"] = sp.attrs
            if sp.events:
                rec["events"] = [
                    dict(e, t=self.wall(e["t"])) for e in sp.events]
            if sp.events_dropped:
                rec["events_dropped"] = sp.events_dropped
            if self.sink is not None:
                self.sink.write(rec)
                self.spans_written += 1
        summary = {"v": TRACE_SCHEMA_VERSION, "kind": "trace",
                   "trace": tr.trace_id, "name": tr.trace_name,
                   "trace_kind": tr.kind, "ts": self.wall(tr.t0),
                   "dur_s": round(tr.dur_s, 9),
                   "spans": len(spans)}
        if tr.escalated:
            summary["escalated"] = tr.escalated
        if tr._dropped:
            summary["spans_dropped"] = tr._dropped
        if tr.attrs:
            summary["attrs"] = tr.attrs
        if self.sink is not None:
            self.sink.write(summary)

    # ------------------------------------------------------------- floating

    def floating(self, name: str, t0: float, t1: float,
                 adopt_kind: str = "step", **attrs):
        """A completed span observed OUTSIDE any trace, for the next trace
        of ``adopt_kind`` to adopt: ``record()`` under its older name."""
        record(name, t0, t1, adopt_kind=adopt_kind, **attrs)

    # ------------------------------------------------------------ WARN hooks

    def current_trace_id(self) -> Optional[str]:
        """This thread's open trace id (innermost scoped span that belongs
        to one), else the most recently started trace anywhere — what a
        WARN record embeds."""
        for sp in reversed(getattr(_tls, "stack", None) or ()):
            tr = sp if isinstance(sp, _Trace) else sp.trace
            if tr is not None and tr.trace_id is not None:
                return tr.trace_id
        return self._last_trace_id

    def escalate(self, trace: Optional[_Trace] = None,
                 reason: str = "warn"):
        """Force-sample ``trace`` — or, with None, EVERY open trace (a
        fleet WARN arriving on the aggregator thread cannot know which of
        the live traces is implicated; keeping all of them is bounded by
        the open-trace count and loses nothing)."""
        if trace is not None:
            trace.escalate(reason)
            return
        with self._slock:
            targets = list(self._open.values())
        for tr in targets:
            tr.escalate(reason)

    # ------------------------------------------------------------- plumbing

    def snapshot_info(self) -> dict:
        """Flight-dump payload: where the trace stream lives and which
        traces were recently active (the crash report names the trace to
        open, not just the metrics at death)."""
        with self._slock:
            open_ids = [tr.trace_id for tr in self._open.values()]
        return {"path": self.path, "current": self.current_trace_id(),
                "open": open_ids,
                "recent": list(reversed(self._recent)), "sample": self.sample,
                "started": self.traces_started,
                "sampled": self.traces_sampled,
                "escalated": self._escalated,
                "escalated_reasons": dict(self._escalate_reasons)}

    def flush(self):
        if self.sink is not None:
            self.sink.flush()

    def close(self):
        # traces still open at close (e.g. requests in flight) are ended so
        # their spans are not silently lost
        with self._slock:
            still_open = list(self._open.values())
        for tr in still_open:
            try:
                tr.end(status="tracer_closed")
            except Exception:
                pass
        if self.sink is not None:
            self.sink.close()


# ---------------------------------------------------------- the one span call


# ``with span("engine/admit") as sp: ...`` — a scoped span. Its parent, trace
# id and trace are those of the enclosing scoped span of this thread.
# ``adopt_kind``: where it runs outside any trace, the kind of trace that
# should adopt it when the sink is on ("step" for the input pipeline's spans).
span = Span


def record(name: str, t0: float, t1: float,
           adopt_kind: Optional[str] = None, **attrs) -> Span:
    """An interval already timed (it cannot become an annotation after the
    fact). Inside a scoped span it is that span's child."""
    sp = Span(name, adopt_kind, **attrs)
    st = getattr(_tls, "stack", None)
    if st:
        sp._under(st[-1])
    sp.t0 = t0
    sp.end(t1)
    return sp


def start_trace(name: str, key=None, kind: str = "trace",
                current: bool = True, root: str = "call", **attrs) -> _Trace:
    """Open the trace of one request or step: through the sink session
    when one is enabled, else a bare one whose spans reach the ring only.
    ``key`` is the id its spans carry as ``trace_id``; the root span is
    ``<name>/<root>``."""
    tracer = _active
    if tracer is not None:
        return tracer.start_trace(name, kind, current, key, root, **attrs)
    tr = _Trace(None, None, name, kind, False, attrs, key, root)
    if current:
        _stack().append(tr)
    return tr


def _snapshot() -> list:
    while True:
        try:
            return list(_ring)
        except RuntimeError:     # another thread appended mid-copy
            continue


def spans(t0: float, t1: float, prefix: Optional[str] = None) -> list:
    """The finished spans wholly inside ``[t0, t1]`` (``perf_counter``
    seconds), oldest first, as :class:`SpanRecord`; ``prefix`` keeps the
    names that start with it. The ring holds the newest ``RING_CAPACITY``
    spans of the process."""
    return [SpanRecord(*s) for s in _snapshot()
            if s[1] >= t0 and s[2] <= t1
            and (prefix is None or s[0].startswith(prefix))]


def ring(on: bool):
    """Switch the ring's recording off or on again. It is on by default;
    this exists so that its cost can be measured, not as a mode."""
    global _ring_on
    _ring_on = bool(on)


# ------------------------------------------------------------- the sink session


def enable(path: Optional[str] = None, *, sample: Optional[float] = None,
           flush_every: int = 32) -> Tracer:
    """Turn the sink session on. ``path`` is the trace JSONL file (None:
    sampling and escalation state only); multi-process runs write
    ``path.procN`` per the sink contract. ``sample``: head-sampling
    probability (default: env ``PADDLE_TRACE_SAMPLE``, else 1.0).
    Idempotent-safe."""
    global _active
    with _lock:
        if _active is not None:
            _teardown_locked()
        _active = Tracer(path, sample=sample, flush_every=flush_every)
    return _active


def _teardown_locked():
    global _active
    tr, _active = _active, None
    if tr is not None:
        tr.close()


def disable():
    with _lock:
        _teardown_locked()


def enabled() -> bool:
    return _active is not None


def get() -> Optional[Tracer]:
    return _active


def current_trace_id() -> Optional[str]:
    tr = _active
    return tr.current_trace_id() if tr is not None else None


def escalate(reason: str = "warn"):
    """Module-level always-sample-on-WARN hook (no-op when disabled)."""
    tr = _active
    if tr is not None:
        tr.escalate(reason=reason)


@atexit.register
def _atexit_close():
    # the sink buffers writes; a process that exits without disable() must
    # not lose its tail spans (open traces are ended + flushed by close)
    tr = _active
    if tr is not None:
        try:
            tr.close()
        except Exception:
            pass
