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
inside its trace, and so in the sink, it keeps the short name. The ring
says what it has lost: ``evicted()`` counts the spans pushed out since the
process began, ``oldest()`` is where what it still holds begins.

The same ring holds what the HOST did to a step, so that a step that took
seconds with the device idle can say why. ``host_clocks()`` reads, once a
step, what the operating system says of the stepping thread (CPU
time of the process and of the thread, run-queue wait, involuntary
switches, major faults; the pressure totals at most once a second). Two
listeners, installed with this module and silent until they fire, record
``gc/collect`` (a collection of ``GC_PAUSE_S`` or more) and
``jax/compile`` (a backend compile or a compilation-cache read). A site
that knows what its step launched (the serving engine its calls,
``Tensor.numpy()`` its own wait) hands the finished step to ``book()``,
which keeps the running mean of each kind of call and, where the step took
far longer than their sum (``stalled``), seals ONE ``host/stall`` record
over it (``stall``): where the time went (``site``), what the clocks moved
by, and what of it was a collection or a compile.

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
import gc
import itertools
import os
import resource
import threading
import time
import warnings
from collections import deque, namedtuple
from typing import Dict, Optional

from jax import monitoring as _jax_monitoring
from jax.profiler import TraceAnnotation

from .sink import JsonlSink

__all__ = ["TRACE_SCHEMA_VERSION", "RING_CAPACITY", "STALL_FACTOR",
           "STALL_EXCESS_S", "STALL_MIN_SAMPLES", "Span", "SpanRecord",
           "Tracer", "span", "record", "spans", "ring", "evicted", "oldest",
           "host_clocks", "stalled", "stall", "book", "start_trace", "enable",
           "disable", "enabled", "get", "current_trace_id", "escalate"]

TRACE_SCHEMA_VERSION = 1
# the fullest cell of the benchmark (chat: a step every 7 ms, nine spans a
# step and the requests' own: up to 45 k spans in a 51 s window) fills a
# third of it; PERF.md section 7 item 5 has the counts and the bytes
RING_CAPACITY = 131072
# a step has stalled where its wall time is over what its calls have been
# taking by BOTH this factor and this many seconds, and every kind of call
# involved has been seen this often (PERF.md section 6, PR 38, says what
# the chip's clean runs read against them)
STALL_FACTOR = 2.0
STALL_EXCESS_S = 0.25
STALL_MIN_SAMPLES = 8
GC_PAUSE_S = 1e-3             # a collection shorter than this is not recorded
PRESSURE_EVERY_S = 1.0        # the pressure files are read at most this often
ANNOTATION_PREFIX = "paddle/"

# the sink session, when one is enabled (sampling, JSONL, escalation)
_active: Optional["Tracer"] = None

_lock = threading.Lock()

# ---- the flight recorder: every finished span of the process
_ring: deque = deque(maxlen=RING_CAPACITY)
_ring_on = True
_evicted = [0]                    # spans the full ring has pushed out
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
            if len(_ring) == _ring.maxlen:
                _evicted[0] += 1
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


def evicted() -> int:
    """Spans the full ring has pushed out since the process began (counted
    without a lock: exact on one thread). 0: it holds every span."""
    return _evicted[0]


def oldest() -> Optional[float]:
    """``t0`` of the oldest span the ring holds; None where it holds
    none. Spans enter as they END: a window that starts after it is held
    whole but for what ended inside that one span."""
    try:
        return _ring[0][1]
    except IndexError:
        return None


# ------------------------------------------------- what the host did to a step

_RUSAGE_THREAD = getattr(resource, "RUSAGE_THREAD", resource.RUSAGE_SELF)
# (stamp, "some" totals in us of cpu, io, memory): the last reading
_pressure = None


class _Fd:
    """A file descriptor that closes with the thread that opened it."""

    __slots__ = ("fd",)

    def __init__(self, fd: int):
        self.fd = fd

    def __del__(self):
        os.close(self.fd)


def _read_clocks() -> tuple:
    sched = getattr(_tls, "sched", None)
    if sched is None:
        try:
            sched = _Fd(os.open("/proc/thread-self/schedstat", os.O_RDONLY))
        except OSError:
            sched = False
        _tls.sched = sched
    wait_ns = None
    if sched:
        try:    # "<on-cpu ns> <run-queue wait ns> <timeslices>"
            wait_ns = int(os.pread(sched.fd, 64, 0).split()[1])
        except (OSError, IndexError, ValueError):
            pass
    ru = resource.getrusage(_RUSAGE_THREAD)
    return (time.process_time(), time.thread_time(), wait_ns,
            ru.ru_nivcsw, ru.ru_majflt)


def _read_pressure() -> tuple:
    out = []
    for what in ("cpu", "io", "memory"):
        try:    # "some avg10=0.00 avg60=0.00 avg300=0.00 total=<us>"
            with open(f"/proc/pressure/{what}", "rb") as f:
                out.append(int(f.readline().rsplit(b"total=", 1)[1]))
        except (OSError, IndexError, ValueError):
            out.append(None)
    return tuple(out)


def host_clocks() -> Optional[tuple]:
    """What the operating system says of the calling thread, read once a
    step where the host has time for four system calls (the engine inside
    ``engine/collect`` before it waits, the device busy with what was just
    launched; ``train_step/call`` on entry): (CPU seconds of the process,
    CPU seconds of the thread, nanoseconds the thread has waited on a run
    queue or None where ``/proc/thread-self/schedstat`` is not there, its
    involuntary context switches, its major page faults). The thread's
    last TWO readings are kept, each with its instant: a ``host/stall``
    record takes its deltas from the newest that was made before the
    stalled step began, so that they cover the whole of it wherever in the
    step this was called (and say over how long, ``clocks_s``). The
    pressure totals (``/proc/pressure/{cpu,io,memory}``) are read beside
    it at most once in ``PRESSURE_EVERY_S``. Nothing is read, and None
    returned, while the ring is off."""
    global _pressure
    if not _ring_on:
        return None
    now = time.perf_counter()
    clocks = _read_clocks()
    last = getattr(_tls, "clocks", None)
    _tls.clocks = (last and last[-1], (now, clocks))
    if _pressure is None or now - _pressure[0] >= PRESSURE_EVERY_S:
        _pressure = (now, _read_pressure())
    return clocks


def _on_gc(phase, info, _t0=[0.0]):
    if phase == "start":
        _t0[0] = time.perf_counter()
        return
    t1 = time.perf_counter()
    if t1 - _t0[0] >= GC_PAUSE_S:
        record("gc/collect", _t0[0], t1, generation=info["generation"],
               collected=info["collected"])


def _on_jax_duration(event: str, duration_secs: float, **_):
    if event.endswith("backend_compile_duration") \
            or event.endswith("cache_retrieval_time_sec"):
        t1 = time.perf_counter()
        record("jax/compile", t1 - duration_secs, t1,
               event=event.rsplit("/", 1)[-1])


# what the two listeners record: children of whatever span was open
_LISTENED = ("gc/collect", "jax/compile")
gc.callbacks.append(_on_gc)
_jax_monitoring.register_event_duration_secs_listener(_on_jax_duration)


def stalled(wall_s: float, expected_s: float) -> bool:
    """Whether a step of ``wall_s`` seconds, whose calls have been taking
    ``expected_s``, has stalled: over by both the factor and the excess."""
    return wall_s > STALL_FACTOR * expected_s \
        and wall_s - expected_s > STALL_EXCESS_S


def book(step: Span, calls: list, means: dict, **attrs) -> Optional[Span]:
    """Hold a finished ``step`` against what its ``calls`` ([(kind,
    seconds)]: what each took this time) have been taking: ``means`` is the
    site's own ``{kind: [samples, running mean]}``. Far over their sum
    (``stalled``), with every kind seen ``STALL_MIN_SAMPLES`` times (a
    compile, an opening step of first chunks are no stall), the step is
    sealed as ONE ``host/stall`` record, which is returned, and its
    intervals do not move the means; else they do. Nothing is held or
    kept while the ring is off."""
    if not _ring_on:
        return None
    expected = 0.0
    for kind, _ in calls:
        m = means.get(kind)
        if m is None or m[0] < STALL_MIN_SAMPLES:
            break
        expected += m[1]
    else:
        if calls and stalled(step.t1 - step.t0, expected):
            counts: dict = {}
            for kind, _ in calls:
                counts[kind] = counts.get(kind, 0) + 1
            return stall(step, expected, counts, **attrs)
    for kind, took in calls:
        m = means.get(kind)
        if m is None:
            means[kind] = [1, took]
        else:
            m[0] += 1
            m[1] += (took - m[1]) / min(m[0], 16)
    return None


def _covered(found: list, name: str) -> float:
    """Seconds that the spans called ``name`` cover together (a cache read
    lies inside its backend compile: counted once)."""
    total, edge = 0.0, float("-inf")
    for s in sorted((s for s in found if s.name == name),
                    key=lambda s: s.t0):
        total += max(0.0, s.t1 - max(s.t0, edge))
        edge = max(edge, s.t1)
    return round(total, 6)


def _moved(keys, then, now, scales) -> dict:
    """{key: how far a counter moved, scaled}; None where either reading
    is missing."""
    return {k: None if a is None or b is None else round((b - a) * sc, 6)
            for k, a, b, sc in zip(keys, then, now, scales)}


def stall(step: Span, expected_s: float, calls: dict, **attrs) -> Span:
    """Seal the ONE ``host/stall`` record of ``step`` (a finished span of
    the calling thread for which ``stalled`` held): ``site`` (the longest
    span under it that has none under itself), ``wall_s``, ``expected_s``,
    ``excess_s``, ``calls`` ({kind: count} of what it launched), how far
    the thread's clocks moved since ``host_clocks()`` last read them
    BEFORE the step began (``clocks_s`` ago, so over all of the step and
    what lay between: ``cpu_process_s``, ``cpu_thread_s``,
    ``runq_wait_s``, ``nivcsw``, ``majflt``), how far the pressure totals
    moved since their last reading (``pressure_s`` ago:
    ``pressure_cpu_s``, ``pressure_io_s``, ``pressure_memory_s``), the
    seconds of ``gc/collect`` and ``jax/compile`` records inside the step
    (any thread's: a collection holds every thread), and its three
    ``longest`` such spans (a ``gc/collect`` or ``jax/compile`` record is
    a child of whatever span was open and makes no parent of it: the span
    a compile ran in is still the site). None stands where the system
    does not say.
    Open traces are escalated (``stall``), and the record is warned of and
    handed to the monitor where one is on, as a dispatch hang is."""
    global _pressure
    now = time.perf_counter()
    found = spans(step.t0, step.t1)
    under, parents = {step.span_id}, set()
    for s in reversed(found):           # a parent ends after its children
        if s.parent_id in under and s.name not in _LISTENED:
            under.add(s.span_id)
            parents.add(s.parent_id)
    longest = sorted(((s.t1 - s.t0, s.name) for s in found
                      if s.span_id in under and s.span_id not in parents),
                     reverse=True)[:3]
    wall = step.t1 - step.t0
    rec = dict(site=longest[0][1] if longest else step.name,
               wall_s=round(wall, 6), expected_s=round(expected_s, 6),
               excess_s=round(wall - expected_s, 6), calls=calls,
               step=step.span_id)
    # the newest reading made before the step began
    then = next((r for r in reversed(getattr(_tls, "clocks", None) or ())
                 if r and r[0] <= step.t0), (None, (None,) * 5))
    rec["clocks_s"] = then[0] and round(now - then[0], 6)
    rec.update(_moved(("cpu_process_s", "cpu_thread_s", "runq_wait_s",
                       "nivcsw", "majflt"), then[1], _read_clocks(),
                      (1, 1, 1e-9, 1, 1)))
    p0, _pressure = _pressure or (None, (None,) * 3), \
        (now, _read_pressure())
    rec["pressure_s"] = p0[0] and round(now - p0[0], 6)
    rec.update(_moved(("pressure_cpu_s", "pressure_io_s",
                       "pressure_memory_s"), p0[1], _pressure[1],
                      (1e-6,) * 3))
    rec["gc_s"] = _covered(found, "gc/collect")
    rec["compile_s"] = _covered(found, "jax/compile")
    rec["longest"] = [[name, round(dur, 6)] for dur, name in longest]
    rec.update(attrs)
    sp = record("host/stall", step.t0, step.t1, **rec)
    escalate("stall")
    from . import emit        # the package imports this module first
    emit("host_stall", **rec)
    warnings.warn(
        f"host stall: {step.name} took {wall:.3f}s where its calls have "
        f"been taking {expected_s:.3f}s; most of it in {rec['site']} "
        f"(run-queue wait {rec['runq_wait_s']}s, process CPU "
        f"{rec['cpu_process_s']}s, gc {rec['gc_s']}s, compile "
        f"{rec['compile_s']}s)", RuntimeWarning, stacklevel=4)
    return sp


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
