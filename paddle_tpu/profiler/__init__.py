"""paddle.profiler — host events, op timing, Chrome trace export, stats.

Reference analog: python/paddle/profiler/profiler.py (Profiler with
scheduler(wait/warmup/active), RecordEvent, export_chrome_tracing),
profiler_statistic.py (summary tables), platform/profiler/host_tracer.cc
(host event recording around op execution) and chrometracing_logger.cc.

TPU-native split: HOST events (op dispatch, user RecordEvent ranges, data
loading) are recorded in-process exactly like the reference's host tracer;
DEVICE timing belongs to the XLA runtime, so `use_device_trace=True` brackets
the active window with jax.profiler.start_trace/stop_trace — the TensorBoard/
perfetto trace is the CUPTI-tracer analog. Host events alone are meaningful on
TPU: per-op host time IS dispatch cost, the thing eager mode needs to minimize.
"""
from __future__ import annotations

import json
import os
import threading
import time
from dataclasses import dataclass, field
from enum import Enum
from typing import Callable, List, Optional, Sequence, Tuple

from .. import monitor as _monitor
from ..core import dispatch
from ..monitor import trace as _trace

__all__ = ["Profiler", "ProfilerTarget", "ProfilerState", "RecordEvent",
           "make_scheduler", "export_chrome_tracing", "load_profiler_result",
           "record_stage"]


class ProfilerTarget(Enum):
    CPU = 0
    CUSTOM_DEVICE = 3   # parity: the TPU is a "custom device" in reference terms
    TPU = 3


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


@dataclass
class _HostEvent:
    name: str
    start: float
    end: float
    kind: str = "op"          # "op" | "user" | "stage"
    tid: int = 0              # OS thread ident of the emitting thread
    tname: str = ""


class _Recorder:
    def __init__(self):
        self.events: List[_HostEvent] = []
        self.enabled = False

    def emit(self, name, start, end, kind="op"):
        if self.enabled:
            # real thread identity: the DeviceLoader producer emits fetch/h2d
            # from its own thread — a Chrome trace must keep it on a separate
            # row from the consumer's wait/dispatch events
            th = threading.current_thread()
            self.events.append(_HostEvent(name, start, end, kind,
                                          th.ident or 0, th.name))
        if kind != "op":
            # stage/user ranges mirror into the monitor sink (one JSONL tells
            # the whole story); op events stay out — the monitor counts those
            # in aggregate via its dispatch hook
            mon = _monitor._active
            if mon is not None:
                mon.stage_event(name, start, end, kind)


_recorder = _Recorder()


def _dispatch_hook(name: str, start: float, end: float):
    _recorder.emit(name, start, end, "op")


def record_stage(name: str, start: float, end: float):
    """Emit a pipeline-stage event. Recorded into the Profiler when one is
    recording, and mirrored as a ``stage`` record into an enabled
    ``paddle_tpu.monitor`` sink — it is only a no-op when BOTH are off.
    The program's own phases (``io.DeviceLoader``, ``jit.TrainStep``, the
    serving engine) do not call this: they make one ``monitor.trace`` span
    call, and while a Profiler records every finished span arrives here
    under its span name (``loader/wait``, ``train_step/dispatch``,
    ``engine/decode_call``, ...)."""
    _recorder.emit(name, start, end, "stage")


class RecordEvent:
    """User-annotated range (reference paddle.profiler.RecordEvent)."""

    def __init__(self, name: str, event_type=None):
        self.name = name
        self._t0 = None

    def begin(self):
        self._t0 = time.perf_counter()

    def end(self):
        if self._t0 is not None:
            _recorder.emit(self.name, self._t0, time.perf_counter(), "user")
            self._t0 = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0) -> Callable[[int], ProfilerState]:
    """reference profiler.py make_scheduler: step number -> state."""
    period = closed + ready + record

    def scheduler(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat > 0 and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    """on_trace_ready handler writing a Chrome trace JSON (reference
    export_chrome_tracing / chrometracing_logger.cc)."""

    def handler(prof: "Profiler"):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"host_{os.getpid()}"
        path = os.path.join(dir_name, f"{name}_time_{int(time.time())}"
                                      f".paddle_trace.json")
        prof._export_chrome(path)
        prof.last_export_path = path

    return handler


def load_profiler_result(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Profiler:
    """reference paddle.profiler.Profiler.

    with Profiler(scheduler=(2, 5)) as p:   # record steps [2, 5)
        for batch in loader:
            train_step(batch)
            p.step()
    print(p.summary())
    """

    def __init__(self, *, targets: Optional[Sequence] = None,
                 scheduler=None, on_trace_ready: Optional[Callable] = None,
                 timer_only: bool = False, use_device_trace: bool = False,
                 trace_dir: Optional[str] = None):
        if isinstance(scheduler, tuple):
            start, stop = scheduler
            scheduler = make_scheduler(closed=max(start, 0), ready=0,
                                       record=stop - start, repeat=1)
        self._scheduler = scheduler or (lambda step: ProfilerState.RECORD)
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        self._use_device_trace = use_device_trace
        self._trace_dir = trace_dir or "./profiler_trace"
        self._step = 0
        self._state = ProfilerState.CLOSED
        self._step_times: List[float] = []
        self._t_last = None
        self._device_tracing = False
        # initialized here, not in start(): stop() without start() must be a
        # clean no-op, not an AttributeError (and must not hand the GLOBAL
        # recorder's events — possibly another run's — to on_trace_ready)
        self._notified = False
        self._started = False
        self.last_export_path: Optional[str] = None

    # ------------------------------------------------------------- lifecycle

    def start(self):
        _recorder.events.clear()     # each profiler run owns a fresh recorder
        self._notified = False
        self._started = True
        self._state = self._scheduler(self._step)
        self._apply_state()
        self._t_last = time.perf_counter()
        return self

    def stop(self):
        self._set_recording(False)
        if self._device_tracing:
            import jax
            jax.profiler.stop_trace()
            self._device_tracing = False
        if self._on_trace_ready is not None and self._started \
                and _recorder.events and not self._notified:
            self._on_trace_ready(self)
            self._notified = True
        self._state = ProfilerState.CLOSED

    def step(self):
        now = time.perf_counter()
        if self._t_last is not None:
            self._step_times.append(now - self._t_last)
        self._t_last = now
        prev = self._state
        self._step += 1
        self._state = self._scheduler(self._step)
        if prev != self._state:
            self._apply_state()
        if prev in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN) \
                and self._state == ProfilerState.CLOSED \
                and self._on_trace_ready is not None:
            self._on_trace_ready(self)
            self._notified = True

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    def _apply_state(self):
        rec = self._state in (ProfilerState.RECORD,
                              ProfilerState.RECORD_AND_RETURN)
        self._set_recording(rec and not self._timer_only)
        if rec and self._use_device_trace and not self._device_tracing:
            import jax
            jax.profiler.start_trace(self._trace_dir)
            self._device_tracing = True
        if not rec and self._device_tracing:
            import jax
            jax.profiler.stop_trace()
            self._device_tracing = False

    def _set_recording(self, on: bool):
        _recorder.enabled = on
        dispatch.set_profiler_hook(_dispatch_hook if on else None)
        # the span layer fans its finished spans out to this recorder
        _trace._profiler_emit = record_stage if on else None

    # ------------------------------------------------------------- reporting

    @property
    def events(self) -> List[_HostEvent]:
        return list(_recorder.events)

    def summary(self, sorted_by: str = "total", row_limit: int = 30) -> str:
        """Aggregated per-name table (reference profiler_statistic tables).

        ``sorted_by``: one of total/avg/max/min/count (milliseconds except
        count)."""
        if sorted_by not in ("total", "avg", "max", "min", "count"):
            raise ValueError(
                f"summary(sorted_by={sorted_by!r}): expected one of "
                f"'total', 'avg', 'max', 'min', 'count'")
        agg = {}
        for e in _recorder.events:
            dur = (e.end - e.start) * 1e3
            entry = agg.setdefault((e.kind, e.name),
                                   {"count": 0, "total": 0.0, "max": 0.0,
                                    "min": float("inf")})
            entry["count"] += 1
            entry["total"] += dur
            entry["max"] = max(entry["max"], dur)
            entry["min"] = min(entry["min"], dur)
        for entry in agg.values():
            entry["avg"] = entry["total"] / max(entry["count"], 1)
        rows = sorted(agg.items(),
                      key=lambda kv: kv[1][sorted_by],
                      reverse=True)[:row_limit]
        out = [f"{'Name':<40}{'Kind':<8}{'Calls':>8}{'Total(ms)':>12}"
               f"{'Avg(ms)':>10}{'Max(ms)':>10}{'Min(ms)':>10}"]
        out.append("-" * len(out[0]))
        for (kind, name), s in rows:
            avg = s["total"] / max(s["count"], 1)
            out.append(f"{name[:39]:<40}{kind:<8}{s['count']:>8}"
                       f"{s['total']:>12.3f}{avg:>10.3f}{s['max']:>10.3f}"
                       f"{s['min']:>10.3f}")
        if self._step_times:
            total = sum(self._step_times)
            out.append("-" * len(out[0]))
            out.append(f"steps: {len(self._step_times)}  total {total:.3f}s  "
                       f"avg {total / len(self._step_times) * 1e3:.2f}ms/step")
        return "\n".join(out)

    def overlap_report(self) -> dict:
        """Attribute recorded wall time to the train-loop pipeline stages.

        ``feed_stall_s`` is the time the consumer actually blocked waiting on
        the DeviceLoader — feed cost that was NOT hidden behind device
        compute; ``feed_fetch_s``/``feed_h2d_s`` ran on the producer thread
        (hidden when stall is ~0); ``dispatch_s`` is TrainStep fast-path
        dispatch. A healthy pipelined loop shows feed_stall_s ≪ wall_s while
        feed_fetch_s + feed_h2d_s can be a large fraction of it."""
        agg = {}
        for e in _recorder.events:
            if e.kind == "stage":
                agg[e.name] = agg.get(e.name, 0.0) + (e.end - e.start)
        if self._step_times:
            wall = sum(self._step_times)
        else:
            # no explicit Profiler.step() calls (the plain `with Profiler()`
            # usage): fall back to the recorded event span
            starts = [e.start for e in _recorder.events]
            ends = [e.end for e in _recorder.events]
            wall = (max(ends) - min(starts)) if starts else 0.0
        return {
            "feed_stall_s": agg.get("loader/wait", 0.0),
            "feed_fetch_s": agg.get("loader/fetch", 0.0),
            "feed_h2d_s": agg.get("loader/h2d", 0.0),
            "dispatch_s": agg.get("train_step/dispatch", 0.0),
            "steps": len(self._step_times),
            "wall_s": wall,
        }

    def step_info(self) -> str:
        if not self._step_times:
            return "no steps recorded"
        avg = sum(self._step_times) / len(self._step_times)
        return (f"avg step {avg * 1e3:.2f}ms, ips {1.0 / avg:.2f} steps/s "
                f"over {len(self._step_times)} steps")

    def _export_chrome(self, path: str):
        # the program's spans (monitor/trace.py) that finished while this
        # profiler recorded are among the stage events already: the span
        # layer fans them out to the recorder, on the same perf_counter
        t0 = min((e.start for e in _recorder.events), default=0.0)
        pid = os.getpid()
        # real thread ids, compacted to stable small ints in order of first
        # appearance, with thread_name metadata rows — the DeviceLoader
        # producer thread lands on its own track instead of folding into the
        # consumer's
        tid_map = {}
        meta = []
        events = []
        for e in _recorder.events:
            tid = tid_map.get(e.tid)
            if tid is None:
                tid = tid_map[e.tid] = len(tid_map)
                meta.append({"name": "thread_name", "ph": "M", "pid": pid,
                             "tid": tid, "ts": 0.0, "dur": 0.0,
                             "args": {"name": e.tname or f"thread-{e.tid}"}})
            events.append({"name": e.name, "ph": "X", "pid": pid, "tid": tid,
                           "ts": (e.start - t0) * 1e6,
                           "dur": (e.end - e.start) * 1e6, "cat": e.kind})
        with open(path, "w") as f:
            json.dump({"traceEvents": meta + events,
                       "displayTimeUnit": "ms"}, f)

    def export(self, path: str, format: str = "json"):
        self._export_chrome(path)

    def reset(self):
        _recorder.events.clear()
        self._step_times.clear()


class SortedKeys(Enum):
    """Summary sort orders (reference profiler.SortedKeys)."""
    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7


class SummaryView(Enum):
    """Summary table selector (reference profiler.SummaryView)."""
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8


def export_protobuf(dir_name: str, worker_name: Optional[str] = None):
    """on_trace_ready handler in the reference's protobuf format slot; the
    trace payload here is the Chrome-trace JSON (documented format
    difference — TPU tooling consumes Chrome/perfetto traces)."""

    def handler(prof: "Profiler"):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"host_{os.getpid()}"
        path = os.path.join(dir_name, f"{name}.paddle_trace.pb.json")
        prof._export_chrome(path)
        prof.last_export_path = path

    return handler


__all__ += ["SortedKeys", "SummaryView", "export_protobuf"]
