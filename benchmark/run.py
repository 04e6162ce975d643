"""One run of one cell:

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (build, weights from the seed, compilation or cache load, warm-up
of the cell's own shapes) is timed as `setup_s`; then the window; then
the comparison with the plain reference. The last line of standard output
is the result. Fails, and prints no result, without a TPU, with fewer
chips than the cell asks for, or on a `device_kind` missing from
`peaks.json`.
"""
from __future__ import annotations

import time

_T_IMPORT = time.time()

import argparse
import json
import os
import shutil
import sys
import tempfile
from contextlib import contextmanager


def process_start() -> float:
    """Wall-clock time this process started (Linux), else import time."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return time.time() - (up - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return _T_IMPORT


class Setup:
    """Where set-up time went, phase by phase; `close()` is the instant
    the window opens."""

    def __init__(self, t_start: float):
        self.t_start, self.phases, self.seconds = t_start, [], None
        self._mark = time.time()
        self.phases.append(("process_and_imports", self._mark - t_start))

    @contextmanager
    def phase(self, name: str):
        t0 = time.time()
        yield
        self.phases.append((name, time.time() - t0))

    def close(self):
        self.seconds = time.time() - self.t_start


class Tracer:
    """Takes the profiler's trace over a part of the window (the mix says
    which), so the trace stays small. Off: every call is a no-op."""

    def __init__(self, on: bool, rec):
        self.on, self.rec = on, rec
        self.dir = self.t_on = self._win = None
        self.state = "off"
        self.host_window = None
        self.cost_s = 0.0        # host time lost to starting the profiler

    def plan(self, t_open: float, seconds: float, cfg):
        """Trace the LAST `seconds` of the window: the steadiest part of
        a cell that starts empty, and stopping the profiler, which takes
        seconds, then falls after the close."""
        if not self.on:
            return
        span = min((cfg or {}).get("seconds", 8), 0.5 * seconds)
        self.t_on = t_open + seconds - span
        self.state = "planned"

    def tick(self):
        if self.state == "planned" and time.perf_counter() >= self.t_on:
            import jax
            t0 = time.perf_counter()
            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self.dir)
            self.cost_s = time.perf_counter() - t0
            self.rec.tracing = True
            self._win = self.rec.span("traced_window")
            self._win.__enter__()
            self.state = "tracing"
            self.host_window = [time.perf_counter(), None]

    def stop(self):
        if self.state != "tracing":
            return
        import jax
        self.host_window[1] = time.perf_counter()
        self._win.__exit__(None, None, None)
        self.rec.tracing = False
        jax.profiler.stop_trace()
        self.state = "done"

    def load(self):
        if self.state != "done":
            return None
        from . import trace
        try:
            return trace.load(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def run_cell(cell, seed: int, seconds: float, traced: bool, device: dict,
             peaks: dict, t_start: float, faults=None) -> tuple:
    """Everything of a run after the look for a chip. Returns (result
    line as a dict, the numbers compared as rows, the driver's record)."""
    from . import correct, system
    from .trace import Recorder
    rec = Recorder()
    tracer = Tracer(traced, rec)
    setup = Setup(t_start)
    with setup.phase("compile_cache"):
        cache = system.enable_compile_cache()
    out = cell.driver()(cell, seed, seconds, rec, tracer, setup, faults)
    out["end_to_end"]["setup_s"] = setup.seconds

    rows, ok = correct.verdict(out["numbers"], cell.limits)
    phases = ", ".join(f"{n} {s:.1f}s" for n, s in setup.phases)
    print(f"[bench] set-up {setup.seconds:.1f}s: {phases}; compile cache "
          f"{cache}; reference {out['reference_s']:.1f}s (not in setup_s)",
          file=sys.stderr)

    metrics = {}
    dev = dict(device, memory_peak_bytes=out["memory_peak_bytes"])
    line = {"correct": ok, "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": dev}
    if not traced:
        for m in cell.metrics("end_to_end"):
            v = out["end_to_end"].get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        tr = tracer.load()
        ctx = {"cell": cell, "facts": out["facts"], "rec": rec, "trace": tr,
               "peaks": peaks, "host_window": tracer.host_window,
               "memory_peak_bytes": out["memory_peak_bytes"]}
        for m in cell.metrics("per_layer"):
            v = cell.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if tr is not None:
            dev["busy_s"], dev["window_s"] = tr.busy_s(), tr.window_s
            line["breakdown"] = {"device_ops": tr.device_ops(10),
                                 "idle_gaps": tr.idle_gaps(10)}
    line["compared"] = {n: {"value": v, "limit": lim}
                        for n, v, lim in rows}
    return line, rows, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t_start = process_start()

    from .spec import Cell, peaks as peak_table
    cell = Cell(args.workload)
    from . import system
    device = system.device_info()
    if device["platform"] != "tpu":
        print(f"benchmark.run: no TPU: jax.devices()[0] is "
              f"{device['platform']}:{device['kind']}. A benchmark number "
              f"comes only from the chip; nothing is measured on a CPU.",
              file=sys.stderr)
        return 2
    if device["count"] < cell.chips:
        print(f"benchmark.run: {cell.name} needs {cell.chips} chip(s), jax "
              f"sees {device['count']}", file=sys.stderr)
        return 2
    pk = peak_table(device["kind"])

    line, rows, out = run_cell(cell, args.seed, args.seconds,
                               bool(args.trace), device, pk, t_start)
    for name, value in out["end_to_end"].items():
        print(f"[bench] end to end, as read: {name} = {value}",
              file=sys.stderr)
    for note in out.get("notes", []):
        print(f"[bench] {note}", file=sys.stderr)
    held = {name for name, _, _ in rows}
    for name, value in out["numbers"].items():
        if name not in held:
            print(f"[bench] read, not compared: {name} = {value}",
                  file=sys.stderr)
    for name, value, limit in rows:
        print(f"[bench] compared {name} = {value} (limit {limit})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
