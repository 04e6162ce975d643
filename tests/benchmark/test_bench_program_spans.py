"""The readers that take their numbers from the program's own spans
(`paddle_tpu.monitor.trace`'s ring, through `readers/_program.py`): each on
a synthetic `ctx` of hand-made spans and busy intervals gives the number
worked out by hand, and None where there are no spans; then all of them on
real runs of the tiny cells on the CPU."""
import json
import os
import shutil
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

# hand-made spans live at instants no real perf_counter reaches (a year and
# a half of uptime), each test in a window of its own: the ring is the
# process's. In nanoseconds a float still tells such instants 8 ns apart.
EPOCH = 5.0e7


ROOT = os.path.dirname(os.path.dirname(HERE))


def stock_bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


class FakeTrace:
    """What the readers use of `benchmark.trace.Trace`: the start of the
    traced window on the trace's clock and device 0's busy intervals."""

    def __init__(self, t0_ns, busy_ns, ops=True):
        self.t0, self._busy = t0_ns, busy_ns
        self.ops = [[("op", a, b) for a, b in busy_ns]] if ops else []

    def busy_intervals(self, dev=0):
        return [list(iv) for iv in self._busy]


def read(metric, ctx):
    """The stock reader of `metric`, found as the harness finds it."""
    from benchmark.spec import Cell
    return Cell("gpt3-1.3b.chat_poisson").reader(metric)(ctx)


def put(name, t0, t1, **attrs):
    from paddle_tpu.monitor import trace
    trace.record(name, t0, t1, **attrs)


def serving_ctx(base, busy_ms):
    """A traced window of 1 s that opens at `base` on the host's clock and
    at 5,000,000 ns on the trace's; busy intervals given in ms from there."""
    t0_ns = 5_000_000
    busy = [(t0_ns + a * 1e6, t0_ns + b * 1e6) for a, b in busy_ms]
    return {"trace": FakeTrace(t0_ns, busy), "host_window": [base, base + 1.0],
            "facts": {"t_open": base - 10.0, "t_close": base + 1.0}}


def test_idle_is_placed_by_engine_phase_and_counted_per_step():
    base = EPOCH + 100.0
    ms = 1e-3
    # two engine steps of 100 ms; the device is busy 20-90 and 130-195
    ctx = serving_ctx(base, [(20, 90), (130, 195)])
    for k in (0, 1):
        s = base + k * 100 * ms
        put("engine/step", s, s + 100 * ms)
        put("engine/sweep", s, s + 1 * ms)
        put("engine/admit", s + 1 * ms, s + 9 * ms, admitted=1, refused=0)
        put("engine/prefill_host", s + 9 * ms, s + 12 * ms)
        put("engine/prefill_call", s + 12 * ms, s + 22 * ms)
        put("engine/prefill_host", s + 22 * ms, s + 24 * ms)
        put("engine/decode_prepare", s + 24 * ms, s + 27 * ms)
        put("engine/decode_prepare", s + 27 * ms, s + 28 * ms)
        put("engine/decode_call", s + 28 * ms, s + 96 * ms)
        put("engine/decode_finish", s + 96 * ms, s + 100 * ms)
    # step 0: idle 0-20 and 90-100; step 1 (at 100): idle 100-130, 195-200
    want = {
        "idle_in_admit_ms_per_step": (8 + 8) / 2,
        "idle_in_prefill_host_ms_per_step": ((3 + 0) + (3 + 2)) / 2,
        "idle_in_decode_prepare_ms_per_step": ((0 + 0) + (3 + 1)) / 2,
        "idle_in_decode_finish_ms_per_step": (4 + 4) / 2,
        # prefill_call 12-22: 8 of it idle in step 0, all 10 in step 1;
        # decode_call 28-96: 90-96 idle in step 0, 128-130 and 195-196 in 1
        "idle_in_calls_ms_per_step": ((8 + 6) + (10 + 2 + 1)) / 2,
    }
    for name, value in want.items():
        for suffix in (".tpot", ".out_tps"):
            assert read(name + suffix, ctx) == pytest.approx(value, abs=1e-4)
    # with the sweep's 1 + 1 ms they are all of the window's idle time
    assert sum(want.values()) * 2 + 2 == pytest.approx(200 - 70 - 65)


def test_idle_readers_return_none_without_spans_or_device_plane():
    base = EPOCH + 200.0
    empty = serving_ctx(base, [(20, 90)])
    assert read("idle_in_admit_ms_per_step.tpot", empty) is None
    put("engine/step", base, base + 0.1)
    put("engine/admit", base, base + 0.01)
    assert read("idle_in_admit_ms_per_step.tpot", empty) \
        == pytest.approx(10.0, abs=1e-4)
    assert read("idle_in_calls_ms_per_step.tpot", empty) is None
    for no_plane in (None, FakeTrace(5_000_000, [], ops=False)):
        ctx = dict(empty, trace=no_plane)
        assert read("idle_in_admit_ms_per_step.tpot", ctx) is None
    assert read("idle_in_admit_ms_per_step.tpot",
                dict(empty, host_window=None)) is None


def test_queue_readers_count_the_requests_submitted_in_the_window():
    base = EPOCH + 300.0
    ctx = {"trace": None, "host_window": [base + 40.0, base + 50.0],
           "facts": {"t_open": base, "t_close": base + 50.0}}
    for name in ("queue_wait_p90_ms.ttft", "kv_block_wait_share.ttft",
                 "prefill_phase_mean_ms.ttft"):
        assert read(name, ctx) is None
    # ten requests a second apart; the i-th queued i ms, prefilled 100+i ms
    # (a span takes its trace id from the trace it belongs to: hand-made
    # ones go in through a bare trace per request)
    from paddle_tpu.monitor import trace
    base2 = EPOCH + 400.0
    ctx = {"trace": None, "host_window": [base2 + 40.0, base2 + 50.0],
           "facts": {"t_open": base2, "t_close": base2 + 50.0}}
    for i in range(1, 11):
        s = base2 + i
        tr = trace.start_trace("request", f"hand-{i}", "request",
                               current=False)
        cause = "blocks" if i in (9, 10) else "slot" if i == 8 else "none"
        tr.record("queue", s, s + i * 1e-3, cause=cause)
        if i == 10:      # preempted once: a second wait, and two prefills
            tr.record("prefill", s + 0.010, s + 0.050, prefix_hit_tokens=0)
            tr.record("queue", s + 0.050, s + 0.950, cause="blocks",
                      requeue=True)
            tr.record("prefill", s + 0.950, s + 1.060, chunks=2)
        else:
            tr.record("prefill", s + i * 1e-3, s + i * 1e-3 + 0.100 + i * 1e-3,
                      chunks=1)
    # one sent before the window opened, one after it closed: not counted
    for s in (base2 - 1.0, base2 + 50.5):
        tr = trace.start_trace("request", "hand-out", "request",
                               current=False)
        tr.record("queue", s, s + 0.400, cause="blocks")
        tr.record("prefill", s + 0.400, s + 0.900, chunks=1)
    assert read("queue_wait_p90_ms.ttft", ctx) \
        == pytest.approx(9.0, abs=1e-3)
    assert read("kv_block_wait_share.ttft", ctx) == pytest.approx(20.0)
    want = (sum(100 + i for i in range(1, 10)) + 110) / 10
    assert read("prefill_phase_mean_ms.ttft", ctx) \
        == pytest.approx(want, abs=1e-3)


def test_train_readers_take_the_traced_part_of_the_window():
    base = EPOCH + 500.0
    ctx = {"trace": None, "host_window": [base, base + 2.0],
           "facts": {"steps": 7}}
    for name in ("loader_wait_ms_per_step.train",
                 "train_host_ms_per_step.train"):
        assert read(name, ctx) is None
    for k in range(4):
        s = base + 0.4 * k
        put("loader/wait", s, s + (0.001 if k else 0.005), qsize=1)
        put("train_step/call", s + 0.01, s + 0.01 + 0.002 * (k + 1))
    put("loader/wait", base + 1.99, base + 2.5)      # straddles the close
    assert read("loader_wait_ms_per_step.train", ctx) \
        == pytest.approx((5 + 1 + 1 + 1) / 4, abs=1e-3)
    assert read("train_host_ms_per_step.train", ctx) \
        == pytest.approx((2 + 4 + 6 + 8) / 4, abs=1e-3)


def test_a_program_without_the_ring_gives_none(monkeypatch):
    from paddle_tpu.monitor import trace
    base = EPOCH + 600.0
    put("engine/step", base, base + 0.1)
    put("engine/admit", base, base + 0.01)
    ctx = serving_ctx(base, [(20, 90)])
    assert read("idle_in_admit_ms_per_step.tpot", ctx) is not None
    monkeypatch.delattr(trace, "spans")              # the parent commit
    assert read("idle_in_admit_ms_per_step.tpot", ctx) is None
    assert read("queue_wait_p90_ms.ttft", ctx) is None
    assert read("train_host_ms_per_step.train", ctx) is None


# ---- on real runs of the tiny cells (CPU: no device plane)


def _tree_with_new_metrics(tmp_path):
    """The tiny fixture tree with this PR's per-layer entries laid over
    its cells (the stock readers are found through the stock directory)."""
    from _tiny import TINY
    root = tmp_path / "tree"
    shutil.copytree(TINY, root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell_of = {"gpt3-1.3b.chat_poisson": "gpt-tiny.chat_tiny",
               "gpt3-1.3b.docqa_closed": "gpt-tiny.docqa_tiny",
               "gpt3-350m.pretrain_2k": "gpt-tiny.train_tiny"}
    bench["per_layer"] += [
        dict(m, workloads=[cell_of[w] for w in m["workloads"]])
        for m in stock_bench()["per_layer"]
        if m["name"].split(".")[0] in NEW]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


NEW = {"idle_in_admit_ms_per_step", "idle_in_prefill_host_ms_per_step",
       "idle_in_decode_prepare_ms_per_step",
       "idle_in_decode_finish_ms_per_step", "idle_in_calls_ms_per_step",
       "queue_wait_p90_ms", "kv_block_wait_share", "prefill_phase_mean_ms",
       "loader_wait_ms_per_step", "train_host_ms_per_step"}


def _run(root, name, seed, seconds):
    from _tiny import CPU, PEAKS
    from benchmark.run import run_cell
    from benchmark.spec import Cell
    cell = Cell(name, root=str(root), here=str(root / "benchmark"))
    return run_cell(cell, seed, seconds, True, CPU, PEAKS, time.time())


def test_every_new_metric_is_listed_for_a_cell_that_reports_its_target():
    stock = stock_bench()
    mine = [m for m in stock["per_layer"]
            if m["name"].split(".")[0] in NEW]
    assert {m["name"].split(".")[0] for m in mine} == NEW
    assert len(mine) == 15
    target = {".tpot": "tpot_mean_ms", ".ttft": "ttft_p90_ms",
              ".out_tps": "serve_out_tokens_per_s",
              ".train": "train_tokens_per_s_per_chip"}
    for m in mine:
        assert m["moves"] == target["." + m["name"].split(".")[1]]
        assert m["better"] == "lower" and len(m["workloads"]) == 1
        assert m["source"] == ("device_trace"
                               if m["name"].startswith("idle_in_")
                               else "program_counter")
        # listed for a cell that reports the end-to-end metric it moves
        reporters = next(e for e in stock["end_to_end"]
                         if e["name"] == m["moves"])["workloads"]
        assert set(m["workloads"]) <= set(reporters)


def test_serving_readers_on_a_real_open_loop_run(tmp_path):
    root = _tree_with_new_metrics(tmp_path)
    line, rows, out = _run(root, "gpt-tiny.chat_tiny", 6, 1.5)
    assert line["correct"] is True, rows
    got = line["metrics"]
    # the engine's own account needs no device: it reads on the CPU too
    assert got["queue_wait_p90_ms.ttft"]["value"] >= 0.0
    assert got["kv_block_wait_share.ttft"]["value"] == 0.0
    prefill = got["prefill_phase_mean_ms.ttft"]["value"]
    assert 0.0 < prefill <= got["ttft_mean_ms.ttft"]["value"] + 1.0
    # no TPU plane in a CPU trace: the idle readers return nothing
    assert not [n for n in got if n.startswith("idle_in_")]
    # but the spans they would place the gaps by are there: one engine/step
    # for every step the driver made (its own records stand in for the
    # traced part of the window, which run_cell keeps to itself)
    from benchmark.readers._common import traced_steps
    from benchmark.readers._program import program_spans, steps_in_trace
    steps = out["facts"]["steps"]
    ctx = {"facts": out["facts"],
           "host_window": [steps[0][0] - 1e-4, steps[-1][1] + 1e-4]}
    assert steps_in_trace(ctx) == len(traced_steps(ctx)) == len(steps)
    names = {s.name for s in program_spans(ctx, "engine/")}
    assert {"engine/admit", "engine/prefill_call", "engine/decode_call",
            "engine/decode_finish"} <= names


def test_train_readers_on_a_real_run(tmp_path):
    root = _tree_with_new_metrics(tmp_path)
    line, rows, out = _run(root, "gpt-tiny.train_tiny", 3, 1.5)
    assert line["correct"] is True, rows
    got = line["metrics"]
    host = got["train_host_ms_per_step.train"]["value"]
    wait = got["loader_wait_ms_per_step.train"]["value"]
    assert host > 0.0 and wait >= 0.0
    # the loader's own account and the benchmark's, of the same wait
    assert wait <= got["feed_wait_ms_per_step.train"]["value"] * 3 + 1.0
