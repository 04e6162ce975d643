"""The four readers ISSUE 38 adds, on hand-made spans: device-idle time
inside the two halves of `engine/collect` (`engine/wait`, `engine/fetch`),
the share of a run that was a stall (`host/stall` records), and whether
the span ring still holds the whole window. Their ten entries in the stock
`BENCHMARK.json`; the two halves summing to `idle_in_collect` on the trace
recorded on a v5e, with the spans laid beside its runs; and all four on a
real run of the tiny cells on the CPU."""
import json
import os
import shutil
import sys
from types import SimpleNamespace as NS

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from test_bench_program_spans import (                   # noqa: E402
    EPOCH as OTHERS, put, read, serving_ctx, stock_bench)

# instants no real perf_counter reaches, well away from the other files'
EPOCH = OTHERS + 3.0e7
MS = 1e-3
NEW = ["idle_in_wait_ms_per_step.tpot", "idle_in_wait_ms_per_step.out_tps",
       "idle_in_fetch_ms_per_step.tpot", "idle_in_fetch_ms_per_step.out_tps",
       "stalled_share.train", "stalled_share.tpot", "stalled_share.out_tps",
       "program_spans_held_share.train", "program_spans_held_share.tpot",
       "program_spans_held_share.out_tps"]
CLOSED = ["gpt3-1.3b.docqa_closed", "qwen3-next-80b-a3b.reason_closed",
          "falcon-h1-34b.reason_closed", "longcat-flash-omni.reason_closed"]


def test_the_ten_entries_are_the_last_and_listed_where_they_move():
    from _tiny import cells_report_what_it_moves
    stock = stock_bench()
    assert len(stock["per_layer"]) == 78
    mine = stock["per_layer"][-10:]
    assert [m["name"] for m in mine] == NEW
    target = {"tpot": ("tpot_mean_ms", ["gpt3-1.3b.chat_poisson"]),
              "out_tps": ("serve_out_tokens_per_s", CLOSED),
              "train": ("train_tokens_per_s_per_chip",
                        ["gpt3-350m.pretrain_2k"])}
    for m in mine:
        base, suffix = m["name"].split(".")
        assert (m["moves"], m["workloads"]) == target[suffix]
        assert cells_report_what_it_moves(stock, m), m["name"]
        idle = base.startswith("idle_in_")
        assert m["source"] == ("device_trace" if idle else "program_counter")
        assert m["layer"] == ("serving engine" if idle else "span layer")
        assert m["unit"] == ("ms" if idle else "%")
        assert m["better"] == ("higher" if base.endswith("held_share")
                               else "lower")
        assert os.path.exists(os.path.join(
            HERE, "..", "..", "benchmark", "readers", base + ".py"))


@pytest.mark.parametrize("suffix", [".tpot", ".out_tps"])
def test_idle_in_wait_and_in_fetch_are_the_two_halves_of_collect(suffix):
    base = EPOCH + (100.0 if suffix == ".tpot" else 150.0)
    # two engine steps of 100 ms; the device is busy 20-85 and 130-192
    ctx = serving_ctx(base, [(20, 85), (130, 192)])
    assert read("idle_in_wait_ms_per_step" + suffix, ctx) is None
    for k in (0, 1):
        s = base + k * 100 * MS
        put("engine/step", s, s + 100 * MS)
        put("engine/decode_call", s + 10 * MS, s + 12 * MS)
        put("engine/wait", s + 60 * MS, s + 90 * MS, calls=(1,))
        put("engine/fetch", s + 90 * MS, s + 96 * MS, arrays=2, bytes=520)
        put("engine/collect", s + 60 * MS, s + 96 * MS)
    # wait 60-90: idle 85-90 in step 0, nothing (busy to 192) in step 1;
    # fetch 90-96: idle throughout in step 0, 192-196 in step 1
    want = {"wait": (5 + 0) / 2, "fetch": (6 + 4) / 2}
    got = {h: read(f"idle_in_{h}_ms_per_step" + suffix, ctx) for h in want}
    assert got == pytest.approx(want, abs=1e-4)
    assert got["wait"] + got["fetch"] == pytest.approx(
        read("idle_in_collect_ms_per_step" + suffix, ctx), abs=1e-4)
    # no device plane, no window: nothing
    for lacking in (dict(ctx, trace=None), dict(ctx, host_window=None)):
        assert read("idle_in_wait_ms_per_step" + suffix, lacking) is None
        assert read("idle_in_fetch_ms_per_step" + suffix, lacking) is None


def test_stalled_share_sums_the_excess_over_the_window_it_is_given():
    base = EPOCH + 300.0
    serving = {"trace": None, "host_window": [base + 40.0, base + 50.0],
               "facts": {"t_open": base, "t_close": base + 50.0}}
    training = {"trace": None, "host_window": [base + 40.0, base + 50.0],
                "facts": {"steps": 150, "tokens": 1}}
    for name, ctx in (("stalled_share.tpot", serving),
                      ("stalled_share.out_tps", serving),
                      ("stalled_share.train", training)):
        assert read(name, ctx) == 0.0               # a clean run
    put("host/stall", base + 10.0, base + 12.7, excess_s=2.5,
        site="engine/wait")                         # before the traced part
    put("host/stall", base + 44.0, base + 44.5, excess_s=0.25,
        site="tensor/sync")
    put("host/stall", base + 49.8, base + 50.4, excess_s=0.5,
        site="engine/fetch")                        # ends after the close
    assert read("stalled_share.tpot", serving) == pytest.approx(
        100.0 * 2.75 / 50.0)
    assert read("stalled_share.train", training) == pytest.approx(
        100.0 * 0.25 / 10.0)
    assert read("stalled_share.train",
                dict(training, host_window=[base + 40.0, None])) is None
    assert read("stalled_share.train",
                dict(training, host_window=None)) is None


def test_stalled_share_reads_nothing_from_a_program_that_seals_no_stall(
        monkeypatch):
    from paddle_tpu.monitor import trace
    base = EPOCH + 400.0
    ctx = {"trace": None, "host_window": [base, base + 1.0],
           "facts": {"t_open": base, "t_close": base + 1.0}}
    assert read("stalled_share.out_tps", ctx) == 0.0
    monkeypatch.delattr(trace, "stall")
    assert read("stalled_share.out_tps", ctx) is None


def test_program_spans_held_share_says_how_much_of_the_window_is_held(
        monkeypatch):
    from paddle_tpu.monitor import trace
    base = EPOCH + 500.0
    serving = {"trace": None, "host_window": [base + 40.0, base + 50.0],
               "facts": {"t_open": base, "t_close": base + 50.0}}
    training = {"trace": None, "host_window": [base + 40.0, base + 50.0],
                "facts": {"steps": 150}}

    def ring(lost, oldest):
        monkeypatch.setattr(trace, "evicted", lambda: lost)
        monkeypatch.setattr(trace, "oldest", lambda: oldest)
    ring(0, base + 45.0)        # nothing lost: whole, wherever it begins
    assert read("program_spans_held_share.tpot", serving) == 100.0
    ring(7, base - 3.0)         # lost, but from before the window
    assert read("program_spans_held_share.out_tps", serving) == 100.0
    ring(7, base + 10.0)        # the first ten seconds are gone
    assert read("program_spans_held_share.tpot", serving) \
        == pytest.approx(80.0)
    assert read("program_spans_held_share.train", training) == 100.0
    ring(7, base + 42.5)
    assert read("program_spans_held_share.train", training) \
        == pytest.approx(75.0)
    ring(7, base + 60.0)        # all of it gone
    assert read("program_spans_held_share.out_tps", serving) == 0.0
    assert read("program_spans_held_share.train",
                dict(training, host_window=None)) is None
    monkeypatch.delattr(trace, "oldest")            # an older program
    assert read("program_spans_held_share.tpot", serving) is None


@pytest.mark.parametrize("gap", [0.0, 15e-6])
def test_the_halves_sum_to_idle_in_collect_on_the_recorded_chat_trace(
        monkeypatch, gap):
    """On the trace recorded on a v5e: every collect laid as a wait that
    ends 20 us (less `gap`) after the step's last run and a fetch that
    takes the last 30 us; one `engine/step` a step of the benchmark's.
    What lies between the two (`gap`: the span layer's own time, the
    device idle) is in the collect's reading and in neither half's."""
    from _tiny import fake_program_spans, recorded_chat, tiny_cell
    from benchmark.readers import _program
    tr, spans, _ = recorded_chat()
    spans["engine/wait"] = [
        NS(name="engine/wait", t0=c.t0, t1=c.t1 - 30e-6 - gap, attrs={})
        for c in spans["engine/collect"]]
    spans["engine/fetch"] = [
        NS(name="engine/fetch", t0=c.t1 - 30e-6, t1=c.t1, attrs={})
        for c in spans["engine/collect"]]
    spans["engine/step"] = [
        NS(name="engine/step", t0=(a - tr.t0) / 1e9, t1=(b - tr.t0) / 1e9,
           attrs={}) for a, b in tr.span_runs("engine_step")]
    monkeypatch.setattr(_program, "program_spans", fake_program_spans(spans))
    ctx = {"trace": tr, "host_window": [0.0, tr.window_s],
           "cell": tiny_cell("gpt-tiny.chat_tiny"), "facts": {}}
    got = {n: ctx["cell"].reader(f"idle_in_{n}_ms_per_step.tpot")(ctx)
           for n in ("wait", "fetch", "collect")}
    assert len(spans["engine/collect"]) >= 20
    assert got["collect"] > 0.0 and got["fetch"] > 0.0
    a_step = len(spans["engine/collect"]) / len(spans["engine/step"])
    assert got["collect"] - got["wait"] - got["fetch"] == pytest.approx(
        1e3 * gap * a_step, abs=1e-6)
    # the device is idle all through a fetch (its last run has ended) ...
    assert got["fetch"] == pytest.approx(
        1e3 * 30e-6 * len(spans["engine/fetch"])
        / len(spans["engine/step"]), rel=0.02)
    # ... and through the wait's last 20 us (less the gap)
    assert got["wait"] >= 0.9 * 1e3 * (20e-6 - gap) * a_step


def _tree_with_the_new_entries(tmp_path):
    from _tiny import TINY
    root = tmp_path / "tree"
    shutil.copytree(TINY, root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell_of = {"gpt3-1.3b.chat_poisson": "gpt-tiny.chat_tiny",
               "gpt3-1.3b.docqa_closed": "gpt-tiny.docqa_tiny",
               "gpt3-350m.pretrain_2k": "gpt-tiny.train_tiny"}
    bench["per_layer"] += [
        dict(m, workloads=[cell_of[w] for w in m["workloads"]
                           if w in cell_of])
        for m in stock_bench()["per_layer"] if m["name"] in NEW]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.mark.parametrize("cell, suffix", [
    ("gpt-tiny.chat_tiny", ".tpot"), ("gpt-tiny.docqa_tiny", ".out_tps"),
    ("gpt-tiny.train_tiny", ".train")])
def test_the_new_readers_on_a_real_traced_run(tmp_path, cell, suffix):
    from _tiny import run_tiny
    line, rows, out = run_tiny(cell, seed=5, seconds=1.5, traced=True,
                               root=_tree_with_the_new_entries(tmp_path))
    assert line["correct"] is True, rows
    got = line["metrics"]
    # the ring holds far more than a tiny window makes
    assert got["program_spans_held_share" + suffix]["value"] == 100.0
    # (a loaded test host may well hold a tiny step up: no more is asked
    # of the share than that it is one)
    assert 0.0 <= got["stalled_share" + suffix]["value"] < 100.0
    # no TPU plane in a CPU trace: the idle readers return nothing
    assert not [n for n in got if n.startswith(("idle_in_wait",
                                                "idle_in_fetch"))]
    if suffix != ".train":
        from paddle_tpu.monitor import trace
        w = out["facts"]["t_open"], out["facts"]["t_close"]
        n = {h: len(trace.spans(*w, "engine/" + h))
             for h in ("collect", "wait", "fetch")}
        # (a collect that straddles the close has its wait inside)
        assert n["collect"] > 0 and n["fetch"] <= n["wait"] \
            and n["collect"] <= n["fetch"] <= n["collect"] + 1
