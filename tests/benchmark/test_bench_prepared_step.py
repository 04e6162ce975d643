"""`prepared_step_share` and `idle_in_collect_ms_per_step` (ISSUE 30): from
hand-made `engine/step` spans with and without a call inside, the share
reads 100, 0, the share between, and nothing where the spans carry no
`plan` (a program from before the prepared step); the idle time inside
hand-made `engine/collect` spans against a hand-made busy plane; the four
new `per_layer` entries name cells that report what they move; then both
serving cells end to end on the CPU at `gpt_tiny`."""
import json
import os
import shutil
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
ROOT = os.path.dirname(os.path.dirname(HERE))

# hand-made spans at instants no real perf_counter reaches, each case in a
# window of its own (the ring is the process's; the other files of this
# directory use 5.0e7 and 6.0e7)
EPOCH = 7.0e7
CHAT, DOCQA = "gpt-tiny.chat_tiny", "gpt-tiny.docqa_tiny"
STOCK = {"gpt3-1.3b.chat_poisson": CHAT, "gpt3-1.3b.docqa_closed": DOCQA}
NEW = ("prepared_step_share", "idle_in_collect_ms_per_step")


def stock_bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def stock_entries():
    return [m for m in stock_bench()["per_layer"]
            if m["name"].split(".")[0] in NEW]


def read(metric, ctx):
    from benchmark.spec import Cell
    return Cell("gpt3-1.3b.chat_poisson").reader(metric)(ctx)


def put(name, t0, t1, **attrs):
    from paddle_tpu.monitor import trace
    trace.record(name, t0, t1, **attrs)


@pytest.mark.parametrize("k,plans,want", [
    (0, ["prepared"] * 5, 100.0),
    (1, ["sync", "rebuilt", "sync"], 0.0),
    (2, ["sync", "prepared", "prepared", "rebuilt"], 50.0),
    (3, [None] * 4, None),                 # the parent: no attribute
    (4, [], None),                         # no engine step in the window
    (5, [None, "prepared", "sync", "prepared"], 200.0 / 3),
])
def test_share_of_the_steps_that_ran_a_call_and_were_prepared(k, plans, want):
    base = EPOCH + 100.0 * k
    ctx = {"trace": None, "host_window": [base, base + 1.0], "facts": {}}
    for i, plan in enumerate(plans):
        s = base + 0.1 * i
        attrs = {} if plan is None else {"plan": plan}
        if plan == "rebuilt":
            attrs["cause"] = "stop"
        put("engine/step", s, s + 0.09, **attrs)
        # a chunk and a decode, or the decode alone
        if i % 2:
            put("engine/prefill_call", s + 0.01, s + 0.02)
        put("engine/decode_call", s + 0.03, s + 0.04, path="gather")
    # a step that ran nothing (an idle poll) is not one of them, whatever
    # it says; nor is one outside the traced part of the window
    put("engine/step", base + 0.95, base + 0.96, plan="sync")
    put("engine/step", base + 1.5, base + 1.6, plan="sync")
    put("engine/decode_call", base + 1.52, base + 1.55)
    for suffix in (".tpot", ".out_tps"):
        got = read("prepared_step_share" + suffix, ctx)
        assert got == want if want is None else got == pytest.approx(want)


def test_idle_inside_collect_per_engine_step():
    from test_bench_program_spans import FakeTrace, serving_ctx
    base = EPOCH + 1000.0
    ms = 1e-3
    # two engine steps of 50 ms; the device is busy 2-40 and 52-96
    ctx = serving_ctx(base, [(2, 40), (52, 96)])
    for suffix in (".tpot", ".out_tps"):
        assert read("idle_in_collect_ms_per_step" + suffix, ctx) is None
    for k in (0, 1):
        s = base + k * 50 * ms
        put("engine/step", s, s + 50 * ms, plan="prepared")
        put("engine/decode_call", s + 1 * ms, s + 2 * ms)
        put("engine/decode_prepare", s + 2 * ms, s + 20 * ms)
        put("engine/collect", s + 20 * ms, s + 47 * ms)
        put("engine/decode_finish", s + 47 * ms, s + 50 * ms)
    # collect 20-47: idle 40-47 in step 0; collect 70-97: idle 96-97 in 1
    for suffix in (".tpot", ".out_tps"):
        assert read("idle_in_collect_ms_per_step" + suffix, ctx) \
            == pytest.approx((7 + 1) / 2, abs=1e-4)
    # the set-up under the running step costs the device nothing
    assert read("idle_in_decode_prepare_ms_per_step.tpot", ctx) \
        == pytest.approx(0.0, abs=1e-4)
    for no_plane in (None, FakeTrace(5_000_000, [], ops=False)):
        assert read("idle_in_collect_ms_per_step.tpot",
                    dict(ctx, trace=no_plane)) is None


def test_the_new_entries_name_cells_that_report_what_they_move():
    bench = stock_bench()
    mine = stock_entries()
    assert [(m["name"], m["moves"], m["workloads"]) for m in mine] == [
        ("prepared_step_share.tpot", "tpot_mean_ms",
         ["gpt3-1.3b.chat_poisson"]),
        ("prepared_step_share.out_tps", "serve_out_tokens_per_s",
         ["gpt3-1.3b.docqa_closed"]),
        ("idle_in_collect_ms_per_step.tpot", "tpot_mean_ms",
         ["gpt3-1.3b.chat_poisson"]),
        ("idle_in_collect_ms_per_step.out_tps", "serve_out_tokens_per_s",
         ["gpt3-1.3b.docqa_closed"])]
    assert mine == bench["per_layer"][-len(mine):]      # appended, last
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in mine:
        assert m["layer"] == "serving engine"
        assert (m["unit"], m["better"], m["source"]) == (
            ("%", "higher", "program_counter")
            if m["name"].startswith("prepared") else
            ("ms", "lower", "device_trace"))
        for cell in m["workloads"]:
            assert cell in e2e[m["moves"]]["workloads"]
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "readers", m["name"].split(".")[0] + ".py"))


@pytest.mark.parametrize("name,seed", [(CHAT, 16), (DOCQA, 18)])
def test_serving_cells_on_the_cpu_read_the_share(tmp_path, name, seed):
    from _tiny import CPU, PEAKS, TINY
    from benchmark.run import run_cell
    from benchmark.spec import Cell
    root = tmp_path / "tree"
    shutil.copytree(TINY, root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    before = {m["name"] for m in bench["per_layer"]}
    bench["per_layer"] += [dict(m, workloads=[STOCK[w]
                                              for w in m["workloads"]])
                           for m in stock_entries()]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = Cell(name, root=str(root), here=str(root / "benchmark"))
    line, rows, out = run_cell(cell, seed, 1.5, True, CPU, PEAKS, time.time())
    assert line["correct"] is True, rows
    got = line["metrics"]
    mine = "prepared_step_share" + (".tpot" if name == CHAT else ".out_tps")
    # no device plane on the CPU: the idle reader finds nothing to place
    assert set(got) - before == {mine}
    assert got[mine]["unit"] == "%"
    # an arrival into the idle engine is a step built in turn; under load
    # every step is prepared
    assert 50.0 <= got[mine]["value"] <= 100.0
    if name == DOCQA:
        assert got[mine]["value"] >= 90.0
