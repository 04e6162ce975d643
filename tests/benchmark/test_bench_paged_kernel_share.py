"""`paged_kernel_step_share` (readers/paged_kernel_step_share.py): from
hand-made `engine/decode_call` spans it reads 100, 0, the share between,
and nothing where the spans carry no `path` (a program from before the
kernel); then both serving cells end to end on the CPU at `gpt_tiny`, on
the gather path and with the Pallas kernel interpreted: `correct`, every
per-layer metric the cell printed before, and the new one."""
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
# window of its own (the ring is the process's; test_bench_program_spans.py
# uses 5.0e7 + up to 700)
EPOCH = 6.0e7
CHAT, DOCQA = "gpt-tiny.chat_tiny", "gpt-tiny.docqa_tiny"
STOCK = {"gpt3-1.3b.chat_poisson": CHAT, "gpt3-1.3b.docqa_closed": DOCQA}


def stock_entries():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [m for m in json.load(f)["per_layer"]
                if m["name"].startswith("paged_kernel_step_share.")]


def read(metric, ctx):
    from benchmark.spec import Cell
    return Cell("gpt3-1.3b.chat_poisson").reader(metric)(ctx)


@pytest.mark.parametrize("k,paths,want", [
    (0, ["paged_kernel"] * 5, 100.0),
    (1, ["gather"] * 5, 0.0),
    (2, ["paged_kernel", "gather", "paged_kernel", "gather"], 50.0),
    (3, [None] * 5, None),                 # the parent: no attribute
    (4, [], None),                         # no decode step in the window
    (5, [None, "paged_kernel", "paged_kernel"], 100.0),
])
def test_share_of_decode_calls_on_the_kernel(k, paths, want):
    from paddle_tpu.monitor import trace
    base = EPOCH + 100.0 * k
    ctx = {"trace": None, "host_window": [base, base + 1.0], "facts": {}}
    for i, path in enumerate(paths):
        attrs = {} if path is None else {"path": path, "kv_blocks": 7}
        trace.record("engine/decode_call", base + 0.1 * i,
                     base + 0.1 * i + 0.05, **attrs)
    # outside the traced part of the window: not counted
    trace.record("engine/decode_call", base + 1.5, base + 1.6, path="gather")
    for suffix in (".tpot", ".out_tps"):
        got = read("paged_kernel_step_share" + suffix, ctx)
        assert got == want if want is None else got == pytest.approx(want)


def test_the_two_entries_are_what_the_issue_names():
    mine = stock_entries()
    assert [(m["name"], m["moves"], m["workloads"]) for m in mine] == [
        ("paged_kernel_step_share.tpot", "tpot_mean_ms",
         ["gpt3-1.3b.chat_poisson"]),
        ("paged_kernel_step_share.out_tps", "serve_out_tokens_per_s",
         ["gpt3-1.3b.docqa_closed"])]
    for m in mine:
        assert (m["unit"], m["better"], m["source"], m["layer"]) == \
            ("%", "higher", "program_counter", "kernels")


# the per-layer metrics a traced CPU run of each tiny cell printed before
# this one (no TPU plane on the CPU: the device readers return nothing)
BEFORE = {
    CHAT: {"gen_lateness_p99_ms.ttft", "ttft_mean_ms.ttft",
           "slot_wait_share.ttft", "tpot_p90_ms.tpot", "slot_occupancy.tpot",
           "prefix_hit_token_share.ttft", "preemptions.tpot",
           "serve_step_mfu.tpot"},
    DOCQA: {"slot_occupancy.out_tps", "prefix_hit_token_share.out_tps",
            "preemptions.out_tps", "serve_step_mfu.out_tps",
            "ttft_p50_ms.out_tps"},
}


@pytest.mark.parametrize("path", ["gather", "paged_kernel"])
@pytest.mark.parametrize("name,seed", [(CHAT, 6), (DOCQA, 8)])
def test_serving_cells_on_the_cpu_print_what_they_printed(tmp_path, name,
                                                          seed, path):
    from _tiny import CPU, PEAKS, TINY
    from benchmark.run import run_cell
    from benchmark.spec import Cell
    from paddle_tpu.kernels.pallas import paged_decode
    root = tmp_path / "tree"
    shutil.copytree(TINY, root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"] += [dict(m, workloads=[STOCK[w]
                                              for w in m["workloads"]])
                           for m in stock_entries()]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = Cell(name, root=str(root), here=str(root / "benchmark"))
    with paged_decode.force_interpret(path == "paged_kernel"):
        line, rows, out = run_cell(cell, seed, 1.5, True, CPU, PEAKS,
                                   time.time())
    assert line["correct"] is True, rows
    got = line["metrics"]
    mine = "paged_kernel_step_share" + (".tpot" if name == CHAT
                                        else ".out_tps")
    assert set(got) - {mine} == BEFORE[name]
    assert got[mine]["value"] == (100.0 if path == "paged_kernel" else 0.0)
    assert got[mine]["unit"] == "%"
