"""`pool_write_kernel_step_share` (readers/pool_write_kernel_step_share.py):
from hand-made call spans it reads 100, 0, the share between, and nothing
where the spans carry no `kv_write` (a program from before the kernel);
the two entries are what the issue names; then both serving cells end to
end on the CPU at `gpt_tiny`, with XLA's scatter and with the block-copy
kernel interpreted: `correct`, every per-layer metric the cell printed
before, and the new one."""
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
# window of its own (test_bench_paged_kernel_share.py uses 6.0e7 + 600)
EPOCH = 6.1e7
CHAT, DOCQA = "gpt-tiny.chat_tiny", "gpt-tiny.docqa_tiny"
STOCK = {"gpt3-1.3b.chat_poisson": CHAT, "gpt3-1.3b.docqa_closed": DOCQA}
SERVING = ["gpt3-1.3b.docqa_closed", "qwen3-next-80b-a3b.reason_closed",
           "falcon-h1-34b.reason_closed", "longcat-flash-omni.reason_closed"]


def stock_entries():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [m for m in json.load(f)["per_layer"]
                if m["name"].startswith("pool_write_kernel_step_share.")]


def read(metric, ctx):
    from benchmark.spec import Cell
    return Cell("gpt3-1.3b.chat_poisson").reader(metric)(ctx)


@pytest.mark.parametrize("k,ways,want", [
    (0, [("decode", "kernel"), ("prefill", "kernel")] * 3, 100.0),
    (1, [("decode", "scatter"), ("prefill", "scatter")], 0.0),
    (2, [("decode", "kernel"), ("prefill", "scatter"), ("decode", "kernel"),
         ("decode", "scatter")], 50.0),
    (3, [("decode", None), ("prefill", None)], None),   # the parent
    (4, [], None),                               # no call in the window
    (5, [("decode", None), ("decode", "kernel")], 100.0),
])
def test_share_of_calls_that_wrote_with_the_kernel(k, ways, want):
    from paddle_tpu.monitor import trace
    base = EPOCH + 100.0 * k
    ctx = {"trace": None, "host_window": [base, base + 1.0], "facts": {}}
    for i, (kind, way) in enumerate(ways):
        attrs = {} if way is None else {"kv_write": way}
        trace.record(f"engine/{kind}_call", base + 0.1 * i,
                     base + 0.1 * i + 0.05, **attrs)
    # outside the traced part of the window: not counted
    trace.record("engine/decode_call", base + 1.5, base + 1.6,
                 kv_write="scatter")
    # not a call span: not counted
    trace.record("engine/collect", base + 0.01, base + 0.02,
                 kv_write="scatter")
    for suffix in (".tpot", ".out_tps"):
        got = read("pool_write_kernel_step_share" + suffix, ctx)
        assert got == want if want is None else got == pytest.approx(want)


def test_the_two_entries_are_what_the_issue_names():
    from _tiny import cells_report_what_it_moves
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = {m["name"]: m for m in stock_entries()}
    assert {n: (m["moves"], m["workloads"]) for n, m in mine.items()} == {
        "pool_write_kernel_step_share.tpot":
            ("tpot_mean_ms", ["gpt3-1.3b.chat_poisson"]),
        "pool_write_kernel_step_share.out_tps":
            ("serve_out_tokens_per_s", SERVING)}
    for m in mine.values():
        assert (m["unit"], m["better"], m["source"], m["layer"]) == \
            ("%", "higher", "program_counter", "kernels")
        assert cells_report_what_it_moves(bench, m), m["name"]
    assert [m["name"] for m in bench["per_layer"][-2:]] == list(mine)


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


@pytest.mark.parametrize("way", ["scatter", "kernel"])
@pytest.mark.parametrize("name,seed", [(CHAT, 16), (DOCQA, 18)])
def test_serving_cells_on_the_cpu_print_what_they_printed(tmp_path, name,
                                                          seed, way):
    from _tiny import CPU, PEAKS, TINY
    from benchmark.run import run_cell
    from benchmark.spec import Cell
    from paddle_tpu.kernels.pallas import pool_write
    root = tmp_path / "tree"
    shutil.copytree(TINY, root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"] += [dict(m, workloads=[STOCK[w]
                                              for w in m["workloads"]
                                              if w in STOCK])
                           for m in stock_entries()]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = Cell(name, root=str(root), here=str(root / "benchmark"))
    with pool_write.force_interpret(way == "kernel"):
        line, rows, out = run_cell(cell, seed, 1.5, True, CPU, PEAKS,
                                   time.time())
    assert line["correct"] is True, rows
    got = line["metrics"]
    mine = "pool_write_kernel_step_share" + (".tpot" if name == CHAT
                                             else ".out_tps")
    assert set(got) - {mine} == BEFORE[name]
    assert got[mine]["value"] == (100.0 if way == "kernel" else 0.0)
    assert got[mine]["unit"] == "%"
