"""Driven by data: a configuration, a mix, a cell and a per-layer metric
added as FILES and list entries are found with no edit to the harness;
and `BENCHMARK.json` keeps to the contract's limits."""
import json
import os
import re
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_a_new_cell_mix_config_and_metric_are_found_as_files(tmp_path):
    from _tiny import CPU, PEAKS, TINY
    from benchmark.run import run_cell
    from benchmark.spec import Cell
    root = tmp_path / "tree"
    shutil.copytree(TINY, root)
    bdir = root / "benchmark"
    # a configuration: one more file of sizes
    cfg = json.loads((bdir / "configs" / "gpt-tiny.json").read_text())
    cfg["model"]["num_layers"] = 1
    (bdir / "configs" / "gpt-one.json").write_text(json.dumps(cfg))
    # a traffic mix: one more file of parameters for the same generator
    mix = json.loads((bdir / "traffic" / "chat_tiny.json").read_text())
    mix.update(rate_per_s=10, check_requests=2,
               output={"dist": "fixed", "value": 5})
    (bdir / "traffic" / "burst_tiny.json").write_text(json.dumps(mix))
    # a per-layer metric: one more reader, found by its name
    (bdir / "readers").mkdir(exist_ok=True)
    (bdir / "readers" / "answers_seen.py").write_text(
        "def read(ctx):\n    return float(ctx['facts']['finished'])\n")
    shutil.copy(bdir / "limits" / "gpt-tiny.chat_tiny.json",
                bdir / "limits" / "gpt-one.burst_tiny.json")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "gpt-one", "source": "test",
                             "file": "benchmark/configs/gpt-one.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "gpt-one.burst_tiny",
                               "config": "gpt-one", "traffic": "burst_tiny",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "answers_seen.ttft", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "generator",
        "moves": "ttft_p90_ms", "workloads": ["gpt-one.burst_tiny"]})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "gpt-tiny.chat_tiny" in m.get("workloads", []):
            m["workloads"].append("gpt-one.burst_tiny")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = Cell("gpt-one.burst_tiny", root=str(root), here=str(bdir))
    assert cell.config["model"]["num_layers"] == 1
    assert cell.mix["rate_per_s"] == 10
    import time
    line, rows, out = run_cell(cell, 5, 1.0, True, CPU, PEAKS, time.time())
    assert line["correct"] is True, rows
    assert line["attempted"] == 10
    got = line["metrics"]
    assert got["answers_seen.ttft"]["value"] == out["facts"]["finished"] > 0
    assert got["slot_wait_share.ttft"]["unit"] == "%"      # a stock reader
    assert list(line)[-1] == "compared"
    line0, _, _ = run_cell(cell, 5, 1.0, False, CPU, PEAKS, time.time())
    assert set(line0["metrics"]) == {"ttft_p90_ms", "tpot_mean_ms", "setup_s"}


def test_unknown_names_are_refused():
    from benchmark.spec import Cell, peaks
    with pytest.raises(SystemExit, match="unknown workload"):
        Cell("no-such.cell")
    with pytest.raises(SystemExit, match="peaks.json"):
        peaks("TPU v9 imaginary")
    with pytest.raises(SystemExit, match="peaks.json"):
        peaks("source")
    assert peaks("TPU v5 lite") == {"flops_bf16": 197e12,
                                    "hbm_bytes_per_s": 819e9,
                                    "hbm_bytes": 16e9}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_keeps_to_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    assert 1 <= bench["run_seconds"] <= 51
    n_cells = len(bench["workloads"])
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= \
        max(1, n_cells // 4)
    cfgs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    assert len(cells) == n_cells
    assert len({(w["config"], w["traffic"]) for w in cells.values()}) == n_cells
    for c in cfgs.values():
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["why"]) <= 200
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert any(w["config"] == c["name"] for w in cells.values())
    for w in cells.values():
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "traffic", w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "limits", w["name"] + ".json"))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in e2e.values():
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
        assert set(m.get("workloads", [])) <= set(cells)


def test_every_per_layer_metric_has_a_reader_a_layer_and_one_target(bench):
    from benchmark.spec import Cell
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    suffix = {"train_tokens_per_s_per_chip": "train", "ttft_p90_ms": "ttft",
              "tpot_mean_ms": "tpot", "serve_out_tokens_per_s": "out_tps"}
    any_cell = Cell(bench["workloads"][0]["name"])
    for m in bench["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert callable(any_cell.reader(m["name"]))
        assert m["moves"] in e2e and m["name"].endswith(
            "." + suffix[m["moves"]])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        reporters = e2e[m["moves"]].get("workloads")
        assert set(m["workloads"]) <= set(reporters)
        if m["name"].endswith("_roofline") or "roofline" in m["name"] \
                or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for w in bench["workloads"]:
        mine = [m["name"] for m in bench["per_layer"]
                if w["name"] in m["workloads"]]
        assert any("mfu" in n for n in mine), w["name"]
        assert any("roofline" in n for n in mine), w["name"]
        assert [m for m in bench["end_to_end"] if m["name"] != "setup_s"
                and w["name"] in m.get("workloads", [w["name"]])]
