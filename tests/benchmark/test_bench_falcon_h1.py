"""The `falcon_h1` family in the benchmark: its configuration held to the
catalog's published config, its counts against hand-worked values, its cell
found with no stock file edited and on the lists its issue names, a
tiny cell of it run through `run_cell` on the CPU (the program correct, the
fp8 control and a planted fault not), and its three per-layer readers on
traces with and without their ops. The readers' three `per_layer` entries
wait in `falcon_h1/per_layer_entries.json`, which says why."""
import gzip
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

CONFIG = os.path.join(ROOT, "benchmark", "configs", "falcon-h1-34b.json")
CELL = "falcon-h1-34b.reason_closed"
HYBRID = "qwen3-next-80b-a3b.reason_closed"
TINY_CELL = "falcon-h1-tiny.reason_tiny2"
NEW = ("ssd_decode_roofline_share.out_tps", "ssd_decode_step_share.out_tps",
       "state_cache_byte_share.out_tps")
SHARED = ("serve_out_tokens_per_s", "engine_host_ms_per_step.out_tps",
          "slot_occupancy.out_tps", "prefix_hit_token_share.out_tps",
          "preemptions.out_tps", "chunk_step_device_ms.out_tps",
          "decode_step_device_ms.out_tps",
          "decode_hbm_roofline_share.out_tps", "serve_step_mfu.out_tps",
          "ttft_p50_ms.out_tps", "device_idle_share.out_tps",
          "peak_hbm_gb.out_tps")
ENTRIES = os.path.join(HERE, "falcon_h1", "per_layer_entries.json")
WIDTHS = ("hidden_size", "intermediate_size", "head_dim",
          "num_attention_heads", "num_key_value_heads", "mamba_d_ssm",
          "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups",
          "mamba_d_conv", "mamba_expand", "mlp_expansion_factor")
MULTIPLIERS = ("embedding_multiplier", "lm_head_multiplier",
               "attention_in_multiplier", "attention_out_multiplier",
               "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier",
               "ssm_multipliers", "mlp_multipliers")


@pytest.fixture(scope="module")
def cfg():
    with open(CONFIG) as f:
        return json.load(f)


def new_entries():
    with open(ENTRIES) as f:
        return json.load(f)["per_layer"]


@pytest.fixture(scope="module")
def fam():
    from benchmark.families import falcon_h1
    return falcon_h1


# --------------------------------------------------- the configuration

def test_the_file_carries_the_published_config_and_names_both_cuts(cfg):
    pub = cfg["published"]
    assert pub["model_type"] == "falcon_h1" and len(pub) == 42
    differs = sorted(k for k in pub if cfg.get(k) != pub[k])
    assert differs == sorted(cfg["reduced"]) == [
        "num_hidden_layers", "vocab_size"]
    assert set(cfg["reduced_how"]) == set(cfg["reduced"])
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (6, 261120 // 4)
    assert (pub["num_hidden_layers"], pub["vocab_size"]) == (72, 261120)
    # no width among the cuts, and every width and multiplier as published
    assert not set(cfg["reduced"]) & set(WIDTHS + MULTIPLIERS)
    assert (pub["hidden_size"], pub["intermediate_size"]) == (5120, 21504)
    assert (pub["num_attention_heads"], pub["num_key_value_heads"],
            pub["head_dim"]) == (20, 4, 128)
    assert (pub["mamba_n_heads"], pub["mamba_d_head"], pub["mamba_d_state"],
            pub["mamba_n_groups"], pub["mamba_d_conv"]) == (32, 128, 256,
                                                            2, 4)
    # what the program is built from is the file's own top level
    m = cfg["model"]
    assert all(m[k] == cfg[k] == (pub[k] if k not in cfg["reduced"]
                                  else cfg[k]) for k in m)
    assert set(WIDTHS) - {"mamba_expand", "mlp_expansion_factor"} <= set(m)
    assert set(MULTIPLIERS) <= set(m)
    assert cfg["family"] == "falcon_h1" and cfg["dtype"] == "bfloat16"
    assert "12 pipeline stages of 6 layers" in cfg["deployment"]
    assert "sliced by rows four ways" in cfg["deployment"]
    assert {"state", "weights", "max_len", "scan_chunk"} <= \
        set(cfg["assumed"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"]
    assert cfg["source"].startswith(entry["source"])
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    # the floors of a cut: >= 4 layers and whole periods (the period is
    # one layer), >= 1/8 of the vocabulary
    assert m["num_hidden_layers"] >= 4
    assert 8 * m["vocab_size"] >= pub["vocab_size"]


def test_the_cell_is_on_the_lists_the_issue_names():
    """By name only, so that a later PR can append a cell to these lists,
    or this cell to another list, without an edit here."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    metrics = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    for name in SHARED:
        assert CELL in metrics[name]["workloads"], name
        assert metrics[name].get("moves", name) == "serve_out_tokens_per_s"
    # the three entries of this family's own readers, well formed and each
    # with its reader, wherever they are kept: in BENCHMARK.json once a PR
    # can append them, until then in `falcon_h1/per_layer_entries.json`
    entries = new_entries()
    assert tuple(m["name"] for m in entries) == NEW
    for m, unit_better in zip(entries, (("%", "higher"), ("%", "lower"),
                                        ("%", "lower"))):
        m = metrics.get(m["name"], m)
        assert CELL in m["workloads"]
        assert (m["unit"], m["better"]) == unit_better
        assert m["moves"] == "serve_out_tokens_per_s"
        assert m["layer"] in {x["layer"] for x in bench["per_layer"]}
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "readers", m["name"].split(".")[0] + ".py"))
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["config"] == "falcon-h1-34b" and entry["chips"] == 1
    assert entry["traffic"] == "reason_closed" and len(entry["why"]) <= 200
    from benchmark.spec import Cell
    cell = Cell(CELL)
    assert cell.mix is not None and cell.mix == Cell(HYBRID).mix
    assert cell.mix["clients"] == cell.config["engine"]["max_slots"] == 128
    assert cell.mix["fresh"]["max"] + cell.mix["output"]["max"] \
        <= cell.config["engine"]["max_len"]
    assert set(cell.limits) == {
        "served_logit_gap", "served_logit_gap_mean", "requests_not_done",
        "answers_of_wrong_length", "nan_logits", "recompiles_in_window"}


# -------------------------------------------------------------- counts

def test_parameter_counts_of_the_share_held(cfg, fam):
    m = cfg["model"]
    attention = 5120 * (2560 + 2 * 512) + 2560 * 5120
    mixer = 5120 * 9248 + 4096 * 5120 + 5120 * 4 + 5120 + 4096 + 3 * 32
    mlp = 3 * 5120 * 21504
    assert (attention, mixer, mlp) == (31_457_280, 68_351_072, 330_301_440)
    layer = attention + mixer + mlp + 2 * 5120
    assert layer == 430_120_032
    table = 65280 * 5120
    assert fam.n_params(m) == 6 * layer + 2 * table + 5120
    assert fam.n_params(m) * 2 == 6_498_385_024            # 6.50 GB
    assert fam.weight_bytes(m) == (fam.n_params(m) - table) * 2
    # what a token multiplies: the matrices, not the 40,032 other scalars
    assert fam.matmul_params(m) == 6 * (layer - 40_032) + table
    shapes = fam.shapes(m)
    assert len(shapes) == 6 * 17 + 3
    assert shapes["l5.ssm_in"] == (5120, 9248)
    assert shapes["l0.ssm_conv"] == (5120, 4)


def test_flops_and_cache_bytes(cfg, fam):
    m = cfg["model"]
    assert fam.kv_bytes_per_token(m) == 6 * 4 * 128 * 2 * 2 == 12_288
    state = 32 * 128 * 256 * 4
    assert fam.ssd_state_bytes(m) == 6 * state == 25_165_824
    assert fam.state_bytes_per_slot(m) == 6 * (state + 3 * 5120 * 2) \
        == 25_350_144
    assert fam.ssd_flops(m, 128) == 6.0 * 32 * 128 * 256 * 6 * 128
    # one token over 1000 cached positions
    assert fam.forward_flops(m, 1, 1000) == pytest.approx(
        2.0 * fam.matmul_params(m) + 4.0 * 6 * 20 * 128 * 1000
        + 6.0 * 32 * 128 * 256 * 6)
    # a step of 128 live slots holding 147,200 tokens of context
    got = fam.decode_step_bytes(m, 147_200, 128)
    assert got == fam.weight_bytes(m) + 2 * 128 * 25_350_144 \
        + 12_288 * (147_200 + 128)
    # the state update is 46 % of the step's bytes, both caches 59 %
    assert 2 * 128 * fam.ssd_state_bytes(m) / got == pytest.approx(
        0.46, abs=0.01)
    assert (2 * 128 * 25_350_144 + 12_288 * 147_328) / got == pytest.approx(
        0.59, abs=0.01)
    # the engine's pools and rows at the configuration's geometry
    e = cfg["engine"]
    pool = e["kv_blocks"] * e["block_size"] * 12_288
    rows = e["max_slots"] * 25_350_144
    assert round(pool / 1e9, 2) == 3.22 and round(rows / 1e9, 2) == 3.24
    assert 12.9e9 < fam.n_params(m) * 2 + pool + rows < 13.0e9


# ------------------------------------------- the cell, found and run

def tree(tmp_path):
    """A copy of the tiny tree + this family's tiny configuration, mix and
    limits + its entries, the three new metrics among them."""
    from _tiny import TINY
    root = tmp_path / "tree"
    shutil.copytree(TINY, root)
    shutil.copytree(os.path.join(HERE, "falcon_h1"), root,
                    dirs_exist_ok=True)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "falcon-h1-tiny", "source": "test",
        "file": "benchmark/configs/falcon-h1-tiny.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": TINY_CELL, "config": "falcon-h1-tiny",
        "traffic": "reason_tiny2", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "gpt-tiny.docqa_tiny" in m.get("workloads", []):
            m["workloads"].append(TINY_CELL)
    bench["per_layer"] += [dict(m, workloads=[TINY_CELL])
                           for m in new_entries()]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def failed(rows):
    return {n for n, v, lim in rows if v is None or not v <= lim}


def test_the_cell_is_found_with_no_stock_file_edited(tmp_path):
    from _tiny import tiny_cell
    root = tree(tmp_path)
    mine = [os.path.relpath(os.path.join(d, f), root)
            for d, _, fs in os.walk(root / "benchmark") for f in fs]
    assert not [p for p in mine if os.path.exists(os.path.join(ROOT, p))]
    cell = tiny_cell(TINY_CELL, root=root)
    # family, reference and the new readers are the STOCK files
    from benchmark.families import falcon_h1
    assert cell.family is falcon_h1
    assert cell.reference.__file__ == os.path.join(
        ROOT, "benchmark", "reference", "falcon_h1.py")
    for name in NEW:
        assert callable(cell.reader(name))
    model = cell.config["model"]
    lm = cell.family.leaf_map(model)
    assert set(lm) == {
        n for n, _ in cell.family.build(cell.config).named_parameters()}
    # every program leaf is handed an array, none a slice of a stack
    assert all(layer is None for _, layer in lm.values())
    assert sorted(k for k, _ in lm.values()) == \
        sorted(cell.family.shapes(model))
    assert cell.reference.LAYER_KEYS == ()


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    from _tiny import run_tiny
    root = tree(tmp_path_factory.mktemp("falcon"))
    return root, run_tiny(TINY_CELL, 2**31 + 7, 2.0, traced=True, root=root)


def test_the_tiny_cell_is_correct_and_reads_its_own_counters(tiny_run):
    """Whatever the host's speed let the window serve, it is correct and
    read by this family's own counts."""
    _, (line, rows, out) = tiny_run
    assert line["correct"] is True, rows
    assert failed(rows) == set() and line["failed"] == 0
    assert out["numbers"]["tokens_compared"] >= 1
    c0, c1 = out["facts"]["counters"]
    assert c1["prefix_hit_tokens"] == c1["shared_tokens"] == 0
    assert c1["preemptions"] == 0
    got = line["metrics"]
    # no device trace on the CPU: the two device readers read nothing
    assert "ssd_decode_roofline_share.out_tps" not in got
    assert "ssd_decode_step_share.out_tps" not in got
    assert got["prefix_hit_token_share.out_tps"]["value"] == 0.0
    assert got["serve_step_mfu.out_tps"]["value"] > 0.0
    # where the traced second held a decode step: both caches were paid
    # for (6.4 KB of state a slot, 256 B of K/V a token of context)
    if "state_cache_byte_share.out_tps" in got:
        assert 5.0 < got["state_cache_byte_share.out_tps"]["value"] < 95.0


def test_the_control_and_a_planted_fault_are_not_correct(tmp_path):
    """Twelve fixed requests served to their end (no window, so the host's
    speed decides nothing): the program's gap is rounding; the fp8 control
    and the least a planted fault can be (the last served token of the
    longest request altered) fail the cell's limit, each pushed through
    `correct.verdict` as `benchmark.tools.calibrate` does."""
    from _tiny import tiny_cell
    from benchmark import correct, system, traffic
    from benchmark.tools.calibrate import altered_last_token
    cell = tiny_cell(TINY_CELL, root=tree(tmp_path))
    seed = 2**31 + 7
    srv = system.Server(cell, seed)
    reqs = []
    for i in range(12):
        prompt = traffic.rng(seed, 40 + i).integers(
            0, 512, 3 + 5 * i).tolist()
        reqs.append((prompt, srv.submit(prompt, 20)))
    srv.engine.run()
    sample = sorted(({"prompt": p, "tokens": list(r.tokens)}
                     for p, r in reqs),
                    key=lambda r: -len(r["prompt"]))
    srv.close()
    exact = {"requests_not_done": 0, "answers_of_wrong_length": 0,
             "nan_logits": 0, "recompiles_in_window": 0}
    limit = cell.limits["served_logit_gap"]
    for name, rows_, control in (
            ("program", sample, False), ("control", sample, True),
            ("fault", altered_last_token(sample, 512, seed), False)):
        gap, mean, n = correct.served_token_gaps(cell, seed, rows_, 128,
                                                 control=control)
        rows, ok = correct.verdict(dict(exact, served_logit_gap=gap),
                                   cell.limits)
        if name == "program":
            assert n == 240 and ok is True and gap < limit / 10
        else:
            assert ok is False and failed(rows) == {"served_logit_gap"}
            assert gap > 2 * limit, (name, gap)


# ------------------------------------------------ the readers, by hand

class Span:
    def __init__(self, name, t0, **attrs):
        self.name, self.t0, self.t1, self.attrs = name, t0, t0 + 0.001, attrs


class FakeTrace:
    """Two engine steps: a chunk run then a decode run of 100 ms, a decode
    run alone. Inside each decode run 6 `ssd_decode` ops of 2 ms; the
    chunk run holds one more op of another name."""
    t0, t1 = 0, 10**9

    def __init__(self, ops=True):
        ms = 10**6
        self.modules = [[("jit_fn(1)", 10 * ms, 90 * ms),
                         ("jit_fn(2)", 100 * ms, 200 * ms),
                         ("jit_fn(2)", 300 * ms, 400 * ms)]]
        ops_ = []
        if ops:
            for start in (110 * ms, 310 * ms):
                for i in range(6):
                    a = start + i * 3 * ms
                    ops_.append(("ssd_decode_f32_128_32_256_", a,
                                 a + 2 * ms))
            ops_.append(("fusion_bf16_1_", 20 * ms, 60 * ms))
        self.ops = [ops_]

    def module_runs(self, pattern="."):
        return list(self.modules[0])

    def span_runs(self, name):
        ms = 10**6
        return [(0, 250 * ms), (290 * ms, 450 * ms)]


def reader_ctx(monkeypatch, trace, calls):
    from benchmark.readers import _program
    from benchmark.spec import Cell
    spans = {"engine/decode_call": calls}
    monkeypatch.setattr(_program, "program_spans",
                        lambda ctx, prefix, window=None: spans[prefix])
    return {"cell": Cell(CELL), "trace": trace, "host_window": [0.0, 1.0],
            "facts": {}, "peaks": {"flops_bf16": 197e12,
                                   "hbm_bytes_per_s": 819e9}}


def test_the_new_readers_count_what_the_spans_counted(monkeypatch, fam, cfg):
    m = cfg["model"]
    calls = [Span("engine/decode_call", 0.1, state_slots=128,
                  state_bytes=128 * 25_350_144, kv_bytes=12_288 * 150_000,
                  path="paged_kernel"),
             Span("engine/decode_call", 0.3, state_slots=120,
                  state_bytes=120 * 25_350_144, kv_bytes=12_288 * 140_000,
                  path="paged_kernel")]
    ctx = reader_ctx(monkeypatch, FakeTrace(), calls)
    cell = ctx["cell"]
    # 12 ms of ssd_decode a decode run; 124 live slots' matrices read and
    # written; memory-bound by far (6 FLOPs on 8 bytes moved)
    assert cell.reader(NEW[0])(ctx) == pytest.approx(
        100.0 * 2 * 124 * fam.ssd_state_bytes(m) / 819e9 / 0.012)
    assert cell.reader(NEW[0])(ctx) < 100.0
    assert cell.reader(NEW[1])(ctx) == pytest.approx(12.0)
    state = 248 * 25_350_144
    assert cell.reader(NEW[2])(ctx) == pytest.approx(
        100.0 * state / (state + 12_288 * 290_000))


def test_each_new_reader_reads_nothing_where_there_is_nothing(monkeypatch):
    """A trace without the kernel's ops (another model's, or the CPU's) and
    spans without the attributes (the parent's program): None, no raise."""
    from jax.profiler import ProfileData
    from benchmark import trace as T
    bare = [Span("engine/decode_call", 0.1, path="paged_kernel",
                 kv_blocks=9)]
    ctx = reader_ctx(monkeypatch, FakeTrace(ops=False), bare)
    for name in NEW:
        assert ctx["cell"].reader(name)(ctx) is None
    # the other hybrid's spans: state, no kv_bytes (the parent's program)
    old = [Span("engine/decode_call", 0.1, state_slots=4, state_bytes=400)]
    ctx = reader_ctx(monkeypatch, FakeTrace(ops=False), old)
    assert ctx["cell"].reader(NEW[2])(ctx) is None
    # spans that carry the counts, a trace that has no such op
    full = [Span("engine/decode_call", 0.1, state_slots=4, state_bytes=300,
                 kv_bytes=100)]
    with gzip.open(os.path.join(HERE, "data", "chat_tiny.xplane.pb.gz")) as f:
        recorded = T.Trace(ProfileData.from_serialized_xspace(
            f.read()).planes)
    ctx = reader_ctx(monkeypatch, recorded, full)
    assert ctx["cell"].reader(NEW[0])(ctx) is None
    assert ctx["cell"].reader(NEW[1])(ctx) is None
    assert ctx["cell"].reader(NEW[2])(ctx) == pytest.approx(75.0)
    # no trace at all
    ctx = reader_ctx(monkeypatch, None, full)
    assert ctx["cell"].reader(NEW[0])(ctx) is None
    assert ctx["cell"].reader(NEW[1])(ctx) is None
