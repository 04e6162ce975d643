"""The `qwen3_next` family in the benchmark: its configuration held to the
catalog's published config, its counts against hand-worked values, its cell
found with no stock file edited, a tiny cell of it run through `run_cell`
on the CPU (the program correct, the fp8 control and a planted fault not),
and its four per-layer readers on traces with and without their ops."""
import gzip
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

CONFIG = os.path.join(ROOT, "benchmark", "configs", "qwen3-next-80b-a3b.json")
CELL = "qwen3-next-80b-a3b.reason_closed"
TINY_CELL = "qwen3-next-tiny.reason_tiny"
NEW = ("moe_grouped_roofline_share.out_tps",
       "gdn_decode_roofline_share.out_tps",
       "moe_local_assignment_share.out_tps",
       "moe_experts_touched_share.out_tps")


@pytest.fixture(scope="module")
def cfg():
    with open(CONFIG) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def fam():
    from benchmark.families import qwen3_next
    return qwen3_next


# --------------------------------------------------- the configuration

def test_the_file_carries_the_published_config_and_names_every_cut(cfg):
    pub = cfg["published"]
    assert pub["model_type"] == "qwen3_next" and len(pub) == 29
    differs = sorted(k for k in pub if cfg.get(k) != pub[k])
    assert differs == sorted(cfg["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert set(cfg["reduced_how"]) == set(cfg["reduced"])
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (8, 128, 151936 // 4)
    # no width among the cuts
    for k in cfg["reduced"]:
        assert not k.endswith(("_dim", "_rank", "_size")) or k == "vocab_size"
    # what the program is built from is the file's own top level, plus the
    # router's published width and where this chip's experts start
    m = cfg["model"]
    assert all(m[k] == cfg[k] for k in m if k in pub)
    assert m["router_experts"] == pub["num_experts"] == 512
    assert m["expert_offset"] == 0 and m["num_experts_per_tok"] == 10
    assert cfg["family"] == "qwen3_next" and cfg["dtype"] == "bfloat16"
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"]
    assert cfg["source"].startswith(entry["source"])
    # the floors of a cut: a whole period and >= 4 layers, >= 8 experts,
    # >= 1/8 of the vocabulary
    assert m["num_hidden_layers"] % m["full_attention_interval"] == 0
    assert m["num_experts"] >= 8 and 8 * m["vocab_size"] >= pub["vocab_size"]


def test_the_cell_joins_the_lists_the_issue_names():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = sorted(m["name"] for m in bench["end_to_end"] + bench["per_layer"]
                  if CELL in m.get("workloads", []))
    assert mine == sorted((
        "serve_out_tokens_per_s", "engine_host_ms_per_step.out_tps",
        "slot_occupancy.out_tps", "prefix_hit_token_share.out_tps",
        "preemptions.out_tps", "chunk_step_device_ms.out_tps",
        "decode_step_device_ms.out_tps", "decode_hbm_roofline_share.out_tps",
        "serve_step_mfu.out_tps", "ttft_p50_ms.out_tps",
        "device_idle_share.out_tps", "peak_hbm_gb.out_tps") + NEW)
    for name in NEW:
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and m["unit"] == "%"
        assert m["moves"] == "serve_out_tokens_per_s"
    from benchmark.spec import Cell
    cell = Cell(CELL)
    assert cell.mix["clients"] == cell.config["engine"]["max_slots"] == 128
    assert cell.mix["shared"] is None
    assert cell.mix["fresh"]["max"] + cell.mix["output"]["max"] \
        <= cell.config["engine"]["max_len"]
    assert set(cell.limits) == {
        "served_logit_gap", "served_logit_gap_mean", "requests_not_done",
        "answers_of_wrong_length", "nan_logits", "recompiles_in_window"}


# -------------------------------------------------------------- counts

def test_parameter_counts_of_the_share_held(cfg, fam):
    m = cfg["model"]
    expert = 3 * 2048 * 512
    assert fam.expert_params(m) == expert == 3_145_728
    lin = 2048 * (2 * 2048 + 2 * 4096 + 64) + 4096 * 2048 \
        + 8192 * 4 + 32 + 32 + 128
    full = 2048 * (2 * 4096 + 2 * 512) + 4096 * 2048 + 2 * 256
    every = 2 * 2048 + 2048 * 512 + 3 * 2048 * 512 + 2048
    held = 8 * 128 * expert
    assert held * 2 == 6_442_450_944                       # 6.44 GB
    outside = 6 * lin + 2 * full + 8 * every
    assert outside == 290_441_344                          # 290 M
    table = 37984 * 2048
    assert fam.n_params(m) == held + outside + 2 * table + 2048
    assert fam.n_params(m) * 2 == 7_334_502_656            # 7.33 GB
    assert fam.weight_bytes(m) == (fam.n_params(m) - table) * 2
    assert fam.expert_bytes(m) == expert * 2


def test_what_a_token_multiplies_here(cfg, fam):
    m = cfg["model"]
    lin = 2048 * (2 * 2048 + 2 * 4096 + 64) + 4096 * 2048
    full = 2048 * (2 * 4096 + 2 * 512) + 4096 * 2048
    every = 2048 * 512 + 3 * 2048 * 512 + 2048
    dense = 6 * lin + 2 * full + 8 * every + 2048 * 37984
    # 10 x 128 / 512 = 2.5 of its experts a layer are held here
    assert fam.matmul_params(m) == dense + 8 * 2.5 * 3_145_728
    # one token over 1000 cached positions: + 4 x 16 x 256 a pair on the 2
    # full layers, + the delta rule's 3 x 2 x 128 x 128 x 32 on the 6 linear
    assert fam.forward_flops(m, 1, 1000) == pytest.approx(
        2.0 * fam.matmul_params(m) + 4.0 * 2 * 16 * 256 * 1000
        + 6 * 3 * 2 * 128 * 128 * 32)
    assert fam.gdn_flops(m, 128) == 6 * 3 * 2 * 128 * 128 * 32 * 128
    assert fam.moe_flops(m, 320) == 2.0 * 3_145_728 * 320


def test_cache_and_decode_step_bytes(cfg, fam):
    m = cfg["model"]
    assert fam.kv_bytes_per_token(m) == 2 * 2 * 2 * 256 * 2 == 4096
    state = 32 * 128 * 128 * 4
    assert fam.gdn_state_bytes(m) == 6 * state
    assert fam.state_bytes_per_slot(m) == 6 * (state + 3 * 8192 * 2) \
        == 12_877_824                                      # 12.9 MB
    touched = 128 * (1 - (1 - 10 / 512) ** 128)
    assert fam.experts_touched(m, 128) == pytest.approx(touched)
    assert touched / 128 == pytest.approx(0.92, abs=0.005)
    dense = fam.weight_bytes(m) - 8 * 128 * fam.expert_bytes(m)
    got = fam.decode_step_bytes(m, 115_000, 128)
    assert got == pytest.approx(
        dense + 8 * touched * fam.expert_bytes(m) + 2 * 128 * 12_877_824
        + 4096 * (115_000 + 128))
    # experts about 57 %, state about 32 % of a step's bytes
    assert 8 * touched * fam.expert_bytes(m) / got == pytest.approx(
        0.57, abs=0.02)
    assert 2 * 128 * 12_877_824 / got == pytest.approx(0.32, abs=0.02)
    # a step with one live slot touches about ten experts a layer
    assert fam.experts_touched(m, 1) == pytest.approx(2.5)


# ------------------------------------------- the cell, found and run

def tree(tmp_path):
    """A copy of the tiny tree + this family's tiny configuration, mix and
    limits + its entries, the four new metrics among them."""
    from _tiny import TINY
    root = tmp_path / "tree"
    shutil.copytree(TINY, root)
    shutil.copytree(os.path.join(HERE, "qwen3_next"), root,
                    dirs_exist_ok=True)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "qwen3-next-tiny", "source": "test",
        "file": "benchmark/configs/qwen3-next-tiny.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": TINY_CELL, "config": "qwen3-next-tiny",
        "traffic": "reason_tiny", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "gpt-tiny.docqa_tiny" in m.get("workloads", []):
            m["workloads"].append(TINY_CELL)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        stock = json.load(f)
    for m in stock["per_layer"]:
        if m["name"] in NEW:
            bench["per_layer"].append(dict(m, workloads=[TINY_CELL]))
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
    # family, reference and the new readers are the STOCK files now
    from benchmark.families import qwen3_next
    assert cell.family is qwen3_next
    assert cell.reference.__file__ == os.path.join(
        ROOT, "benchmark", "reference", "qwen3_next.py")
    for name in NEW:
        assert callable(cell.reader(name))
    model = cell.config["model"]
    assert set(cell.family.leaf_map(model)) == {
        n for n, _ in cell.family.build(cell.config).named_parameters()}


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    from _tiny import run_tiny
    root = tree(tmp_path_factory.mktemp("qwen"))
    return root, run_tiny(TINY_CELL, 2**31 + 7, 2.0, traced=True, root=root)


def test_the_tiny_cell_is_correct_and_reads_its_own_counters(tiny_run):
    """Whatever the host's speed let the window serve (under the suite's
    load a step can take a second), it is correct and read by this
    family's own counters."""
    _, (line, rows, out) = tiny_run
    assert line["correct"] is True, rows
    assert failed(rows) == set() and line["failed"] == 0
    assert out["numbers"]["tokens_compared"] >= 1
    c0, c1 = out["facts"]["counters"]
    assert c1["prefix_hit_tokens"] == c1["shared_tokens"] == 0
    assert c1["preemptions"] == 0
    got = line["metrics"]
    # no device trace on the CPU: the two roofline readers read nothing
    assert "moe_grouped_roofline_share.out_tps" not in got
    assert "gdn_decode_roofline_share.out_tps" not in got
    assert got["prefix_hit_token_share.out_tps"]["value"] == 0.0
    assert got["serve_step_mfu.out_tps"]["value"] > 0.0
    # where the traced second held a decode step: a quarter of the
    # router's experts are held, so about a quarter of the assignments are
    # local, and 4 slots x 4 choices touch some of the 8 held experts
    if "moe_local_assignment_share.out_tps" in got:
        assert 0.0 <= got["moe_local_assignment_share.out_tps"]["value"] \
            <= 60.0
        assert 0.0 < got["moe_experts_touched_share.out_tps"]["value"] \
            <= 100.0


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
    """Two engine steps: a chunk run then a decode run, a decode run alone.
    Inside each decode run 8 `moe_grouped` ops of 1 ms and 6 `gdn_decode`
    ops of 0.5 ms; the chunk run has `moe_grouped` ops too (not counted)."""
    t0, t1 = 0, 10**9

    def __init__(self, ops=True):
        self.modules = [[("jit_fn(1)", 10, 90), ("jit_fn(2)", 100, 200),
                         ("jit_fn(2)", 300, 400)]]
        ops_ = []
        if ops:
            ms = 10**6
            base = {100: 110 * ms, 300: 310 * ms}
            self.modules = [[("jit_fn(1)", 10 * ms, 90 * ms),
                             ("jit_fn(2)", 100 * ms, 200 * ms),
                             ("jit_fn(2)", 300 * ms, 400 * ms)]]
            for start in base.values():
                for i in range(8):
                    a = start + i * 2 * ms
                    ops_.append(("moe_grouped_bf16_3328_2048_", a, a + ms))
                for i in range(6):
                    a = start + 40 * ms + i * ms
                    ops_.append(("gdn_decode_f32_128_32_128_", a,
                                 a + ms // 2))
            ops_ += [("moe_grouped_bf16_7168_2048_", 20 * ms, 60 * ms),
                     ("fusion_bf16_1_", 95 * ms, 96 * ms)]
        self.ops = [ops_]

    def module_runs(self, pattern="."):
        return list(self.modules[0])

    def span_runs(self, name):
        ms = 10**6
        return [(0, 250 * ms), (290 * ms, 450 * ms)] if self.ops[0] \
            else [(0, 250), (290, 450)]


def reader_ctx(monkeypatch, trace, finish, calls):
    from benchmark.readers import _program
    from benchmark.spec import Cell
    spans = {"engine/decode_finish": finish, "engine/decode_call": calls}
    monkeypatch.setattr(_program, "program_spans",
                        lambda ctx, prefix, window=None: spans[prefix])
    return {"cell": Cell(CELL), "trace": trace, "host_window": [0.0, 1.0],
            "facts": {}, "peaks": {"flops_bf16": 197e12,
                                   "hbm_bytes_per_s": 819e9}}


def test_the_roofline_readers_count_what_the_spans_counted(monkeypatch, fam,
                                                           cfg):
    m = cfg["model"]
    finish = [Span("engine/decode_finish", 0.1, moe_assignments=10240,
                   moe_local=2560, moe_touched=940, tokens=128),
              Span("engine/decode_finish", 0.3, moe_assignments=10240,
                   moe_local=2400, moe_touched=900, tokens=128)]
    calls = [Span("engine/decode_call", 0.1, state_slots=128,
                  state_bytes=128 * 12_877_824, path="paged_kernel"),
             Span("engine/decode_call", 0.3, state_slots=120,
                  state_bytes=120 * 12_877_824, path="paged_kernel")]
    ctx = reader_ctx(monkeypatch, FakeTrace(), finish, calls)
    cell = ctx["cell"]
    # 8 ms of moe_grouped a decode run; 2480 assignments on 920 experts
    nbytes = 920 * fam.expert_bytes(m) + 2 * 2480 * 2048 * 2
    assert cell.reader(NEW[0])(ctx) == pytest.approx(
        100.0 * nbytes / 819e9 / 0.008)
    # 3 ms of gdn_decode a run; 124 live slots' matrices read and written
    assert cell.reader(NEW[1])(ctx) == pytest.approx(
        100.0 * 2 * 124 * fam.gdn_state_bytes(m) / 819e9 / 0.003)
    assert cell.reader(NEW[2])(ctx) == pytest.approx(
        100.0 * 4960 / 20480)
    assert cell.reader(NEW[3])(ctx) == pytest.approx(
        100.0 * 920 / (128 * 8))


def test_each_new_reader_reads_nothing_where_there_is_nothing(monkeypatch):
    """A trace without the kernels' ops (another model's, or the CPU's) and
    spans without the attributes (the parent's program): None, no raise."""
    from jax.profiler import ProfileData
    from benchmark import trace as T
    bare = [Span("engine/decode_finish", 0.1, tokens=4, finished=0)]
    calls = [Span("engine/decode_call", 0.1, path="paged_kernel")]
    ctx = reader_ctx(monkeypatch, FakeTrace(ops=False), bare, calls)
    for name in NEW:
        assert ctx["cell"].reader(name)(ctx) is None
    # spans that carry the counts, a trace that has no such op
    full = [Span("engine/decode_finish", 0.1, moe_assignments=40,
                 moe_local=10, moe_touched=9)]
    calls = [Span("engine/decode_call", 0.1, state_slots=4)]
    with gzip.open(os.path.join(HERE, "data", "chat_tiny.xplane.pb.gz")) as f:
        recorded = T.Trace(ProfileData.from_serialized_xspace(
            f.read()).planes)
    ctx = reader_ctx(monkeypatch, recorded, full, calls)
    assert ctx["cell"].reader(NEW[0])(ctx) is None
    assert ctx["cell"].reader(NEW[1])(ctx) is None
    assert ctx["cell"].reader(NEW[2])(ctx) == pytest.approx(25.0)
    # no trace at all
    ctx = reader_ctx(monkeypatch, None, full, calls)
    assert ctx["cell"].reader(NEW[0])(ctx) is None
    assert ctx["cell"].reader(NEW[1])(ctx) is None
    # and on the recorded GPT trace the helper does find ops inside the
    # decode runs it tells apart
    from benchmark.readers import _decode_ops
    ctx = reader_ctx(monkeypatch, recorded, full, calls)
    ctx["cell"] = __import__("_tiny").tiny_cell("gpt-tiny.chat_tiny")
    secs, runs = _decode_ops.op_seconds_per_run(ctx, "fusion")
    assert runs == 29 and 0 < secs
