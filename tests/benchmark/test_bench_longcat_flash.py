"""The `longcat-flash-omni` configuration and its cell in the benchmark:
the file's published widths, cuts and floors, the family's counts pinned to
the digit, the seeded weights (one array a leaf), a tiny cell of it run
through `run_cell` on the CPU (the program correct, the fp8 control and a
planted fault not), and its three per-layer readers on traces with and
without their ops. The readers' three `per_layer` entries and the list
joins this cell is owed wait in `longcat_flash/per_layer_entries.json`,
which says why."""
import json
import os
import shutil
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

CONFIG = os.path.join(ROOT, "benchmark", "configs", "longcat-flash-omni.json")
CELL = "longcat-flash-omni.reason_closed"
SAME_MIX = "falcon-h1-34b.reason_closed"
TINY_CELL = "longcat-flash-tiny.reason_tiny3"
NEW = ("mla_decode_roofline_share.out_tps", "mla_decode_step_share.out_tps",
       "moe_zero_assignment_share.out_tps")
SHARED = ("serve_out_tokens_per_s", "engine_host_ms_per_step.out_tps",
          "slot_occupancy.out_tps", "prefix_hit_token_share.out_tps",
          "preemptions.out_tps", "chunk_step_device_ms.out_tps",
          "decode_step_device_ms.out_tps",
          "decode_hbm_roofline_share.out_tps", "serve_step_mfu.out_tps",
          "ttft_p50_ms.out_tps", "device_idle_share.out_tps",
          "peak_hbm_gb.out_tps")
OWED = ("moe_grouped_roofline_share.out_tps",
        "moe_local_assignment_share.out_tps",
        "moe_experts_touched_share.out_tps",
        "idle_in_admit_ms_per_step.out_tps",
        "idle_in_prefill_host_ms_per_step.out_tps",
        "idle_in_decode_prepare_ms_per_step.out_tps",
        "idle_in_decode_finish_ms_per_step.out_tps",
        "idle_in_calls_ms_per_step.out_tps",
        "idle_in_collect_ms_per_step.out_tps",
        "prepared_step_share.out_tps", "paged_kernel_step_share.out_tps")
ENTRIES = os.path.join(HERE, "longcat_flash", "per_layer_entries.json")
WIDTHS = ("hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size",
          "num_attention_heads", "kv_lora_rank", "q_lora_rank",
          "qk_rope_head_dim", "qk_nope_head_dim", "v_head_dim", "moe_topk")


@pytest.fixture(scope="module")
def cfg():
    with open(CONFIG) as f:
        return json.load(f)


def waiting():
    with open(ENTRIES) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def fam():
    from benchmark.families import longcat_flash
    return longcat_flash


# --------------------------------------------------- the configuration

def test_the_file_carries_the_published_config_and_names_its_cuts(cfg):
    pub = cfg["published"]
    assert pub["attention_method"] == "MLA" and len(pub) == 23
    differs = sorted(k for k in pub if cfg.get(k) != pub[k])
    assert differs == sorted(cfg["reduced"]) == [
        "n_routed_experts", "num_layers", "vocab_size"]
    assert set(cfg["reduced_how"]) == set(cfg["reduced"])
    assert (cfg["num_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (4, 16, 131072 // 8)
    assert (pub["num_layers"], pub["n_routed_experts"],
            pub["vocab_size"]) == (28, 512, 131072)
    # no width among the cuts, and every width as published
    assert not set(cfg["reduced"]) & set(WIDTHS)
    assert (pub["hidden_size"], pub["ffn_hidden_size"],
            pub["expert_ffn_hidden_size"]) == (6144, 12288, 2048)
    assert (pub["num_attention_heads"], pub["kv_lora_rank"],
            pub["q_lora_rank"], pub["qk_nope_head_dim"],
            pub["qk_rope_head_dim"], pub["v_head_dim"]) == (
                64, 512, 1536, 128, 64, 128)
    assert (pub["zero_expert_num"], pub["zero_expert_type"],
            pub["moe_topk"], pub["routed_scaling_factor"]) == (
                256, "identity", 12, 6)
    # what the program is built from: the published numbers under the
    # program's names (layers and HELD experts named as the repo's other
    # held-expert model names them), the router at its published width
    m = cfg["model"]
    renamed = {"num_hidden_layers": "num_layers",
               "num_experts": "n_routed_experts"}
    for k, v in m.items():
        if k in ("router_experts", "expert_offset"):
            continue
        assert v == cfg[renamed.get(k, k)], k
    assert set(WIDTHS) <= set(m)
    assert (m["router_experts"], m["zero_expert_num"], m["expert_offset"],
            m["num_experts"]) == (512, 256, 0, 16)
    assert m["router_experts"] + m["zero_expert_num"] == 768
    assert cfg["family"] == "longcat_flash" and cfg["dtype"] == "bfloat16"
    assert "7 stages, 224 chips" in cfg["deployment"]
    assert "expert-parallel, 16 experts a chip" in cfg["deployment"]
    assert {"mla_scale", "router", "choice_bias", "rope", "head", "cache",
            "weights", "max_len", "omni", "decode_precision"} <= \
        set(cfg["assumed"])
    assert cfg["engine"] == {"max_slots": 128, "max_len": 4096,
                             "block_size": 16, "kv_blocks": 16384,
                             "prefill_chunk": 512}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    assert cfg["source"].startswith(entry["source"])
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    # the floors of a cut: >= 4 layers and whole periods (the period is
    # one layer), >= 8 experts held, >= 1/8 of the vocabulary
    assert m["num_hidden_layers"] >= 4 and m["num_experts"] >= 8
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
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["config"] == "longcat-flash-omni" and entry["chips"] == 1
    assert entry["traffic"] == "reason_closed" and len(entry["why"]) <= 200
    from benchmark.spec import Cell
    cell = Cell(CELL)
    assert cell.mix is not None and cell.mix == Cell(SAME_MIX).mix
    assert cell.mix["clients"] == cell.config["engine"]["max_slots"] == 128
    assert cell.mix["fresh"]["max"] + cell.mix["output"]["max"] \
        <= cell.config["engine"]["max_len"]
    assert set(cell.limits) == {
        "served_logit_gap", "served_logit_gap_mean", "requests_not_done",
        "answers_of_wrong_length", "nan_logits", "recompiles_in_window"}


def test_the_waiting_entries_are_well_formed_each_with_its_reader():
    """The three entries of this family's own readers and the joins this
    cell is owed, wherever they are kept: in BENCHMARK.json once a PR can
    append them, until then in `longcat_flash/per_layer_entries.json`."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    metrics = {m["name"]: m for m in bench["per_layer"]}
    doc = waiting()
    assert tuple(m["name"] for m in doc["per_layer"]) == NEW
    for m, better, source, layer in zip(
            doc["per_layer"], ("higher", "lower", "lower"),
            ("device_trace", "device_trace", "program_counter"),
            ("kernels", "decode executables", "expert layer")):
        m = metrics.get(m["name"], m)
        assert CELL in m["workloads"]
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (
            "%", better, source, layer)
        assert m["moves"] == "serve_out_tokens_per_s"
        assert m["layer"] in {x["layer"] for x in bench["per_layer"]}
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "readers", m["name"].split(".")[0] + ".py"))
    joins = doc["joins"]
    assert joins["cell"] == CELL
    assert tuple(joins["append_to_workloads_of"]) == OWED
    for name in OWED:
        # an accepted metric that moves what this cell reports, and whose
        # reader is there; joined already, or still waiting
        assert metrics[name]["moves"] == "serve_out_tokens_per_s"
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "readers", name.split(".")[0] + ".py"))
    assert set(joins["notes"]) <= set(OWED)


# -------------------------------------------------------------- counts

def test_parameter_counts_of_the_share_held(cfg, fam):
    m = cfg["model"]
    mla = 6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256 \
        + 64 * 128 * 6144
    assert (6144 * 1536, 1536 * 64 * 192, 6144 * 576, 512 * 64 * 256,
            64 * 128 * 6144) == (9_437_184, 18_874_368, 3_538_944,
                                 8_388_608, 50_331_648)
    assert fam.mla_params(m) == mla and mla + 1536 + 512 == 90_572_800
    ffn = 3 * 6144 * 12288
    router = 6144 * 768
    assert (ffn, router) == (226_492_416, 4_718_592)
    dense = 2 * (mla + 2048) + 2 * ffn + router + 768 + 4 * 6144
    assert dense == 638_874_368                         # 1.278 GB
    expert = 3 * 6144 * 2048
    assert expert == fam.expert_params(m) == 37_748_736
    assert fam.expert_bytes(m) == 75_497_472
    table = 16384 * 6144
    assert fam.n_params(m) == 4 * (dense + 16 * expert) + 2 * table + 6144 \
        == 5_172_749_312
    assert fam.n_params(m) * 2 == 10_345_498_624            # 10.35 GB
    assert fam.weight_bytes(m) == (fam.n_params(m) - table) * 2
    # what a token multiplies: the matrices (norms and biases left out)
    # and 12 x 16 / 768 of an expert a layer; a zero expert counts 0
    assert fam.matmul_params(m) == pytest.approx(
        4 * (2 * mla + 2 * ffn + router) + table + 4 * expert * 0.25)
    shapes = fam.shapes(m)
    assert len(shapes) == 4 * 29 + 3
    assert shapes["l3.a2_kvb"] == (512, 64 * 256)
    assert shapes["l0.router"] == (6144, 768)
    assert shapes["l0.router_b"] == (768,)
    assert shapes["l2.exp_down"] == (16, 2048, 6144)


def test_flops_and_cache_bytes(cfg, fam):
    m = cfg["model"]
    assert fam.kv_bytes_per_token(m) == 8 * 576 * 2 == 9_216
    assert fam.mla_bytes(m, 1000) == 1000 * 1_152
    assert fam.mla_flops(m, 1000) == 2.0 * 1000 * 64 * (576 + 512)
    # 121 FLOP a byte: under the chip's 240, so memory-bound in one pass
    assert fam.mla_flops(m, 1) / fam.mla_bytes(m, 1) == pytest.approx(
        120.9, abs=0.1)
    assert fam.moe_flops(m, 32) == 2.0 * 37_748_736 * 32
    # 128 live slots touch 13.9 of the 16 held experts a layer
    assert fam.experts_touched(m, 128) == pytest.approx(
        16 * (1 - (1 - 12 / 768) ** 128))
    assert 13.8 < fam.experts_touched(m, 128) < 13.9
    # one token over 1000 cached positions
    assert fam.forward_flops(m, 1, 1000) == pytest.approx(
        2.0 * fam.matmul_params(m) + 2.0 * 8 * 64 * 320 * 1000)
    # a step of 128 live slots holding 150,000 tokens of context
    got = fam.decode_step_bytes(m, 150_000, 128)
    dense = fam.weight_bytes(m) - 4 * 16 * 75_497_472
    assert got == pytest.approx(
        dense + 4 * fam.experts_touched(m, 128) * 75_497_472
        + 9_216 * 150_128)
    assert round(got / 1e9, 1) == 10.9
    # the latent rows are 13 % of the step's bytes, the experts 38 %
    assert 9_216 * 150_128 / got == pytest.approx(0.13, abs=0.01)
    assert 4 * fam.experts_touched(m, 128) * 75_497_472 / got == \
        pytest.approx(0.38, abs=0.01)
    # the engine's pool at the configuration's geometry: counted and held
    e = cfg["engine"]
    counted = e["kv_blocks"] * e["block_size"] * 9_216
    held = e["kv_blocks"] * e["block_size"] * 8 * 640 * 2
    assert round(counted / 1e9, 2) == 2.42 and round(held / 1e9, 2) == 2.68
    assert 12.9e9 < fam.n_params(m) * 2 + held < 13.1e9


def test_make_is_deterministic_and_one_array_a_leaf(fam):
    from benchmark.spec import Cell
    import jax.numpy as jnp
    with open(os.path.join(
            HERE, "longcat_flash", "benchmark", "configs",
            "longcat-flash-tiny.json")) as f:
        m = json.load(f)["model"]
    a, b = fam.make(m, 2**31 + 9, "float32"), fam.make(m, 2**31 + 9,
                                                       "float32")
    c = fam.make(m, 2**31 + 10, "float32")
    assert set(a) == set(fam.shapes(m))
    for k in a:
        assert a[k].shape == fam.shapes(m)[k]
        assert bool(jnp.array_equal(a[k], b[k]))
    assert not bool(jnp.array_equal(a["l0.router"], c["l0.router"]))
    # the recipe: norms about 1, the choice bias small and not zero, the
    # router's logits spread about 1.5
    assert abs(float(a["l1.n3"].mean()) - 1.0) < 0.05
    bias = np.asarray(a["l0.router_b"])
    assert 2e-4 < float(bias.std()) < 5e-3
    assert float(np.asarray(a["l0.router"]).std()) * 8.0 == pytest.approx(
        fam.ROUTER_SPREAD, rel=0.15)
    # no leaf is a slice of a stack
    lm = fam.leaf_map(m)
    assert all(layer is None for _, layer in lm.values())
    assert sorted(k for k, _ in lm.values()) == sorted(fam.shapes(m))
    assert Cell(CELL).family is fam and fam.FUSED == {}


# ------------------------------------------- the cell, found and run

def tree(tmp_path):
    """A copy of the tiny tree + this family's tiny configuration, mix and
    limits + its entries, the three new metrics among them."""
    from _tiny import TINY
    root = tmp_path / "tree"
    shutil.copytree(TINY, root)
    shutil.copytree(os.path.join(HERE, "longcat_flash"), root,
                    dirs_exist_ok=True)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "longcat-flash-tiny", "source": "test",
        "file": "benchmark/configs/longcat-flash-tiny.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": TINY_CELL, "config": "longcat-flash-tiny",
        "traffic": "reason_tiny3", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "gpt-tiny.docqa_tiny" in m.get("workloads", []):
            m["workloads"].append(TINY_CELL)
    bench["per_layer"] += [dict(m, workloads=[TINY_CELL])
                           for m in waiting()["per_layer"]]
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
    from benchmark.families import longcat_flash
    assert cell.family is longcat_flash
    assert cell.reference.__file__ == os.path.join(
        ROOT, "benchmark", "reference", "longcat_flash.py")
    for name in NEW:
        assert callable(cell.reader(name))
    model = cell.config["model"]
    lm = cell.family.leaf_map(model)
    assert set(lm) == {
        n for n, _ in cell.family.build(cell.config).named_parameters()}
    assert cell.reference.LAYER_KEYS == ()
    # a share that is not the first: experts 6-11 of 24
    assert (model["expert_offset"], model["num_experts"]) == (6, 6)


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    from _tiny import run_tiny
    root = tree(tmp_path_factory.mktemp("longcat"))
    return root, run_tiny(TINY_CELL, 2**31 + 7, 2.0, traced=True, root=root)


def test_the_tiny_cell_is_correct_and_reads_its_own_counters(tiny_run):
    """Whatever the host's speed let the window serve, it is correct and
    read by this family's own counts."""
    _, (line, rows, out) = tiny_run
    assert line["correct"] is True, rows
    assert failed(rows) == set() and line["failed"] == 0
    assert out["numbers"]["tokens_compared"] >= 1
    c0, c1 = out["facts"]["counters"]
    assert c1["preemptions"] == 0
    got = line["metrics"]
    # no device trace on the CPU: the two device readers read nothing
    assert "mla_decode_roofline_share.out_tps" not in got
    assert "mla_decode_step_share.out_tps" not in got
    assert got["prefix_hit_token_share.out_tps"]["value"] == 0.0
    assert got["serve_step_mfu.out_tps"]["value"] > 0.0
    # where the traced second held a decode step: 12 of the router's 36
    # outputs are zero experts
    if "moe_zero_assignment_share.out_tps" in got:
        assert 15.0 < got["moe_zero_assignment_share.out_tps"]["value"] \
            < 55.0


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
    run alone. Inside each decode run 8 `mla_decode` ops of 2 ms; the
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
                for i in range(8):
                    a = start + i * 3 * ms
                    ops_.append(("mla_decode_bf16_128_64_512_", a,
                                 a + 2 * ms))
            ops_.append(("fusion_bf16_1_", 20 * ms, 60 * ms))
        self.ops = [ops_]

    def module_runs(self, pattern="."):
        return list(self.modules[0])

    def span_runs(self, name):
        ms = 10**6
        return [(0, 250 * ms), (290 * ms, 450 * ms)]


def reader_ctx(monkeypatch, trace, calls, finishes=()):
    from benchmark.readers import _program
    from benchmark.spec import Cell
    spans = {"engine/decode_call": calls,
             "engine/decode_finish": list(finishes)}
    monkeypatch.setattr(_program, "program_spans",
                        lambda ctx, prefix, window=None: spans[prefix])
    return {"cell": Cell(CELL), "trace": trace, "host_window": [0.0, 1.0],
            "facts": {}, "peaks": {"flops_bf16": 197e12,
                                   "hbm_bytes_per_s": 819e9}}


def test_the_new_readers_count_what_the_spans_counted(monkeypatch, fam, cfg):
    m = cfg["model"]
    calls = [Span("engine/decode_call", 0.1, kv_bytes=9_216 * 150_000,
                  path="mla_decode"),
             Span("engine/decode_call", 0.3, kv_bytes=9_216 * 140_000,
                  path="mla_decode")]
    fins = [Span("engine/decode_finish", 0.2, moe_assignments=6144,
                 moe_local=130, moe_touched=55, moe_zero=2000),
            Span("engine/decode_finish", 0.4, moe_assignments=6000,
                 moe_local=120, moe_touched=54, moe_zero=2048)]
    ctx = reader_ctx(monkeypatch, FakeTrace(), calls, fins)
    cell = ctx["cell"]
    # 16 ms of mla_decode a decode run; 145,000 tokens' rows read once by
    # each of 8 sublayers; memory-bound in one pass (121 FLOP/B under 240)
    assert cell.reader(NEW[0])(ctx) == pytest.approx(
        100.0 * 8 * fam.mla_bytes(m, 145_000) / 819e9 / 0.016)
    assert 8 * fam.mla_flops(m, 145_000) / 197e12 \
        < 8 * fam.mla_bytes(m, 145_000) / 819e9
    assert cell.reader(NEW[0])(ctx) < 100.0
    assert cell.reader(NEW[1])(ctx) == pytest.approx(16.0)
    assert cell.reader(NEW[2])(ctx) == pytest.approx(
        100.0 * 4048 / 12144)


def test_each_new_reader_reads_nothing_where_there_is_nothing(monkeypatch):
    """A trace without the kernel's ops (another model's, or the CPU's) and
    spans without the attributes (the parent's program): None, no raise."""
    bare = [Span("engine/decode_call", 0.1, path="paged_kernel",
                 kv_blocks=9)]
    old = [Span("engine/decode_finish", 0.2, moe_assignments=100,
                moe_local=30, moe_touched=20)]
    ctx = reader_ctx(monkeypatch, FakeTrace(ops=False), bare, old)
    for name in NEW:
        assert ctx["cell"].reader(name)(ctx) is None
    # spans that carry the counts, a trace that has no such op
    full = [Span("engine/decode_call", 0.1, kv_bytes=9_216 * 100)]
    ctx = reader_ctx(monkeypatch, FakeTrace(ops=False), full)
    assert ctx["cell"].reader(NEW[0])(ctx) is None
    assert ctx["cell"].reader(NEW[1])(ctx) is None
    # the ops, spans without `kv_bytes` (a program from before PR 33)
    ctx = reader_ctx(monkeypatch, FakeTrace(), bare)
    assert ctx["cell"].reader(NEW[0])(ctx) is None
    assert ctx["cell"].reader(NEW[1])(ctx) == pytest.approx(16.0)
    # no trace at all
    ctx = reader_ctx(monkeypatch, None, full, old)
    for name in NEW:
        assert ctx["cell"].reader(name)(ctx) is None
    # another family's cell (no `mla_flops`): nothing, no raise
    from benchmark.spec import Cell
    ctx = reader_ctx(monkeypatch, FakeTrace(), full)
    ctx["cell"] = Cell(SAME_MIX)
    assert ctx["cell"].reader(NEW[0])(ctx) is None
