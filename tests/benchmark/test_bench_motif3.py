"""The `motif-3-beta` configuration and its cell in the benchmark: the
file's published keys and its cuts, the family's counts pinned to the
digit (a window layer read at most 128 rows a sequence, the
hyper-connections' own counts), a tiny cell of it run through `run_cell`
on the CPU (the program correct, the fp8 control and a planted fault
not), and the `mhc_roofline_share` reader on traces with and without its
ops. That reader's `per_layer` entry waits in
`motif3/per_layer_entries.json` (its reason is there)."""
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

CONFIG = os.path.join(ROOT, "benchmark", "configs", "motif-3-beta.json")
CELL = "motif-3-beta.reason_closed"
SAME_MIX = "deepseek-v3.reason_closed"
TINY_CELL = "motif3-tiny.reason_tiny4"
REDUCED = ["n_dense_first_layers", "num_experts", "num_hidden_layers",
           "num_nextn_predict_layers", "vocab_size"]
# every .out_tps entry whose reader reads this cell; not the walk of K/V
# pages (`mla_decode` here), zero experts, the state and SSD/GDN entries,
# the experts-touched share (its reader counts the dense layer as a routed
# one) or the pool-write share (a test of its own pins its list)
NOT_JOINED = {"paged_kernel_step_share.out_tps",
              "moe_zero_assignment_share.out_tps",
              "moe_experts_touched_share.out_tps",
              "pool_write_kernel_step_share.out_tps",
              "gdn_decode_roofline_share.out_tps",
              "ssd_decode_roofline_share.out_tps",
              "ssd_decode_step_share.out_tps",
              "state_cache_byte_share.out_tps"}
WAITING = "mhc_roofline_share.out_tps"


@pytest.fixture(scope="module")
def cfg():
    with open(CONFIG) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def fam():
    from benchmark.families import motif3
    return motif3


def waiting():
    with open(os.path.join(HERE, "motif3", "per_layer_entries.json")) as f:
        return json.load(f)["waiting"]


# --------------------------------------------------- the configuration

def test_the_file_differs_from_the_published_config_by_its_cuts(cfg, bench):
    pub = cfg["published"]
    assert pub["model_type"] == "Motif" and len(pub) == 53
    assert sorted(k for k in pub if cfg.get(k) != pub[k]) == REDUCED \
        == sorted(cfg["reduced"]) == sorted(cfg["reduced_how"])
    assert (cfg["num_hidden_layers"], cfg["n_dense_first_layers"],
            cfg["num_experts"], cfg["vocab_size"],
            cfg["num_nextn_predict_layers"]) == (5, 1, 48, 220160 // 8, 0)
    # every width as published
    assert (pub["hidden_size"], pub["intermediate_size"],
            pub["moe_intermediate_size"], pub["num_attention_heads"],
            pub["num_key_value_heads"], pub["num_noise_heads"],
            pub["q_lora_rank"], pub["kv_lora_rank"], pub["head_dim"],
            pub["qk_rope_head_dim"], pub["v_head_dim"],
            pub["mhc_expansion_rate"], pub["sliding_window"]) == (
                4096, 12288, 1280, 80, 16, 16, 1024, 512, 192, 64, 128, 4,
                128)
    m = cfg["model"]
    for k, v in m.items():
        if k == "num_experts":
            assert v == cfg["num_experts"] == 48
        elif k in cfg:
            assert v == cfg[k], k
    assert m["qk_nope_head_dim"] + m["qk_rope_head_dim"] == pub["head_dim"]
    assert (m["router_experts"], m["expert_offset"]) == (384, 0)
    assert m["rope_scaling"] == pub["rope_scaling"] and m["rope_interleave"]
    # the stage is published layers 2-5 behind one dense layer: full iff
    # l mod 4 = 3 under `interleave`, period 4
    assert m["layer_types"] == [
        "full_attention" if layer % 4 == 3 else "sliding_attention"
        for layer in (1, 2, 3, 4, 5)]
    assert {"head_split", "gate", "lambda", "pattern", "rope", "mhc",
            "polynorm", "router", "choice_bias", "shared_expert", "cache",
            "weights", "max_len", "mtp"} <= set(cfg["assumed"])
    assert "8 chips, expert-parallel, 48 routed experts a chip" \
        in cfg["deployment"] and "3,802,617,890 parameters" \
        in cfg["deployment"]
    assert cfg["engine"] == {"max_slots": 128, "max_len": 4096,
                             "block_size": 16, "kv_blocks": 16384,
                             "prefill_chunk": 512}
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert sorted(entry["reduced"]) == REDUCED
    assert cfg["source"].startswith(entry["source"])
    assert m["num_hidden_layers"] - m["n_dense_first_layers"] >= 4
    assert m["num_experts"] >= 8 and 8 * m["vocab_size"] >= pub["vocab_size"]


def test_the_cell_joins_the_lists_and_adds_no_entry(bench):
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "motif-3-beta", "reason_closed", 1) and len(entry["why"]) <= 200
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["serve_out_tokens_per_s"]["workloads"]
    for m in bench["per_layer"]:
        if m["name"].endswith(".out_tps"):
            assert (CELL in m["workloads"]) == (m["name"] not in NOT_JOINED), \
                m["name"]
    assert WAITING not in {m["name"] for m in bench["per_layer"]}
    from benchmark.spec import Cell
    cell = Cell(CELL)
    assert cell.mix == Cell(SAME_MIX).mix
    assert cell.mix["clients"] == cell.config["engine"]["max_slots"] == 128
    assert set(cell.limits) == {
        "served_logit_gap", "served_logit_gap_mean", "requests_not_done",
        "answers_of_wrong_length", "nan_logits", "recompiles_in_window"}


def test_the_waiting_entry_is_well_formed_with_its_reader(bench):
    from _tiny import cells_report_what_it_moves
    (m,) = waiting()
    assert m["name"] == WAITING and m["workloads"] == [CELL]
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
        "%", "higher", "device_trace", "kernels", "serve_out_tokens_per_s")
    assert cells_report_what_it_moves(bench, m)
    assert os.path.exists(os.path.join(ROOT, "benchmark", "readers",
                                       "mhc_roofline_share.py"))


# -------------------------------------------------------------- counts

def test_parameter_counts_of_the_share_held(cfg, fam):
    m = cfg["model"]
    gdla = 4096 * 1024 + 1024 * (80 * 192 + 64 * 128) + 4096 * 576 \
        + 512 * 16 * 256 + 4096 * 64 + 64 * 128 * 4096 + 1024 + 512
    assert 1024 * (80 * 192 + 64 * 128) == 24_117_248
    assert fam.mla_params(m) == gdla == 66_586_112
    hc = 16384 + 24 * 16384 + 3 + 24
    assert fam.mhc_params(m) == hc == 409_627
    expert = 3 * 4096 * 1280 + 4
    assert fam.expert_params(m) == expert == 15_728_644
    outside = gdla + 2 * hc + 2 * 4096 + 4096 * 384 + 384 + expert
    assert outside == 84_715_450
    routed = outside + 48 * expert
    assert routed == 839_690_362
    dense = gdla + 2 * hc + 2 * 4096 + 3 * 4096 * 12288 + 4
    assert dense == 218_408_506
    top = 2 * 27520 * 4096 + 4096
    assert top == 225_447_936
    assert fam.n_params(m) == dense + 4 * routed + top == 3_802_617_890
    assert round(fam.n_params(m) * 2 / 1e9, 2) == 7.61
    shapes = fam.shapes(m)
    assert shapes["l0.f_gate"] == (4096, 12288) and "l0.router" not in shapes
    assert shapes["l4.exp_down"] == (48, 1280, 4096)
    assert shapes["l1.hc_a_phi"] == (24, 16384)
    assert shapes["l2.a_qb"] == (1024, 23552)
    assert shapes["l3.exp_poly"] == (48, 4) and shapes["l0.f_poly"] == (4,)


def test_flops_and_bytes_of_a_decode_step(cfg, fam):
    m = cfg["model"]
    row = 576 * 2
    assert fam.kv_bytes_per_token(m) == 5 * row
    # one sequence of 1,000 positions: the full layer holds 1,000 rows, a
    # window layer 128
    assert fam.kv_bytes_per_token(m, context=1000) == pytest.approx(
        row * (1000 + 4 * 128) / 1000)
    # 142 FLOP a byte at 80 heads: under the v5e's 240, memory-bound
    assert fam.mla_flops(m, 1) / fam.mla_bytes(m, 1) == pytest.approx(
        2 * 80 * 1088 / 1152)
    # 128 live slots touch about 44.8 of the 48 held experts a layer
    assert 44.7 < fam.experts_touched(m, 128) < 44.9
    # a step of 128 slots at a mean context of 1,150: about 6.97 GB of
    # weights and 0.27 GB of latent rows (the window layers' 84 MB of it)
    got = fam.decode_step_bytes(m, 128 * 1150, 128)
    experts = 4 * fam.experts_touched(m, 128) * fam.expert_bytes(m)
    rows = row * (128 * 1150 + 4 * 128 * 128 + 5 * 128)
    assert got == pytest.approx(fam.weight_bytes(m) - 4 * 48
                                * fam.expert_bytes(m) + experts + rows)
    assert round((got - rows) / 1e9, 2) == 6.97
    assert round(rows / 1e9, 2) == 0.25 and round(
        row * 4 * 128 * 128 / 1e6) == 75
    assert experts / got == pytest.approx(0.78, abs=0.01)
    assert round(got / 819e9 * 1e3, 1) == 8.8
    # the hyper-connections: about 82 KB a row a boundary and its share of
    # the parameters, 11.7 FLOP a byte: memory-bound, 0.14 ms a decode step
    assert fam.mhc_bytes(m, 1280) == pytest.approx(
        1280 * ((2 * 4 * 4096 + 2 * 4096) * 2 + 2 * 20 * 4)
        + 10 * 409_627 * 2)
    assert 11 < fam.mhc_flops(m, 1) / (fam.mhc_bytes(m, 1280) / 1280) < 12
    assert round(fam.mhc_bytes(m, 1280) / 819e9 * 1e3, 2) == 0.14
    e = cfg["engine"]
    held = e["kv_blocks"] * e["block_size"] * 640 * 2 \
        + 4 * (1 + 128 * 8) * 16 * 640 * 2
    assert round(held / 1e9, 2) == 0.42
    assert 8.0e9 < fam.n_params(m) * 2 + held < 8.1e9


def test_the_training_counts_the_harness_asks_of_a_family(cfg, fam):
    """No cell trains this family; a window layer's pairs stop at 128 keys
    a query."""
    m = cfg["model"]
    pair = 2 * 80 * (128 + 64 + 128)
    keys = 0.5 * 4096 + 4 * 128
    assert fam.train_flops_per_token(m, 4096) == pytest.approx(
        3 * fam.forward_flops(m, 1, 0) + 3 * pair * keys)
    assert fam.attention_train_flops(m, 2, 4096) == pytest.approx(
        3 * pair * keys * 4096 * 2)
    assert fam.forward_flops(m, 1, 1000) == pytest.approx(
        fam.forward_flops(m, 1, 0) + pair * (1000 + 4 * 128))


# ------------------------------------------- the cell, found and run

def tree(tmp_path):
    from _tiny import TINY
    root = tmp_path / "tree"
    shutil.copytree(TINY, root)
    shutil.copytree(os.path.join(HERE, "motif3"), root, dirs_exist_ok=True)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "motif3-tiny", "source": "test",
        "file": "benchmark/configs/motif3-tiny.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": TINY_CELL, "config": "motif3-tiny",
        "traffic": "reason_tiny4", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "gpt-tiny.docqa_tiny" in m.get("workloads", []):
            m["workloads"].append(TINY_CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_the_tiny_cell_is_correct(tmp_path):
    from _tiny import run_tiny
    root = tree(tmp_path)
    line, rows, out = run_tiny(TINY_CELL, 2**31 + 7, 2.0, traced=True,
                               root=root)
    assert line["correct"] is True, rows
    assert out["numbers"]["tokens_compared"] >= 1
    got = line["metrics"]
    assert got["serve_step_mfu.out_tps"]["value"] > 0.0
    assert got["prefix_hit_token_share.out_tps"]["value"] == 0.0


def test_the_control_and_a_planted_fault_are_not_correct(tmp_path):
    """Twelve fixed requests served to their end: the program's gap is
    rounding; the fp8 control and the last served token of the longest
    request altered fail the cell's limit."""
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
                     for p, r in reqs), key=lambda r: -len(r["prompt"]))
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
            assert ok is False and gap > 2 * limit, (name, gap)


# ------------------------------------------------ the reader, by hand

class Span:
    def __init__(self, name, t0, **attrs):
        self.name, self.t0, self.t1, self.attrs = name, t0, t0 + 0.001, attrs


class FakeTrace:
    """A chunk run then a decode run of 100 ms, a decode run alone; inside
    each decode run 10 `mhc` ops of 0.02 ms."""
    t0, t1 = 0, 10**9

    def __init__(self, ops=True):
        ms = 10**6
        self.modules = [[("jit_fn(1)", 10 * ms, 90 * ms),
                         ("jit_fn(2)", 100 * ms, 200 * ms),
                         ("jit_fn(2)", 300 * ms, 400 * ms)]]
        ops_ = []
        if ops:
            for start in (110 * ms, 310 * ms):
                for i in range(10):
                    a = start + i * ms
                    ops_.append(("mhc_bf16_128_16384_", a, a + 20_000))
            ops_.append(("fusion_bf16_1_", 20 * ms, 60 * ms))
        self.ops = [ops_]

    def module_runs(self, pattern="."):
        return list(self.modules[0])

    def place(self, skew_ns):
        from _tiny import place_fake
        place_fake(self, skew_ns)


def reader_ctx(monkeypatch, trace, calls, cell=CELL):
    from benchmark.readers import _program
    from benchmark.spec import Cell
    from _tiny import fake_program_spans
    monkeypatch.setattr(_program, "program_spans", fake_program_spans(
        {"engine/decode_call": calls, "engine/decode_finish": []}))
    return {"cell": Cell(cell), "trace": trace, "host_window": [0.0, 1.0],
            "facts": {}, "peaks": {"flops_bf16": 197e12,
                                   "hbm_bytes_per_s": 819e9}}


def test_the_mhc_reader_counts_what_the_spans_counted(monkeypatch, fam, cfg):
    m = cfg["model"]
    calls = [Span("engine/decode_call", 0.1, mhc_rows=1280),
             Span("engine/decode_call", 0.3, mhc_rows=1280)]
    ctx = reader_ctx(monkeypatch, FakeTrace(), calls)
    got = ctx["cell"].reader(WAITING)(ctx)
    # 0.2 ms of mhc a decode run; memory-bound (11.7 FLOP a byte)
    assert got == pytest.approx(100.0 * fam.mhc_bytes(m, 1280) / 819e9
                                / 0.0002)
    assert 0.0 < got < 100.0


def test_the_mhc_reader_reads_nothing_where_there_is_nothing(monkeypatch):
    """A trace without the kernel's ops, spans without `mhc_rows` (the
    parent's program), no trace, another family's cell: None, no raise."""
    full = [Span("engine/decode_call", 0.1, mhc_rows=1280)]
    bare = [Span("engine/decode_call", 0.1, path="mla_decode")]
    for trace, calls in ((FakeTrace(ops=False), full), (FakeTrace(), bare),
                         (None, full)):
        ctx = reader_ctx(monkeypatch, trace, calls)
        assert ctx["cell"].reader(WAITING)(ctx) is None
    ctx = reader_ctx(monkeypatch, FakeTrace(), full, cell=SAME_MIX)
    assert ctx["cell"].reader(WAITING)(ctx) is None
