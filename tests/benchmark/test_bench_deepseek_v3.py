"""The `deepseek-v3` configuration and its cell in the benchmark: the
file's published keys and its cuts, the family's counts pinned to the
digit, and a tiny cell of it run through `run_cell` on the CPU (the
program correct, the fp8 control and a planted fault not)."""
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

CONFIG = os.path.join(ROOT, "benchmark", "configs", "deepseek-v3.json")
CELL = "deepseek-v3.reason_closed"
SAME_MIX = "longcat-flash-omni.reason_closed"
TINY_CELL = "deepseek-v3-tiny.reason_tiny4"
REDUCED = ["first_k_dense_replace", "n_routed_experts", "num_hidden_layers",
           "num_nextn_predict_layers", "vocab_size"]
# every .out_tps entry whose reader reads this cell; not the walk of K/V
# pages (`mla_decode` here), zero experts, or the state and SSD/GDN
# entries; not the experts-touched share, whose reader counts the dense
# layer as a routed one; not the pool-write share, whose list a test of
# its own pins
NOT_JOINED = {"paged_kernel_step_share.out_tps",
              "moe_zero_assignment_share.out_tps",
              "moe_experts_touched_share.out_tps",
              "pool_write_kernel_step_share.out_tps",
              "gdn_decode_roofline_share.out_tps",
              "ssd_decode_roofline_share.out_tps",
              "ssd_decode_step_share.out_tps",
              "state_cache_byte_share.out_tps"}


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
    from benchmark.families import deepseek_v3
    return deepseek_v3


# --------------------------------------------------- the configuration

def test_the_file_differs_from_the_published_config_by_its_cuts(cfg, bench):
    pub = cfg["published"]
    assert pub["model_type"] == "deepseek_v3" and len(pub) == 33
    assert sorted(k for k in pub if cfg.get(k) != pub[k]) == REDUCED \
        == sorted(cfg["reduced"]) == sorted(cfg["reduced_how"])
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["n_routed_experts"], cfg["vocab_size"],
            cfg["num_nextn_predict_layers"]) == (5, 1, 16, 129280 // 8, 0)
    # every width and every routing number as published
    assert (pub["hidden_size"], pub["intermediate_size"],
            pub["moe_intermediate_size"], pub["num_attention_heads"],
            pub["q_lora_rank"], pub["kv_lora_rank"], pub["qk_nope_head_dim"],
            pub["qk_rope_head_dim"], pub["v_head_dim"]) == (
                7168, 18432, 2048, 128, 1536, 512, 128, 64, 128)
    assert (pub["n_group"], pub["topk_group"], pub["num_experts_per_tok"],
            pub["routed_scaling_factor"], pub["scoring_func"],
            pub["topk_method"], pub["n_shared_experts"]) == (
                8, 4, 8, 2.5, "sigmoid", "noaux_tc", 1)
    # the program's `model` group: the published numbers under the
    # program's names, the router at its published width, experts 0-15
    m = cfg["model"]
    for k, v in m.items():
        if k == "num_experts":
            assert v == cfg["n_routed_experts"] == 16
        elif k in cfg:
            assert v == cfg[k], k
    assert (m["router_experts"], m["expert_offset"]) == (256, 0)
    assert m["rope_scaling"] == pub["rope_scaling"] and m["rope_interleave"]
    assert {"router", "choice_bias", "shared_expert", "rope", "head",
            "cache", "weights", "max_len", "mtp"} <= set(cfg["assumed"])
    assert "16 chips, expert-parallel, 16 routed experts a chip" \
        in cfg["deployment"] and "4,565,721,088 parameters" \
        in cfg["deployment"]
    assert cfg["engine"] == {"max_slots": 128, "max_len": 4096,
                             "block_size": 16, "kv_blocks": 16384,
                             "prefill_chunk": 512}
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert sorted(entry["reduced"]) == REDUCED
    assert cfg["source"].startswith(entry["source"])
    # the floors of a cut: four routed layers after the dense one, >= 8
    # experts held, >= 1/8 of the vocabulary
    assert m["num_hidden_layers"] - m["first_k_dense_replace"] >= 4
    assert m["num_experts"] >= 8 and 8 * m["vocab_size"] >= pub["vocab_size"]


def test_the_cell_joins_the_lists_and_adds_no_entry(bench):
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "deepseek-v3", "reason_closed", 1) and len(entry["why"]) <= 200
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["serve_out_tokens_per_s"]["workloads"]
    for m in bench["per_layer"]:
        if m["name"].endswith(".out_tps"):
            assert (CELL in m["workloads"]) == (m["name"] not in NOT_JOINED), \
                m["name"]
    assert not [m["name"] for m in bench["per_layer"]
                if m.get("workloads") == [CELL]]
    from benchmark.spec import Cell
    cell = Cell(CELL)
    assert cell.mix == Cell(SAME_MIX).mix
    assert cell.mix["clients"] == cell.config["engine"]["max_slots"] == 128
    assert set(cell.limits) == {
        "served_logit_gap", "served_logit_gap_mean", "requests_not_done",
        "answers_of_wrong_length", "nan_logits", "recompiles_in_window"}


# -------------------------------------------------------------- counts

def test_parameter_counts_of_the_share_held(cfg, fam):
    m = cfg["model"]
    mla = 7168 * 1536 + 1536 * 128 * 192 + 7168 * 576 + 512 * 128 * 256 \
        + 128 * 128 * 7168
    assert fam.mla_params(m) == mla and mla + 1536 + 512 == 187_107_328
    ffn = 3 * 7168 * 18432
    dense_layer = mla + 2048 + ffn + 2 * 7168
    assert dense_layer == 583_483_392
    expert = 3 * 7168 * 2048
    assert expert == fam.expert_params(m) == 44_040_192
    routed_outside = mla + 2048 + 7168 * 256 + 256 + expert + 2 * 7168
    assert routed_outside == 232_997_120
    assert routed_outside + 16 * expert == 937_640_192
    table = 16160 * 7168
    assert 2 * table == 231_669_760
    routed_layer = routed_outside + 16 * expert
    assert fam.n_params(m) == dense_layer + 4 * routed_layer + 2 * table \
        + 7168 == 4_565_721_088
    assert round(fam.n_params(m) * 2 / 1e9, 2) == 9.13
    shapes = fam.shapes(m)
    assert shapes["l0.f_gate"] == (7168, 18432) and "l0.router" not in shapes
    assert shapes["l4.exp_down"] == (16, 2048, 7168)
    assert shapes["l1.router"] == (7168, 256) and shapes["l1.router_b"] == (
        256,)
    assert shapes["l2.sh_up"] == (7168, 2048)
    # what a token multiplies: the matrices and 8 x 16 / 256 of an expert a
    # routed layer
    assert fam.matmul_params(m) == pytest.approx(
        5 * mla + ffn + 4 * (7168 * 256 + expert) + table + 4 * expert * 0.5)


def test_flops_and_bytes_of_a_decode_step(cfg, fam):
    m = cfg["model"]
    assert fam.kv_bytes_per_token(m) == 5 * 576 * 2 == 5_760
    # 242 FLOP a byte at 128 heads: on the v5e's ridge (197e12 / 819e9)
    assert fam.mla_flops(m, 1) / fam.mla_bytes(m, 1) == pytest.approx(
        2 * 128 * 1088 / 1152)
    assert 241 < fam.mla_flops(m, 1) / fam.mla_bytes(m, 1) < 242
    # 128 live slots touch about 15.7 of the 16 held experts a layer
    assert fam.experts_touched(m, 128) == pytest.approx(
        16 * (1 - (1 - 8 / 256) ** 128))
    assert 15.7 < fam.experts_touched(m, 128) < 15.75
    # a step of 128 slots at a mean context of 1,150: 9.65 GB, the held
    # experts 58 % of it
    got = fam.decode_step_bytes(m, 128 * 1150, 128)
    assert round(got / 1e9, 2) == 9.65
    assert 4 * fam.experts_touched(m, 128) * fam.expert_bytes(m) / got \
        == pytest.approx(0.57, abs=0.01)
    e = cfg["engine"]
    held = e["kv_blocks"] * e["block_size"] * 5 * 640 * 2
    assert round(held / 1e9, 2) == 1.68
    assert 10.8e9 < fam.n_params(m) * 2 + held < 10.82e9


def test_the_training_counts_the_harness_asks_of_a_family(cfg, fam):
    """No cell trains this family; its counts are the serving ones'
    arithmetic: 3x the forward's matmuls, and the expanded attention's
    pairs over half of the causal square, 3x."""
    m = cfg["model"]
    pair = 2 * 128 * (128 + 64 + 128)
    assert fam.train_flops_per_token(m, 4096) == pytest.approx(
        3 * 2 * fam.matmul_params(m) + 0.5 * 3 * 5 * pair * 4096)
    assert fam.attention_train_flops(m, 2, 4096) == pytest.approx(
        0.5 * 3 * 5 * pair * 4096 * 4096 * 2)
    assert fam.attention_train_bytes(m, 2, 4096) == pytest.approx(
        6 * 128 * 320 * 2 * 4096 * 2 * 5)


# ------------------------------------------- the cell, found and run

def tree(tmp_path):
    """A copy of the tiny tree + this family's tiny configuration, mix and
    limits, and the cell on the lists this cell is on."""
    from _tiny import TINY
    root = tmp_path / "tree"
    shutil.copytree(TINY, root)
    shutil.copytree(os.path.join(HERE, "deepseek_v3"), root,
                    dirs_exist_ok=True)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "deepseek-v3-tiny", "source": "test",
        "file": "benchmark/configs/deepseek-v3-tiny.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": TINY_CELL, "config": "deepseek-v3-tiny",
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


def test_the_control_and_a_planted_fault_are_not_correct(tmp_path):
    """Twelve fixed requests served to their end: the program's gap is
    rounding; the fp8 control and the last served token of the longest
    request altered fail the cell's limit, pushed through
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

