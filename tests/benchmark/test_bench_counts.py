"""FLOP and byte counts against hand-worked values, both configurations."""
import json
import os

import pytest

from benchmark import counts
from benchmark.families import gpt as fam

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def model(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)["model"]


def test_parameter_counts():
    m350, m13 = model("gpt3-350m"), model("gpt3-1.3b")
    # blocks: 12 H^2 weights + 13 H biases and LayerNorms a layer;
    # embeddings (V + P) H; final LayerNorm 2 H
    def by_hand(h, n_l, v=50304, p=2048):
        return n_l * (12 * h * h + 13 * h) + (v + p) * h + 2 * h
    assert fam.n_params(m350) == by_hand(1024, 24) == 355_919_872
    assert fam.n_params(m13) == by_hand(2048, 24) == 1_315_819_520
    assert fam.matmul_params(m350) == 12 * 24 * 1024 ** 2 + 50304 * 1024
    assert fam.matmul_params(m13) == 12 * 24 * 2048 ** 2 + 50304 * 2048


def test_train_flops_per_token():
    # 350M: 6 x 353,501,184 + 6 x 24 x 1024 x 2048 (causal half of 12LHS)
    assert fam.train_flops_per_token(model("gpt3-350m"), 2048) == \
        pytest.approx(2.121007104e9 + 0.301989888e9)
    # 1.3B: 6 x 1,310,982,144 + 6 x 24 x 2048 x 2048
    assert fam.train_flops_per_token(model("gpt3-1.3b"), 2048) == \
        pytest.approx(7.865892864e9 + 0.603979776e9)


def test_attention_counts():
    m = model("gpt3-350m")
    # 8 rows of 2048: 6 L H S^2 B = 6 x 24 x 1024 x 2048^2 x 8
    assert fam.attention_train_flops(m, 8, 2048) == \
        pytest.approx(4.947802324992e12)
    # q k v o in, dq dk dv out, q k v o do again: 12 tensors of B S H bf16
    assert fam.attention_train_bytes(m, 8, 2048) == \
        12 * 8 * 2048 * 1024 * 2 * 24
    # the share of the step's model FLOPs: 12.5 %
    step = fam.train_flops_per_token(m, 2048) * 8 * 2048
    assert fam.attention_train_flops(m, 8, 2048) / step == \
        pytest.approx(0.1246, abs=1e-3)


def test_decode_step_bytes():
    m = model("gpt3-1.3b")
    n = fam.n_params(m)
    assert fam.kv_bytes_per_token(m) == 2 * 24 * 2048 * 2 == 196_608
    assert fam.weight_bytes(m) == (n - 2048 * 2048) * 2
    # 20 live slots holding 9000 tokens of context between them
    got = fam.decode_step_bytes(m, 9000, 20)
    assert got == (n - 2048 * 2048) * 2 + 196_608 * 9020
    share, bound = counts.roofline_share(
        fam.forward_flops(m, 20, 9000), got, 0.126, 197e12, 819e9)
    assert bound == "memory"
    # 4.396 GB over 819 GB/s is 5.37 ms: 4.26 % of a 126 ms step
    assert share == pytest.approx(4.26, abs=0.02)


def test_forward_flops():
    m = model("gpt3-1.3b")
    # one 256-token chunk at the start of a prompt: 256 x 2 x matmul
    # params + 4 L H x (1 + 2 + ... + 256) attended pairs
    pairs = 256 * 257 // 2
    assert fam.forward_flops(m, 256, pairs) == pytest.approx(
        2 * 1_310_982_144 * 256 + 4 * 24 * 2048 * pairs)


def test_roofline_share_names_the_bound_and_never_clips():
    share, bound = counts.roofline_share(197e12, 1.0, 0.5, 197e12, 819e9)
    assert (share, bound) == (200.0, "compute")     # over 100 is shown
    share, bound = counts.roofline_share(1.0, 819e9, 4.0, 197e12, 819e9)
    assert (share, bound) == (25.0, "memory")


# what `benchmark/counts.py` and `benchmark/weights.py` returned on commit
# df29a34, before the counts moved into the family's file: the yardstick
# of every share with `mfu` or `roofline` in its name
PARENT = {
    "gpt3-350m": {"n_params": 355_919_872, "matmul_params": 353_501_184,
                  "train_flops_per_token": 2_422_996_992.0,
                  "decode_step_bytes": 1_594_347_520,
                  "forward_flops": 199_251_197_952.0,
                  "attention_train_flops": 4_947_802_324_992.0,
                  "attention_train_bytes": 9_663_676_416.0,
                  "kv_bytes_per_token": 98_304, "weight_bytes": 707_645_440},
    "gpt3-1.3b": {"n_params": 1_315_819_520, "matmul_params": 1_310_982_144,
                  "train_flops_per_token": 8_469_872_640.0,
                  "decode_step_bytes": 4_396_654_592,
                  "forward_flops": 731_899_232_256.0,
                  "attention_train_flops": 9_895_604_649_984.0,
                  "attention_train_bytes": 19_327_352_832.0,
                  "kv_bytes_per_token": 196_608,
                  "weight_bytes": 2_623_250_432},
}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_the_gpt_familys_counts_are_the_parents(name):
    """Reached as the readers reach them, through the cell's family:
    `train_flops_per_token` at 2048, `decode_step_bytes` at one fixed
    step (20 live slots, 9000 tokens of context), a chunk-sized
    `forward_flops` (276 tokens, 41,896 pairs), attention at 8 x 2048."""
    from benchmark.spec import Cell
    cell = next(Cell(w) for w in ("gpt3-350m.pretrain_2k",
                                  "gpt3-1.3b.chat_poisson")
                if w.startswith(name))
    f, m = cell.family, cell.config["model"]
    assert m == model(name) and f is fam
    got = {"n_params": f.n_params(m), "matmul_params": f.matmul_params(m),
           "train_flops_per_token": f.train_flops_per_token(m, 2048),
           "decode_step_bytes": f.decode_step_bytes(m, 9000, 20),
           "forward_flops": f.forward_flops(m, 276, 41896),
           "attention_train_flops": f.attention_train_flops(m, 8, 2048),
           "attention_train_bytes": f.attention_train_bytes(m, 8, 2048),
           "kv_bytes_per_token": f.kv_bytes_per_token(m),
           "weight_bytes": f.weight_bytes(m)}
    assert got == PARENT[name]
