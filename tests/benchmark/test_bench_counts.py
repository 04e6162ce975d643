"""FLOP and byte counts against hand-worked values, both configurations."""
import json
import os

import pytest

from benchmark import counts, weights

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
    assert weights.n_params(m350) == by_hand(1024, 24) == 355_919_872
    assert weights.n_params(m13) == by_hand(2048, 24) == 1_315_819_520
    assert counts.matmul_params(m350) == 12 * 24 * 1024 ** 2 + 50304 * 1024
    assert counts.matmul_params(m13) == 12 * 24 * 2048 ** 2 + 50304 * 2048


def test_train_flops_per_token():
    # 350M: 6 x 353,501,184 + 6 x 24 x 1024 x 2048 (causal half of 12LHS)
    assert counts.train_flops_per_token(model("gpt3-350m"), 2048) == \
        pytest.approx(2.121007104e9 + 0.301989888e9)
    # 1.3B: 6 x 1,310,982,144 + 6 x 24 x 2048 x 2048
    assert counts.train_flops_per_token(model("gpt3-1.3b"), 2048) == \
        pytest.approx(7.865892864e9 + 0.603979776e9)


def test_attention_counts():
    m = model("gpt3-350m")
    # 8 rows of 2048: 6 L H S^2 B = 6 x 24 x 1024 x 2048^2 x 8
    assert counts.attention_train_flops(m, 8, 2048) == \
        pytest.approx(4.947802324992e12)
    # q k v o in, dq dk dv out, q k v o do again: 12 tensors of B S H bf16
    assert counts.attention_train_bytes(m, 8, 2048) == \
        12 * 8 * 2048 * 1024 * 2 * 24
    # the share of the step's model FLOPs: 12.5 %
    step = counts.train_flops_per_token(m, 2048) * 8 * 2048
    assert counts.attention_train_flops(m, 8, 2048) / step == \
        pytest.approx(0.1246, abs=1e-3)


def test_decode_step_bytes():
    m = model("gpt3-1.3b")
    n = weights.n_params(m)
    assert counts.kv_bytes_per_token(m) == 2 * 24 * 2048 * 2 == 196_608
    assert counts.weight_bytes(m, n) == (n - 2048 * 2048) * 2
    # 20 live slots holding 9000 tokens of context between them
    got = counts.decode_step_bytes(m, n, 9000, 20)
    assert got == (n - 2048 * 2048) * 2 + 196_608 * 9020
    share, bound = counts.roofline_share(
        counts.forward_flops(m, 20, 9000), got, 0.126, 197e12, 819e9)
    assert bound == "memory"
    # 4.396 GB over 819 GB/s is 5.37 ms: 4.26 % of a 126 ms step
    assert share == pytest.approx(4.26, abs=0.02)


def test_forward_flops():
    m = model("gpt3-1.3b")
    # one 256-token chunk at the start of a prompt: 256 x 2 x matmul
    # params + 4 L H x (1 + 2 + ... + 256) attended pairs
    pairs = 256 * 257 // 2
    assert counts.forward_flops(m, 256, pairs) == pytest.approx(
        2 * 1_310_982_144 * 256 + 4 * 24 * 2048 * pairs)


def test_roofline_share_names_the_bound_and_never_clips():
    share, bound = counts.roofline_share(197e12, 1.0, 0.5, 197e12, 819e9)
    assert (share, bound) == (200.0, "compute")     # over 100 is shown
    share, bound = counts.roofline_share(1.0, 819e9, 4.0, 197e12, 819e9)
    assert (share, bound) == (25.0, "memory")
