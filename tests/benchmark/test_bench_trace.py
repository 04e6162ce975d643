"""The reduction from a profiler trace to metrics, on a small trace
recorded on a TPU v5e in PR 24's first round (the tiny open-loop cell of
`tests/benchmark/tiny/`, one second traced) and kept beside the tests."""
import gzip
import os

import pytest

from benchmark import trace as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "chat_tiny.xplane.pb.gz")


@pytest.fixture(scope="module")
def tr():
    from jax.profiler import ProfileData
    with gzip.open(DATA) as f:
        return T.Trace(ProfileData.from_serialized_xspace(f.read()).planes)


def test_planes_lines_and_spans_are_found(tr):
    assert len(tr.ops) == 1 and len(tr.modules) == 1      # one chip
    assert len(tr.ops[0]) == 7484 and len(tr.modules[0]) == 73
    assert {k: len(v) for k, v in tr.spans.items()} == {
        "traced_window": 1, "wait_for_arrival": 357, "submit": 5,
        "engine_step": 32}
    assert tr.window_s == pytest.approx(0.955186546)


def test_busy_union_and_idle_gaps_add_up_to_the_window(tr):
    busy = tr.busy_s()
    assert busy == pytest.approx(0.00113879, rel=1e-6)
    iv = tr.busy_intervals(0)
    assert all(a < b for a, b in iv)
    assert all(iv[i][1] < iv[i + 1][0] for i in range(len(iv) - 1))
    gaps = dict(tr.idle_gaps(10))
    assert sum(gaps.values()) + busy == pytest.approx(tr.window_s, rel=1e-6)
    # the chip waits while the generator waits for the next arrival
    assert max(gaps, key=gaps.get) == "wait_for_arrival"
    assert "engine_step" in gaps


def test_device_ops_are_named_from_their_hlo_line(tr):
    top = tr.device_ops(10)
    assert len(top) == 10 and top == sorted(top, key=lambda x: -x[1])
    assert top[0][0] == "fusion_bf16_32_16_2_64_"
    assert all(" " not in n and "%" not in n for n, _ in top)
    assert T.op_name("%fusion.775.remat = bf16[8,2047,1024]{2,1,0:T(8,128)}"
                     " fusion(bf16[50304,1024]{1,0} %p)") == \
        "fusion.remat_bf16_8_2047_1024_"
    assert T.op_name("%transpose_jvp_jit__pair_bwd___.65 = (bf16[8,2048,"
                     "1024]{2,1,0}, bf16[8]{0}) custom-call(") == \
        "transpose_jvp_jit__pair_bwd____bf16_8_2048_1024_"
    assert T.op_name("%all-gather-start.3 = (f32[4]{0}, f32[16]{0}) "
                     "all-gather-start(") == "all-gather-start_f32_4_"


def test_serving_executables_are_told_apart_by_their_place_in_a_step(tr):
    from benchmark.readers import _common

    class Cell:
        def selector(self, base):
            return {"module_pattern": r"^jit_fn\("}

    runs = _common.serve_module_runs({"trace": tr, "cell": Cell()})
    # two executables are both called jit_fn; 29 + 11 runs in the trace
    names = {n for n, _, _ in tr.module_runs(r"^jit_fn\(")}
    assert len(names) == 2
    assert len(runs["decode"]) + len(runs["chunk"]) == \
        len(tr.module_runs(r"^jit_fn\("))
    assert len(runs["decode"]) == 29 and len(runs["chunk"]) == 11
    assert all(0 < s < 1e-3 for s in runs["decode"] + runs["chunk"])


def test_overlap_and_union():
    assert T._union([(5, 7), (1, 3), (2, 4)]) == [[1, 4], [5, 7]]
    assert T.overlap([[0, 10], [20, 30]], [[5, 25]]) == 10
    assert T.overlap([], [[1, 2]]) == 0


def test_recorder_totals():
    rec = T.Recorder()
    with rec.span("a"):
        pass
    rec.spans["b"] = [(0.0, 1.0), (2.0, 2.5), (9.0, 9.5)]
    assert rec.total("b") == 2.0
    assert len(rec.spans["a"]) == 1 and rec.total("missing") == 0
