"""A training cell end to end on the CPU at `gpt_tiny` (everything of a
run but the look for a chip): `correct` comes out true, the fp8 control
comes out not correct, and each fault a training cell can have is seen."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

CELL = "gpt-tiny.train_tiny"


def failed(rows):
    return {n for n, v, lim in rows if v is None or not v <= lim}


@pytest.mark.parametrize("seed", [3, 2**31 + 17])
def test_sound_run_is_correct(seed):
    from _tiny import run_tiny
    line, rows, out = run_tiny(CELL, seed=seed, seconds=1.0)
    assert line["correct"] is True, rows
    assert failed(rows) == set()
    assert set(line["metrics"]) == {"train_tokens_per_s_per_chip", "setup_s"}
    assert line["attempted"] == out["facts"]["steps"] > 3
    assert out["facts"]["tokens"] == out["facts"]["steps"] * 4 * 128
    assert list(line)[-1] == "compared" and len(line["compared"]) == 5
    assert len(out["facts"]["losses"]) == 3


def test_traced_run_reports_the_per_layer_metrics_it_can_read():
    from _tiny import run_tiny
    line, rows, _ = run_tiny(CELL, seed=5, seconds=1.5, traced=True)
    assert line["correct"] is True, rows
    got = line["metrics"]
    # host-side readers read; device readers find no TPU plane in a CPU
    # trace and return nothing, never 0
    assert {"feed_wait_ms_per_step.train", "train_step_mfu.train"} <= set(got)
    assert "flash_roofline_share.train" not in got
    assert "device_idle_share.train" not in got
    assert "setup_s" not in got
    assert 0 < got["train_step_mfu.train"]["value"] < 100
    assert line["device"]["window_s"] > 0 and "breakdown" in line


def test_no_limits_is_not_correct():
    from _tiny import run_tiny
    line, _, _ = run_tiny(CELL, seed=3, seconds=0.5, limits={})
    assert line["correct"] is False


def test_unchanged_state_is_seen():
    """A step that hands its parameters back unchanged."""
    from _tiny import run_tiny
    line, rows, _ = run_tiny(
        CELL, seed=3, seconds=0.5,
        faults={"trainer": lambda tr: tr.opt.set_lr(0.0)})
    assert line["correct"] is False
    bad = failed(rows)
    assert "update_norm_gap" in bad
    gap = dict((n, v) for n, v, _ in rows)["update_norm_gap"]
    assert gap == pytest.approx(1.0, abs=0.02)


def test_half_batch_is_seen():
    """Half of the batch left out, the mean taken over the rest."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from _tiny import run_tiny

    def plant(tr):
        step = tr.step

        def half(batch):
            out = []
            for t in batch:
                a = t.value()
                h = a.shape[0] // 2
                out.append(paddle.to_tensor(jnp.concatenate([a[:h], a[:h]])))
            return step(tuple(out))
        tr.step = half

    line, rows, _ = run_tiny(CELL, seed=3, seconds=0.5,
                             faults={"trainer": plant})
    assert line["correct"] is False
    assert "grad_norm_gap" in failed(rows)


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_fp8_control_is_not_correct(seed):
    """The reference put in the program's place, its products in fp8."""
    from _tiny import tiny_cell
    from benchmark import correct, traffic
    from benchmark.reference import common
    cell = tiny_cell(CELL)
    rows = lambda i: traffic.train_row(seed, i, 256, cell.mix["seq"])  # noqa

    def follow(**kw):
        return correct.follow_reference(cell, seed, rows, 3, **kw)

    want = follow()
    numbers = correct.compare_training(follow(dot=common.fp8_dot), want)
    rows_, ok = correct.verdict(numbers, {k: v for k, v in cell.limits.items()
                                          if k in numbers})
    assert ok is False
    assert {"grad_sketch_gap", "grad_norm_gap"} <= failed(rows_)
    same = correct.compare_training(want, want)
    assert same["loss_gap"] == same["grad_norm_gap"] == 0.0
    assert same["grad_sketch_gap"] == 0.0


def test_worst_leaf_gap_and_moving_leaves():
    from benchmark import correct
    want = {"a": 1.0, "b": 2.0, "c": 1e-9, "d": 4.0}
    got = {"a": 1.1, "b": 2.0, "c": 5e-9, "d": 2.0}
    gap, who = correct.worst_leaf_gap(got, want)
    assert who == "d" and gap == pytest.approx(0.5)
    # a leaf that is all but zero is measured against the median leaf
    gap, who = correct.worst_leaf_gap({"a": 1.0, "b": 2.0, "c": 0.5,
                                       "d": 4.0}, want)
    assert who == "c" and gap == pytest.approx(0.5 / 1.5, rel=1e-6)
    assert correct.moving_leaves(want) == ["a", "b", "d"]


def test_calibration_holds_program_control_and_fault_to_the_limits():
    """`benchmark.tools.calibrate` reads the program, the fp8 control and
    the half-batch fault and pushes each through `correct.verdict`: the
    program comes out correct, the other two not."""
    from _tiny import tiny_cell
    from benchmark.tools import calibrate
    rec, = calibrate.training(tiny_cell("gpt-tiny.train_tiny"), [5], 1, 0)
    assert rec["program"]["correct"] is True, rec["program"]
    assert rec["control_fp8"]["correct"] is False
    assert rec["fault_half_batch"]["correct"] is False
    assert rec["fault_half_batch"]["failed"]
