"""The serving cells end to end on the CPU at `gpt_tiny` (everything of a
run but the look for a chip): `correct` true on sound runs, false for the
fp8 control and for a token altered where it is produced."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

CHAT, DOCQA = "gpt-tiny.chat_tiny", "gpt-tiny.docqa_tiny"


def failed(rows):
    return {n for n, v, lim in rows if v is None or not v <= lim}


@pytest.mark.parametrize("cell,seed", [(CHAT, 2), (CHAT, 2**31 + 9),
                                       (DOCQA, 4)])
def test_sound_run_is_correct(cell, seed):
    from _tiny import run_tiny
    line, rows, out = run_tiny(cell, seed=seed, seconds=1.5)
    assert line["correct"] is True, rows
    assert line["failed"] == 0 and line["attempted"] == out["facts"]["requests"]
    assert out["numbers"]["tokens_compared"] > 50
    want = {CHAT: {"ttft_p90_ms", "tpot_mean_ms", "setup_s"},
            DOCQA: {"serve_out_tokens_per_s", "setup_s"}}[cell]
    assert set(line["metrics"]) == want
    assert all(m["value"] > 0 for m in line["metrics"].values())


def test_open_loop_sends_the_whole_plan_and_shares_nothing():
    from _tiny import run_tiny
    line, rows, out = run_tiny(CHAT, seed=6, seconds=1.5, traced=True)
    assert line["attempted"] == round(6 * 1.5)
    got = line["metrics"]
    assert got["prefix_hit_token_share.ttft"]["value"] == 0.0
    assert got["preemptions.tpot"]["value"] == 0.0
    assert 0 < got["slot_occupancy.tpot"]["value"] <= 100
    assert 0 <= got["slot_wait_share.ttft"]["value"] <= 100
    assert got["gen_lateness_p99_ms.ttft"]["value"] >= 0
    assert got["ttft_mean_ms.ttft"]["value"] > 0
    assert 0 < got["serve_step_mfu.tpot"]["value"] < 100
    # no TPU plane in a CPU trace: the device readers return nothing
    assert "decode_step_device_ms.tpot" not in got
    assert "decode_hbm_roofline_share.tpot" not in got


def test_closed_loop_keeps_every_client_busy_and_hits_the_prefix_cache():
    from _tiny import run_tiny
    line, rows, out = run_tiny(DOCQA, seed=8, seconds=1.5, traced=True)
    got = line["metrics"]
    assert got["prefix_hit_token_share.out_tps"]["value"] > 40
    assert got["slot_occupancy.out_tps"]["value"] > 50
    assert got["ttft_p50_ms.out_tps"]["value"] > 0
    c0, c1 = out["facts"]["counters"]
    assert c1["prefix_hits"] > c0["prefix_hits"]
    assert line["attempted"] > 4


def alter_tokens(vocab):
    """Every request's third token altered where it is produced."""
    def plant(srv):
        submit, step, reqs = srv.submit, srv.step, []

        def sub(prompt, n):
            r = submit(prompt, n)
            reqs.append([r, False])
            return r

        def stp():
            out = step()
            for pair in reqs:
                r, hit = pair
                if not hit and len(r.tokens) >= 3:
                    r.tokens[2] = (r.tokens[2] + 1 + vocab // 2) % vocab
                    pair[1] = True
            return out
        srv.submit, srv.step = sub, stp
    return plant


@pytest.mark.parametrize("cell", [CHAT, DOCQA])
def test_altered_token_is_seen(cell):
    from _tiny import run_tiny
    line, rows, _ = run_tiny(cell, seed=2, seconds=1.0,
                             faults={"server": alter_tokens(256)})
    assert line["correct"] is False
    assert "served_logit_gap" in failed(rows)


def test_short_answer_is_seen():
    """A request that ends before its stated length is counted."""
    from _tiny import run_tiny

    def plant(srv):
        submit = srv.submit
        srv.submit = lambda prompt, n: submit(prompt, max(n - 1, 1))
    line, rows, _ = run_tiny(CHAT, seed=2, seconds=1.0,
                             faults={"server": plant})
    assert line["correct"] is False
    assert "answers_of_wrong_length" in failed(rows)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_fp8_control_is_not_correct(seed):
    """At the positions of served tokens, the token the fp8 forward puts
    first lies further below the reference's best than the limit."""
    import time
    from _tiny import tiny_cell
    from benchmark import correct, drive_serve
    from benchmark.run import Setup, Tracer
    from benchmark.trace import Recorder
    cell = tiny_cell(DOCQA)
    rec = Recorder()
    res = drive_serve.run(cell, seed, 2.0, rec, Tracer(False, rec),
                          Setup(time.time()))
    assert res["numbers"]["served_logit_gap"] <= \
        cell.limits["served_logit_gap"]
    gap, mean, n = correct.served_token_gaps(cell, seed, res["sample"], 128,
                                             control=True)
    assert 0 < mean <= gap
    assert n == res["numbers"]["tokens_compared"] > 50
    # the control has to fail ONE of the cell's numbers, through the same
    # verdict a run gets
    rows, ok = correct.verdict(dict(res["numbers"], served_logit_gap=gap,
                                    served_logit_gap_mean=mean), cell.limits)
    assert not ok and failed(rows) <= {"served_logit_gap",
                                       "served_logit_gap_mean"}


def test_sample_holds_the_longest_and_is_drawn_from_the_seed():
    from benchmark import correct
    fin = [{"prompt": [0] * n, "tokens": [1] * 3} for n in range(5, 25)]
    a = correct.pick_sample(fin, 4, 9)
    assert len(a) == 4 and len(a[0]["prompt"]) == 24
    assert a == correct.pick_sample(fin, 4, 9)
    assert a != correct.pick_sample(fin, 4, 10)
    assert correct.pick_sample([], 4, 1) == []
    assert len(correct.pick_sample(fin, 100, 1)) == 20


def test_masking_the_non_live_table_rows_leaves_a_sound_run_correct():
    """`calibrate --mask-nonlive` plants the cure for the engine's write
    at position 0 of mid-prefill slots from outside; the run stays
    correct and every answer complete."""
    from _tiny import run_tiny
    from benchmark.tools.calibrate import mask_nonlive
    line, rows, out = run_tiny(CHAT, seed=2, seconds=1.5,
                               faults={"server": mask_nonlive})
    assert line["correct"] is True, rows
    assert out["facts"]["finished"] > 3


def test_the_least_altered_token_fails_the_widest_gap():
    """The fault the widest gap's limit is set against: ONE token, the
    last of the longest sampled request, altered."""
    import time
    from _tiny import tiny_cell
    from benchmark import correct, drive_serve
    from benchmark.run import Setup, Tracer
    from benchmark.tools.calibrate import altered_last_token, step_times
    from benchmark.trace import Recorder
    cell = tiny_cell(DOCQA)
    rec = Recorder()
    res = drive_serve.run(cell, 3, 2.0, rec, Tracer(False, rec),
                          Setup(time.time()))
    bad = altered_last_token(res["sample"], 256, 3)
    assert len(bad) == 1 and bad[0]["tokens"][:-1] == \
        res["sample"][0]["tokens"][:-1]
    assert bad[0]["tokens"][-1] != res["sample"][0]["tokens"][-1]
    gap, mean, n = correct.served_token_gaps(cell, 3, bad, 128)
    rows, ok = correct.verdict(dict(res["numbers"], served_logit_gap=gap),
                               cell.limits)
    assert not ok and "served_logit_gap" in failed(rows)
    st = step_times(res["facts"])
    assert st is None or 0 < st["p50"] <= st["p90"]
