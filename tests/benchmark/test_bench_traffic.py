"""The general generator: every seed gets the same lengths and count, in
another order and at other instants; due-time accounting under a stall."""
import json
import os
from collections import Counter

import numpy as np
import pytest

from benchmark import traffic
from benchmark.drive_serve import percentile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def mix(name):
    with open(os.path.join(ROOT, "benchmark", "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_open_loop_same_work_for_every_seed(seed):
    m = mix("chat_poisson")
    a = traffic.open_loop(m, 1, 51.0, 50304)
    b = traffic.open_loop(m, seed, 51.0, 50304)
    assert len(a) == len(b) == round(m["rate_per_s"] * 51.0)
    assert Counter(r.fresh_tokens for r in a) == \
        Counter(r.fresh_tokens for r in b)
    assert Counter(r.new_tokens for r in a) == Counter(r.new_tokens for r in b)
    if seed != 1:
        assert [r.fresh_tokens for r in a] != [r.fresh_tokens for r in b]
        assert [r.due_s for r in a] != [r.due_s for r in b]
    due = [r.due_s for r in b]
    assert due == sorted(due) and 0.0 <= due[0] and due[-1] < 51.0


def test_open_loop_lengths_are_the_stated_quantiles():
    """The mix states the trace it is shaped as; the generated lengths
    have the trace's published medians, and the context's clip shows."""
    m = mix("chat_poisson")
    pub = m["source"]["published"]
    reqs = traffic.open_loop(m, 3, 51.0, 50304)
    fresh = sorted(r.fresh_tokens for r in reqs)
    out = sorted(r.new_tokens for r in reqs)
    assert fresh[0] >= 32 and fresh[-1] == 1536 and out[0] >= 16 \
        and out[-1] <= 448
    assert abs(fresh[len(fresh) // 2] - pub["prompt_tokens"]["median"]) <= 40
    assert abs(out[len(out) // 2] - pub["generated_tokens"]["median"]) <= 8
    # the top fifth of prompts sits at the clip, so the 90th percentile
    # prompt is the same 6 chunks in every run
    assert percentile(fresh, 90) == percentile(fresh, 82) == 1536
    assert all(r.doc is None for r in reqs)
    p = reqs[0].prompt()
    assert len(p) == reqs[0].prompt_len and max(p) < 50304 and min(p) >= 0
    assert p != reqs[1].prompt()[:len(p)]


@pytest.mark.parametrize("name,part,key", [
    ("chat_poisson", "fresh", "prompt_tokens"),
    ("chat_poisson", "output", "generated_tokens"),
    ("docqa_closed", "output", "generated_tokens")])
def test_a_mix_keeps_to_the_trace_it_names(name, part, key):
    """median as published; sigma = sqrt(2 ln(mean / median))."""
    import math
    m = mix(name)
    pub = m["source"]["published"][key]
    assert "Azure" in m["source"]["trace"] and "Azure" in m["why"]
    assert m[part]["median"] == pub["median"]
    assert m[part]["sigma"] == pytest.approx(
        math.sqrt(2 * math.log(pub["mean"] / pub["median"])), abs=0.01)


def test_prompts_fit_the_engine():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "gpt3-1.3b.json")) as f:
        eng = json.load(f)["engine"]
    for name in ("chat_poisson", "docqa_closed"):
        m = mix(name)
        longest = m["fresh"]["max"] + m["output"]["max"]
        if m.get("shared"):
            longest += m["shared"]["tokens"]["max"]
        assert max(traffic.stratified(m["fresh"], 500)) <= m["fresh"]["max"]
        assert longest <= eng["max_len"], name


@pytest.mark.parametrize("seed", [5, 2**31 + 5])
def test_closed_loop_blocks_hold_the_same_mix(seed):
    m = mix("docqa_closed")
    src = traffic.closed_loop(m, seed, 50304)
    ref = traffic.closed_loop(m, 1, 50304)
    n = m["clients"]
    block1 = [next(src) for _ in range(n)]
    block2 = [next(src) for _ in range(n)]
    other = [next(ref) for _ in range(n)]
    for blk in (block2, other):
        assert Counter(r.fresh_tokens for r in blk) == \
            Counter(r.fresh_tokens for r in block1)
        assert Counter(r.new_tokens for r in blk) == \
            Counter(r.new_tokens for r in block1)
        assert Counter(r.doc for r in blk) == Counter(r.doc for r in block1)
    # every document is asked equally often, and is the same text each time
    assert set(Counter(r.doc for r in block1).values()) == \
        {n // m["shared"]["documents"]}
    a = next(r for r in block1 if r.doc == 0)
    b = next(r for r in block2 if r.doc == 0)
    assert a.prompt()[:a.doc_tokens] == b.prompt()[:b.doc_tokens]
    assert a.prompt()[a.doc_tokens:] != b.prompt()[b.doc_tokens:]
    assert m["shared"]["tokens"]["min"] <= a.doc_tokens <= 1664
    assert [r.index for r in block1 + block2] == list(range(2 * n))


def test_train_rows_differ_and_repeat_from_the_seed():
    a = traffic.train_row(2**31 + 9, 0, 50304, 2048)
    assert a.shape == (2048,) and a.dtype == np.int32
    assert (a == traffic.train_row(2**31 + 9, 0, 50304, 2048)).all()
    assert (a != traffic.train_row(2**31 + 9, 1, 50304, 2048)).any()
    assert (a != traffic.train_row(9, 0, 50304, 2048)).any()


def test_percentile_is_nearest_rank_over_all_values():
    v = list(range(1, 11))
    assert percentile(v, 90) == 9 and percentile(v, 50) == 5
    assert percentile(v, 100) == 10 and percentile([4.0], 90) == 4.0
    # a request that never answered ranks last
    assert percentile([1.0] * 8 + [float("inf")] * 2, 90) == float("inf")


class StalledEngine:
    """A fake `system.Server`: every step takes `step_s`; a request gets
    its first token `steps_to_first` steps after it was submitted."""

    def __init__(self, step_s, steps_to_first=1, out=3):
        import types
        self.step_s, self.k, self.out = step_s, steps_to_first, out
        self.max_slots, self.reqs, self.model = 4, [], None
        self.ns = types.SimpleNamespace

    def warm(self, *a):
        pass

    def submit(self, prompt, new_tokens):
        r = self.ns(tokens=[], status="queued", age=0, want=new_tokens)
        self.reqs.append(r)
        return r

    def busy(self):
        return sum(r.status != "done" for r in self.reqs)

    def live(self):
        return self.busy()

    def step(self):
        import time
        time.sleep(self.step_s)
        for r in self.reqs:
            if r.status == "done":
                continue
            r.age += 1
            if r.age >= self.k:
                r.status = "running"
                r.tokens.append(1)
                if len(r.tokens) >= r.want:
                    r.status = "done"

    def counters(self):
        return {"compile_count": 0, "decode_steps": 0, "tokens_generated": 0,
                "preemptions": 0, "shared_tokens": 0, "prefix_hit_tokens": 0,
                "prefix_hits": 0, "nan_logits": 0}

    def temp_bytes(self):
        return 0

    def close(self):
        pass


def test_ttft_counts_from_when_a_request_was_due(monkeypatch):
    """Two requests come due while the engine is stuck in a long step:
    they are SENT late, and their time to first token counts the wait."""
    import time
    from benchmark import correct, drive_serve, system
    from benchmark.run import Setup, Tracer
    from benchmark.trace import Recorder
    import sys
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from _tiny import tiny_cell

    cell = tiny_cell("gpt-tiny.chat_tiny")
    eng = StalledEngine(step_s=0.25)
    monkeypatch.setattr(system, "Server", lambda *a, **k: eng)
    monkeypatch.setattr(system, "memory_peak_bytes", lambda: 0)
    monkeypatch.setattr(system, "memory_live_bytes", lambda: 0)
    monkeypatch.setattr(correct, "served_token_gaps",
                        lambda *a, **k: (0.0, 0.0, 1))
    plan = [traffic.Request(i, due, None, 0, 4, 3, 1, 256)
            for i, due in enumerate((0.0, 0.05, 0.10))]
    monkeypatch.setattr(traffic, "open_loop", lambda *a, **k: plan)
    rec = Recorder()
    res = drive_serve.run(cell, 1, 1.0, rec, Tracer(False, rec),
                          Setup(time.time()))
    f = res["facts"]
    late = sorted(f["lateness_ms"])
    # request 0 is sent at once; 1 and 2 came due inside the first step
    assert late[0] < 20 and 120 < late[1] < 260 and 170 < late[2] < 300
    ttft = sorted(f["ttft_ms"])
    # first token one step (250 ms) after SENDING, so due + wait + step
    assert 230 < ttft[0] < 330
    assert 380 < ttft[1] < 520 and 430 < ttft[2] < 560
    assert res["failed"] == 0 and res["attempted"] == 3
    assert f["found_busy"] == 0
