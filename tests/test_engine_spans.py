"""The serving engine's own account of a step and of a request's wait
(ISSUE 25): the phases of ``engine/step`` as spans that tile it, what a
``request/queue`` span says it waited for, the cumulative counters in
``stats()``, and the repair of the stale K/V write into slots that are
still mid-prefill.
"""
import statistics
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTForCausalLM, gpt_tiny
from paddle_tpu.monitor import trace
from paddle_tpu.serving import DecodeEngine


@pytest.fixture(scope="module")
def tiny():
    paddle.seed(0)
    m = GPTForCausalLM(gpt_tiny(hidden_dropout_prob=0.0,
                                attention_dropout_prob=0.0,
                                use_flash_attention=False))
    m.eval()
    return m


def _prompts(n, length, seed):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 256, length).tolist() for _ in range(n)]


def _run(eng, prompts, new_tokens, max_steps=2000):
    """Submit, drain, and hand back (requests, window on perf_counter)."""
    t0 = time.perf_counter()
    reqs = [eng.submit(p, max_new_tokens=new_tokens) for p in prompts]
    eng.run(max_steps=max_steps)
    assert all(r.status == "done" for r in reqs)
    return reqs, t0, time.perf_counter()


def _queue_spans(t0, t1, req):
    return [s for s in trace.spans(t0, t1, "request/queue")
            if s.trace_id == req.id]


def test_children_of_engine_step_tile_it():
    # what no phase covers is some tens of microseconds of Python between
    # them: a share that means something needs steps of milliseconds, so
    # this one test runs a wider gpt_tiny than the others
    paddle.seed(0)
    wide = GPTForCausalLM(gpt_tiny(
        hidden_size=128, num_layers=4, intermediate_size=512,
        hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
        use_flash_attention=False))
    wide.eval()
    eng = DecodeEngine(wide, max_slots=8, max_len=96, block_size=8,
                       prefill_chunk=16)
    eng.submit([1, 2, 3], max_new_tokens=2)
    eng.run()                                   # mint both executables
    reqs, t0, t1 = _run(eng, _prompts(12, 40, seed=1), 24)
    spans = trace.spans(t0, t1, "engine/")
    steps = [s for s in spans if s.name == "engine/step"]
    assert len(steps) >= 20
    kids = {}
    for s in spans:
        if s.name != "engine/step":
            kids.setdefault(s.parent_id, []).append(s)
    names, loose = set(), []
    for st in steps:
        mine = sorted(kids[st.span_id], key=lambda s: s.t0)
        names.update(s.name for s in mine)
        edge, covered = st.t0, 0.0
        for s in mine:
            assert st.t0 <= s.t0 and s.t1 <= st.t1
            assert s.t0 >= edge, "two phases of one step overlap"
            edge = s.t1
            covered += s.t1 - s.t0
        loose.append(1.0 - covered / (st.t1 - st.t0))
    assert names == {"engine/sweep", "engine/admit", "engine/prefill_host",
                     "engine/prefill_call", "engine/decode_prepare",
                     "engine/decode_call", "engine/collect",
                     "engine/decode_finish"}
    # host time of a step that no phase accounts for: the median step, so
    # that one descheduled worker of a loaded test host decides nothing
    assert statistics.median(loose) < 0.05, sorted(loose)[-5:]
    # the two halves of the read-back are children of engine/collect, not
    # phases of the step: one wait and one fetch each, which tile it but
    # for the reading of the host's clocks before the wait and the span
    # layer's own time between and after them, and the wait names the
    # calls whose results it waited for
    between = []
    collects = [s for s in spans if s.name == "engine/collect"]
    assert len(collects) == len([s for s in steps if s.span_id in kids
                                 and any(k.name == "engine/collect"
                                         for k in kids[s.span_id])])
    for c in collects:
        wait, fetch = sorted(kids[c.span_id], key=lambda s: s.t0)
        assert (wait.name, fetch.name) == ("engine/wait", "engine/fetch")
        assert c.t0 <= wait.t0 <= wait.t1 <= fetch.t0 <= fetch.t1 <= c.t1
        between.append(((c.t1 - c.t0) - (wait.t1 - wait.t0)
                        - (fetch.t1 - fetch.t0)) / (c.t1 - c.t0))
        launched = [k for k in kids[c.parent_id]
                    if k.name in ("engine/prefill_call",
                                  "engine/decode_call")]
        assert list(wait.attrs["calls"]) == [k.span_id for k in sorted(
            launched, key=lambda s: s.t0)]
        assert fetch.attrs["arrays"] == 2 * len(launched)
        assert fetch.attrs["bytes"] > 0
    # (measured, like the step's own loose time: the median collect)
    assert statistics.median(between) < 0.25, sorted(between)[-5:]
    assert {s.name for s in spans} - names \
        == {"engine/step", "engine/wait", "engine/fetch"}
    admits = [s for s in spans if s.name == "engine/admit"]
    assert sum(s.attrs["admitted"] for s in admits) == len(reqs)
    fin = [s for s in spans if s.name == "engine/decode_finish"]
    assert sum(s.attrs["finished"] for s in fin) == len(reqs)
    assert sum(s.attrs["tokens"] for s in fin) \
        == sum(len(r.tokens) - 1 for r in reqs)


def test_queue_span_names_blocks_when_the_pool_is_too_small(tiny):
    # 7 usable blocks of 8 tokens, whole-prompt prefill: the second
    # 30-token prompt needs 4 blocks while the first still holds 4-5
    eng = DecodeEngine(tiny, max_slots=2, max_len=48, block_size=8,
                       kv_blocks=8)
    (a, b), t0, t1 = _run(eng, _prompts(2, 30, seed=2), 8)
    (qa,), (qb,) = _queue_spans(t0, t1, a), _queue_spans(t0, t1, b)
    assert qa.attrs["cause"] == "none" and qa.attrs["page_rejects"] == 0
    assert qb.attrs["cause"] == "blocks"
    assert qb.attrs["page_rejects"] >= 1
    assert 0.0 < qb.attrs["block_wait_s"] <= qb.t1 - qb.t0
    assert qb.attrs["slot_wait_s"] == 0.0      # a slot was free throughout
    st = eng.stats()
    assert st["block_waits"] == 1 and st["queue_waits"] == 2
    assert st["page_rejects"] == qb.attrs["page_rejects"]
    assert abs(st["block_wait_s_sum"] - qb.attrs["block_wait_s"]) < 1e-5
    assert abs(st["queue_wait_s_sum"]
               - ((qa.t1 - qa.t0) + (qb.t1 - qb.t0))) < 1e-5
    assert eng.stats()["paged"]["preemptions"] == 0


def test_queue_span_names_slot_when_the_table_is_full(tiny):
    eng = DecodeEngine(tiny, max_slots=2, max_len=64, block_size=8,
                       prefill_chunk=16)
    reqs, t0, t1 = _run(eng, _prompts(3, 12, seed=3), 6)
    first, second, third = (_queue_spans(t0, t1, r) for r in reqs)
    assert first[0].attrs["cause"] == second[0].attrs["cause"] == "none"
    (q,) = third
    assert q.attrs["cause"] == "slot"
    assert 0.0 < q.attrs["slot_wait_s"] <= q.t1 - q.t0
    assert q.attrs["block_wait_s"] == 0.0 and q.attrs["page_rejects"] == 0
    st = eng.stats()
    assert st["block_waits"] == 0 and st["queue_waits"] == 3


def test_requeue_after_preemption_opens_a_new_queue_span(tiny):
    eng = DecodeEngine(tiny, max_slots=4, max_len=48, block_size=8,
                       kv_blocks=9, prefill_chunk=8)
    reqs, t0, t1 = _run(eng, _prompts(4, 20, seed=6), 20)
    victim = max(reqs, key=lambda r: r.preemptions)
    assert victim.preemptions >= 1
    qs = _queue_spans(t0, t1, victim)
    assert len(qs) == victim.preemptions + 1
    assert "requeue" not in qs[0].attrs
    assert all(q.attrs["requeue"] is True for q in qs[1:])
    assert [q.attrs["nth"] for q in qs[1:]] \
        == list(range(1, victim.preemptions + 1))


# ---- a slot that is mid-prefill must not be written by the decode step


def _first_row(eng, req):
    """Layer 0's K and V at position 0 of the request's first block."""
    blk = int(eng._pager.tables[req.slot, 0])
    pk, pv = eng._pools[0]
    return np.asarray(pk[blk, 0]), np.asarray(pv[blk, 0])


def test_decode_leaves_a_mid_prefill_slot_alone(tiny):
    long_prompt = _prompts(1, 40, seed=4)[0]       # three chunks of 16

    def prefilled(with_neighbour):
        eng = DecodeEngine(tiny, max_slots=2, max_len=96, block_size=8,
                           prefill_chunk=16)
        if with_neighbour:
            a = eng.submit([7, 8, 9], max_new_tokens=40)
            while a.status != "running":
                eng.step()
        b = eng.submit(long_prompt, max_new_tokens=2)
        eng.step()
        eng.step()              # two chunks in, and two decode steps beside
        assert b.status == "prefilling"
        assert eng.live_count == int(with_neighbour)
        return _first_row(eng, b)

    alone, beside = prefilled(False), prefilled(True)
    # while b was being chunked, every decode step of its neighbour also
    # ran b's row of the batch at pos 0: that write must land in the trash
    np.testing.assert_array_equal(beside[0], alone[0])
    np.testing.assert_array_equal(beside[1], alone[1])


def test_served_tokens_match_the_plain_forward_under_chunked_load(tiny):
    """PERF.md section 7 item 1's drill: float32, 4 closed-loop clients,
    chunk 16, shared documents. No served token may differ from the plain
    forward's argmax where its top-2 gap exceeds 1e-3."""
    eng = DecodeEngine(tiny, max_slots=4, max_len=128, block_size=8,
                       prefill_chunk=16)
    rng = np.random.RandomState(0)
    docs = [rng.randint(1, 256, 40).tolist() for _ in range(3)]
    n_req, active, done, sent = 40, [], [], 0
    while len(done) < n_req:
        while len(active) < 4 and sent < n_req:
            tail = rng.randint(1, 256, 8 + sent % 9).tolist()
            active.append(eng.submit(docs[sent % 3] + tail,
                                     max_new_tokens=12 + sent % 7))
            sent += 1
        eng.step()
        for r in [r for r in active if r.status == "done"]:
            active.remove(r)
            done.append(r)
    compared = 0
    for r in done:
        ids = np.asarray([r.prompt + r.tokens], np.int32)
        logits = np.asarray(tiny(paddle.to_tensor(ids)).value())[0]
        for j, t in enumerate(r.tokens):
            row = logits[len(r.prompt) - 1 + j]
            second, best = np.sort(row)[-2:]
            if best - second > 1e-3:
                compared += 1
                assert t == int(row.argmax()), (r.id, j, best - second)
    assert compared > 400


@pytest.mark.parametrize("kernel", [False, True], ids=["scatter", "kernel"])
def test_call_spans_say_how_the_pools_were_written(tiny, kernel):
    """Every ``engine/prefill_call`` and ``engine/decode_call`` span carries
    ``kv_write``: what its executable was traced with, ``kernel``
    (``kernels/pallas/pool_write.py``, interpreted here) or ``scatter``
    (XLA's, the CPU's), as ``stats()["kv_write"]`` says by executable."""
    from paddle_tpu.kernels.pallas import pool_write
    eng = DecodeEngine(tiny, max_slots=4, max_len=64, block_size=8,
                       prefill_chunk=16)
    way = "kernel" if kernel else "scatter"
    with pool_write.force_interpret(kernel):
        reqs, t0, t1 = _run(eng, _prompts(3, 20, seed=7), 5)
    calls = [s for name in ("engine/prefill_call", "engine/decode_call")
             for s in trace.spans(t0, t1, name)]
    assert {s.name for s in calls} == {"engine/prefill_call",
                                       "engine/decode_call"}
    assert {s.attrs["kv_write"] for s in calls} == {way}
    assert eng.stats()["kv_write"] == {"16": way, "decode": way}
