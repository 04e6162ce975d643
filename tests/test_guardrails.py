"""Serving guardrail tests (ISSUE 15): deadlines, cancellation, graceful
drain, dispatch watchdog, and the PADDLE_SERVE_FAULT chaos seam.

The contract under test:
  * ONE terminal-status set (scheduler.TERMINAL_STATUSES) shared by
    ``Request.finished``, step() returns and metrics_summary accounting —
    a rejected/expired/cancelled request always reads finished (the
    poller-spin regression).
  * Deadlines (ttft + total) enforced at step boundaries across every
    state — queued, requeued-after-preemption, mid-chunked-prefill,
    decoding — with the slot and pager blocks released exactly ONCE
    (``BlockPager.check_invariants()`` after every step of scripted
    schedules; shared-prefix refcounts intact, parked blocks re-park).
  * cancel() works from queue, mid-prefill and mid-decode.
  * drain(): door answers ``rejected_draining``, live slots finish or
    expire within the grace budget, drained engines report it once.
  * The watchdog turns a wedged decode/chunk dispatch into a trace-linked
    WARN + flight dump + loud engine failure — driven deterministically
    through the chaos seam's ``slow`` action.
  * The tier-1 chaos gate: a scripted schedule mixing expiry, cancel,
    preemption and drain completes with every request terminal, invariants
    clean after every step, and ZERO steady-state recompiles.

Same budget discipline as tests/test_serving.py: a 2-layer/32-wide GPT on
CPU XLA, module-scoped fixtures sharing compiled executables.
"""
import io
import json
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import (TERMINAL_STATUSES, DecodeEngine,
                                EngineHangError, FaultSchedule,
                                InjectedFault)
from paddle_tpu.serving.scheduler import Request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny_gpt(seed=0):
    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
                    max_position_embeddings=64, hidden_dropout_prob=0.0,
                    attention_dropout_prob=0.0, use_flash_attention=False)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _eager(m, prompt, n):
    ids = np.asarray([prompt], np.int32)
    return m.generate(paddle.to_tensor(ids),
                      max_new_tokens=n).numpy()[0, len(prompt):]


@pytest.fixture(scope="module")
def tiny():
    return _tiny_gpt()


@pytest.fixture(scope="module")
def engine(tiny):
    """Shared paged chunked engine; every test must leave it idle and
    NOT draining."""
    eng = DecodeEngine(tiny, max_slots=4, max_len=48, block_size=8,
                       prefill_chunk=8)
    eng.submit([1, 2, 3], max_new_tokens=2)    # mint chunk-8 + decode
    eng.run()
    return eng


# ------------------------------------------------- satellite: terminal set


def test_terminal_status_set_poller_regression(tiny):
    """The latent poller-spin bug: ``finished`` must be True for EVERY
    terminal status, not just done/failed — a poller waiting on a
    rejected_overload request used to spin forever."""
    assert TERMINAL_STATUSES == {"done", "failed", "rejected_overload",
                                 "rejected_draining", "expired",
                                 "cancelled"}
    eng = DecodeEngine(tiny, max_slots=2, max_len=32, block_size=8,
                       prefill_chunk=8, max_queue=2)
    try:
        good = eng.submit([1, 2, 3], max_new_tokens=2)
        q = eng.submit([4, 5, 6], max_new_tokens=2)
        over = eng.submit([7, 8, 9], max_new_tokens=2)
        assert over.status == "rejected_overload"
        assert over.finished, "rejected_overload must read finished " \
                              "(poller-spin regression)"
        bad = eng.submit([], max_new_tokens=2)
        assert bad.status == "failed" and bad.finished
        eng.run()
        assert good.finished and q.finished
        for status in TERMINAL_STATUSES:
            r = Request([1], max_new_tokens=1)
            r.status = status
            assert r.finished, status
        r = Request([1], max_new_tokens=1)
        for status in ("queued", "prefilling", "running"):
            r.status = status
            assert not r.finished, status
    finally:
        eng.close()


# ------------------------------------------------------------- deadlines


def test_deadline_precedence_unit():
    """ttft bounds submit->first-token and stops applying once one is
    out; total applies always; total reports first when both blow."""
    r = Request([1, 2], max_new_tokens=4, ttft_deadline_s=1.0,
                deadline_s=5.0)
    t0 = r.t_submit
    assert r.deadline_exceeded(t0 + 0.5) is None
    assert r.deadline_exceeded(t0 + 2.0) == "ttft"
    r.t_first_token = t0 + 0.5             # first token out: ttft retires
    assert r.deadline_exceeded(t0 + 2.0) is None
    assert r.deadline_exceeded(t0 + 6.0) == "total"
    r2 = Request([1], max_new_tokens=1, ttft_deadline_s=1.0, deadline_s=2.0)
    assert r2.deadline_exceeded(r2.t_submit + 3.0) == "total"
    with pytest.raises(ValueError, match="deadline_s"):
        Request([1], max_new_tokens=1, deadline_s=-1.0)
    with pytest.raises(ValueError, match="ttft_deadline_s"):
        Request([1], max_new_tokens=1, ttft_deadline_s=-0.5)


def test_expiry_in_queue_and_mid_decode(engine, tiny):
    """A queued request with an already-blown deadline expires at the next
    step boundary without ever taking a slot; a decoding request expires
    mid-stream with slot + blocks released exactly once (invariants), and
    the surviving tenant's greedy output is untouched."""
    rng = np.random.RandomState(10)
    survivor_p = rng.randint(1, 64, 5).tolist()
    survivor = engine.submit(survivor_p, max_new_tokens=10)
    doomed_q = engine.submit(rng.randint(1, 64, 4).tolist(),
                             max_new_tokens=4, deadline_s=0.0)
    fin = engine.step()
    engine._pager.check_invariants()
    assert doomed_q in fin
    assert doomed_q.status == "expired" and doomed_q.finished
    assert "queue" in doomed_q.error and doomed_q.slot is None
    assert not doomed_q.tokens
    # mid-decode expiry via the injectable clock (no sleeps)
    doomed_d = engine.submit(rng.randint(1, 64, 4).tolist(),
                             max_new_tokens=30, deadline_s=120.0)
    while doomed_d.status != "running":
        engine.step()
    free_before = engine._pager.free_blocks + engine._pager.lru_blocks
    real = engine._clock
    try:
        engine._clock = lambda: time.time() + 600.0
        fin = engine.step()
    finally:
        engine._clock = real
    engine._pager.check_invariants()
    assert doomed_d in fin and doomed_d.status == "expired"
    assert "mid-decode" in doomed_d.error
    assert len(doomed_d.tokens) >= 1          # it was decoding for real
    # its blocks came back (freed or parked — released exactly once)
    assert engine._pager.free_blocks + engine._pager.lru_blocks \
        > free_before
    engine.run()
    assert survivor.status == "done"
    np.testing.assert_array_equal(_eager(tiny, survivor_p, 10),
                                  survivor.output_tokens)


def test_ttft_expiry_mid_chunked_prefill(engine):
    """A ttft deadline blowing BETWEEN prefill chunks expires the request
    with its partial (unregistered) blocks freed and any adopted shared
    blocks decref'd — invariants clean, engine keeps serving."""
    rng = np.random.RandomState(11)
    req = engine.submit(rng.randint(1, 64, 20).tolist(), max_new_tokens=4,
                        ttft_deadline_s=300.0)
    engine.step()                              # chunk 1 of 3
    assert req.status == "prefilling"
    real = engine._clock
    try:
        engine._clock = lambda: time.time() + 600.0
        fin = engine.step()
    finally:
        engine._clock = real
    engine._pager.check_invariants()
    assert req in fin and req.status == "expired"
    assert "mid-prefill" in req.error and req.finished
    assert engine.live_count == 0 and not engine._prefilling
    probe = engine.submit([5, 6, 7], max_new_tokens=2)
    engine.run()
    assert probe.status == "done"


def test_triple_point_preempt_requeue_expire(tiny):
    """The deadline x preemption x chunked-prefill triple point: a
    follower sharing the leader's prefix is preempted mid-prefill by pool
    pressure (deterministic — the pool is sized to force it), requeued,
    and its deadline expires while it waits. Its blocks must release
    exactly once (invariants after EVERY step), the shared prefix must
    keep serving the leader, and the leader's greedy output must equal
    the eager loop."""
    eng = DecodeEngine(tiny, max_slots=4, max_len=48, block_size=8,
                       kv_blocks=9, prefill_chunk=8)   # 8 usable blocks
    try:
        rng = np.random.RandomState(12)
        prefix = rng.randint(1, 64, 8).tolist()
        lead_p = prefix + rng.randint(1, 64, 4).tolist()
        lead = eng.submit(lead_p, max_new_tokens=24)
        while lead.status != "running":
            eng.step()
            eng._pager.check_invariants()
        # follower: adopts the registered prefix block, then its own
        # prefill + the leader's decode growth exhaust the 6-block pool —
        # the follower (youngest) is preempted back to the queue
        follower = eng.submit(prefix + rng.randint(1, 64, 12).tolist(),
                              max_new_tokens=24, deadline_s=900.0)
        steps = 0
        while follower.preemptions == 0:
            eng.step()
            eng._pager.check_invariants()
            steps += 1
            assert steps < 200, "pool never forced a preemption"
        assert follower.status == "queued"     # requeued, blocks released
        # deadline expires WHILE requeued: fast-forward the clock
        real = eng._clock
        try:
            eng._clock = lambda: time.time() + 3600.0
            # the sweep must also not re-admit it first: expiry runs
            # before admission in step()
            fin = eng.step()
        finally:
            eng._clock = real
        eng._pager.check_invariants()
        assert follower in fin and follower.status == "expired"
        assert follower.preemptions >= 1
        assert "queue" in follower.error
        eng.run()
        eng._pager.check_invariants()
        assert lead.status == "done"
        np.testing.assert_array_equal(_eager(tiny, lead_p, 24),
                                      lead.output_tokens)
        # every block accounted for: free + parked == usable, refs zero
        pg = eng._pager
        assert pg.free_blocks + pg.lru_blocks == pg.usable_blocks
        assert (pg._ref == 0).all()
    finally:
        eng.close()


# ------------------------------------------------------------ cancellation


def test_cancel_queue_prefill_decode(engine):
    """cancel() from all three states — by Request and by id — releases
    exactly once and never disturbs co-tenants."""
    rng = np.random.RandomState(13)
    keeper = engine.submit(rng.randint(1, 64, 4).tolist(),
                           max_new_tokens=12)
    while keeper.status != "running":
        engine.step()
    # (a) queued: three tenants fill the other slots first
    fillers = [engine.submit(rng.randint(1, 64, 4).tolist(),
                             max_new_tokens=8) for _ in range(3)]
    queued = engine.submit(rng.randint(1, 64, 4).tolist(), max_new_tokens=8)
    assert engine.cancel(queued) is True
    assert queued.status == "cancelled" and queued.finished
    assert "queued" in queued.error
    # (b) mid-prefill: a 20-token prompt takes 3 chunks; cancel after one
    fin = engine.run()
    assert queued in fin                       # buffered terminal returned
    mid = engine.submit(rng.randint(1, 64, 20).tolist(), max_new_tokens=8)
    engine.step()
    assert mid.status == "prefilling"
    assert engine.cancel(mid.id) is True       # by id
    engine._pager.check_invariants()
    assert mid.status == "cancelled" and "prefill" in mid.error
    # (c) mid-decode
    dec = engine.submit(rng.randint(1, 64, 4).tolist(), max_new_tokens=30)
    while dec.status != "running":
        engine.step()
    assert engine.cancel(dec.id) is True
    engine._pager.check_invariants()
    assert dec.status == "cancelled" and "decode" in dec.error
    assert len(dec.tokens) >= 1
    # double-cancel and unknown ids are polite no-ops
    assert engine.cancel(dec) is False
    assert engine.cancel(999999) is False
    engine.run()
    assert keeper.status == "done" and all(f.status == "done"
                                           for f in fillers)
    assert engine.live_count == 0 and engine.queue_depth == 0


# ------------------------------------------------------------------ drain


def test_drain_door_grace_and_completion(tiny):
    """begin_drain closes the door (rejected_draining), bounces the
    queue, lets live slots run — and grace exhaustion expires the
    stragglers. The drain reports exactly once."""
    eng = DecodeEngine(tiny, max_slots=2, max_len=48, block_size=8,
                       prefill_chunk=8)
    try:
        rng = np.random.RandomState(14)
        fast = eng.submit(rng.randint(1, 64, 4).tolist(), max_new_tokens=3)
        slow = eng.submit(rng.randint(1, 64, 4).tolist(), max_new_tokens=40)
        while eng.live_count < 2:
            eng.step()
        queued = eng.submit(rng.randint(1, 64, 4).tolist(),
                            max_new_tokens=4)
        eng.begin_drain(grace_s=900.0)
        assert eng.draining and not eng.drained
        late = eng.submit(rng.randint(1, 64, 4).tolist(), max_new_tokens=4)
        assert late.status == "rejected_draining" and late.finished
        assert "draining" in late.error
        fin = eng.step()
        assert queued in fin and queued.status == "rejected_draining"
        # fast finishes inside grace; slow gets expired when grace blows
        while fast.status != "done":
            eng.step()
        assert slow.status == "running"
        real = eng._clock
        try:
            eng._clock = lambda: time.time() + 3600.0
            fin = eng.step()
        finally:
            eng._clock = real
        eng._pager.check_invariants()
        assert slow in fin and slow.status == "expired"
        assert "drain grace" in slow.error
        assert eng.drained and eng.drains == 1
        assert eng.step() == []                # idempotent, reports once
        assert eng.drains == 1
    finally:
        eng.close()


def test_drain_method_blocks_until_empty(tiny):
    """drain(grace_s=None): live requests simply finish; the caller gets
    every terminal transition back."""
    eng = DecodeEngine(tiny, max_slots=2, max_len=32, block_size=8,
                       prefill_chunk=8)
    try:
        a = eng.submit([1, 2, 3], max_new_tokens=3)
        b = eng.submit([4, 5, 6], max_new_tokens=5)
        while eng.live_count == 0:
            eng.step()
        out = eng.drain()
        assert eng.drained
        assert a in out and b in out
        assert a.status == "done" and b.status == "done"
    finally:
        eng.close()


# ------------------------------------------- watchdog + chaos seam (tentpole)


def test_fault_schedule_parsing():
    fs = FaultSchedule.parse("slow@decode:3:0.2, raise@admit:1,"
                             "raise@alloc:5")
    assert len(fs.entries) == 3
    assert fs.entries[0] == ("slow", "decode", 3, 0.2)
    assert fs.entries[1][3] > 0                # default slow arg
    for bad in ("explode@decode:1", "raise@gpu:1", "raise@decode:0",
                "raise@decode", "slow@chunk:x"):
        with pytest.raises(ValueError):
            FaultSchedule.parse(bad)
    # fire(): slow sleeps in place, raise raises at exactly the Nth call
    fs = FaultSchedule.parse("raise@admit:2")
    fs.fire("admit")
    with pytest.raises(InjectedFault):
        fs.fire("admit")
    fs.fire("admit")                           # 3rd call: clean again
    assert fs.fired("admit") == 3


def test_injected_admission_fault_fails_one_request(tiny):
    """raise@admit fails exactly the head-of-line request, cleanly — the
    live batch never notices."""
    eng = DecodeEngine(tiny, max_slots=2, max_len=32, block_size=8,
                       prefill_chunk=8,
                       fault_schedule=FaultSchedule.parse("raise@admit:2"))
    try:
        a = eng.submit([1, 2, 3], max_new_tokens=4)
        b = eng.submit([4, 5, 6], max_new_tokens=4)
        fin = eng.run()
        eng._pager.check_invariants()
        assert a.status == "done"
        assert b.status == "failed" and "injected admit fault" in b.error
        assert b in fin
    finally:
        eng.close()


def test_watchdog_hang_warn_dump_and_loud_failure(tiny, tmp_path):
    """slow@decode inside the armed window: the watchdog WARNs (naming
    the executable), bumps serve/hang_warns, flight-dumps — all WHILE the
    dispatch is stuck — then the engine fails loudly with every in-flight
    request terminal and state consistent."""
    path = str(tmp_path / "hang.jsonl")
    monitor.enable(path)
    eng = DecodeEngine(
        tiny, max_slots=2, max_len=32, block_size=8, prefill_chunk=8,
        hang_s=0.05,
        fault_schedule=FaultSchedule.parse("slow@decode:1:0.5"))
    try:
        req = eng.submit([1, 2, 3], max_new_tokens=4)
        with pytest.warns(RuntimeWarning, match="dispatch hang"):
            with pytest.raises(EngineHangError, match="decode dispatch"):
                eng.run()
        eng._pager.check_invariants()
        assert req.status == "failed" and req.finished
        assert "engine failed" in req.error
        assert eng.live_count == 0 and not eng._prefilling
        snap = monitor.snapshot()
        assert snap["counters"]["serve/hang_warns"] == 1
        # the flight dump landed next to the sink while the hang was live
        assert os.path.exists(str(tmp_path / "hang.flight.json"))
        # the engine is usable again after the failure (fresh state)
        ok = eng.submit([7, 8, 9], max_new_tokens=2)
        fin = eng.run()
        assert ok.status == "done" and req in fin  # buffered terminal
        monitor.get().flush()
        recs = [json.loads(l) for l in open(path)]
        hang = [r for r in recs if r.get("kind") == "serve_hang"]
        assert len(hang) == 1
        assert hang[0]["path"] == "decode"
        assert hang[0]["elapsed_s"] >= 0.05
    finally:
        eng.close()
        monitor.disable()


def test_hang_then_raise_does_not_poison_next_dispatch(tiny):
    """slow+raise at the same decode call (a hang that then errors): the
    raise is the failure that propagates, and the latched hang verdict
    must NOT leak into the reused engine's next healthy dispatch."""
    eng = DecodeEngine(
        tiny, max_slots=2, max_len=32, block_size=8, prefill_chunk=8,
        hang_s=0.05,
        fault_schedule=FaultSchedule.parse(
            "slow@decode:1:0.3,raise@decode:1"))
    try:
        doomed = eng.submit([1, 2, 3], max_new_tokens=4)
        with pytest.warns(RuntimeWarning, match="dispatch hang"):
            with pytest.raises(InjectedFault):
                eng.run()
        assert doomed.status == "failed"
        # next dispatch is healthy: no stale EngineHangError (a latched
        # verdict raises whatever the limit; the decode executable's FIRST
        # run is this one, 12 ms alone and more beside five other workers,
        # which is no hang)
        eng._watchdog.hang_s = 30.0
        ok = eng.submit([4, 5, 6], max_new_tokens=3)
        eng.run()
        assert ok.status == "done"
        eng._pager.check_invariants()
    finally:
        eng.close()


def test_watchdog_names_the_call_of_the_step_that_hung(tiny):
    """A step launches its chunk and its decode back to back and waits
    once. A chunk launch that hangs keeps its verdict while the window
    moves on to the decode behind it: the failure names the chunk."""
    eng = DecodeEngine(
        tiny, max_slots=2, max_len=32, block_size=8, prefill_chunk=8,
        hang_s=0.05,
        fault_schedule=FaultSchedule.parse("slow@chunk:1:0.4"))
    try:
        req = eng.submit([1, 2, 3], max_new_tokens=4)
        with pytest.warns(RuntimeWarning, match="chunk executable"):
            with pytest.raises(EngineHangError, match="chunk dispatch"):
                eng.run()
        eng._pager.check_invariants()
        assert req.status == "failed" and eng.live_count == 0
        assert eng._plan is None and not eng._prefilling
        ok = eng.submit([7, 8, 9], max_new_tokens=3)
        eng.run()
        assert ok.status == "done"
    finally:
        eng.close()


def test_watchdog_window_is_open_until_the_step_has_collected(tiny,
                                                              monkeypatch):
    """The launches return at once; a device that never answers shows in
    the step's one wait. The window is still armed there, on the last
    call launched."""
    import jax
    eng = DecodeEngine(tiny, max_slots=2, max_len=32, block_size=8,
                       prefill_chunk=8, hang_s=0.05)
    try:
        # each executable's first run takes 30 ms alone and more beside
        # five other workers: that is no hang
        eng._watchdog.hang_s = 30.0
        warm = eng.submit([1, 2, 3], max_new_tokens=2)
        eng.run()
        assert warm.status == "done"
        eng._watchdog.hang_s = 0.05
        real = jax.block_until_ready     # the wait of engine/wait

        def stuck(tree):
            time.sleep(0.4)
            return real(tree)

        req = eng.submit([4, 5, 6], max_new_tokens=4)
        monkeypatch.setattr(jax, "block_until_ready", stuck)
        with pytest.warns(RuntimeWarning, match="decode executable"):
            with pytest.raises(EngineHangError, match="decode dispatch"):
                eng.run()
        monkeypatch.setattr(jax, "block_until_ready", real)
        eng._pager.check_invariants()
        assert req.status == "failed" and eng.live_count == 0
        ok = eng.submit([7, 8, 9], max_new_tokens=3)
        eng.run()
        assert ok.status == "done"
    finally:
        eng.close()


def test_chaos_gate_mixed_schedule(tiny, monkeypatch):
    """THE tier-1 chaos gate: a scripted PADDLE_SERVE_FAULT schedule (env
    path) over a pressure-sized pool, mixing expiry + cancel + injected
    alloc/admit faults + preemption + drain. The engine must complete
    without wedging, every request must end terminal, invariants must
    hold after EVERY step, and the steady state must stay at zero
    recompiles even under fault."""
    monkeypatch.setenv("PADDLE_SERVE_FAULT",
                       "raise@alloc:25,raise@alloc:31,raise@admit:6,"
                       "slow@chunk:4:0.005,slow@decode:7:0.005")
    eng = DecodeEngine(tiny, max_slots=4, max_len=48, block_size=8,
                       kv_blocks=9, prefill_chunk=8)
    try:
        assert eng._faults is not None         # env seam engaged
        warm = eng.submit([1, 2, 3], max_new_tokens=2)
        eng.run()
        assert warm.status == "done"
        base = eng.compile_count
        rng = np.random.RandomState(15)
        prefix = rng.randint(1, 64, 8).tolist()
        reqs = []
        for i in range(8):
            p = prefix + rng.randint(1, 64, int(rng.randint(2, 12))).tolist()
            kw = {}
            if i in (2, 5):
                kw["deadline_s"] = 0.0         # guaranteed queue expiry
            reqs.append(eng.submit(p, max_new_tokens=int(rng.randint(4, 16)),
                                   **kw))
        steps = 0
        while not all(r.finished for r in reqs):
            if steps == 2:
                assert eng.cancel(reqs[3]) is True
            if steps == 6:
                eng.begin_drain(grace_s=600.0)
            eng.step()
            eng._pager.check_invariants()
            steps += 1
            assert steps < 400, "chaos schedule wedged the engine"
        if not eng.draining:       # everything terminal before step 6
            eng.begin_drain(grace_s=600.0)
            eng.step()
        assert eng.drained
        statuses = {r.status for r in reqs}
        assert statuses <= TERMINAL_STATUSES
        assert "expired" in statuses           # the deadline path fired
        assert "cancelled" in statuses         # the cancel path fired
        assert eng.expired >= 2 and eng.cancelled == 1
        # faults + tight pool forced real preemption churn
        assert eng.preemptions >= 1
        assert eng.compile_count == base, \
            "chaos (host-side faults) must never mint executables"
        pg = eng._pager
        assert pg.free_blocks + pg.lru_blocks == pg.usable_blocks
        assert (pg._ref == 0).all()
    finally:
        eng.close()


# -------------------------------------------------------------- telemetry


def test_monitor_guardrail_counters(tiny, tmp_path):
    """serve/{expired,cancelled,drained,rejected_draining} reach the
    registry and the sink carries the per-event records."""
    path = str(tmp_path / "guard.jsonl")
    monitor.enable(path)
    eng = DecodeEngine(tiny, max_slots=2, max_len=32, block_size=8,
                       prefill_chunk=8)
    try:
        live = eng.submit([1, 2, 3], max_new_tokens=6)
        gone = eng.submit([4, 5, 6], max_new_tokens=6, deadline_s=0.0)
        vict = eng.submit([7, 8, 9], max_new_tokens=6)
        eng.step()
        assert gone.status == "expired"
        eng.cancel(vict)
        eng.drain(grace_s=60.0)
        assert live.status == "done"
        snap = monitor.snapshot()
        c = snap["counters"]
        assert c["serve/expired"] == 1
        assert c["serve/cancelled"] == 1
        assert c["serve/drained"] == 1
        monitor.get().flush()
        kinds = [json.loads(l).get("kind") for l in open(path)]
        for k in ("serve_expire", "serve_cancel", "serve_drain_begin",
                  "serve_drain_end"):
            assert k in kinds, k
    finally:
        eng.close()
        monitor.disable()


def test_trace_phases_for_guardrail_terminals(tiny, tmp_path):
    """Request traces end with the guardrail terminal status and a
    gap-free phase chain: an expired/cancelled request's open phase is
    closed at the same instant the trace ends (the TTFT-decomposition
    invariant survives the new exits)."""
    from paddle_tpu.monitor import trace
    t = trace.enable(str(tmp_path / "t.jsonl"), sample=1.0)
    eng = DecodeEngine(tiny, max_slots=2, max_len=32, block_size=8,
                       prefill_chunk=8)
    try:
        gone = eng.submit([1, 2, 3], max_new_tokens=4, deadline_s=0.0)
        vict = eng.submit([4, 5, 6], max_new_tokens=20)
        eng.step()
        assert gone.status == "expired"
        eng.cancel(vict)
        eng.run()
        t.flush()
        recs = [json.loads(l) for l in open(t.path)]
    finally:
        eng.close()
        trace.disable()
    ends = {r["attrs"]["request"]: r for r in recs
            if r.get("kind") == "trace" and r.get("attrs", {}).get("status")
            in ("expired", "cancelled")}
    assert ends[gone.id]["attrs"]["status"] == "expired"
    assert ends[vict.id]["attrs"]["status"] == "cancelled"
    # phase spans of the cancelled request: every boundary is shared
    # (gap-free) and none is left open past the trace end
    spans = [r for r in recs if r.get("kind") == "span"
             and r["trace"] == ends[vict.id]["trace"] and r["span"] != 0]
    assert spans, "cancelled request lost its phase spans"
    for sp in spans:
        assert sp["dur_s"] >= 0
    root = next(r for r in recs if r.get("kind") == "span"
                and r["trace"] == ends[vict.id]["trace"] and r["span"] == 0)
    last_end = max(sp["ts"] + sp["dur_s"] for sp in spans)
    assert last_end <= root["ts"] + root["dur_s"] + 1e-6


def _load_metrics_summary():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "metrics_summary", os.path.join(REPO, "tools", "metrics_summary.py"))
    ms = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ms)
    return ms


def test_summary_guardrails_block_and_pool_thrash_warn(tmp_path):
    """metrics_summary renders the guardrails sub-block from the terminal
    counters and WARNs on the pool-thrash signature — expirations whose
    requests had been preempted first. Expiries WITHOUT preemption stay
    quiet."""
    ms = _load_metrics_summary()
    eng_rec = {"kind": "serve_engine", "ts": 0.5, "max_slots": 2,
               "max_len": 16, "prefill_buckets": [8], "quantize": None,
               "engine": 0, "kv_blocks": 9, "block_size": 8,
               "prefill_chunk": 8}

    def sink(name, preemptions):
        ctr = {"kind": "counters", "ts": 5.0, "metrics": {
            "counters": {"serve/requests": 6, "serve/completions": 3,
                         "serve/expired": 2, "serve/cancelled": 1,
                         "serve/drained": 1, "serve/preemptions": 3},
            "gauges": {}, "histograms": {}}}
        recs = [eng_rec, ctr] + [
            {"kind": "serve_expire", "ts": 2.0 + i, "where": "queue",
             "preemptions": p, "tokens": 0}
            for i, p in enumerate(preemptions)]
        p2 = tmp_path / name
        p2.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
        return str(p2)

    healthy = sink("clean.jsonl", [0, 0])      # expiries, never preempted
    out = io.StringIO()
    assert ms.summarize([healthy], out=out) == 0
    text = out.getvalue()
    assert "guardrails: expired 2  cancelled 1  drains 1" in text
    assert "pool-thrash" not in text

    thrash = sink("thrash.jsonl", [0, 2])      # one expiry post-preemption
    out = io.StringIO()
    assert ms.summarize([thrash], out=out) == 0
    text = out.getvalue()
    assert "WARNING" in text and "pool-thrash" in text
    assert "raise kv_blocks or lower deadlines" in text
