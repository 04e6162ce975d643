"""Fleet front-door tests (ISSUE 19): discovery + staleness + incarnation
ordering, cache-aware placement, retry backoff, engine failover with
idempotent requeue, rolling restarts, the PADDLE_ROUTE_FAULT chaos seam,
and the router telemetry surfaces (metrics_summary / fleet_top).

The contract under test:
  * Placement order is affinity -> least-loaded spill -> reject: a prompt
    whose first-block digest matches an advertised prefix key lands on
    that engine even when it is busier; draining/cordoned/ejected/stale
    doors never place; an all-draining fleet REJECTS (backpressure, not a
    hang).
  * Freshness is judged on the ROUTER's receive clock per blob seq (a
    stalled heartbeat goes stale even if the store answers), and
    incarnations order by (gen, start) with token tie-reject — a dead
    incarnation's late blob never resurrects it, an ejected name only
    re-enters placement under a strictly NEWER incarnation.
  * Every dispatch runs under utils/retry.py backoff (injectable sleep =
    the clock seam asserted here); injected drops back off WITHOUT
    feeding the ejection tally.
  * Failover: a killed engine is ejected after ``eject_after``
    consecutive transport failures, its tickets requeue elsewhere with
    the SAME id, and the engine-side id dedup guarantees one id never
    produces two token streams (the kill-during-decode regression).
  * rolling_restart() chains cordon/drain/restart/uncordon so a full
    fleet bounce drops zero requests.

Unit tests drive a stub directory/clients (no engine, no jax dispatch);
the integration gates use the same 2-layer/32-wide GPT + tiny paged
engines as tests/test_guardrails.py.
"""
import io
import json
import os
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import (DecodeEngine, EngineDown, EngineEndpoint,
                                InjectedRouteFault, LocalDirectory,
                                LocalEngineClient, RouteFaultSchedule,
                                Router, prefix_digest)
from paddle_tpu.serving.guardrails import ROUTE_FAULT_ENV
from paddle_tpu.utils.retry import RetryPolicy

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NO_FAULTS = RouteFaultSchedule.parse("")   # tests must ignore ambient env


def _tiny_gpt(seed=0):
    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
                    max_position_embeddings=64, hidden_dropout_prob=0.0,
                    attention_dropout_prob=0.0, use_flash_attention=False)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture(scope="module")
def tiny():
    return _tiny_gpt()


# ------------------------------------------------------- stub fleet plumbing


class StubDir:
    """Directory double: whatever blobs the test says, verbatim."""

    def __init__(self):
        self.blobs = {}

    def put(self, name, blob):
        self.blobs[name] = blob
        return True

    def delete(self, name):
        self.blobs.pop(name, None)
        return True

    def list(self):
        return {k: json.loads(json.dumps(v)) for k, v in self.blobs.items()}


def _blob(name, state="accepting", queue=0, active=0, free_slots=4,
          prefix_keys=(), block_size=8, gen=0, start=1.0, token="tok",
          seq=1, ttl_s=3.0, addr=None):
    return {"name": name,
            "inc": {"gen": gen, "start": start, "token": token},
            "seq": seq, "ts": 0.0, "ttl_s": ttl_s, "addr": addr,
            "door": {"state": state, "engine_id": 0,
                     "free_slots": free_slots, "queue_depth": queue,
                     "active": active, "free_blocks": 8,
                     "block_size": block_size,
                     "prefix_keys": list(prefix_keys), "prefix_hits": 0}}


class StubClient:
    """Engine-client double with scripted failures and mutable statuses."""

    def __init__(self):
        self.dead = False
        self.fail_next = 0         # raise OSError on the next N submits
        self.submits = []
        self.requests = {}

    def _check(self):
        if self.dead:
            raise EngineDown("stub dead")

    def submit(self, prompt, max_new_tokens, eos_token_id, request_id):
        self._check()
        if self.fail_next > 0:
            self.fail_next -= 1
            raise OSError("connection reset (scripted)")
        self.submits.append(str(request_id))
        view = {"id": str(request_id), "status": "queued", "error": None,
                "tokens": []}
        self.requests[str(request_id)] = view
        return dict(view)

    def status(self, request_id):
        self._check()
        v = self.requests.get(str(request_id))
        return dict(v) if v is not None else None

    def door(self):
        self._check()
        return {}

    def begin_drain(self, grace_s=None):
        self._check()

    def kill(self):
        self.dead = True


def _stub_fleet(blobs, clock=None, **router_kw):
    d = StubDir()
    clients = {}
    for b in blobs:
        d.put(b["name"], b)
        clients[b["name"]] = StubClient()
    router_kw.setdefault("fault_schedule", NO_FAULTS)
    r = Router(d, clock=clock or time.time, **router_kw)
    for name, c in clients.items():
        r.attach(name, c)
    return d, clients, r


# ------------------------------------------------------- chaos seam parsing


def test_route_fault_schedule_parse_and_fire():
    s = RouteFaultSchedule.parse(
        "drop@submit:2,kill@route:3,slow@status:1:0.0")
    assert s.entries == [("drop", "submit", 2, 0.0)] or len(s.entries) == 3
    # 1st submit clean, 2nd drops
    assert s.fire("submit") is None
    with pytest.raises(InjectedRouteFault):
        s.fire("submit")
    assert isinstance(InjectedRouteFault("x"), OSError), \
        "drops must be OSErrors so the retry policy covers them unconfigured"
    assert s.fire("route") is None
    assert s.fire("route") is None
    assert s.fire("route") == "kill"
    assert s.fire("status") is None     # slow: sleeps 0.0, no action value
    assert s.fired("submit") == 2 and s.fired("route") == 3


def test_route_fault_schedule_rejects_malformed():
    for bad in ("boom@submit:1", "drop@nowhere:1", "drop@submit:0",
                "drop@submit", "drop@submit:x"):
        with pytest.raises(ValueError):
            RouteFaultSchedule.parse(bad)


def test_route_fault_schedule_from_env(monkeypatch):
    monkeypatch.delenv(ROUTE_FAULT_ENV, raising=False)
    assert RouteFaultSchedule.from_env() is None
    monkeypatch.setenv(ROUTE_FAULT_ENV, "drop@route:1")
    s = RouteFaultSchedule.from_env()
    assert s is not None and s.entries == [("drop", "route", 1, 0.05)]


# ------------------------------------------------------------- placement


def test_affinity_beats_load():
    """A busier engine that advertises the prompt's first-block digest
    wins over an idle one without it — that is the cache-aware point."""
    prompt = list(range(1, 12))
    key = prefix_digest(prompt[:8])
    _, clients, r = _stub_fleet([
        _blob("busy", queue=3, active=1, free_slots=0, prefix_keys=[key]),
        _blob("idle")])
    t = r.route(prompt, max_new_tokens=4)
    assert t.engine == "busy"
    assert r.counters["affinity_hits"] == 1 and r.counters["spills"] == 0
    assert clients["busy"].submits == [t.id]


def test_spill_is_least_loaded_with_free_slot_tiebreak():
    _, _, r = _stub_fleet([
        _blob("a", queue=2, active=1),
        _blob("b", queue=0, active=1),
        _blob("c", queue=0, active=1, free_slots=9)])
    t = r.route([1, 2, 3], max_new_tokens=4)
    assert t.engine == "c"          # load tie with b, more free slots
    assert r.counters["spills"] == 1


def test_draining_doors_excluded_and_all_draining_rejects():
    _, clients, r = _stub_fleet([
        _blob("drn", state="draining"),
        _blob("ok", queue=5)])
    t = r.route([1, 2, 3], max_new_tokens=4)
    assert t.engine == "ok" and not clients["drn"].submits
    # whole fleet draining -> explicit reject, not a hang or a retry loop
    _, _, r2 = _stub_fleet([_blob("d0", state="draining"),
                            _blob("d1", state="drained")])
    t2 = r2.route([1, 2, 3], max_new_tokens=4)
    assert t2.status == "rejected" and t2.finished
    assert r2.counters["rejected"] == 1


def test_round_robin_control_arm_cycles():
    _, _, r = _stub_fleet([_blob("a"), _blob("b")], policy="round_robin")
    engines = [r.route([1, 2, 3], max_new_tokens=2).engine
               for _ in range(4)]
    assert engines == ["a", "b", "a", "b"]
    assert r.counters["affinity_hits"] == 0


def test_auto_minted_ids_unique_across_router_instances():
    """Two routers fronting the same fleet (or one restarted) must not
    mint colliding request ids: the engine-side dedup window would hand
    one router the OTHER router's completed request — stale tokens for
    the wrong prompt — instead of generating."""
    _, _, r1 = _stub_fleet([_blob("a")])
    _, _, r2 = _stub_fleet([_blob("a")])
    ids1 = {r1.route([1, 2, 3], max_new_tokens=2).id for _ in range(5)}
    ids2 = {r2.route([1, 2, 3], max_new_tokens=2).id for _ in range(5)}
    assert not ids1 & ids2


def test_cordoned_engine_never_places():
    _, clients, r = _stub_fleet([_blob("a"), _blob("b")])
    r._cordoned.add("a")
    for _ in range(3):
        assert r.route([1, 2, 3], max_new_tokens=2).engine == "b"
    assert not clients["a"].submits


# ------------------------------------- staleness + incarnation ordering


def test_stale_heartbeat_unplaceable_until_seq_moves():
    clk = [100.0]
    d, _, r = _stub_fleet([_blob("a", ttl_s=2.0)], clock=lambda: clk[0])
    assert r.route([1, 2, 3], max_new_tokens=2).engine == "a"
    # same seq, router clock past 2.5*ttl: stale -> rejected
    clk[0] += 6.0
    t = r.route([4, 5, 6], max_new_tokens=2)
    assert t.status == "rejected"
    # heartbeat resumes (seq bump): fresh again at the new rx
    d.put("a", _blob("a", ttl_s=2.0, seq=2))
    assert r.route([7, 8, 9], max_new_tokens=2).engine == "a"


def test_incarnation_supersession_and_late_blob_rejected():
    clk = [100.0]
    d, _, r = _stub_fleet([_blob("a", start=1.0, token="t1")],
                          clock=lambda: clk[0])
    r.refresh()
    assert r._seen["a"]["key"] == (0, 1.0)
    # strictly newer (gen, start) supersedes
    d.put("a", _blob("a", start=2.0, token="t2", seq=7))
    r.refresh()
    assert r._seen["a"]["key"] == (0, 2.0)
    assert r._seen["a"]["token"] == "t2"
    # the dead incarnation's late blob must NOT win the name back
    d.put("a", _blob("a", start=1.0, token="t1", seq=99))
    r.refresh()
    assert r._seen["a"]["key"] == (0, 2.0)
    # same order, different mint: also rejected
    d.put("a", _blob("a", start=2.0, token="imposter", seq=100))
    r.refresh()
    assert r._seen["a"]["token"] == "t2"
    # higher gen beats higher start (elastic restart ordering)
    d.put("a", _blob("a", gen=1, start=0.5, token="t3"))
    r.refresh()
    assert r._seen["a"]["key"] == (1, 0.5)


def test_ejected_name_readmits_only_on_newer_incarnation():
    d, _, r = _stub_fleet([_blob("a", start=1.0), _blob("b")])
    r.refresh()
    r._eject("a", "test")
    assert r.route([1, 2, 3], max_new_tokens=2).engine == "b"
    # same incarnation keeps knocking: still dead to us
    d.put("a", _blob("a", start=1.0, seq=5))
    r.refresh()
    assert "a" in r._ejected
    # a strictly newer incarnation redeems the name
    d.put("a", _blob("a", start=9.0, token="t9"))
    r.refresh()
    assert "a" not in r._ejected
    assert r._seen["a"]["key"] == (0, 9.0)


# ------------------------------------------------------- retry backoff


def test_injected_drops_backoff_without_ejection():
    """Two scripted drops then success: the recorded sleeps are EXACTLY
    the policy's jitter-free schedule, the ticket lands on the same
    engine (drops model lost packets, not sick engines), and the
    ejection/failure tallies stay untouched — the distinction the
    requeue-storm WARN patrols."""
    sleeps = []
    pol = RetryPolicy(max_attempts=4, base_delay=0.1, max_delay=10.0,
                      multiplier=2.0, jitter=0.0, retry_on=(OSError,),
                      sleep=sleeps.append)
    _, clients, r = _stub_fleet(
        [_blob("a")], retry=pol,
        fault_schedule=RouteFaultSchedule.parse(
            "drop@submit:1,drop@submit:2"))
    t = r.route([1, 2, 3], max_new_tokens=2)
    assert t.engine == "a" and t.status == "queued"
    assert t.attempts == 3
    assert sleeps == [pytest.approx(0.1), pytest.approx(0.2)]
    assert r.counters["ejections"] == 0 and not r._fail_counts
    assert clients["a"].submits == [t.id]


def test_real_transport_failure_avoids_engine_and_counts():
    """A genuine OSError from submit (not injected) marks the engine and
    the retry lands elsewhere; ``eject_after`` consecutive failures
    ejects it."""
    sleeps = []
    pol = RetryPolicy(max_attempts=4, base_delay=0.01, jitter=0.0,
                      retry_on=(OSError,), sleep=sleeps.append)
    _, clients, r = _stub_fleet([_blob("a"), _blob("b", queue=9)],
                                retry=pol, eject_after=2)
    clients["a"].fail_next = 1      # a places first (least loaded), fails
    t = r.route([1, 2, 3], max_new_tokens=2)
    assert t.engine == "b"
    assert r._fail_counts.get("a") == 1
    assert r.counters["ejections"] == 0
    clients["a"].fail_next = 1      # second consecutive failure: ejected
    t2 = r.route([4, 5, 6], max_new_tokens=2)
    assert t2.engine == "b"
    assert "a" in r._ejected and r.counters["ejections"] == 1


def test_requeue_limit_terminalizes_orbiting_ticket():
    _, clients, r = _stub_fleet([_blob("a"), _blob("b")], requeue_limit=2)
    t = r.route([1, 2, 3], max_new_tokens=2)
    name = t.engine
    for i in range(3):
        # whoever holds the ticket forgets it (restart): requeue
        clients[t.engine].requests.pop(t.id, None)
        r.poll()
        if t.finished:
            break
    assert t.status == "failed" and "requeue limit" in t.error
    assert t.requeues == 2


# ----------------------------------------- engine door + submit-id dedup


def test_door_state_lifecycle_and_submit_id_dedup(tiny):
    """One engine, two satellite contracts: the ``door_state()`` snapshot
    (accepting -> draining -> drained, advertised prefix digests) and
    ``submit(request_id=)`` idempotency — a duplicate id, live or already
    terminal, returns the existing request and decodes NOTHING."""
    eng = DecodeEngine(tiny, max_slots=2, max_len=48, block_size=8,
                       prefill_chunk=8, kv_blocks=24)
    try:
        door = eng.door_state()
        assert door["state"] == "accepting"
        assert door["free_slots"] == 2 and door["queue_depth"] == 0
        assert door["block_size"] == 8 and door["prefix_keys"] == []
        prompt = list(range(1, 13))
        a = eng.submit(prompt, max_new_tokens=3, request_id="rid-1")
        assert eng.door_state()["queue_depth"] == 1
        dup = eng.submit([9, 9, 9], max_new_tokens=7, request_id="rid-1")
        assert dup is a, "duplicate id while live must return the original"
        eng.run()
        assert a.status == "done" and len(a.output_tokens) == 3
        door = eng.door_state()
        # the registered first block is advertised as a digest, newest first
        assert prefix_digest(prompt[:8]) in door["prefix_keys"]
        assert all(isinstance(k, str) and len(k) == 16
                   for k in door["prefix_keys"])
        steps = eng.decode_steps
        late = eng.submit(prompt, max_new_tokens=3, request_id="rid-1")
        assert late is a, "duplicate id after completion: the done request"
        eng.run()
        assert eng.decode_steps == steps, \
            "a deduped resubmit must not decode anything"
        # auto-minted ids never collide with the window
        b = eng.submit([4, 5, 6], max_new_tokens=2)
        assert b is not a
        eng.run()
        eng.begin_drain(grace_s=5.0)
        assert eng.door_state()["state"] in ("draining", "drained")
        eng.drain(grace_s=5.0)
        assert eng.door_state()["state"] == "drained"
    finally:
        eng.close()


# --------------------------------------------------- integration fixtures


def _mk_fleet(model, names=("eng0", "eng1"), **router_kw):
    directory = LocalDirectory()
    engines, endpoints = {}, {}

    def make(name):
        eng = DecodeEngine(model, max_slots=2, max_len=48, block_size=8,
                           prefill_chunk=8, kv_blocks=24)
        engines[name] = eng
        endpoints[name] = EngineEndpoint(eng, name, directory, ttl_s=5.0)
        endpoints[name].publish()
        return eng

    router_kw.setdefault("fault_schedule", NO_FAULTS)
    router_kw.setdefault("stale_after", 1e9)
    router = Router(directory, **router_kw)
    for n in names:
        make(n)
        router.attach(n, LocalEngineClient(engines[n]))

    def step(check_invariants=False):
        for n, eng in list(engines.items()):
            client = router._clients.get(n)
            if client is not None and getattr(client, "dead", False):
                continue            # SIGKILL stand-in: nobody steps it
            eng.step()
            endpoints[n].publish()
            if check_invariants:
                eng._pager.check_invariants()

    return directory, engines, endpoints, router, make, step


def test_a_healthy_fleet_requeues_ejects_and_recompiles_nothing(tiny):
    """Two engines behind the router, no fault: every ticket is served
    where it was first placed, and after a warming wave the engines mint
    nothing with the router in the loop."""
    (_, engines, _, router, _, step) = _mk_fleet(tiny)
    rng = np.random.RandomState(5)
    prefix = rng.randint(1, 64, 8).tolist()

    def wave(n):
        tickets = [router.route(prefix + rng.randint(1, 64, 4).tolist(),
                                max_new_tokens=4) for _ in range(n)]
        router.join(tickets, step=step, timeout_s=60)
        assert [t.status for t in tickets] == ["done"] * n
        assert all(t.attempts == 1 and not t.requeues for t in tickets)
        return tickets

    wave(4)
    warm = {n: e.compile_count for n, e in engines.items()}
    assert {t.engine for t in wave(6)} <= set(engines)
    assert {n: e.compile_count for n, e in engines.items()} == warm
    c = router.counters
    assert c["routed"] == 10 and c["requeues"] == c["ejections"] == 0
    assert c["rejected"] == 0 and c["affinity_hits"] >= 1
    for eng in engines.values():
        eng.close()


def test_rolling_restart_drops_nothing(tiny):
    """Fleet upgrade: drain + restart every engine in turn while four
    requests are in flight — all of them terminalize done, none rejected,
    and both replicas come back under a newer incarnation."""
    (_, engines, endpoints, router, make, step) = _mk_fleet(tiny)
    restarted = []

    def restart(name):
        restarted.append(name)
        old = engines[name]
        endpoints[name].deregister()
        eng = make(name)
        router.attach(name, LocalEngineClient(eng))
        old.close()

    rng = np.random.RandomState(3)
    tickets = [router.route(rng.randint(1, 64, 6).tolist(),
                            max_new_tokens=4) for _ in range(4)]
    old_incs = {n: dict(endpoints[n].incarnation) for n in engines}
    router.rolling_restart(grace_s=30.0, restart=restart, step=step,
                           wait_s=60.0)
    router.join(tickets, step=step, timeout_s=60)
    assert [t.status for t in tickets] == ["done"] * 4
    assert all(len(t.tokens) == 4 for t in tickets)
    assert sorted(restarted) == sorted(engines)
    assert router.counters["rejected"] == 0, \
        "a rolling restart must never drop (reject) an in-flight request"
    assert sum(t.requeues for t in tickets) >= 1
    for n, ep in endpoints.items():
        assert (ep.incarnation["gen"], ep.incarnation["start"]) > \
            (old_incs[n]["gen"], old_incs[n]["start"]) or \
            ep.incarnation["token"] != old_incs[n]["token"]
    for eng in engines.values():
        eng.close()


def test_chaos_gate_scripted_route_faults(tiny, monkeypatch):
    """The tier-1 chaos gate: 2 in-process engines behind the router, a
    scripted PADDLE_ROUTE_FAULT mixing drop (backoff), slow (latency) and
    kill (engine death at the Nth status poll). Pager invariants hold
    after every step, every ticket terminalizes done with full streams,
    requeues and ejections both fired, and the surviving engine minted
    ZERO executables after its warmup."""
    monkeypatch.setenv(ROUTE_FAULT_ENV,
                       "drop@submit:2,slow@status:2:0.001,kill@status:6")
    _, engines, _, router, _, step = _mk_fleet(
        tiny, eject_after=2, fault_schedule=None)   # None -> from_env
    assert router._faults is not None and router._faults.entries
    # warm both engines (chunk + decode mints), then compile counts are
    # the zero-steady-state-recompile baseline the gate closes on
    for name, eng in engines.items():
        eng.submit([1, 2, 3], max_new_tokens=2)
        eng.run()
    warm = {n: e.compile_count for n, e in engines.items()}
    rng = np.random.RandomState(11)
    tickets = [router.route(rng.randint(1, 64, 6).tolist(),
                            max_new_tokens=6, request_id=f"cg-{i}")
               for i in range(4)]
    deadline = time.monotonic() + 120
    while not all(t.finished for t in tickets):
        assert time.monotonic() < deadline, [t.status for t in tickets]
        step(check_invariants=True)
        router.poll()
    assert [t.status for t in tickets] == ["done"] * 4
    assert all(len(t.tokens) == 6 for t in tickets)
    assert router.counters["requeues"] >= 1, "kill must force a requeue"
    assert router.counters["ejections"] >= 1, "kill must force an ejection"
    assert router._faults.fired("submit") >= 2
    assert router._faults.fired("status") >= 6
    dead = [n for n, c in router._clients.items()
            if getattr(c, "dead", False)]
    assert len(dead) == 1
    survivor = next(n for n in engines if n not in dead)
    # the tickets the kill displaced landed on the survivor with the SAME
    # ids — and THE kill-during-decode regression: a duplicate resubmit
    # of a completed id answers from the engine dedup window with the
    # identical stream, zero new decode work (exactly one completion,
    # never two)
    assert all(t.engine == survivor for t in tickets if t.requeues)
    t0 = next(t for t in tickets if t.requeues)
    steps_before = engines[survivor].decode_steps
    # straight at the CLIENT (router.route would answer from its own
    # ticket table): the engine's terminal dedup window replies done with
    # the identical tokens and nothing decodes
    view = router._clients[survivor].submit(t0.prompt, 6, None, t0.id)
    assert view["status"] == "done" and view["tokens"] == t0.tokens
    step()
    assert engines[survivor].decode_steps == steps_before
    for name, eng in engines.items():
        if name not in dead:
            assert eng.compile_count == warm[name], \
                f"{name} re-minted with the router in the loop"
        eng._pager.check_invariants()
        eng.close()


# ------------------------------------------------- telemetry surfaces


def _load_tool(name):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import importlib
        mod = importlib.import_module(name)
        return importlib.reload(mod)
    finally:
        sys.path.pop(0)


def test_metrics_summary_router_section_and_requeue_storm(tmp_path):
    """Drain-bounce three tickets between two live engines with the
    monitor on: the summary renders a router section from the route/*
    counters + events and WARNs on the storm signature (requeues
    climbing, ejections zero — nothing actually died)."""
    path = str(tmp_path / "run.jsonl")
    monitor.enable(path, flush_every=1)
    try:
        d, clients, r = _stub_fleet([_blob("e0"),
                                     _blob("e1", state="draining")])
        tickets = [r.route([i, 2, 3], max_new_tokens=2,
                           request_id=f"st-{i}") for i in range(3)]
        assert all(t.engine == "e0" for t in tickets)
        # e0 begins draining and flushes its queue; e1 reopens
        d.put("e0", _blob("e0", state="draining", seq=2))
        d.put("e1", _blob("e1", seq=2))
        for t in tickets:
            clients["e0"].requests[t.id]["status"] = "rejected_draining"
        r.poll()
        assert all(t.engine == "e1" for t in tickets)
        assert r.counters["requeues"] == 3 and r.counters["ejections"] == 0
        r.emit_state()
    finally:
        monitor.disable()
    ms = _load_tool("metrics_summary")
    buf = io.StringIO()
    assert ms.summarize([path], out=buf) == 0
    out = buf.getvalue()
    assert "== router ==" in out
    assert "requeues 3" in out and "ejections 0" in out
    assert "engine e0" in out and "engine e1" in out
    assert "requeues[drain_flush] x3" in out
    assert "WARNING" in out and "requeue-storm" in out


def test_fleet_top_router_panel(tmp_path):
    path = str(tmp_path / "route.jsonl")
    doors = {"eng0": {"state": "accepting", "queue_depth": 1, "active": 2,
                      "free_slots": 0, "free_blocks": 5, "prefix_hits": 7},
             "eng1": {"state": "ejected", "queue_depth": 0, "active": 0,
                      "free_slots": 2, "free_blocks": 8, "prefix_hits": 0}}
    recs = [
        {"kind": "route_state", "ts": 10.0, "doors": doors,
         "counters": {"routed": 6, "affinity_hits": 4, "spills": 2,
                      "requeues": 0, "ejections": 0, "rejected": 0,
                      "live_tickets": 3}},
        {"kind": "route_state", "ts": 11.0, "doors": doors,
         "counters": {"routed": 9, "affinity_hits": 6, "spills": 3,
                      "requeues": 4, "ejections": 0, "rejected": 0,
                      "live_tickets": 3}},
    ]
    with open(path, "w") as f:
        for rec in recs:
            f.write(json.dumps(rec) + "\n")
    ft = _load_tool("fleet_top")
    meta, fleets, warns, routes = ft.load_stream(path, routes=True)
    assert not fleets and len(routes) == 2
    frame = ft.render(meta, fleets, warns, now=11.0, routes=routes)
    assert "router: 2 engines" in frame
    assert "live requests 3" in frame
    assert "affinity 67%" in frame
    assert "eng0" in frame and "accepting" in frame
    assert "eng1" in frame and "ejected" in frame
    # requeues moved between records with zero ejections: the live view
    # flags the same storm signature the offline summary WARNs on
    assert "REQUEUE STORM" in frame
    # legacy 3-tuple call sites keep working
    meta3, fleets3, warns3 = ft.load_stream(path)
    assert fleets3 == [] and warns3 == []
    # CLI smoke: a router-only stream renders and exits 0
    import contextlib
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = ft.main([path, "--once"])
    assert rc == 0 and "router: 2 engines" in buf.getvalue()
