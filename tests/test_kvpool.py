"""Cross-process prefix-cache tier (ISSUE 20): host-RAM KV block pool.

The contract under test:
  * Pool round-trips: LocalPool (bounded LRU, generation clears) and
    KVPool over a real launch KV master (base64 envelope, generation-keyed
    entries, torn entries read as misses).
  * Cold-start adoption: a fresh engine sharing a pool with a warm one
    fetches + splices the warm engine's exported prefix blocks on its
    FIRST shared-prompt admission — before any local registration exists
    — with greedy output bitwise-equal to a no-pool control and the
    pager's invariants clean after every step.
  * Versioning: ``drop_prefix_cache`` bumps the pool generation, so a
    stale-generation entry can never splice into the new model's cache.
  * Chaos: ``raise@export`` / ``raise@adopt`` degrade to the cold path
    (skip the export / prefill the blocks), never corrupt.
  * Restart-adopt e2e (satellite): kill one engine mid-workload under the
    router; the replacement's first shared-prompt prefill adopts from the
    pool.
  * Router admission queue (satellite): every live door at capacity parks
    the request in a bounded queue instead of rejecting; deadline expiry
    and overflow still terminalize.
  * Incremental streaming (satellite): ``status(id, since=N)`` ships only
    new tokens; the router's poll reconstructs streams across resets.
  * metrics_summary: pool section renders, the allocator-bug WARN skips
    pool-tagged rejects, and the cold-start-never-adopts WARN fires.
"""
import io
import json
import os
import socket
import subprocess
import sys
import time
import types

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import (DecodeEngine, DoorServer, EngineEndpoint,
                                FaultSchedule, KVPool, LocalDirectory,
                                LocalEngineClient, LocalPool,
                                RouteFaultSchedule, Router)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NO_FAULTS = RouteFaultSchedule.parse("")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


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


def _mk_engine(model, pool=None, faults=None):
    return DecodeEngine(model, max_slots=2, max_len=48, block_size=8,
                        prefill_chunk=8, kv_pool=pool, fault_schedule=faults)


SHARED = list(np.random.RandomState(0).randint(1, 64, 16)) + [40, 50, 60]
SHARED = [int(t) for t in SHARED]        # 2 full blocks + 3-token tail


# ----------------------------------------------------------- pool round-trips


def test_localpool_roundtrip_capacity_and_generation():
    p = LocalPool(capacity=2)
    assert p.generation() == 0 and len(p) == 0
    assert p.put("a", b"xx", {"tokens": 8})
    assert p.put("b", b"yy", {"tokens": 16})
    data, meta = p.get("a")
    assert data == b"xx" and meta["tokens"] == 8
    # capacity bound: "a" was just touched (MRU), so "b" evicts
    assert p.put("c", b"zz", {})
    assert len(p) == 2 and p.get("b") is None and p.get("a") is not None
    # a generation bump clears every entry — the local analog of master
    # entries becoming unreachable under the new generation key
    assert p.bump_generation() == 1
    assert p.generation() == 1 and len(p) == 0 and p.get("a") is None
    assert p.counters["gen_bumps"] == 1 and p.counters["misses"] == 2


def test_kvpool_master_roundtrip_generation_and_torn_entry():
    from paddle_tpu.distributed.launch.master import KVClient, KVServer
    port = _free_port()
    srv = KVServer(port)
    srv.start()
    try:
        client = KVClient(f"127.0.0.1:{port}", timeout=5.0)
        pool = KVPool(client, job="t")
        assert pool.generation() == 0
        payload = np.arange(8, dtype=np.float32).tobytes()
        assert pool.put("d1", payload, {"tokens": 8, "gen": 0})
        got = pool.get("d1")
        assert got is not None and got[0] == payload \
            and got[1]["tokens"] == 8
        # a second pool over the same master sees the entry (the whole
        # point: the bytes moved through the wire, not the process)
        pool2 = KVPool(KVClient(f"127.0.0.1:{port}", timeout=5.0), job="t")
        assert pool2.get("d1")[0] == payload
        # generation bump: the same digest misses (key includes the gen)
        assert pool.bump_generation() == 1
        assert pool.get("d1") is None and pool2.generation() == 1
        # a torn/mis-encoded entry is a MISS, never a crash
        client.put("/t/kvpool/blk/1/torn", "not json {")
        assert pool.get("torn") is None
    finally:
        srv.stop()


# -------------------------------------------------------- cold-start adoption


def test_cold_engine_adopts_from_pool(tiny, tmp_path):
    """Warm engine A exports its parked prefix blocks; cold engine B's
    FIRST shared-prompt admission (empty registry) fetches + adopts them,
    decodes bitwise-identically to a no-pool control, and the second
    identical prompt is served locally with zero further fetches or
    compiles."""
    monitor.enable(str(tmp_path / "pool.jsonl"))
    try:
        shared_pool = LocalPool()
        ea = _mk_engine(tiny, pool=shared_pool)
        ra = ea.submit(SHARED, max_new_tokens=4)
        ea.run()
        assert ra.status == "done"
        assert ea.pool_stats()["exports"] == 2 and len(shared_pool) == 2
        ea._pager.check_invariants()

        eb = _mk_engine(tiny, pool=shared_pool)
        assert not eb._pager._registry     # genuinely cold
        rb = eb.submit(SHARED, max_new_tokens=4)
        eb.run()
        assert rb.status == "done"
        ps = eb.pool_stats()
        assert ps["fetch_hits"] == 2 and ps["adopted_blocks"] == 2
        assert ps["adopted_tokens"] == 16
        assert eb._pager.pool_hits == 1 and eb._pager.pool_hit_tokens == 16
        # an adoption is a prefix-cache win: the tier-independent ledgers
        # (prefix/shared hits) count it alongside the pool-specific ones
        assert eb._pager.prefix_hits == 1
        eb._pager.check_invariants()

        # parity: the control arm never saw the pool
        ec = _mk_engine(tiny)
        rc2 = ec.submit(SHARED, max_new_tokens=4)
        ec.run()
        np.testing.assert_array_equal(rc2.output_tokens, rb.output_tokens)
        np.testing.assert_array_equal(rc2.output_tokens, ra.output_tokens)

        # steady state: the second identical prompt hits the LOCAL
        # registry — no new fetch, no new executable
        compiles, fetches = eb.compile_count, ps["fetches"]
        rb2 = eb.submit(SHARED, max_new_tokens=4)
        eb.run()
        assert rb2.status == "done"
        assert eb.compile_count == compiles, "steady-state recompile"
        assert eb.pool_stats()["fetches"] == fetches, \
            "locally registered prefix must not re-fetch"
        np.testing.assert_array_equal(rb2.output_tokens, rb.output_tokens)
        eb._pager.check_invariants()
        snap = monitor.snapshot()
        assert snap["gauges"]["pool/fetch_hits"] == 2
        assert snap["gauges"]["pool/adopted_tokens"] == 16
        assert snap["gauges"]["serve/pool_hits"] == 1
    finally:
        monitor.disable()


def test_chaos_export_and_adopt_sites_degrade_cold(tiny):
    """``raise@export`` skips that block's export (the pool just stays
    colder); ``raise@adopt`` skips the splice (plain prefill) — both with
    clean invariants and parity."""
    shared_pool = LocalPool()
    ea = _mk_engine(tiny, pool=shared_pool,
                    faults=FaultSchedule.parse("raise@export:1"))
    ra = ea.submit(SHARED, max_new_tokens=4)
    ea.run()
    assert ra.status == "done"
    ps = ea.pool_stats()
    # first export chaos-killed, second landed
    assert ps["export_errors"] == 1 and ps["exports"] == 1
    assert len(shared_pool) == 1
    ea._pager.check_invariants()

    # refill the pool properly for the adopt-side chaos
    ea2 = _mk_engine(tiny, pool=shared_pool)
    ea2.submit(SHARED, max_new_tokens=4)
    ea2.run()
    assert len(shared_pool) == 2

    eb = _mk_engine(tiny, pool=shared_pool,
                    faults=FaultSchedule.parse("raise@adopt:1"))
    rb = eb.submit(SHARED, max_new_tokens=4)
    eb.run()
    assert rb.status == "done"
    assert eb.pool_stats()["adopted_blocks"] == 0
    assert eb._pager.pool_hits == 0
    eb._pager.check_invariants()
    ec = _mk_engine(tiny)
    rc2 = ec.submit(SHARED, max_new_tokens=4)
    ec.run()
    np.testing.assert_array_equal(rc2.output_tokens, rb.output_tokens)


def test_drop_prefix_cache_bumps_pool_generation(tiny):
    """A weight swap invalidates the tier: after ``drop_prefix_cache``
    the old entries are unreachable (generation mismatch), a cold engine
    at the old generation cannot adopt them, and fresh exports land under
    the new generation."""
    shared_pool = LocalPool()
    ea = _mk_engine(tiny, pool=shared_pool)
    ea.submit(SHARED, max_new_tokens=4)
    ea.run()
    assert len(shared_pool) == 2 and ea.pool_stats()["gen"] == 0
    dropped = ea.drop_prefix_cache()
    assert dropped >= 2
    assert shared_pool.generation() == 1 and ea.pool_stats()["gen"] == 1
    assert len(shared_pool) == 0, "bump must invalidate old entries"
    # the same engine re-serves and re-exports under the NEW generation
    ea.submit(SHARED, max_new_tokens=4)
    ea.run()
    assert len(shared_pool) == 2
    eb = _mk_engine(tiny, pool=shared_pool)
    assert eb.pool_stats()["gen"] == 1
    rb = eb.submit(SHARED, max_new_tokens=4)
    eb.run()
    assert rb.status == "done" and eb.pool_stats()["fetch_hits"] == 2
    eb._pager.check_invariants()


# ------------------------------------------- satellite: restart-adopt e2e


def _mk_pool_fleet(model, shared_pool, names=("eng0", "eng1"),
                   **router_kw):
    directory = LocalDirectory()
    engines, endpoints = {}, {}

    def make(name):
        eng = DecodeEngine(model, max_slots=2, max_len=48, block_size=8,
                           prefill_chunk=8, kv_blocks=24,
                           kv_pool=shared_pool)
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

    def step():
        for n, eng in list(engines.items()):
            client = router._clients.get(n)
            if client is not None and getattr(client, "dead", False):
                continue
            eng.step()
            eng._pager.check_invariants()
            endpoints[n].publish()

    return directory, engines, endpoints, router, make, step


def test_restart_adopt_under_router(tiny):
    """Kill one engine mid-workload under the router; its replacement
    (fresh pager, same host pool) serves the fleet's shared prompt by
    ADOPTING the dead engine's exported blocks on its first prefill —
    pool fetch counted before any local registration — with greedy
    parity against a local-only control and invariants after every
    step."""
    shared_pool = LocalPool()
    _, engines, endpoints, router, make, step = _mk_pool_fleet(
        tiny, shared_pool)

    # control arm: one engine, no pool, same weights
    ctrl = _mk_engine(tiny)
    rc = ctrl.submit(SHARED, max_new_tokens=4)
    ctrl.run()
    expect = [int(t) for t in rc.output_tokens]

    # phase 1: the shared prompt lands somewhere (affinity keeps it
    # there), parks, and exports to the host pool
    t1 = router.route(SHARED, max_new_tokens=4)
    router.join([t1], step=step, timeout_s=60)
    assert t1.status == "done" and t1.tokens == expect
    victim = t1.engine
    survivor = next(n for n in engines if n != victim)
    deadline = time.monotonic() + 30
    while len(shared_pool) < 2:      # export drain runs at step boundaries
        assert time.monotonic() < deadline, shared_pool.stats()
        step()

    # phase 2: kill the warm engine MID-WORKLOAD (tickets in flight)
    mid = [router.route(SHARED, max_new_tokens=6, request_id=f"mw-{i}")
           for i in range(2)]
    router._clients[victim].kill()
    router.join(mid, step=step, timeout_s=90)
    assert all(t.status == "done" for t in mid), \
        [(t.status, t.error) for t in mid]

    # phase 3: replacement under the same name, FRESH pager, same pool;
    # drain the survivor's door so placement must choose the replacement
    endpoints[victim].deregister()
    replacement = make(victim)
    router.attach(victim, LocalEngineClient(replacement))
    engines[survivor].begin_drain(grace_s=10.0)
    endpoints[survivor].publish()
    assert not replacement._pager._registry
    t2 = router.route(SHARED, max_new_tokens=4)
    router.join([t2], step=step, timeout_s=90)
    assert t2.status == "done" and t2.engine == victim
    ps = replacement.pool_stats()
    assert ps["fetch_hits"] >= 2 and ps["adopted_blocks"] >= 2, ps
    assert replacement._pager.pool_hits >= 1, \
        "replacement's first shared-prompt prefill must adopt from pool"
    assert t2.tokens == expect, "adopted blocks changed the tokens"
    replacement._pager.check_invariants()
    # the door advertises the tier so fleet_view (and fleet_top) can
    # render it
    view = router.fleet_view()
    assert view["doors"][victim]["pool_gen"] == 0
    assert view["doors"][victim]["pool_hits"] >= 1
    for eng in engines.values():
        eng.close()
    ctrl.close()


# ------------------------------------------- satellite: router admission queue


class _BouncyClient:
    """Door double that bounces submits as rejected_overload while
    ``bounce`` is set — the every-live-door-at-capacity shape."""

    def __init__(self):
        self.dead = False
        self.bounce = True
        self.requests = {}

    def submit(self, prompt, max_new_tokens, eos_token_id, request_id):
        rid = str(request_id)
        if self.bounce:
            return {"id": rid, "status": "rejected_overload",
                    "error": "admission queue full", "tokens": []}
        view = {"id": rid, "status": "queued", "error": None, "tokens": []}
        self.requests[rid] = view
        return dict(view)

    def status(self, request_id, since=None):
        v = self.requests.get(str(request_id))
        return dict(v) if v is not None else None

    def door(self):
        return {}

    def begin_drain(self, grace_s=None):
        pass

    def kill(self):
        self.dead = True


def _queue_fleet(clock, **router_kw):
    d = LocalDirectory()
    blob = lambda name: {
        "name": name, "inc": {"gen": 0, "start": 1.0, "token": "t"},
        "seq": 1, "ts": 0.0, "ttl_s": 3.0, "addr": None,
        "door": {"state": "accepting", "free_slots": 0, "queue_depth": 4,
                 "active": 2, "free_blocks": 0, "block_size": 8,
                 "prefix_keys": [], "prefix_hits": 0}}
    clients = {}
    for n in ("a", "b"):
        d.put(n, blob(n))
        clients[n] = _BouncyClient()
    router_kw.setdefault("fault_schedule", NO_FAULTS)
    router_kw.setdefault("stale_after", 1e9)
    r = Router(d, clock=clock, **router_kw)
    for n, c in clients.items():
        r.attach(n, c)
    return clients, r


def test_router_queues_when_all_doors_at_capacity(tmp_path):
    """Every live door bouncing overload parks the request in the router
    queue (route/queued counted) instead of rejecting; capacity freeing
    re-dispatches it on the next poll."""
    monitor.enable(str(tmp_path / "q.jsonl"))
    try:
        now = [1000.0]
        clients, r = _queue_fleet(lambda: now[0], max_queue=4,
                                  queue_deadline_s=30.0)
        t = r.route([1, 2, 3], max_new_tokens=4)
        assert t.status == "queued_router" and not t.finished
        assert r.counters["queued"] == 1 and r.counters["rejected"] == 0
        assert len(r._queue) == 1
        # still saturated: the ticket survives the poll, stays queued,
        # and the counter does NOT recount the re-park
        r.poll()
        assert t.status == "queued_router" and r.counters["queued"] == 1
        # capacity frees: the next poll places it
        for c in clients.values():
            c.bounce = False
        r.poll()
        assert t.engine in ("a", "b") and t.status == "queued"
        assert len(r._queue) == 0
        snap = monitor.snapshot()
        assert snap["counters"]["route/queued"] == 1
    finally:
        monitor.disable()


def test_router_queue_deadline_and_overflow():
    """A queued ticket past its deadline terminalizes as ``expired``;
    queue overflow still rejects; an EMPTY fleet rejects immediately
    (queueing cannot help a fleet that is gone)."""
    now = [1000.0]
    clients, r = _queue_fleet(lambda: now[0], max_queue=1,
                              queue_deadline_s=5.0)
    t1 = r.route([1, 2, 3], max_new_tokens=4)
    assert t1.status == "queued_router"
    # overflow: the bound is the backpressure
    t2 = r.route([4, 5, 6], max_new_tokens=4)
    assert t2.status == "rejected" and t2.finished
    assert r.counters["rejected"] == 1
    # deadline: the clock jumps past the budget, the ticket expires
    now[0] += 6.0
    r.poll()
    assert t1.status == "expired" and t1.finished
    assert "deadline" in t1.error
    assert r.counters["queue_expired"] == 1
    # fleet-gone arm: no directory entries at all -> immediate reject
    # even with queueing on
    r2 = Router(LocalDirectory(), fault_schedule=NO_FAULTS, max_queue=4)
    t3 = r2.route([1, 2, 3], max_new_tokens=4)
    assert t3.status == "rejected"


# ------------------------------------------ satellite: incremental streaming


def _fake_req(tokens, status="running"):
    return types.SimpleNamespace(id="r1", status=status, error=None,
                                 tokens=list(tokens))


def test_door_status_since_cursor(tiny):
    """``/status?since=N`` returns only tokens past the cursor, with the
    EFFECTIVE (clamped) cursor and the authoritative total."""
    eng = _mk_engine(tiny)
    door = DoorServer(eng)
    door.start()        # stop() joins serve_forever; it must be running
    try:
        door._requests["r1"] = _fake_req([10, 11, 12, 13])
        full = door._status("r1")
        assert full["tokens"] == [10, 11, 12, 13] and "since" not in full
        inc = door._status("r1", since=2)
        assert inc["tokens"] == [12, 13] and inc["since"] == 2 \
            and inc["n_tokens"] == 4
        assert door._status("r1", since=99) == dict(
            id="r1", status="running", error=None, tokens=[], since=4,
            n_tokens=4)
        # a preemption reset the stream: the cursor clamps to the new
        # (shorter) length so the client replays from there
        door._requests["r1"] = _fake_req([10])
        clamped = door._status("r1", since=3)
        assert clamped["since"] == 1 and clamped["tokens"] == []
    finally:
        door.stop()


def test_router_poll_reconstructs_incremental_stream():
    """poll() passes its cursor, appends the delta, and survives a
    server-side stream reset (clamped cursor truncates before append)."""
    d = LocalDirectory()
    d.put("a", {"name": "a", "inc": {"gen": 0, "start": 1.0, "token": "t"},
                "seq": 1, "ts": 0.0, "ttl_s": 3.0, "addr": None,
                "door": {"state": "accepting", "free_slots": 2,
                         "queue_depth": 0, "active": 0, "free_blocks": 8,
                         "block_size": 8, "prefix_keys": [],
                         "prefix_hits": 0}})
    r = Router(d, fault_schedule=NO_FAULTS, stale_after=1e9)

    class IncClient(_BouncyClient):
        def __init__(self):
            super().__init__()
            self.bounce = False
            self.since_seen = []
            self.view = {"id": "", "status": "running", "error": None,
                         "tokens": []}

        def submit(self, prompt, max_new_tokens, eos_token_id, request_id):
            self.view["id"] = str(request_id)
            return dict(self.view, tokens=[])

        def status(self, request_id, since=None):
            self.since_seen.append(since)
            toks = self.view["tokens"]
            eff = min(max(0, int(since or 0)), len(toks))
            return dict(self.view, tokens=toks[eff:], since=eff,
                        n_tokens=len(toks))

    c = IncClient()
    r.attach("a", c)
    t = r.route([1, 2, 3], max_new_tokens=8)
    c.view["tokens"] = [10, 11]
    r.poll()
    assert t.tokens == [10, 11] and c.since_seen[-1] == 0
    c.view["tokens"] = [10, 11, 12]
    r.poll()
    assert t.tokens == [10, 11, 12] and c.since_seen[-1] == 2
    # preemption reset: the engine replays from scratch; the clamped
    # cursor (1) makes the router truncate-then-append, never duplicate
    c.view["tokens"] = [10]
    r.poll()
    assert t.tokens == [10]
    c.view["tokens"] = [10, 21, 22]
    c.view["status"] = "done"
    r.poll()
    assert t.tokens == [10, 21, 22] and t.status == "done"


# --------------------------------------------- satellite: metrics_summary


def _load_metrics_summary():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "metrics_summary", os.path.join(REPO, "tools", "metrics_summary.py"))
    ms = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ms)
    return ms


def _serve_sink(tmp_path, name, gauges=None, events=()):
    eng = {"kind": "serve_engine", "ts": 0.5, "max_slots": 2,
           "max_len": 32, "prefill_buckets": [8], "quantize": None,
           "engine": 0, "kv_blocks": 9, "block_size": 8,
           "prefill_chunk": 8, "tp": 1}
    g = {"serve/kv_blocks": 9}
    g.update(gauges or {})
    metrics = {"kind": "counters", "ts": 2.0, "metrics": {
        "counters": {"serve/admissions": 4}, "gauges": g,
        "histograms": {}}}
    p = tmp_path / name
    p.write_text("\n".join(json.dumps(r)
                           for r in (eng, *events, metrics)) + "\n")
    return str(p)


def test_summary_pool_blocks_excluded_from_allocator_warn(tmp_path):
    """A free>=needed reject tagged ``pool_blocks`` adopted blocks
    mid-admission — it must NOT fire the allocator-bug WARN; the same
    record untagged must."""
    ms = _load_metrics_summary()
    rej = {"kind": "serve_page_reject", "ts": 1.0, "free_blocks": 5,
           "needed_blocks": 3}
    tagged = _serve_sink(tmp_path, "tagged.jsonl",
                         events=[dict(rej, pool_blocks=2)])
    out = io.StringIO()
    assert ms.summarize([tagged], out=out) == 0
    assert "allocator" not in out.getvalue()
    untagged = _serve_sink(tmp_path, "untagged.jsonl", events=[rej])
    out = io.StringIO()
    assert ms.summarize([untagged], out=out) == 0
    assert "WARNING" in out.getvalue() and "allocator" in out.getvalue()


def test_summary_kv_pool_section_and_cold_start_warn(tmp_path):
    """The kv pool line renders the export/fetch/adopt ledger; a pool
    others populated that never once hit across repeated fetches fires
    the cold-start-never-adopts WARN; a hitting pool stays quiet."""
    ms = _load_metrics_summary()
    buggy = _serve_sink(tmp_path, "cold.jsonl", gauges={
        "pool/gen": 0, "pool/exports": 3, "pool/fetches": 4,
        "pool/fetch_hits": 0, "pool/fetch_misses": 4,
        "pool/adopted_blocks": 0, "pool/adopted_tokens": 0,
        "pool/pending_exports": 0, "pool/export_errors": 0})
    out = io.StringIO()
    assert ms.summarize([buggy], out=out) == 0
    text = out.getvalue()
    assert "kv pool: gen 0  exports 3" in text
    assert "cold-start-never-adopts" in text
    healthy = _serve_sink(tmp_path, "warmed.jsonl", gauges={
        "pool/gen": 0, "pool/exports": 3, "pool/fetches": 4,
        "pool/fetch_hits": 2, "pool/fetch_misses": 2,
        "pool/adopted_blocks": 2, "pool/adopted_tokens": 16,
        "pool/pending_exports": 0, "pool/export_errors": 0})
    out = io.StringIO()
    assert ms.summarize([healthy], out=out) == 0
    text = out.getvalue()
    assert "adopted 2 blocks / 16 tokens" in text
    assert "WARNING" not in text


# ------------------------------------------- acceptance: two-process gate


@pytest.mark.slow
def test_two_process_pool_gate():
    """ISSUE 20 acceptance (slow lane): exporter and adopter are SEPARATE
    processes sharing only the launch KV master — the cold process's
    first shared-prompt admission adopts both full blocks (pool hits
    before any local registration), decodes bitwise-equal to its no-pool
    control, re-serves the second request with zero steady-state
    recompiles, and a chaos-killed fetch falls back to plain prefill
    with invariants clean."""
    from paddle_tpu.distributed.launch.master import KVServer
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for k in ("PADDLE_MONITOR", "PADDLE_SERVE_FAULT", "PADDLE_SERVE_MASTER",
              "PADDLE_CKPT_MASTER"):
        env.pop(k, None)
    port = _free_port()
    srv = KVServer(port)
    srv.start()
    try:
        def run(phase):
            out = subprocess.run(
                [sys.executable,
                 os.path.join(REPO, "tests", "serve_pool_worker.py"),
                 phase, f"127.0.0.1:{port}"],
                capture_output=True, text=True, timeout=300, env=env,
                cwd=REPO)
            assert out.returncode == 0, \
                f"{phase} rc={out.returncode}:\n{out.stdout}\n{out.stderr}"
            tail = [l for l in out.stdout.splitlines()
                    if l.startswith("{")]
            assert tail, out.stdout
            return json.loads(tail[-1])

        warm = run("warm")
        assert warm["pool"]["exports"] >= 2
        assert warm["invariants"] == "ok"
        cold = run("cold")
        assert cold["parity"] is True, cold
        assert cold["tokens"] == warm["tokens"]
        assert cold["pool"]["fetch_hits"] >= 2
        assert cold["pool"]["adopted_blocks"] >= 2
        assert cold["pool_hits"] >= 1
        assert cold["steady_state_recompiles"] == 0
        assert cold["refetches"] == 0
        assert cold["chaos_fallback"] == "plain_prefill"
        assert cold["invariants"] == "ok"
    finally:
        srv.stop()
