"""Span-tracer tests (ISSUE 12): request/step-scoped causal telemetry.

The contract under test:
  * A paged-serving run with tracing ON reconstructs each request's TTFT
    from its phase spans (queue + prefill chunks, across preemption/requeue
    episodes) within 5% of the emitted serve/ttft_s observation — the
    acceptance gate.
  * serve/queue_wait_s can never go negative and AGREES with the trace's
    queue phase (the engine.py queue-wait audit).
  * Zero steady-state recompiles with the tracer enabled, serving AND
    train step: span instrumentation is host-side data, never a traced
    value.
  * Head sampling is deterministic (PADDLE_TRACE_SAMPLE credit
    accumulator) and WARNs escalate the implicated trace past it.
  * Trace ids land in monitor WARN events, flight dumps and fleet blobs.
  * tools/trace_view.py and tools/fleet_prom.py smoke (the
    metrics_summary pattern); fleet_top --window renders deltas.
  * Gated microbench (PADDLE_MONITOR_BENCH=1): tracer-disabled throughput
    within noise of enabled; sampled-on overhead bounded.
"""
import io
import json
import os
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu import nn
from paddle_tpu.monitor import trace
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import DecodeEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# what the span layer's two listeners record, whenever they fire
LISTENED = {"gc/collect", "jax/compile"}


@pytest.fixture(autouse=True)
def _clean_trace():
    yield
    trace.disable()
    if monitor.enabled():
        monitor.disable()


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


@pytest.fixture(scope="module")
def engine(tiny):
    """Small-pool chunked paged engine (9 blocks: pressure preempts) —
    executables minted once, shared by every test in this module."""
    eng = DecodeEngine(tiny, max_slots=4, max_len=48, block_size=8,
                       kv_blocks=9, prefill_chunk=8)
    eng.submit([1, 2, 3], max_new_tokens=2)   # mint chunk-8 + decode
    eng.run()
    return eng


def _spans_by_trace(path):
    out = {}
    for line in open(path):
        r = json.loads(line)
        if r.get("kind") == "span":
            out.setdefault(r["trace"], []).append(r)
    return out


# ------------------------------------------------------------- primitives


def test_span_schema_parents_and_ring(tmp_path):
    t = trace.enable(str(tmp_path / "t.jsonl"), sample=1.0)
    t_before = time.perf_counter()
    tr = t.start_trace("unit", kind="step", step=7, key=7)
    child = tr.span("phase_a")
    child.event("tick", n=1)
    child.end()
    t_b = time.perf_counter()
    tr.record("phase_b", t_b, t_b + 0.005)
    tr.end(status="ok")
    t.flush()
    recs = [json.loads(l) for l in open(t.path)]
    assert recs[0]["kind"] == "trace_meta" and recs[0]["sample"] == 1.0
    spans = [r for r in recs if r["kind"] == "span"]
    assert [s["name"] for s in spans] == ["unit", "phase_a", "phase_b"]
    root = spans[0]
    assert root["parent"] is None and root["attrs"]["step"] == 7 \
        and root["attrs"]["status"] == "ok"
    assert all(s["parent"] == root["span"] for s in spans[1:])
    assert spans[1]["events"][0]["name"] == "tick"
    assert all(s["dur_s"] >= 0 for s in spans)
    summary = [r for r in recs if r["kind"] == "trace"]
    assert summary and summary[0]["spans"] == 3
    # the same spans lie in the process-wide ring under their full names,
    # each with its parent's id and the id the whole trace shares
    got = trace.spans(t_before, time.perf_counter() + 1.0, prefix="unit/")
    assert [g.name for g in got] == ["unit/phase_a", "unit/phase_b",
                                     "unit/call"]
    root_rec = got[-1]
    assert root_rec.parent_id is None and root_rec.attrs["step"] == 7
    assert all(g.parent_id == root_rec.span_id for g in got[:-1])
    assert all(g.trace_id == 7 for g in got)
    assert all(g.t1 >= g.t0 for g in got)


def test_ring_is_bounded_and_spans_selects_the_window():
    """The flight recorder: on with nothing enabled, bounded, and
    ``spans(t0, t1)`` returns exactly the spans wholly inside."""
    assert not trace.enabled()
    with trace.span("unit/before"):
        pass
    t0 = time.perf_counter()
    with trace.span("unit/outer", n=1) as outer:
        with trace.span("unit/inner") as inner:
            pass
        trace.record("unit/timed", inner.t0, inner.t1, k="v")
    t1 = time.perf_counter()
    straddles = trace.span("unit/straddles").__enter__()
    t1 = min(t1, straddles.t0)
    straddles.__exit__(None, None, None)
    got = trace.spans(t0, t1, prefix="unit/")
    assert [g.name for g in got] == ["unit/inner", "unit/timed",
                                     "unit/outer"]
    by = {g.name: g for g in got}
    assert by["unit/outer"].attrs == {"n": 1}
    assert by["unit/outer"].parent_id is None
    assert by["unit/inner"].parent_id == by["unit/outer"].span_id
    assert by["unit/timed"].parent_id == by["unit/outer"].span_id
    assert by["unit/timed"].attrs == {"k": "v"}
    assert (by["unit/inner"].t0, by["unit/inner"].t1) == (inner.t0, inner.t1)
    assert trace.spans(t0, t1, prefix="engine/") == []
    # bounded: flooding it keeps the newest RING_CAPACITY spans
    for _ in range(trace.RING_CAPACITY + 10):
        trace.record("unit/flood", 0.0, 0.0)
    everything = trace.spans(float("-inf"), float("inf"))
    assert len(everything) == trace.RING_CAPACITY
    assert {g.name for g in everything} - LISTENED == {"unit/flood"}


def test_ring_and_parent_links_hold_under_threads():
    """The ring is the one thing threads share (the loader's producer
    records beside the training loop): every thread's spans arrive, each
    inner span under ITS thread's outer one, while a reader copies the
    ring all along."""
    import sys
    n_threads, n_each = 8, 300
    t0 = time.perf_counter()
    stop = threading.Event()
    seen_by_reader = []

    def reader():
        while not stop.is_set():
            seen_by_reader.append(len(trace.spans(t0, float("inf"),
                                                  "stress/")))

    def worker(k):
        for i in range(n_each):
            with trace.span(f"stress/outer{k}", i=i):
                with trace.span(f"stress/inner{k}", i=i):
                    pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        rd = threading.Thread(target=reader, daemon=True)
        rd.start()
        ws = [threading.Thread(target=worker, args=(k,), daemon=True)
              for k in range(n_threads)]
        for w in ws:
            w.start()
        for w in ws:
            w.join(timeout=60)
        stop.set()
        rd.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not rd.is_alive() and not any(w.is_alive() for w in ws)
    got = trace.spans(t0, time.perf_counter(), "stress/")
    assert len(got) == 2 * n_threads * n_each
    assert len({g.span_id for g in got}) == len(got)
    outer = {g.span_id: g for g in got if g.name.startswith("stress/outer")}
    for g in got:
        if g.name.startswith("stress/inner"):
            up = outer[g.parent_id]
            assert up.name == "stress/outer" + g.name[len("stress/inner"):]
            assert up.attrs["i"] == g.attrs["i"]
            assert up.t0 <= g.t0 and g.t1 <= up.t1
    assert seen_by_reader and seen_by_reader[-1] <= len(got)


def test_ring_off_makes_a_site_record_nothing():
    t0 = time.perf_counter()
    trace.ring(False)
    try:
        with trace.span("unit/dark") as sp:
            pass
        trace.record("unit/dark_timed", sp.t0, sp.t1)
    finally:
        trace.ring(True)
    with trace.span("unit/lit"):
        pass
    assert sp.t1 >= sp.t0          # the span is still timed for its caller
    assert [g.name for g in trace.spans(t0, time.perf_counter(), "unit/")] \
        == ["unit/lit"]


def test_spans_lie_in_a_plain_jax_profile(engine, tmp_path):
    """Every scoped span is also a TraceAnnotation: a profile taken with
    plain jax.profiler.start_trace round engine.step() holds the engine's
    phases in its host plane."""
    import glob

    import jax
    from jax.profiler import ProfileData
    r = engine.submit([4, 5, 6, 7], max_new_tokens=3)
    jax.profiler.start_trace(str(tmp_path / "prof"))
    try:
        engine.run(max_steps=50)
    finally:
        jax.profiler.stop_trace()
    assert r.status == "done"
    paths = glob.glob(str(tmp_path / "prof" / "**" / "*.xplane.pb"),
                      recursive=True)
    assert paths, "the profiler wrote no .xplane.pb"
    names = set()
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(e.name for e in line.events
                             if e.name.startswith("paddle/"))
    assert "paddle/engine/decode_call" in names
    assert {"paddle/engine/step", "paddle/engine/admit",
            "paddle/engine/prefill_call",
            "paddle/engine/decode_finish"} <= names


def test_head_sampling_deterministic_and_escalation(tmp_path):
    t = trace.enable(str(tmp_path / "s.jsonl"), sample=0.25)
    kept = []
    for i in range(8):
        tr = t.start_trace("r", kind="request")
        kept.append(tr.sampled)
        tr.end()
    # credit accumulator: starts at 1.0 (first trace always kept), then
    # every 4th — exact rate, no PRNG
    assert kept == [True, False, False, True, False, False, False, True]
    assert t.traces_sampled == 3
    # escalation: an unsampled trace that WARNs is force-kept, spans intact
    t2 = trace.enable(str(tmp_path / "e.jsonl"), sample=0.0)
    tr = t2.start_trace("r", kind="request")
    sp = tr.span("queue")
    assert not tr.sampled
    tr.escalate("page_reject")
    sp.end()
    tr.end()
    t2.flush()
    spans = _spans_by_trace(t2.path)
    assert tr.trace_id in spans
    assert {s["name"] for s in spans[tr.trace_id]} == {"r", "queue"}
    recs = [json.loads(l) for l in open(t2.path)]
    summ = [r for r in recs if r["kind"] == "trace"][0]
    assert summ["escalated"] == "page_reject"


def test_per_process_path_suffix(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TRAINERS_NUM", "2")
    monkeypatch.setenv("PADDLE_TRAINER_ID", "1")
    t = trace.enable(str(tmp_path / "run.trace.jsonl"))
    assert t.path.endswith("run.trace.proc1.jsonl")


# ------------------------------------------------- serving: the acceptance


def test_ttft_reconstruction_with_preemption(engine, tmp_path):
    """ACCEPTANCE: every request's TTFT decomposes into its queue +
    prefill phase spans within 5% of the emitted serve/ttft_s observation
    — including requests that survived a preemption/requeue episode (the
    9-block pool under 4x20-token prompts forces them)."""
    path = str(tmp_path / "run.jsonl")
    monitor.enable(path, trace=True)
    rng = np.random.RandomState(6)
    prompts = [rng.randint(1, 64, 20).tolist() for _ in range(4)]
    reqs = [engine.submit(p, max_new_tokens=20) for p in prompts]
    base = engine.compile_count
    engine.run(max_steps=600)
    assert all(r.status == "done" for r in reqs)
    assert engine.compile_count == base, "tracer leaked into shapes"
    assert any(r.preemptions > 0 for r in reqs), "no preemption exercised"
    t = trace.get()
    t.flush()
    spans = _spans_by_trace(t.path)
    ttft_hist = monitor.snapshot()["histograms"]["serve/ttft_s"]
    assert ttft_hist["count"] >= len(reqs)
    preempted_checked = 0
    for r in reqs:
        # the emitted serve/ttft_s observation is exactly this quantity
        ttft = r.t_first_token - r.t_submit
        all_phases = sorted(
            (s for s in spans[r._trace.trace_id]
             if s["span_kind"] == "phase"), key=lambda s: s["ts"])
        # everything up to the FINAL decode phase is pre-first-token: the
        # queue/prefill chain, plus any decode run a preemption discarded
        phases = all_phases[:-1] if all_phases[-1]["name"] == "decode" \
            else all_phases
        recon = sum(p["dur_s"] for p in phases)
        assert abs(recon - ttft) <= 0.05 * ttft, \
            f"req {r.id}: reconstructed {recon:.4f}s vs ttft {ttft:.4f}s"
        if r.preemptions:
            preempted_checked += 1
            queues = [p for p in phases if p["name"] == "queue"]
            assert len(queues) >= 2, "requeue episode lost its queue phase"
            root = [s for s in spans[r._trace.trace_id]
                    if s["parent"] is None][0]
            assert any(e["name"] == "preempt"
                       for e in root.get("events") or [])
            assert root["attrs"]["preemptions"] == r.preemptions
    assert preempted_checked >= 1
    monitor.disable()


def test_queue_wait_agrees_with_trace_and_never_negative(engine, tmp_path):
    """The audit satellite: serve/queue_wait_s observations are >= 0 and
    match the request's queue phase duration (same instants, same value)
    even when chunked prefill spans several step() iterations."""
    monitor.enable(str(tmp_path / "q.jsonl"), trace=True)
    rng = np.random.RandomState(3)
    # long prompt admits over 3 chunk iterations while a live slot decodes
    a = engine.submit(rng.randint(1, 64, 5).tolist(), max_new_tokens=10)
    b = engine.submit(rng.randint(1, 64, 20).tolist(), max_new_tokens=3)
    engine.run(max_steps=200)
    snap = monitor.snapshot()["histograms"]["serve/queue_wait_s"]
    assert snap["count"] >= 2
    assert snap["min"] >= 0.0, "queue wait went negative"
    t = trace.get()
    t.flush()
    spans = _spans_by_trace(t.path)
    for r in (a, b):
        if r.preemptions:
            continue  # requeued waits are separate observations
        q = [s for s in spans[r._trace.trace_id] if s["name"] == "queue"]
        assert len(q) == 1
        # same boundary instants feed both: agreement within clock noise
        assert q[0]["dur_s"] <= snap["max"] + 0.02
    monitor.disable()


def test_request_reject_and_overload_traces(engine, tmp_path):
    monitor.enable(str(tmp_path / "rj.jsonl"), trace=True)
    bad = engine.submit([], max_new_tokens=2)
    assert bad.status == "failed"
    t = trace.get()
    t.flush()
    spans = _spans_by_trace(t.path)
    root = [s for s in spans[bad._trace.trace_id] if s["parent"] is None][0]
    assert root["attrs"]["status"] == "failed"
    assert "empty prompt" in root["attrs"]["error"]
    monitor.disable()


def test_serving_decode_span_carries_tokens_and_cow(engine, tmp_path):
    monitor.enable(str(tmp_path / "d.jsonl"), trace=True)
    shared = list(range(2, 15))
    a = engine.submit(shared, max_new_tokens=3)
    while a.status != "running":
        engine.step()
    b = engine.submit(shared, max_new_tokens=3)   # sharing + COW on admit
    engine.run(max_steps=200)
    t = trace.get()
    t.flush()
    spans = _spans_by_trace(t.path)
    dec = [s for s in spans[a._trace.trace_id] if s["name"] == "decode"][0]
    # the decode span says once what a per-token event said 256 times at
    # most: how many tokens, and when the first and the last one came
    # (seconds since submit), i.e. it runs from the one to the other
    assert dec["attrs"]["tokens"] == 3
    first, last = dec["attrs"]["first_token_s"], dec["attrs"]["last_token_s"]
    assert 0.0 < first < last
    assert abs((last - first) - dec["dur_s"]) < 1e-4
    assert abs(first - (a.t_first_token - a.t_submit)) < 0.02
    assert not any(e["name"] == "decode_step"
                   for e in dec.get("events") or [])
    b_spans = spans[b._trace.trace_id]
    pre = [s for s in b_spans if s["name"] == "prefill"][0]
    assert pre["attrs"]["prefix_hit_tokens"] > 0
    has_cow = any(e["name"] == "cow"
                  for s in b_spans for e in s.get("events") or [])
    assert has_cow, "COW batch never landed as a span event"
    monitor.disable()


# -------------------------------------------------------- training: steps


class _MLP(nn.Layer):
    def __init__(self):
        super().__init__()
        self.l1 = nn.Linear(16, 32)
        self.l2 = nn.Linear(32, 1)

    def forward(self, x, y):
        p = self.l2(paddle.nn.functional.relu(self.l1(x)))
        return ((p - y) ** 2).mean()


def test_train_step_trace_spans_and_zero_recompile(tmp_path):
    paddle.seed(11)
    model = _MLP()
    opt = paddle.optimizer.AdamW(learning_rate=0.01,
                                 parameters=model.parameters())
    monitor.enable(str(tmp_path / "ts.jsonl"), trace=True)
    step = paddle.jit.TrainStep(model, opt)
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(8, 16).astype("float32"))
    y = paddle.to_tensor(rng.randn(8, 1).astype("float32"))
    for _ in range(4):
        float(step(x, y))
    assert step.num_compiles == 1, \
        "tracing minted executables (a span value leaked into the trace)"
    t = trace.get()
    t.flush()
    spans = _spans_by_trace(t.path)
    steps = {tid: s for tid, s in spans.items()
             if any(p["span_kind"] == "step" for p in s)}
    assert len(steps) == 4
    first = min(steps, key=lambda tid: min(p["ts"] for p in steps[tid]))
    names_first = [p["name"] for p in steps[first]]
    assert "compile" in names_first and "dispatch" in names_first
    for tid, s in steps.items():
        if tid != first:
            # (the listeners' records are children of whatever was open:
            # the second step compiles the optimizer's `dev + 1.0`)
            assert [p["name"] for p in s if p["parent"] is not None
                    and p["name"] not in LISTENED] == ["prepare", "dispatch"]
            d = [p for p in s if p["name"] == "dispatch"][0]
            assert d["attrs"]["path"] == "aot" and d["attrs"]["bucket"] == 1
    # the recompile sentinel event carries the step's trace id
    monitor.get().flush()
    recompiles = [json.loads(l) for l in open(str(tmp_path / "ts.jsonl"))
                  if '"recompile"' in l]
    assert recompiles and recompiles[0].get("trace") == first
    monitor.disable()


def test_loader_floats_adopt_into_step_trace(tmp_path):
    from paddle_tpu.io import DeviceLoader
    paddle.seed(12)
    model = _MLP()
    opt = paddle.optimizer.AdamW(learning_rate=0.01,
                                 parameters=model.parameters())
    step = paddle.jit.TrainStep(model, opt)
    rng = np.random.RandomState(1)
    batches = [(rng.randn(8, 16).astype("float32"),
                rng.randn(8, 1).astype("float32")) for _ in range(4)]
    float(step(*batches[0]))  # compile outside the traced region
    t = trace.enable(str(tmp_path / "ld.jsonl"))
    for xb, yb in DeviceLoader(batches[1:], prefetch_depth=2):
        float(step(xb, yb))
    t.flush()
    spans = _spans_by_trace(t.path)
    loader_names = {s["name"] for ss in spans.values() for s in ss
                    if s["name"].startswith("loader/")}
    assert "loader/wait" in loader_names
    assert "loader/h2d" in loader_names   # producer-thread spans adopted
    # every loader span is a CHILD of a step trace, not an orphan
    for ss in spans.values():
        root = [s for s in ss if s["parent"] is None][0]
        assert root["span_kind"] == "step"


def test_request_trace_cannot_steal_step_floats(tmp_path):
    """A serving request trace starting between training steps must NOT
    adopt the loader/ckpt floating spans addressed to the next STEP trace
    (mixed train+serve process)."""
    t = trace.enable(str(tmp_path / "mx.jsonl"))
    now = time.perf_counter()
    trace.record("loader/wait", now - 0.002, now, adopt_kind="step")
    req_tr = t.start_trace("request", kind="request", current=False)
    req_tr.end(status="done")
    step_tr = t.start_trace("train_step", kind="step")
    step_tr.end()
    t.flush()
    spans = _spans_by_trace(t.path)
    assert not any(s["name"] == "loader/wait"
                   for s in spans[req_tr.trace_id])
    assert any(s["name"] == "loader/wait"
               for s in spans[step_tr.trace_id])


def test_skip_update_event_and_escalation(tmp_path):
    from paddle_tpu.amp import GradScaler
    paddle.seed(13)
    model = _MLP()
    opt = paddle.optimizer.AdamW(learning_rate=0.01,
                                 parameters=model.parameters())
    scaler = GradScaler(init_loss_scaling=1024.0)
    step = paddle.jit.TrainStep(model, opt, grad_scaler=scaler)
    rng = np.random.RandomState(2)
    x = rng.randn(8, 16).astype("float32")
    y = rng.randn(8, 1).astype("float32")
    float(step(paddle.to_tensor(x), paddle.to_tensor(y)))   # compile
    t = trace.enable(str(tmp_path / "sk.jsonl"), sample=0.0)
    bad = x.copy()
    bad[0, 0] = np.inf                      # found-inf -> skipped update
    float(step(paddle.to_tensor(bad), paddle.to_tensor(y)))
    t.flush()
    spans = _spans_by_trace(t.path)
    # sample=0.0: only the escalated skip-update step survived
    assert len(spans) == 1
    ss = list(spans.values())[0]
    root = [s for s in ss if s["parent"] is None][0]
    assert any(e["name"] == "skip_update"
               for e in root.get("events") or [])


# ------------------------------------------------- WARN / fleet embedding


def test_fleet_warn_names_rank_trace_and_escalates(tmp_path):
    from paddle_tpu.monitor.collector import (Aggregator, LocalTransport,
                                              Publisher)
    from paddle_tpu.monitor.registry import Registry
    t = trace.enable(str(tmp_path / "fw.jsonl"), sample=0.0)
    tr_open = t.start_trace("train_step", kind="step", current=True)
    transport = LocalTransport()
    regs = [Registry(), Registry()]
    pubs = [Publisher(regs[r], transport, r) for r in (0, 1)]
    agg = Aggregator(transport, world=2,
                     fleet_path=str(tmp_path / "f.fleet.jsonl"),
                     skew_warn=1.5)
    for r, dur in ((0, 0.01), (1, 0.5)):
        for _ in range(3):
            regs[r].histogram("train_step/dispatch_s").observe(dur)
        pubs[r].publish_once()
    agg.poll_once()           # window basis
    for r, dur in ((0, 0.01), (1, 0.5)):
        for _ in range(3):
            regs[r].histogram("train_step/dispatch_s").observe(dur)
        pubs[r].publish_once()
    agg.poll_once()           # skew computed -> straggler WARN
    agg.stop(final=False)
    warns = [json.loads(l) for l in open(agg.fleet_path)
             if '"fleet_warn"' in l]
    assert warns, "straggler WARN never fired"
    w = warns[0]
    assert w["warn"] == "straggler" and w["rank"] == 1
    # the WARN names the slow RANK's trace (published in its blobs) ...
    assert w.get("trace") == t.current_trace_id()
    assert f"[trace {w['trace']}" in w["msg"]
    # ... and escalated rank 0's open trace past sample=0.0
    assert tr_open.sampled and tr_open.escalated is not None
    tr_open.end()


def test_flight_dump_embeds_trace_context(tmp_path):
    monitor.enable(str(tmp_path / "fd.jsonl"), trace=True)
    t = trace.get()
    tr = t.start_trace("train_step", kind="step")
    path = monitor.dump()
    dump = json.load(open(path))
    assert dump["trace"]["current"] == tr.trace_id
    assert tr.trace_id in dump["trace"]["open"]
    assert dump["trace"]["path"] == t.path
    tr.end()
    monitor.disable()


def test_prom_render_registry_and_fleet():
    snap = {"counters": {"train_step/steps": 4},
            "gauges": {"serve/kv_util": 0.5},
            "histograms": {"serve/ttft_s": {"count": 2, "sum": 0.4,
                                            "p50": 0.1, "p95": 0.3,
                                            "p99": 0.3}}}
    text = monitor.prom_render(snap)
    assert "# TYPE paddle_train_step_steps_total counter" in text
    assert "paddle_train_step_steps_total 4" in text
    assert "paddle_serve_kv_util 0.5" in text
    assert 'paddle_serve_ttft_s{quantile="0.95"} 0.3' in text
    assert "paddle_serve_ttft_s_count 2" in text
    fleet = {"kind": "fleet", "ranks": [0, 1], "stale": [1],
             "derived": {"fleet/step_skew": 1.25},
             "metrics": {"counters": {"train_step/steps": {
                 "sum": 7, "min": 3, "max": 4,
                 "per_rank": {"0": 3, "1": 4}}},
                 "gauges": {}, "histograms": {}}}
    text = monitor.prom_render(fleet)
    assert 'paddle_train_step_steps_total{rank="0"} 3' in text
    assert 'paddle_train_step_steps_total{rank="1"} 4' in text
    assert "paddle_fleet_step_skew 1.25" in text
    assert 'paddle_fleet_rank_stale{rank="1"} 1' in text


# ----------------------------------------------------------------- tooling


def _make_trace_file(tmp_path):
    t = trace.enable(str(tmp_path / "tv.jsonl"))
    for i in range(3):
        tr = t.start_trace("request", kind="request", request=i)
        q = tr.span("queue")
        time.sleep(0.002 * (i + 1))
        q.end()
        p = tr.span("prefill")
        p.event("chunk", p0=0, end=8)
        time.sleep(0.003)
        p.end()
        tr.end(status="done", tokens=4)
    t.flush()
    path = t.path
    trace.disable()
    return path


def test_trace_view_cli_smoke(tmp_path):
    path = _make_trace_file(tmp_path)
    cli = os.path.join(REPO, "tools", "trace_view.py")
    out = subprocess.run([sys.executable, cli, path, "--slowest", "5"],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "queue(ms)" in out.stdout and "request" in out.stdout
    out = subprocess.run([sys.executable, cli, path, "--waterfall"],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "#" in out.stdout and "prefill" in out.stdout
    out = subprocess.run([sys.executable, cli, path, "--slo", "90"],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "SLO attribution" in out.stdout and "dominated" in out.stdout
    chrome = str(tmp_path / "c.json")
    out = subprocess.run([sys.executable, cli, path, "--chrome", chrome],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    doc = json.load(open(chrome))
    assert any(e.get("ph") == "X" for e in doc["traceEvents"])
    assert any(e.get("ph") == "M" for e in doc["traceEvents"])


def _make_fleet_file(tmp_path):
    path = str(tmp_path / "pf.fleet.jsonl")
    recs = [
        {"v": 2, "kind": "fleet_meta", "ts": 1.0, "world": 2,
         "publish_s": 1.0, "job": "t"},
        {"v": 2, "kind": "fleet", "ts": 2.0, "round": 0,
         "ranks": [0, 1], "live": [0, 1], "stale": [],
         "derived": {"fleet/step_skew": 1.1},
         "metrics": {"counters": {"train_step/steps": {
             "sum": 10, "min": 5, "max": 5,
             "per_rank": {"0": 5, "1": 5}}},
             "gauges": {}, "histograms": {}}},
        {"v": 2, "kind": "fleet", "ts": 4.0, "round": 1,
         "ranks": [0, 1], "live": [0, 1], "stale": [],
         "derived": {"fleet/step_skew": 1.2},
         "metrics": {"counters": {"train_step/steps": {
             "sum": 30, "min": 15, "max": 15,
             "per_rank": {"0": 15, "1": 15}}},
             "gauges": {}, "histograms": {}}},
    ]
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    return path


def test_fleet_prom_cli_smoke(tmp_path):
    path = _make_fleet_file(tmp_path)
    cli = os.path.join(REPO, "tools", "fleet_prom.py")
    out = subprocess.run([sys.executable, cli, path],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert 'paddle_train_step_steps_total{rank="0"} 15' in out.stdout
    assert "paddle_fleet_step_skew 1.2" in out.stdout


def test_fleet_prom_one_shot_serve(tmp_path):
    path = _make_fleet_file(tmp_path)
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import fleet_prom
    finally:
        sys.path.pop(0)
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    th = threading.Thread(target=fleet_prom.serve, args=([path], port),
                          daemon=True)
    th.start()
    import urllib.request
    body = None
    for _ in range(50):
        try:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=2).read()
            break
        except OSError:
            time.sleep(0.1)
    assert body and b"paddle_train_step_steps_total" in body
    th.join(5)
    assert not th.is_alive(), "--serve default must exit after ONE scrape"


def test_fleet_top_window_renders_deltas(tmp_path):
    path = _make_fleet_file(tmp_path)
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import fleet_top
    finally:
        sys.path.pop(0)
    meta, fleets, warns = fleet_top.load_stream(path, keep=2)
    frame = fleet_top.render(meta, fleets, warns, window=1)
    assert "window=1 rounds" in frame and "Δsteps" in frame
    # cumulative 15 per rank, but the WINDOW delta is 10
    assert "        10" in frame and "        15" not in frame
    cum = fleet_top.render(meta, fleets, warns)
    assert "        15" in cum


# ------------------------------------------------------- gated microbench


def _decode_tput(engine, n):
    # keep one slot hot: a fixed short request per measurement window
    t0 = time.perf_counter()
    for _ in range(n):
        r = engine.submit([5, 6, 7], max_new_tokens=2)
        engine.run(max_steps=50)
        assert r.status == "done"
    return n / (time.perf_counter() - t0)


@pytest.mark.skipif(not os.environ.get("PADDLE_MONITOR_BENCH"),
                    reason="gated microbench: set PADDLE_MONITOR_BENCH=1")
def test_trace_overhead_microbench(engine, tmp_path):
    """Gated bench (ISSUE 12 acceptance): with the tracer DISABLED the
    serving hot path pays only `trace._active is None` checks — throughput
    within noise of (>= 0.8x) the no-tracer baseline, which IS the
    disabled path; and the sampled-on path stays bounded (>= 0.5x)."""
    _decode_tput(engine, 3)   # warm
    ratios_on = []
    ratios_off = []
    for _ in range(3):
        off = _decode_tput(engine, 10)
        trace.enable(str(tmp_path / "b.jsonl"), sample=1.0)
        on = _decode_tput(engine, 10)
        trace.disable()
        off2 = _decode_tput(engine, 10)
        ratios_off.append(max(off, off2) / max(on, 1e-9))
        ratios_on.append(on / max(off, off2))
    # disabled path can't be materially slower than enabled (it does
    # strictly less work), and enabled stays within 2x of disabled
    assert max(ratios_off) >= 0.8, f"disabled/enabled {ratios_off}"
    assert max(ratios_on) >= 0.5, f"enabled/disabled {ratios_on}"
