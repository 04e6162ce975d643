"""What the span layer says of a step the HOST held up (ISSUE 38): the two
halves of the engine's read-back, ``tensor/sync`` where a training loop
blocks, what the operating system's clocks say where a step starts, the
two listeners' records (``gc/collect``, ``jax/compile``), the ONE
``host/stall`` record of a step far over what its calls have been taking,
and the ring's account of what it has lost. All on the CPU; a stall is
provoked by patching the engine's wait to sleep once.
"""
import gc
import json
import time
import warnings
from collections import deque

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.core import tensor as tensor_mod
from paddle_tpu.models import GPTForCausalLM, gpt_tiny
from paddle_tpu.monitor import trace
from paddle_tpu.serving import DecodeEngine
from paddle_tpu.serving import engine as engine_mod

# every attribute a host/stall record carries (ISSUE 38, tentpole 4)
STALL_ATTRS = {"site", "wall_s", "expected_s", "excess_s", "calls", "step",
               "clocks_s", "cpu_process_s", "cpu_thread_s", "runq_wait_s",
               "nivcsw", "majflt", "pressure_s", "pressure_cpu_s",
               "pressure_io_s", "pressure_memory_s", "gc_s", "compile_s",
               "longest"}
# instants no real perf_counter reaches (the ring is the process's; other
# files' hand-made spans live round 5.0e7 and 6.0e7)
EPOCH = 7.0e7


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


def _stalls(t0):
    return trace.spans(t0, time.perf_counter(), "host/stall")


def _steps(t0):
    return trace.spans(t0, time.perf_counter(), "engine/step")


# ------------------------------------------------------------ the thresholds


@pytest.mark.parametrize("wall, expected, held", [
    (0.30, 0.005, True),       # a freeze in a 5 ms chat step
    (0.20, 0.005, False),      # forty times over, but under the excess
    (0.60, 0.330, False),      # a train step's fetch: 0.27 s over, not 2x
    (0.70, 0.330, True),
    (3.00, 2.000, False),      # a second over, not 2x: a long opening step
    (0.005, 0.005, False)])
def test_stalled_asks_for_the_factor_and_the_excess(wall, expected, held):
    assert (trace.STALL_FACTOR, trace.STALL_EXCESS_S,
            trace.STALL_MIN_SAMPLES) == (2.0, 0.25, 8)
    assert trace.stalled(wall, expected) is held


# ------------------------------------------------------- the engine's stall


def test_a_wait_that_sleeps_once_yields_one_stall_record(
        tiny, tmp_path, monkeypatch):
    # the suite's other workers can hold a tiny CPU step up by tenths of a
    # second: the excess asked for here is under the planted 0.6 s and
    # over what they do
    monkeypatch.setattr(trace, "STALL_EXCESS_S", 0.45)
    eng = DecodeEngine(tiny, max_slots=4, max_len=128, block_size=8,
                       prefill_chunk=16)
    monitor.enable(str(tmp_path / "mon.jsonl"))
    sink = trace.enable(str(tmp_path / "tr.jsonl"), sample=0.0)
    try:
        t0 = time.perf_counter()
        reqs = [eng.submit(p, max_new_tokens=90)
                for p in _prompts(3, 20, seed=4)]
        for _ in range(24):
            eng.step()                         # warmed: every kind has 8
        assert all(r.status == "running" for r in reqs)
        assert eng._call_means["decode"][0] >= trace.STALL_MIN_SAMPLES
        assert _stalls(t0) == [] and eng.stats()["stalls"] == 0
        before = list(eng._call_means["decode"])

        real, slept = jax.block_until_ready, []

        def sleeps_once(x):
            if not slept:
                a = time.perf_counter()
                time.sleep(0.6)
                slept.append(time.perf_counter() - a)
            return real(x)
        monkeypatch.setattr(engine_mod.jax, "block_until_ready", sleeps_once)
        with pytest.warns(RuntimeWarning, match="host stall: engine/step"):
            eng.step()
        monkeypatch.setattr(engine_mod.jax, "block_until_ready", real)
        stalled_step = _steps(t0)[-1]
        for _ in range(12):
            eng.step()
        (st,) = _stalls(t0)                     # one, and none after
        assert eng.stats()["stalls"] == 1
    finally:
        eng.run()
        monitor.get().flush()
        sink.flush()
        trace.disable()
        monitor.disable()
    a = st.attrs
    assert STALL_ATTRS <= set(a) and a["engine"] == eng.engine_id
    assert (st.t0, st.t1, a["step"]) == (stalled_step.t0, stalled_step.t1,
                                         stalled_step.span_id)
    assert a["site"] == "engine/wait"
    assert a["longest"][0][0] == "engine/wait" and len(a["longest"]) == 3
    assert abs(a["longest"][0][1] - slept[0]) < 0.05
    assert a["calls"] == {"decode": 1}
    assert abs(a["excess_s"] - slept[0]) < 0.1
    assert abs(a["wall_s"] - a["expected_s"] - a["excess_s"]) < 1e-5
    assert a["expected_s"] == pytest.approx(before[1], abs=1e-6)
    # the stalled step's interval did not move the mean by 0.6 s / 16
    assert eng._call_means["decode"][1] < before[1] + 0.02
    # a sleeping thread is on no run queue and burns no CPU
    assert a["gc_s"] == a["compile_s"] == 0.0
    # (the deltas run from the reading of the step BEFORE, made where it
    # began to wait, so they cover all of this one)
    assert a["wall_s"] <= a["clocks_s"] < a["wall_s"] + 0.3
    assert a["cpu_thread_s"] < 0.1
    assert a["runq_wait_s"] is None or a["runq_wait_s"] < 0.3
    # the monitor's event, and the live requests' traces kept past
    # sample=0.0 because of it
    events = [json.loads(ln) for ln in open(tmp_path / "mon.jsonl")]
    (ev,) = [e for e in events if e["kind"] == "host_stall"]
    assert ev["site"] == "engine/wait" and ev["excess_s"] == a["excess_s"]
    import io
    from test_serving import _load_metrics_summary
    shown = io.StringIO()
    _load_metrics_summary().summarize([str(tmp_path / "mon.jsonl")],
                                      out=shown)
    assert "WARNING: host stall of" in shown.getvalue()
    assert "in engine/wait" in shown.getvalue()
    kept = [json.loads(ln) for ln in open(sink.path)]
    kept = [r for r in kept if r["kind"] == "trace"]
    assert len(kept) == 3
    assert all(r["escalated"] == "stall" for r in kept)


def test_an_engines_first_steps_compile_and_seal_nothing(tiny, monkeypatch):
    # any step over 50 ms would count, were its calls known: the opening
    # steps compile for longer than that and their kinds have no history
    monkeypatch.setattr(trace, "STALL_EXCESS_S", 0.05)
    eng = DecodeEngine(tiny, max_slots=4, max_len=64, block_size=8,
                       prefill_chunk=32)
    booked, real = [], trace.book
    monkeypatch.setattr(
        trace, "book", lambda step, calls, means, **kw: booked.append(
            (step, list(calls))) or real(step, calls, means, **kw))
    t0 = time.perf_counter()
    for p in _prompts(2, 20, seed=5):
        eng.submit(p, max_new_tokens=6)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        eng.run()
    # what a step hands over: each call an equal share of the time from
    # its first launch to the end of its wait
    spans = trace.spans(t0, time.perf_counter(), "engine/")
    assert {k for _, calls in booked for k, _ in calls} \
        == {"chunk32", "decode"}
    assert max(len(calls) for _, calls in booked) >= 2
    for step, calls in booked:
        mine = [s for s in spans if s.parent_id == step.span_id]
        first = min(s.t0 for s in mine if s.name.endswith("_call"))
        (collect,) = [s for s in mine if s.name == "engine/collect"]
        assert len({took for _, took in calls}) == 1
        assert sum(took for _, took in calls) == pytest.approx(
            collect.t1 - first, abs=1e-9)
    steps = _steps(t0)
    opening = steps[:trace.STALL_MIN_SAMPLES - 1]
    assert max(s.t1 - s.t0 for s in opening) > 0.05
    compiled = trace.spans(t0, time.perf_counter(), "jax/compile")
    assert any(opening[0].t0 <= c.t0 and c.t1 <= opening[-1].t1
               for c in compiled), "no step of the opening ones compiled"
    ids = {s.span_id for s in opening}
    assert not [s for s in _stalls(t0) if s.attrs["step"] in ids]
    assert max(m[0] for m in eng._call_means.values()) >= 5


# ----------------------------------------------------- book() on hand-made spans


def _step_with_children(base, wall, children):
    """A finished scoped span [base, base + wall] with scoped children at
    the given (name, start, end) offsets, as the engine would leave."""
    with trace.span("engine/step") as step:
        for name, a, b in children:
            with trace.span(name) as kid:
                pass
            kid.t0, kid.t1 = base + a, base + b
    # rewrite what the ring holds of them to the hand-made instants
    made = {}
    for i, s in enumerate(trace._ring):
        if s[3] == step.span_id:
            made[i] = (s[0], base, base + wall) + s[3:]
        elif s[4] == step.span_id:
            name, a, b = children[[c[0] for c in children].index(s[0])]
            made[i] = (s[0], base + a, base + b) + s[3:]
    for i, s in made.items():
        trace._ring[i] = s
    step.t0, step.t1 = base, base + wall
    return step


def test_book_keeps_means_and_seals_only_what_it_has_history_for():
    means = {}
    base = EPOCH
    quiet = _step_with_children(base, 0.010, [("engine/collect", 0.0, 0.008)])
    for n in range(trace.STALL_MIN_SAMPLES - 1):
        # a second already at the seventh sample: too little history
        wall = 1.0 if n == 6 else 0.010
        step = _step_with_children(base + 10 * n, wall, [])
        assert trace.book(step, [("decode", 0.008)], means) is None
    assert means["decode"][0] == 7
    assert means["decode"][1] == pytest.approx(0.008)
    assert trace.book(quiet, [("decode", 0.008)], means) is None
    assert means["decode"][0] == 8
    # a kind never seen keeps the step from being judged at all
    slow = _step_with_children(base + 100, 0.9, [
        ("engine/decode_call", 0.0, 0.001),
        ("engine/collect", 0.002, 0.85),
        ("engine/decode_finish", 0.85, 0.9)])
    assert trace.book(slow, [("decode", 0.8), ("chunk16", 0.05)],
                      means) is None
    assert means["chunk16"] == [1, 0.05] and means["decode"][0] == 9
    mean = means["decode"][1]
    assert mean == pytest.approx(0.008 + (0.8 - 0.008) / 9)
    # judged: 0.9 s against two decodes' worth
    with pytest.warns(RuntimeWarning, match="most of it in engine/collect"):
        rec = trace.book(slow, [("decode", 0.4), ("decode", 0.4)], means,
                         engine=7)
    assert rec.name == "host/stall" and (rec.t0, rec.t1) == (slow.t0, slow.t1)
    assert rec.attrs["calls"] == {"decode": 2} and rec.attrs["engine"] == 7
    assert rec.attrs["expected_s"] == pytest.approx(2 * mean, abs=1e-6)
    assert rec.attrs["excess_s"] == pytest.approx(0.9 - 2 * mean, abs=1e-5)
    assert [n for n, _ in rec.attrs["longest"]] == [
        "engine/collect", "engine/decode_finish", "engine/decode_call"]
    assert means["decode"] == [9, mean]         # a stall moves no mean


def test_stall_names_the_leaf_and_sums_gc_and_compile_once(monkeypatch):
    base = EPOCH + 1000
    with trace.span("engine/step") as step:
        with trace.span("engine/collect") as col:
            with trace.span("engine/wait") as wait:
                pass
            with trace.span("engine/fetch") as fetch:
                pass
        with trace.span("engine/decode_finish") as fin:
            pass
    at = {step.span_id: (0.0, 2.0), col.span_id: (0.1, 1.9),
          wait.span_id: (0.1, 0.3), fetch.span_id: (0.3, 1.9),
          fin.span_id: (1.9, 2.0)}
    for i, s in enumerate(trace._ring):
        if s[3] in at:
            a, b = at[s[3]]
            trace._ring[i] = (s[0], base + a, base + b) + s[3:]
    step.t0, step.t1 = base, base + 2.0
    # another thread's span inside the interval is none of the step's
    trace.record("loader/wait", base + 0.0, base + 1.99)
    # a cache read inside its backend compile counts once; two collections
    trace.record("jax/compile", base + 0.5, base + 0.9,
                 event="backend_compile_duration")
    trace.record("jax/compile", base + 0.6, base + 0.7,
                 event="cache_retrieval_time_sec")
    trace.record("gc/collect", base + 1.0, base + 1.1, generation=2)
    trace.record("gc/collect", base + 1.2, base + 1.25, generation=0)
    with pytest.warns(RuntimeWarning, match="host stall"):
        rec = trace.stall(step, 0.02, {"decode": 1})
    a = rec.attrs
    assert STALL_ATTRS <= set(a)
    assert a["site"] == "engine/fetch"          # not its parent, the collect
    assert a["longest"] == [["engine/fetch", 1.6], ["engine/wait", 0.2],
                            ["engine/decode_finish", 0.1]]
    assert a["compile_s"] == pytest.approx(0.4)
    assert a["gc_s"] == pytest.approx(0.15)
    assert (a["wall_s"], a["expected_s"], a["excess_s"]) == (2.0, 0.02, 1.98)


def test_a_compile_or_a_collection_inside_a_leaf_leaves_it_the_site():
    # the listeners' records are children of whatever span was open: the
    # call that compiled for two seconds is still where the time went
    base = EPOCH + 1500
    with trace.span("engine/step") as step:
        with trace.span("engine/decode_call") as call:
            compiled = trace.record("jax/compile", 0.0, 0.0,
                                    event="backend_compile_duration")
        with trace.span("engine/collect") as col:
            with trace.span("engine/wait") as wait:
                pass
        with trace.span("engine/decode_finish") as fin:
            swept = trace.record("gc/collect", 0.0, 0.0, generation=2)
    assert compiled.parent_id == call.span_id
    assert swept.parent_id == fin.span_id
    at = {step.span_id: (0.0, 3.0), call.span_id: (0.0, 2.1),
          compiled.span_id: (0.05, 2.05), col.span_id: (2.1, 2.4),
          wait.span_id: (2.1, 2.4), fin.span_id: (2.4, 3.0),
          swept.span_id: (2.5, 2.9)}
    for i, s in enumerate(trace._ring):
        if s[3] in at:
            a, b = at[s[3]]
            trace._ring[i] = (s[0], base + a, base + b) + s[3:]
    step.t0, step.t1 = base, base + 3.0
    with pytest.warns(RuntimeWarning, match="most of it in "
                                            "engine/decode_call"):
        rec = trace.stall(step, 0.02, {"decode": 1})
    a = rec.attrs
    assert a["site"] == "engine/decode_call"
    assert a["longest"] == [["engine/decode_call", 2.1],
                            ["engine/decode_finish", 0.6],
                            ["engine/wait", 0.3]]
    assert a["compile_s"] == pytest.approx(2.0)
    assert a["gc_s"] == pytest.approx(0.4)


# -------------------------------------------------------------- tensor/sync


@jax.jit
def _slow(a):
    for _ in range(12):
        a = a @ a / 1000.0
    return a


def _fetch(t):
    return t.numpy()                # ONE line of a caller's that waits


def _fetch_elsewhere(t):
    return float(t.sum())           # another (through item())


def test_numpy_on_a_ready_array_adds_no_span_and_a_wait_adds_one(
        monkeypatch):
    monkeypatch.setattr(tensor_mod, "_SYNC_MEANS", {})
    x = paddle.to_tensor(np.ones((4, 4), "float32"))
    jax.block_until_ready(x.value())
    t0 = time.perf_counter()
    for _ in range(3):
        assert x.numpy().shape == (4, 4)
        assert float(x.sum()) == 16.0
    assert trace.spans(t0, time.perf_counter(), "tensor/") == []
    a = jnp.ones((1200, 1200), jnp.float32)
    _slow(a).block_until_ready()                    # compiled
    t0 = time.perf_counter()
    y = paddle.Tensor(_slow(a))
    assert not y.value().is_ready()
    out = _fetch(y)
    (sync,) = trace.spans(t0, time.perf_counter(), "tensor/")
    assert sync.name == "tensor/sync"
    assert sync.attrs == {"bytes": 1200 * 1200 * 4} and out.shape == a.shape
    # the mean is the waiting LINE's: this file's, outside tensor.py
    ((at, mean),) = tensor_mod._SYNC_MEANS.items()
    assert at == f"{__file__}:{_fetch.__code__.co_firstlineno + 1}"
    assert mean == [1, sync.t1 - sync.t0]
    _fetch(y)                                       # ready now: no second
    assert len(trace.spans(t0, time.perf_counter(), "tensor/")) == 1


def test_a_sync_far_over_its_own_waits_is_sealed_as_a_stall(monkeypatch):
    monkeypatch.setattr(trace, "STALL_EXCESS_S", 0.02)
    at = f"{__file__}:{_fetch.__code__.co_firstlineno + 1}"
    monkeypatch.setattr(tensor_mod, "_SYNC_MEANS", {at: [8, 1e-4]})
    a = jnp.ones((1200, 1200), jnp.float32)
    _slow(a).block_until_ready()
    t0 = time.perf_counter()
    trace.host_clocks()               # as train_step/call does on entry
    # a wait as long on ANOTHER line is held against that line's own
    # history, of which there is none: a loop's microsecond fetches do not
    # make a stall of the evaluation's one long one
    _fetch_elsewhere(paddle.Tensor(_slow(a)))
    assert _stalls(t0) == [] and len(tensor_mod._SYNC_MEANS) == 2
    y = paddle.Tensor(_slow(a))
    with pytest.warns(RuntimeWarning, match="host stall: tensor/sync"):
        _fetch(y)
    sync = trace.spans(t0, time.perf_counter(), "tensor/")[-1]
    (st,) = _stalls(t0)
    assert STALL_ATTRS <= set(st.attrs)
    assert st.attrs["site"] == "tensor/sync" == st.attrs["longest"][0][0]
    assert st.attrs["calls"] == {at: 1} and st.attrs["at"] == at
    assert st.attrs["step"] == sync.span_id
    assert (st.t0, st.t1) == (sync.t0, sync.t1)
    assert st.attrs["expected_s"] == 1e-4
    assert st.attrs["clocks_s"] >= st.attrs["wall_s"]
    assert tensor_mod._SYNC_MEANS[at] == [8, 1e-4]


# ------------------------------------------------- clocks and the listeners


def test_host_clocks_reads_five_counters_and_pressure_once_a_second(
        monkeypatch):
    reads = []
    real = trace._read_pressure
    monkeypatch.setattr(trace, "_read_pressure",
                        lambda: reads.append(1) or real())
    monkeypatch.setattr(trace, "_pressure", None)
    monkeypatch.setattr(trace._tls, "clocks", None, raising=False)
    first = trace.host_clocks()
    sum(i * i for i in range(200_000))              # burn some CPU
    second = trace.host_clocks()
    assert len(first) == len(second) == 5 and len(reads) == 1
    assert second[0] > first[0] and second[1] > first[1]      # CPU seconds
    for a, b in zip(first[2:], second[2:]):         # wait ns, nivcsw, majflt
        assert (a is None and b is None) or b >= a
    stamp, totals = trace._pressure
    assert len(totals) == 3
    monkeypatch.setattr(trace, "_pressure",
                        (stamp - trace.PRESSURE_EVERY_S, totals))
    third = trace.host_clocks()
    assert len(reads) == 2 and trace._pressure[0] > stamp
    # the thread's last two readings, each with its instant: a stall
    # record starts from the newest made before its step began
    (t2, c2), (t3, c3) = trace._tls.clocks
    assert (c2, c3) == (second, third) and t2 < t3


def test_with_the_ring_off_no_clock_is_read_and_no_step_is_held(
        monkeypatch):
    made = []
    real = trace._read_clocks
    monkeypatch.setattr(trace, "_read_clocks",
                        lambda: made.append(1) or real())
    means = {"decode": [8, 0.001]}
    trace.ring(False)
    try:
        assert trace.host_clocks() is None and not made
        step = _step_with_children(EPOCH + 1800, 1.0, [])
        assert trace.book(step, [("decode", 0.9)], means) is None
    finally:
        trace.ring(True)
    assert means == {"decode": [8, 0.001]}
    assert trace.spans(EPOCH + 1799, EPOCH + 1802) == []
    assert len(trace.host_clocks()) == 5 and made


class _Knot:
    pass


def test_a_long_collection_and_a_fresh_compile_leave_their_records():
    knots = []
    for _ in range(200_000):
        k = _Knot()
        k.me = k
        knots.append(k)
    gc.collect()
    del knots, k
    t0 = time.perf_counter()
    with trace.span("unit/holds_gc") as holder:
        gc.collect()
    found = [s for s in trace.spans(t0, time.perf_counter(), "gc/collect")
             if s.attrs["generation"] == 2]
    assert found and found[-1].attrs["collected"] >= 200_000
    assert found[-1].t1 - found[-1].t0 >= trace.GC_PAUSE_S
    assert found[-1].parent_id == holder.span_id
    t0 = time.perf_counter()
    salt = int(time.time() * 1e6) % 100_003         # no cache has seen it
    jax.jit(lambda v: v * salt + 1)(jnp.ones(7)).block_until_ready()
    compiled = trace.spans(t0, time.perf_counter(), "jax/compile")
    assert compiled
    assert {c.attrs["event"] for c in compiled} <= {
        "backend_compile_duration", "cache_retrieval_time_sec"}
    assert all(t0 <= c.t0 <= c.t1 for c in compiled)


# ------------------------------------------------ what the ring has lost


def test_the_ring_counts_what_it_pushed_out_and_says_where_it_begins(
        monkeypatch):
    monkeypatch.setattr(trace, "_ring", deque(maxlen=8))
    monkeypatch.setattr(trace, "_evicted", [0])
    assert trace.evicted() == 0 and trace.oldest() is None
    for i in range(8):
        trace.record("unit/fill", EPOCH + 2000 + i, EPOCH + 2000.5 + i)
    assert trace.evicted() == 0 and trace.oldest() == EPOCH + 2000
    for i in range(8, 12):
        trace.record("unit/fill", EPOCH + 2000 + i, EPOCH + 2000.5 + i)
    assert trace.evicted() == 4 and trace.oldest() == EPOCH + 2004
    held = trace.spans(EPOCH, EPOCH + 3000, "unit/fill")
    assert [s.t0 for s in held] == [EPOCH + 2000 + i for i in range(4, 12)]
    trace.ring(False)
    try:
        trace.record("unit/fill", EPOCH + 2100, EPOCH + 2101)
    finally:
        trace.ring(True)
    assert trace.evicted() == 4                  # off: nothing in, none out


def test_the_ring_holds_a_window_of_the_fullest_cell_twice_over():
    # chat: a step every 7.4 ms for 51 s, nine spans a step (PERF.md
    # section 7 item 5 has the chip's count)
    assert trace.RING_CAPACITY == trace._ring.maxlen
    assert 2 * (51 / 0.0074) * 9 < trace.RING_CAPACITY
