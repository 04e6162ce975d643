"""Goodput & MFU accounting plane (ISSUE 14 acceptance).

* synthetic-timeline ledger units: overlapping / out-of-order hook
  intervals classify into a GAP-FREE, NON-OVERLAPPING state timeline
  (priority attribution, fold clipping, exact fraction reconstruction);
* TrainStep integration: cost_analysis captured per bucket, the gap-free
  gate on a short instrumented run, zero steady-state recompiles with
  accounting ON;
* MFU cross-check gate: measured-FLOPs MFU within 15% of the analytic 6ND
  number on a tiny GPT config (no recompute); HFU > MFU with recompute;
* DecodeEngine integration: decode/chunk executables cost-ledgered, the
  serving burst classifies gap-free, zero steady-state recompiles with
  accounting ON, model-FLOPs/token + tokens/s/chip accounting;
* fleet: the aggregator derives pod goodput = min over ranks, floor rank
  named; fleet_top renders the goodput column; prom export carries
  goodput/* and mfu/*;
* tools/goodput_report.py + metrics_summary goodput section smokes (incl.
  the lost-accounting and MFU>HFU-inversion WARNs);
* gated microbench (PADDLE_MONITOR_BENCH=1): accounting off adds nothing
  beyond the existing monitor._active check.
"""
import io
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu import monitor
from paddle_tpu.monitor.goodput import (GOODPUT_STATES, GoodputLedger,
                                        device_peak_flops,
                                        executable_cost_stats)
from paddle_tpu.monitor.registry import Registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _monitor_off():
    monitor.disable()
    yield
    monitor.disable()


def _states(gauges):
    return {s: gauges.get(f"goodput/{s}_s", 0.0) for s in GOODPUT_STATES}


def _assert_identity(gauges):
    """The exported contract: states are non-negative, never overlap (sum
    == wall), and the fraction reconstructs EXACTLY from the gauges."""
    vals = _states(gauges)
    assert all(v >= 0 for v in vals.values()), vals
    total = sum(vals[s] for s in GOODPUT_STATES)
    assert gauges["goodput/fraction"] == (
        vals["productive"] / total if total else 0.0)
    # covered time can never exceed wall (no overlap, no double count)
    covered = total - vals["idle"]
    assert covered <= gauges["goodput/wall_s"] + 1e-9
    return vals, total


# --------------------------------------------------------------- ledger units


def test_ledger_gap_free_overlapping_out_of_order():
    """Overlapping and out-of-order intervals classify with no overlap:
    every instant goes to the highest-priority covering state, uncovered
    time is idle, and the sum of states equals wall exactly."""
    reg = Registry()
    led = GoodputLedger(reg)
    t = led._anchor
    # out of order + overlapping: a dispatch [1,3], a compile inside it
    # [1.5, 2.5] (wins by priority), a loader wait [0.2, 0.8] reported
    # late, an async ckpt [0, 4] spanning everything (claims only time
    # nothing foreground owns)
    led.add("productive", t + 1.0, t + 3.0)
    led.add("compile", t + 1.5, t + 2.5)
    led.add("ckpt_bg", t + 0.0, t + 4.0)
    led.add("data_wait", t + 0.2, t + 0.8)   # out-of-order arrival
    led.add("overhead", t + 3.0, t + 3.5)    # host bracket: foreground too
    vals = led.refresh(now=t + 5.0)
    assert vals["compile"] == pytest.approx(1.0)
    assert vals["productive"] == pytest.approx(1.0)   # [1,1.5] + [2.5,3]
    assert vals["data_wait"] == pytest.approx(0.6)
    # the async write ranks below EVERY foreground state incl. overhead:
    # ckpt_bg claims [0,0.2] + [0.8,1.0] + [3.5,4] = 0.9s nobody owned
    assert vals["overhead"] == pytest.approx(0.5)
    assert vals["ckpt"] == pytest.approx(0.9)
    assert vals["idle"] == pytest.approx(1.0)         # [4,5]
    total = sum(vals[s] for s in GOODPUT_STATES)
    assert total == pytest.approx(5.0)
    snap = reg.snapshot()["gauges"]
    _assert_identity(snap)
    assert snap["goodput/fraction"] == pytest.approx(1.0 / 5.0)


def test_ledger_sync_ckpt_outranks_productive():
    reg = Registry()
    led = GoodputLedger(reg)
    t = led._anchor
    led.add("productive", t + 0.0, t + 2.0)
    led.add("ckpt", t + 1.0, t + 3.0)        # emergency save blocks the loop
    vals = led.refresh(now=t + 3.0)
    assert vals["productive"] == pytest.approx(1.0)
    assert vals["ckpt"] == pytest.approx(2.0)
    assert vals["idle"] == pytest.approx(0.0)


def test_ledger_fold_clips_never_double_counts():
    """A straggler interval reaching back before the fold watermark is
    clipped, not double-counted: the no-overlap invariant survives folds.
    """
    from paddle_tpu.monitor import goodput as gp_mod
    reg = Registry()
    led = GoodputLedger(reg)
    t = led._anchor
    n = gp_mod._FOLD_AT
    for i in range(n):  # force a fold: n back-to-back 1ms dispatches
        led.add("productive", t + i * 0.001, t + (i + 1) * 0.001)
    assert not led._pending                   # the fold ran
    wm = led._folded_until
    # late arrival spanning the whole folded region
    led.add("ckpt_bg", t, wm + 0.5)
    vals = led.refresh(now=wm + 1.0)
    assert vals["productive"] == pytest.approx(n * 0.001)
    assert vals["ckpt"] == pytest.approx(0.5)  # clipped to the watermark
    total = sum(vals[s] for s in GOODPUT_STATES)
    assert total == pytest.approx(vals["wall"])


def test_ledger_late_interval_claims_past_idle_gaps():
    """An interval reported after a refresh folded past it (a long async
    ckpt write under the 5s fleet publisher) claims exactly the idle gaps
    of the folded region — attributed time is never re-claimed, so the
    no-double-count invariant survives any refresh cadence."""
    reg = Registry()
    led = GoodputLedger(reg)
    t = led._anchor
    # folded region [0, 1.0]: productive on even milliseconds only
    for i in range(0, 1000, 2):
        led.add("productive", t + i * 1e-3, t + (i + 1) * 1e-3)
    led.refresh(now=t + 1.0)           # publisher-style mid-run fold
    assert led._folded_until >= t + 0.999
    # the async write spanned the whole folded region + a fresh tail
    led.add("ckpt_bg", t, t + 1.5)
    vals = led.refresh(now=t + 1.5)
    assert vals["productive"] == pytest.approx(0.5)
    assert vals["ckpt"] == pytest.approx(1.0)   # 0.5 of gaps + [1.0, 1.5]
    assert vals["idle"] == pytest.approx(0.0, abs=1e-6)
    total = sum(vals[s] for s in GOODPUT_STATES)
    assert total == pytest.approx(vals["wall"])
    # a SECOND late claimant over the same past gaps gets nothing
    led.add("data_wait", t, t + 1.0)
    vals = led.refresh(now=t + 1.5)
    assert vals["data_wait"] == pytest.approx(0.0, abs=1e-9)


def test_ledger_flop_accounting_recompute_split():
    """MFU sources from the analytic model when measured FLOPs include
    recompute replays; HFU always counts what the hardware ran; a live-
    token fraction scales model FLOPs only (serving dead slots)."""
    class FakeExe:
        def cost_analysis(self):
            return {"flops": 1000.0, "bytes accessed": 64.0}

    reg = Registry()
    led = GoodputLedger(reg, peak=1e6)
    t = led._anchor
    led.record_executable("train", 1, FakeExe(), tokens_per_call=10,
                          analytic_flops=800.0, recompute=True,
                          label="train_bucket1")
    led.dispatch("train", 1, t + 0.0, t + 0.1)
    vals = led.refresh(now=t + 1.0)
    g = reg.snapshot()["gauges"]
    assert g["mfu/train_bucket1/flops"] == 1000.0
    assert g["mfu/train_bucket1/analytic_flops"] == 800.0
    assert g["mfu/hw_flops"] == 1000.0
    assert g["mfu/model_flops"] == 800.0          # replays excluded
    assert g["mfu/hfu"] > g["mfu/mfu"]
    assert g["mfu/hfu"] == pytest.approx(1000.0 / (vals["wall"] * 1e6))
    # serving: 4 of 10 rows live -> model flops scale, hardware does not;
    # only GENERATED (decode) tokens feed the throughput figure — prefill
    # prompt tokens scale FLOPs but are not tokens/s
    led.record_executable("serve", ("decode", None), FakeExe(),
                          tokens_per_call=10, analytic_flops=900.0,
                          label="serve_decode")
    led.dispatch("serve", ("decode", None), t + 0.2, t + 0.3, tokens=4,
                 generated=True)
    led.dispatch("serve", ("decode", None), t + 0.3, t + 0.4, tokens=8)
    led.refresh(now=t + 1.0)
    g = reg.snapshot()["gauges"]
    assert g["mfu/hw_flops"] == 3000.0
    assert g["mfu/model_flops"] == pytest.approx(
        800.0 + 1000.0 * 0.4 + 1000.0 * 0.8)
    assert led._serve_tokens == 4                 # the non-generated 8 stay out


def test_serve_flops_per_token_is_decode_only(tmp_path):
    """serve/model_flops_per_token is a DECODE figure: a prefill bucket
    minting later must not overwrite it with its own per-token cost."""
    class FakeExe:
        def __init__(self, flops):
            self._f = flops

        def cost_analysis(self):
            return {"flops": self._f, "bytes accessed": 0.0}

    monitor.enable(str(tmp_path / "run.jsonl"))
    mon = monitor.get()
    mon.serve_compiled("decode", None, 0.01, 1, compiled=FakeExe(400.0),
                       tokens=4)
    mon.serve_compiled("prefill", 64, 0.01, 2, compiled=FakeExe(64000.0),
                       tokens=64)
    g = monitor.snapshot()["gauges"]
    assert g["serve/model_flops_per_token"] == pytest.approx(100.0)


def test_executable_cost_stats_shapes():
    class Full:
        def cost_analysis(self):
            return {"flops": 5.0, "bytes accessed": 7.0}

    class DictShape:
        def cost_analysis(self):
            return {"flops": 5.0}

    class Broken:
        def cost_analysis(self):
            raise RuntimeError("no analysis")

    assert executable_cost_stats(Full()) == {"flops": 5.0, "bytes": 7.0}
    assert executable_cost_stats(DictShape()) == {"flops": 5.0, "bytes": 0.0}
    assert executable_cost_stats(Broken()) is None
    assert executable_cost_stats(object()) is None


def test_device_peak_flops_env_override(monkeypatch):
    monkeypatch.setenv("PADDLE_PEAK_FLOPS", "123e9")
    assert device_peak_flops("weird accelerator") == pytest.approx(123e9)
    monkeypatch.delenv("PADDLE_PEAK_FLOPS")
    assert device_peak_flops("TPU v4 chip") == pytest.approx(275e12)
    assert device_peak_flops("weird accelerator") is None


# ------------------------------------------------------------- train vertical


class MLP(nn.Layer):
    def __init__(self, din=32, hidden=64, nclass=8):
        super().__init__()
        self.fc1 = nn.Linear(din, hidden)
        self.fc2 = nn.Linear(hidden, nclass)

    def forward(self, x, labels):
        return F.cross_entropy(self.fc2(F.relu(self.fc1(x))), labels).mean()


def _mlp_step(seed=7):
    paddle.seed(seed)
    model = MLP()
    opt = paddle.optimizer.AdamW(learning_rate=0.01,
                                 parameters=model.parameters())
    return paddle.jit.TrainStep(model, opt)


def _mlp_batch(bs=16, seed=0):
    rng = np.random.RandomState(seed)
    return (paddle.to_tensor(rng.randn(bs, 32).astype("float32")),
            paddle.to_tensor(rng.randint(0, 8, (bs, 1)).astype("int64")))


def test_train_step_gap_free_gate(tmp_path):
    """Acceptance: a short instrumented train run classifies >= 99% of
    wall time gap-free, fraction reconstructs exactly, cost_analysis is
    captured for the minted bucket, and accounting ON keeps the
    zero-steady-state-recompile contract."""
    path = str(tmp_path / "run.jsonl")
    monitor.enable(path)
    t_en = time.perf_counter()
    step = _mlp_step()
    x, y = _mlp_batch()
    for _ in range(8):
        loss = step(x, y)
    float(loss)
    assert step.num_compiles == 1          # accounting never retraces
    t_done = time.perf_counter()
    g = monitor.snapshot()["gauges"]
    vals, total = _assert_identity(g)
    # >= 99% of the bracket's wall time is on the ledger's clock (the
    # snapshot itself runs after t_done, so wall >= the bracket)
    assert g["goodput/wall_s"] >= 0.99 * (t_done - t_en)
    assert total == pytest.approx(g["goodput/wall_s"], rel=1e-6)
    assert vals["productive"] > 0
    assert vals["compile"] > 0             # the warmup mint
    # per-bucket FLOP ledger: measured cost_analysis + analytic fallback
    assert g["mfu/train_bucket1/flops"] > 0
    assert g["mfu/train_bucket1/analytic_flops"] > 0
    assert g["mfu/hw_flops"] > 0
    monitor.disable()
    # the final counters record carries the gauges for offline tooling
    with open(path) as f:
        recs = [json.loads(l) for l in f if l.strip()]
    final = [r for r in recs if r["kind"] == "counters"][-1]
    assert "goodput/fraction" in final["metrics"]["gauges"]
    assert any(r["kind"] == "exec_cost" for r in recs)


def _bench_gpt_step(recompute=None, seed=0):
    """A tiny GPT training config, as a TrainStep."""
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    paddle.seed(seed)
    cfg = GPTConfig(hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    recompute_granularity=recompute or "none",
                    vocab_size=256, hidden_size=64, num_layers=2,
                    num_heads=4, max_position_embeddings=128)
    model = GPTForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                                 parameters=model.parameters(),
                                 multi_precision=True)
    step = paddle.jit.TrainStep(model, opt)
    ids = paddle.to_tensor(np.random.RandomState(0)
                           .randint(0, 256, (2, 128)).astype("int32"))
    return cfg, step, ids


def test_mfu_cross_check_gate(tmp_path, monkeypatch):
    """Acceptance: measured-FLOPs MFU agrees with the analytic 6ND number
    within 15% on the tiny GPT config (no recompute) — the analytic
    formula incl. the attention-dots term, against cost_analysis()."""
    monkeypatch.setenv("PADDLE_PEAK_FLOPS", "1e15")
    monitor.enable(str(tmp_path / "run.jsonl"))
    cfg, step, ids = _bench_gpt_step(recompute=None)
    float(step(ids, ids))
    batch, seq = 2, 128
    n_block = 12 * cfg.num_layers * cfg.hidden_size ** 2
    fpt_analytic = (6.0 * (n_block + cfg.vocab_size * cfg.hidden_size)
                    + 12.0 * cfg.num_layers * cfg.hidden_size * seq)
    g = monitor.snapshot()["gauges"]
    measured_fpt = g["mfu/train_bucket1/flops"] / (batch * seq)
    assert abs(measured_fpt / fpt_analytic - 1.0) < 0.15, \
        f"measured {measured_fpt:.0f} vs analytic {fpt_analytic:.0f}"
    # no recompute: the hardware runs exactly the model's FLOPs
    float(step(ids, ids))
    g = monitor.snapshot()["gauges"]
    assert g["mfu/hfu"] == g["mfu/mfu"] > 0


def test_hfu_exceeds_mfu_with_recompute(tmp_path, monkeypatch):
    """Acceptance: HFU > MFU when recompute is on — backward replays
    forward FLOPs the model's math never asked for."""
    monkeypatch.setenv("PADDLE_PEAK_FLOPS", "1e15")
    monitor.enable(str(tmp_path / "run.jsonl"))
    _, step, ids = _bench_gpt_step(recompute="full")
    for _ in range(2):
        float(step(ids, ids))
    g = monitor.snapshot()["gauges"]
    assert g["mfu/hfu"] > g["mfu/mfu"] > 0
    # the ledger knows WHY: the bucket is flagged recompute, with the
    # analytic model beside the inflated measured count
    recs = [r for r in (monitor.get().flight.events())
            if r.get("kind") == "exec_cost"]
    assert recs and recs[-1]["recompute"] is True
    assert recs[-1]["flops"] > recs[-1]["analytic_flops"]


def test_two_train_steps_do_not_cross_bill(tmp_path):
    """Two TrainSteps in one monitor session: each dispatch accrues its
    OWN executable's FLOPs (the ledger keys per instance), not whichever
    minted last."""
    monitor.enable(str(tmp_path / "run.jsonl"))
    paddle.seed(3)
    big = MLP(hidden=256)
    small = MLP(hidden=8)
    step_big = paddle.jit.TrainStep(
        big, paddle.optimizer.AdamW(learning_rate=0.01,
                                    parameters=big.parameters()))
    step_small = paddle.jit.TrainStep(
        small, paddle.optimizer.AdamW(learning_rate=0.01,
                                      parameters=small.parameters()))
    x, y = _mlp_batch()
    float(step_big(x, y))
    float(step_small(x, y))     # minted LAST: would win a shared key
    led = monitor.get().goodput
    flops = {rec.label or k: rec.flops
             for k, rec in led._exes.items()}
    big_flops = led._exes[("train", (step_big._gp_id, 1))].flops
    small_flops = led._exes[("train", (step_small._gp_id, 1))].flops
    assert big_flops > small_flops > 0, flops
    before = led._hw_flops
    float(step_big(x, y))
    assert led._hw_flops - before == pytest.approx(big_flops)
    before = led._hw_flops
    float(step_small(x, y))
    assert led._hw_flops - before == pytest.approx(small_flops)


def test_loader_wait_classifies_as_data_wait(tmp_path):
    from paddle_tpu.io import DeviceLoader

    def slow_batches():
        for i in range(3):
            time.sleep(0.05)   # producer slower than consumer: real stalls
            yield np.zeros((4, 4), np.float32)

    monitor.enable(str(tmp_path / "run.jsonl"))
    for _ in DeviceLoader(slow_batches(), prefetch_depth=1):
        pass
    g = monitor.snapshot()["gauges"]
    assert g["goodput/data_wait_s"] > 0.04


# ----------------------------------------------------------- serving vertical


def test_decode_engine_accounting_gap_free(tmp_path):
    """Acceptance: a DecodeEngine burst classifies gap-free with
    accounting ON and zero steady-state recompiles; decode/chunk
    executables are cost-ledgered; per-token serving accounting lands."""
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import DecodeEngine
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_heads=2, max_position_embeddings=64,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    use_flash_attention=False)
    m = GPTForCausalLM(cfg)
    m.eval()
    monitor.enable(str(tmp_path / "run.jsonl"))
    t_en = time.perf_counter()
    engine = DecodeEngine(m, max_slots=4, max_len=48, paged=True,
                          block_size=8, prefill_chunk=8)
    rng = np.random.RandomState(1)

    def burst(n):
        reqs = [engine.submit(rng.randint(0, 64, rng.randint(6, 14))
                              .tolist(), max_new_tokens=6)
                for _ in range(n)]
        engine.run(max_steps=200)
        assert all(r.status == "done" for r in reqs)

    burst(6)
    warm = engine.compile_count
    burst(6)
    assert engine.compile_count == warm    # accounting ON never re-mints
    t_done = time.perf_counter()
    g = monitor.snapshot()["gauges"]
    vals, total = _assert_identity(g)
    assert g["goodput/wall_s"] >= 0.99 * (t_done - t_en)
    assert vals["productive"] > 0
    assert vals["compile"] > 0
    assert vals["overhead"] > 0            # the scheduler bracket
    # decode + chunk executables cost-ledgered (per-bucket gauges)
    assert g["mfu/serve_decode/flops"] > 0
    assert g["mfu/serve_prefill8/flops"] > 0
    assert g["mfu/serve_decode/analytic_flops"] > 0
    # per-request serving accounting: model-FLOPs/token + tokens/s/chip
    assert g["serve/model_flops_per_token"] > 0
    assert g["serve/tokens_per_s_chip"] > 0
    # hardware ran full [max_slots] decode shapes; only live rows are
    # model work — HFU-side flops must dominate model flops
    assert g["mfu/hw_flops"] >= g["mfu/model_flops"]


# ------------------------------------------------------------------ fleet min


def test_fleet_pod_goodput_is_min_over_ranks(tmp_path):
    from paddle_tpu.monitor.collector import (Aggregator, LocalTransport,
                                              Publisher)
    transport = LocalTransport()
    regs = {0: Registry(), 1: Registry()}
    regs[0].gauge("goodput/fraction").set(0.9)
    regs[0].gauge("goodput/idle_s").set(1.0)
    regs[1].gauge("goodput/fraction").set(0.4)
    regs[1].gauge("goodput/idle_s").set(6.0)
    for r, reg in regs.items():
        Publisher(reg, transport, r, interval=60).publish_once(full=True)
    agg = Aggregator(transport, world=2,
                     fleet_path=str(tmp_path / "run.fleet.jsonl"),
                     interval=60)
    rec = agg.poll_once()
    d = rec["derived"]
    assert d["fleet/goodput"] == pytest.approx(0.4)     # pod = min
    assert d["fleet/goodput_min_rank"] == 1             # floor rank named
    assert d["fleet/goodput_min_rank_idle_s"] == pytest.approx(6.0)
    agg.stop(final=False)

    # fleet_top: per-rank goodput column + the pod floor in the header
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import fleet_top
    finally:
        sys.path.pop(0)
    frame = fleet_top.render({"world": 2}, [rec], [])
    assert "goodput" in frame
    assert "pod goodput 40%" in frame
    assert "(floor: rank 1)" in frame
    assert "90%" in frame and "40%" in frame


def test_prom_export_carries_goodput_and_mfu(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_PEAK_FLOPS", "1e15")
    monitor.enable(str(tmp_path / "run.jsonl"))
    step = _mlp_step()
    x, y = _mlp_batch()
    float(step(x, y))
    text = monitor.prom_render()
    assert "paddle_goodput_fraction" in text
    assert "paddle_goodput_productive_s" in text
    assert "paddle_mfu_train_bucket1_flops" in text
    assert "paddle_mfu_hfu" in text


# ------------------------------------------------------------------- tooling


def test_goodput_report_cli_smoke(tmp_path):
    path = str(tmp_path / "run.jsonl")
    monitor.enable(path)
    step = _mlp_step()
    x, y = _mlp_batch()
    for _ in range(3):
        float(step(x, y))
    monitor.disable()
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "goodput_report.py"),
         path], capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "goodput report" in out.stdout
    assert "productive" in out.stdout and "compile" in out.stdout
    assert "goodput fraction" in out.stdout
    assert "train_bucket1" in out.stdout        # the FLOP ledger table
    assert "top goodput losses" in out.stdout


def test_goodput_report_multi_rank_pod_rollup(tmp_path):
    """Two rank files -> per-rank tables + pod roll-up naming the floor
    rank, and the worst compile episode carries its trace id."""
    def fake_rank(path, proc, frac, trace=None):
        t0 = 1000.0
        recs = [{"v": 1, "ts": t0, "kind": "meta", "proc": proc},
                {"v": 1, "ts": t0 + 1,
                 "kind": "recompile", "compile_s": 2.5 - proc,
                 **({"trace": trace} if trace else {})},
                {"v": 1, "ts": t0 + 10, "kind": "counters", "metrics": {
                    "counters": {}, "histograms": {}, "gauges": {
                        "goodput/productive_s": 10.0 * frac,
                        "goodput/compile_s": 10.0 * (1 - frac),
                        "goodput/data_wait_s": 0.0, "goodput/ckpt_s": 0.0,
                        "goodput/reshard_s": 0.0, "goodput/overhead_s": 0.0,
                        "goodput/idle_s": 0.0, "goodput/wall_s": 10.0,
                        "goodput/fraction": frac}}}]
        with open(path, "w") as f:
            for r in recs:
                f.write(json.dumps(r) + "\n")

    p0 = str(tmp_path / "run.jsonl")
    p1 = str(tmp_path / "run.proc1.jsonl")
    fake_rank(p0, 0, 0.9, trace="abc-1")
    fake_rank(p1, 1, 0.5)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "goodput_report.py"),
         p0, p1], capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "rank 0" in out.stdout and "rank 1" in out.stdout
    assert "pod roll-up" in out.stdout
    assert "rank 1 is the floor" in out.stdout
    assert "[trace abc-1]" in out.stdout        # worst compile episode


def _summary(paths):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import metrics_summary
    finally:
        sys.path.pop(0)
    buf = io.StringIO()
    metrics_summary.summarize(paths, out=buf)
    return buf.getvalue()


def test_metrics_summary_goodput_section(tmp_path):
    path = str(tmp_path / "run.jsonl")
    monitor.enable(path)
    step = _mlp_step()
    x, y = _mlp_batch()
    for _ in range(3):
        float(step(x, y))
    monitor.disable()
    text = _summary([path])
    assert "== goodput ==" in text
    assert "goodput fraction" in text
    assert "WARNING" not in text.split("== goodput ==")[1] \
                               .split("==")[0]


def _fake_stream(path, gauges, span_s=10.0, proc=0):
    t0 = 1000.0
    recs = [{"v": 1, "ts": t0, "kind": "meta", "proc": proc},
            {"v": 1, "ts": t0 + span_s, "kind": "counters",
             "metrics": {"counters": {}, "histograms": {},
                         "gauges": gauges}}]
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")


def _gp_gauges(frac, wall=10.0):
    g = {f"goodput/{s}_s": 0.0 for s in GOODPUT_STATES}
    g.update({"goodput/productive_s": wall * frac,
              "goodput/idle_s": wall * (1 - frac),
              "goodput/wall_s": wall, "goodput/fraction": frac})
    return g


def test_metrics_summary_goodput_pod_min_not_max(tmp_path):
    """Multi-rank: the headline is the POD-MIN fraction (naming the floor
    rank), never the generic max-merge's best-rank figure — a straggler
    pod must not read as healthy."""
    p0 = str(tmp_path / "run.jsonl")
    p1 = str(tmp_path / "run.proc1.jsonl")
    _fake_stream(p0, _gp_gauges(0.9), proc=0)
    _fake_stream(p1, _gp_gauges(0.6), proc=1)
    text = _summary([p0, p1])
    sect = text.split("== goodput ==")[1].split("\n==")[0]
    assert "pod goodput 60.0%" in sect
    assert "rank 1 is the floor" in sect
    assert "90.0%" not in sect.split("pod goodput")[1].split("(")[0]
    # per-state rows sum across ranks: productive 9 + 6 = 15s
    assert "15.000s" in sect


def test_metrics_summary_lost_accounting_warn(tmp_path):
    """Classified time << record span = the ledger went stale mid-run."""
    path = str(tmp_path / "run.jsonl")
    g = {f"goodput/{s}_s": 0.0 for s in GOODPUT_STATES}
    g.update({"goodput/productive_s": 1.0, "goodput/wall_s": 1.0,
              "goodput/fraction": 1.0})
    _fake_stream(path, g, span_s=100.0)
    text = _summary([path])
    assert "lost-accounting signature" in text


def test_metrics_summary_mfu_inversion_warn(tmp_path):
    """MFU > HFU cannot happen (model FLOPs <= hardware FLOPs): WARN."""
    path = str(tmp_path / "run.jsonl")
    g = {f"goodput/{s}_s": 0.0 for s in GOODPUT_STATES}
    g.update({"goodput/productive_s": 10.0, "goodput/wall_s": 10.0,
              "goodput/fraction": 1.0, "mfu/mfu": 0.5, "mfu/hfu": 0.3})
    _fake_stream(path, g, span_s=10.0)
    text = _summary([path])
    assert "impossible inversion" in text
    # and the healthy shape does NOT warn
    g.update({"mfu/mfu": 0.3, "mfu/hfu": 0.5})
    _fake_stream(path, g, span_s=10.0)
    assert "impossible inversion" not in _summary([path])


# -------------------------------------------------------- overhead microbench


def _tput(step, x, y, n):
    t0 = time.perf_counter()
    loss = None
    for _ in range(n):
        loss = step(x, y)
    float(loss)
    return n / (time.perf_counter() - t0)


@pytest.mark.skipif(not os.environ.get("PADDLE_MONITOR_BENCH"),
                    reason="gated microbench: set PADDLE_MONITOR_BENCH=1")
def test_goodput_disabled_path_microbench(tmp_path):
    """Acceptance: accounting off adds no per-step hooks beyond the
    existing monitor._active check — disabled throughput within noise of
    (>= 0.8x) the enabled path that does the real ledger work."""
    step = _mlp_step()
    x, y = _mlp_batch(bs=32)
    float(step(x, y))
    n = 30
    ratios = []
    for _ in range(3):
        off = _tput(step, x, y, n)
        monitor.enable(str(tmp_path / "bench.jsonl"))
        on = _tput(step, x, y, n)
        monitor.disable()
        ratios.append(off / on)
    assert max(ratios) >= 0.8, f"disabled/enabled throughput {ratios}"
