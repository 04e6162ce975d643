"""Driver benchmark: GPT causal-LM throughput on one chip.

Two workloads: training (default) and serving decode (``bench.py decode`` —
DecodeEngine continuous batching, tokens/s/chip).

Prints a JSON line {"metric", "value", "unit", "vs_baseline", ...} after EVERY
measurement window (best-so-far value, flushed immediately) — a run killed by
the driver's timeout (rc=124) still leaves parseable result lines behind; the
LAST line is the final answer. Warmup is one compile call; the first timed
window doubles as dispatch warmup (the best-of across windows discards it).

Config: GPT-medium (sized for one chip's HBM), bf16 compute via AMP-O2
semantics (params fp32, matmuls bf16 — TPU-native mixed precision), full train step
compiled to a single XLA executable (paddle_tpu.jit.TrainStep). vs_baseline is
relative to REF_TOKENS_PER_SEC below.

At full size this measures a TPU and refuses to start on anything else (exit
code != 0): a CPU run is never printed under a device metric's name.

``--recompute[=selective|full|dots]`` (default selective) turns on activation
recompute in the blocks (fleet/recompute.py policy layer) and SPENDS the freed
residual memory on a larger per-chip microbatch (``--batch=N`` to override).
``BENCH_TINY=1`` shrinks the model/iterations to a seconds-scale smoke config
(CI exercises the CLI contract without a TPU; it is the ONLY way onto a CPU).
The persistent compile cache lives where paddle_tpu.utils.compile_cache says.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

# first self-measured value (round 1) on one v4 chip; later rounds compare to this
REF_TOKENS_PER_SEC = 33064.0

# decode baseline: None until the first `bench.py decode` round lands a
# value on real hardware — that first line defines the reference
REF_DECODE_TOKENS_PER_SEC = None


def _cli_flag(argv, name):
    """--name -> "", --name=value -> "value", absent -> None."""
    for a in argv:
        if a == f"--{name}":
            return ""
        if a.startswith(f"--{name}="):
            return a.split("=", 1)[1]
    return None


def _trace_fields():
    """Span-tracer context for the best-so-far line (tracer comes up via
    PADDLE_MONITOR + PADDLE_TRACE env): how many traces landed and where —
    the line then names the file trace_view opens to decompose this
    round's outliers. Empty when tracing is off."""
    try:
        from paddle_tpu.monitor import trace as _trace
        t = _trace.get()
    except Exception:
        return {}
    if t is None:
        return {}
    t.flush()
    return {"traces": t.traces_sampled, "trace_path": t.path}


def _fleet_fields():
    """step_skew/ranks for the best-so-far line, SOURCED from the telemetry
    collector (monitor/collector.py aggregates them on rank 0 when bench
    runs under the launcher with PADDLE_MONITOR + PADDLE_MONITOR_FLEET set)
    — bench measures nothing new here. Empty off the multichip path."""
    try:
        from paddle_tpu import monitor
        st = monitor.fleet_state()
    except Exception:
        return {}
    if not st:
        return {}
    d = st.get("derived") or {}
    out = {"ranks": len(st.get("ranks") or [])}
    if d.get("fleet/step_skew") is not None:
        out["step_skew"] = round(float(d["fleet/step_skew"]), 3)
    return out


def _health_fields():
    """health_trips for the best-so-far line: a best-of figure measured
    across windows that tripped the numerics plane is not a clean number —
    the line says so. Empty when the monitor is off."""
    try:
        from paddle_tpu import monitor
        mon = monitor.get()
    except Exception:
        return {}
    h = getattr(mon, "health", None)
    if h is None:
        return {}
    return {"health_trips": int(h.nan_trips + h.overflow_trips + h.spikes)}


def _start_jax():
    """Device gate + compile cache, shared by every lane: full size runs on a
    TPU or not at all; ``BENCH_TINY=1`` is the CPU CLI smoke."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not os.environ.get("BENCH_TINY"):
        raise SystemExit(
            f"bench.py: full size measures a TPU, but jax.devices()[0] is "
            f"{dev.platform}:{dev.device_kind} — refusing to print device "
            f"metrics from it (BENCH_TINY=1 is the CPU CLI smoke)")
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()


def _heartbeat(what, window):
    """One flushed line the moment a measurement window OPENS. A round the
    driver kills mid-window (rc=124 with no result line) then shows WHERE
    it died — dispatch inside window N, not warmup — in place of an empty
    log."""
    print(json.dumps({"heartbeat": what, "window": window,
                      "ts": round(time.time(), 3)}))
    sys.stdout.flush()


def main(argv=()):
    _start_jax()
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    recompute = _cli_flag(argv, "recompute")
    if recompute == "":
        recompute = "selective"   # bare --recompute: the Megatron-style default
    elif recompute == "none":
        recompute = None          # explicit off: the true B=16 control run
    tiny = bool(os.environ.get("BENCH_TINY"))

    paddle.seed(0)
    # GPT-medium-ish: fits one chip with Adam states; representative MXU shapes.
    # head_dim 128 (8 heads), the TPU-native choice: the MXU contracts 128-wide,
    # so d=64 heads run the attention dots at half rate and pad every kernel
    # operand to 128 lanes (device-profiled: d=128 is ~1.2x whole-step).
    size = (dict(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                 max_position_embeddings=128) if tiny else
            dict(vocab_size=50304, hidden_size=1024, num_layers=16,
                 num_heads=8, max_position_embeddings=1024))
    cfg = GPTConfig(hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    recompute_granularity=recompute or "none", **size)
    model = GPTForCausalLM(cfg)

    # AMP-O2 analog: bf16 activations/matmuls (params stay fp32 in the optimizer)
    for _, p in model.named_parameters():
        p._data = p.value().astype("bfloat16")
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                                 parameters=model.parameters(),
                                 multi_precision=True)

    # B=16 profiled fastest at no-remat (B=24 hits logits-remat pressure);
    # with recompute on, the freed block residuals are spent on a larger
    # microbatch — that is the whole point of the knob
    batch, seq = (24 if recompute else 16), 1024
    if tiny:
        batch, seq = 2, 128
    b_over = _cli_flag(argv, "batch")
    if b_over:
        batch = int(b_over)
    ids_np = np.random.RandomState(0).randint(0, cfg.vocab_size, (batch, seq))
    ids = paddle.to_tensor(ids_np.astype("int32"))

    step = paddle.jit.TrainStep(model, opt)

    # warmup: ONE compile call (the persistent cache makes repeats cheap);
    # dispatch warmth comes from the first timed window
    loss = step(ids, ids)
    final = float(loss)
    assert np.isfinite(final), f"loss diverged in warmup: {final}"

    # ---- MFU accounting (absolute FLOPs vs hardware peak)
    # the analytic FORMULA (6 FLOPs/param/token + 12*L*d*S attention dots)
    # is shared with the goodput plane's ledger; bench feeds it matmul
    # params only — 12*L*d^2 block weights + the tied lm-head projection
    # (embedding GATHERS are not matmul FLOPs and stay out). Kept as
    # `mfu_analytic`, the cross-check against the measured number below.
    # Peak table + PADDLE_PEAK_FLOPS override also live in
    # monitor/goodput.py (the accounting plane's source of truth): an
    # unknown device kind no longer pins mfu to null.
    from paddle_tpu.monitor.goodput import (analytic_train_flops_per_token,
                                            device_peak_flops,
                                            executable_cost_stats)
    n_block = 12 * cfg.num_layers * cfg.hidden_size ** 2
    flops_per_token = analytic_train_flops_per_token(
        n_block + cfg.vocab_size * cfg.hidden_size,
        cfg.num_layers, cfg.hidden_size, seq)
    kind = jax.devices()[0].device_kind
    peak_flops = device_peak_flops(kind)

    # measured FLOPs: the warmup minted the (single) shape bucket's AOT
    # executable — its cost_analysis() counts what XLA actually scheduled,
    # recompute replays and all. With --recompute the measured count is the
    # HARDWARE number (HFU); the model's own FLOPs stay the analytic 6ND.
    measured_fpt = None
    if step._fast:
        stats = executable_cost_stats(next(iter(step._fast.values())))
        if stats:
            measured_fpt = stats["flops"] / (batch * seq)
    if measured_fpt is not None and not recompute:
        drift = measured_fpt / flops_per_token - 1.0
        if abs(drift) > 0.10:
            # one of the two FLOP models is wrong — say so rather than
            # letting the rounds silently track a broken constant
            print(f"WARNING: measured cost_analysis FLOPs/token "
                  f"({measured_fpt:.3e}) diverges {drift:+.0%} from the "
                  f"analytic 6ND model ({flops_per_token:.3e}); mfu is "
                  f"measured-sourced, check the analytic constant",
                  file=sys.stderr)

    def report(tokens_per_sec, window):
        model_tflops = tokens_per_sec * flops_per_token / 1e12
        mfu_analytic = (round(model_tflops * 1e12 / peak_flops, 3)
                        if peak_flops else None)
        mfu = mfu_analytic
        hfu = None
        if measured_fpt is not None and peak_flops:
            measured_util = round(
                tokens_per_sec * measured_fpt / peak_flops, 3)
            if recompute:
                # measured includes recompute replays: that is HFU; MFU
                # (model FLOPs only) stays the analytic number — the old
                # single figure silently conflated them under --recompute
                hfu = measured_util
            else:
                mfu = measured_util
                hfu = measured_util
        payload = {
            "metric": "gpt_medium_train_tokens_per_sec_per_chip",
            "value": round(tokens_per_sec, 1),
            "unit": "tokens/s",
            "vs_baseline": round(tokens_per_sec / REF_TOKENS_PER_SEC, 3),
            "model_tflops": round(model_tflops, 1),
            "mfu": mfu,
            "mfu_analytic": mfu_analytic,
            "hfu": hfu,
            "mfu_source": ("measured" if measured_fpt is not None
                           and not recompute else "analytic"),
            "recompute": recompute or None,
            "batch": batch,
            "device_kind": kind,
            "window": window,
        }
        payload.update(_fleet_fields())
        payload.update(_trace_fields())
        payload.update(_health_fields())
        print(json.dumps(payload))
        sys.stdout.flush()

    # measure in short windows, print the best-so-far after each one: the
    # driver's timeout can land anywhere and the tail line still parses
    iters, windows = (1, 2) if tiny else (5, 6)
    best = 0.0
    for w in range(windows):
        _heartbeat("train_window_open", w)
        t0 = time.time()
        for _ in range(iters):
            loss = step(ids, ids)
        final = float(loss)  # blocks on the last step
        dt = time.time() - t0
        assert np.isfinite(final), f"loss diverged: {final}"
        best = max(best, batch * seq * iters / dt)
        report(best, w)


def _decode_router(n_engines):
    """Fleet decode lane (``bench.py decode --router N``): N in-process
    paged engines on a LocalDirectory behind the Router, affinity policy.
    Prompts open with one of a few shared system prefixes — cache-aware
    placement pins each prefix to the engine whose pager already holds its
    blocks, which is the number ``affinity_hit_rate`` reports."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import (DecodeEngine, EngineEndpoint,
                                    LocalDirectory, LocalEngineClient,
                                    Router)

    tiny = bool(os.environ.get("BENCH_TINY"))
    paddle.seed(0)
    size = (dict(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                 max_position_embeddings=128) if tiny else
            dict(vocab_size=50304, hidden_size=1024, num_layers=16,
                 num_heads=8, max_position_embeddings=1024))
    cfg = GPTConfig(hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    **size)
    model = GPTForCausalLM(cfg)
    for _, p in model.named_parameters():
        p._data = p.value().astype("bfloat16")

    slots, horizon = (2, 64) if tiny else (8, 256)
    block = 16
    directory = LocalDirectory()
    router = Router(directory, policy="affinity", stale_after=1e9)
    engines, endpoints = {}, {}
    for i in range(n_engines):
        name = f"eng{i}"
        eng = DecodeEngine(model, max_slots=slots, max_len=horizon,
                           paged=True, block_size=block,
                           prefill_chunk=16 if tiny else 32)
        engines[name] = eng
        endpoints[name] = EngineEndpoint(eng, name, directory, ttl_s=30.0)
        endpoints[name].publish()
        router.attach(name, LocalEngineClient(eng))

    rng = np.random.RandomState(0)
    # one shared system prefix per engine, each exactly one block long, so
    # a placement either lands on the engine already holding those blocks
    # (affinity hit) or pays a fresh prefill elsewhere (spill)
    prefixes = [rng.randint(0, cfg.vocab_size, block).tolist()
                for _ in range(n_engines)]
    lo, hi = block + 4, horizon // 2

    def mk_prompt():
        g = int(rng.randint(len(prefixes)))
        n = int(rng.randint(lo, hi + 1))
        return prefixes[g] + rng.randint(
            0, cfg.vocab_size, n - block).tolist()

    # warm every engine before the first window: one request through
    # prefill + first decode mints the chunk and decode executables, and
    # seeds each pager's prefix registry with one of the shared prefixes
    for i, (name, eng) in enumerate(sorted(engines.items())):
        n = int(rng.randint(lo, hi + 1))
        eng.submit(prefixes[i % len(prefixes)] + rng.randint(
            0, cfg.vocab_size, n - block).tolist(), max_new_tokens=4)
        while eng.decode_steps == 0:
            eng.step()
        endpoints[name].publish()
    warm = {name: eng.compile_count for name, eng in engines.items()}

    def step_fleet():
        for name, eng in engines.items():
            if eng.queue_depth + eng.active_count:
                eng.step()
            endpoints[name].publish()
        router.poll()

    cap = n_engines * slots
    tickets = []

    def refill():
        while sum(e.queue_depth + e.active_count
                  for e in engines.values()) < cap:
            tickets.append(router.route(
                mk_prompt(),
                max_new_tokens=int(rng.randint(horizon // 4,
                                               horizon // 2))))

    kind = jax.devices()[0].device_kind
    iters, windows = (4, 2) if tiny else (20, 6)
    best = 0.0
    for w in range(windows):
        _heartbeat("decode_router_window_open", w)
        tok0 = sum(e.tokens_generated for e in engines.values())
        t0 = time.time()
        for _ in range(iters):
            refill()
            step_fleet()
        dt = time.time() - t0
        best = max(best,
                   (sum(e.tokens_generated for e in engines.values())
                    - tok0) / dt)
        c = dict(router.counters)
        placed = c.get("affinity_hits", 0) + c.get("spills", 0)
        print(json.dumps(dict(_fleet_fields(), **_trace_fields(),
                              **_health_fields(), **{
            "metric": "gpt_medium_decode_router_tokens_per_sec",
            "value": round(best, 1),
            "unit": "tokens/s (decode, fleet total)",
            "engines": n_engines,
            "routed": c.get("routed", 0),
            "affinity_hit_rate": (round(c.get("affinity_hits", 0)
                                        / placed, 3) if placed else None),
            "requeues": c.get("requeues", 0),
            "ejections": c.get("ejections", 0),
            "rejected": c.get("rejected", 0),
            "prefix_hits": sum(int(e._pager.prefix_hits)
                               for e in engines.values()),
            "steady_state_recompiles": sum(
                e.compile_count - warm[name]
                for name, e in engines.items()),
            "device_kind": kind,
            "window": w,
        })))
        sys.stdout.flush()
    router.emit_state()


def main_decode(argv=()):
    """Serving decode throughput: a DecodeEngine over the GPT-medium
    config, every slot kept hot with staggered requests so admissions and
    evictions run continuously — the steady state being measured.

    ``--paged`` serves through the block page table + chunked prefill
    (shared-prefix workload: every prompt opens with a common system-prompt
    prefix, so the pager's sharing/COW machinery is ON the measured path);
    default is the slot-owns-a-row control arm. Same output contract as
    training: best-so-far JSON line after every window, flushed
    (rc=124-safe), now carrying ``kv_util`` (live tokens / pooled token
    capacity) and TTFT p50/p95 from the window's completed requests.
    ``steady_state_recompiles`` must stay 0; a nonzero value means the
    zero-recompile contract broke and the tokens/s number is compile-bound
    garbage. ``BENCH_TINY=1`` shrinks everything to a seconds-scale CI
    smoke config.

    ``--tp N`` (requires ``--paged``) runs tensor-parallel decode over a
    "model"-axis mesh of N chips: GPT weights ride shard_gpt_tp's Column/
    RowParallel placements, the engine shards each KV pool's head axis and
    keeps the block table replicated. On a CPU host the mesh is virtual
    (the host-platform device-count flag is set before jax initializes);
    on a real TPU the first N chips form the mesh. The best-so-far line
    then carries per-chip tokens/s and the prefix-cache hit rate.

    ``--chaos`` (requires ``--paged``) measures throughput UNDER FAULT: a
    fixed PADDLE_SERVE_FAULT-style schedule injects slow decodes, pager
    alloc failures (deterministic preemption pressure) and admission
    faults through the guardrails seam, every 6th request carries an
    impossible deadline (guaranteed expiry) and every 9th is cancelled
    mid-flight; after the last window the engine drains. The best-so-far
    line gains ``chaos``/``expired``/``cancelled`` so the driver can see
    p95 TTFT and throughput degradation under fault next to the clean
    number — the line stays rc=124-safe.

    ``--spec[=prompt_lookup|draft_model|early_exit]`` (requires
    ``--paged``; default drafter prompt_lookup) turns on speculative
    decoding: each decode step drafts k tokens and verifies them in one
    chunk-shaped dispatch, so tokens/s rises with the workload's
    acceptance rate while greedy output stays bitwise identical. The
    shared-prefix workload is exactly where prompt-lookup shines (the
    output keeps re-quoting the repetitive context). The best-so-far line
    gains ``spec``/``accepted_per_step``/``draft_hit_rate``.

    ``--pool`` (requires ``--paged``) measures the cross-process
    prefix-cache tier: a "previous incarnation" engine serves the shared
    system prompt once and exports its parked blocks to a host pool
    (serving/kvpool.py), then the MEASURED engine starts cold with the
    pool attached — its first shared-prompt admission adopts the
    exported blocks instead of re-prefilling them. The best-so-far line
    gains ``pool_hit_rate`` / ``adopted_tokens`` / ``pool_fetch_hits``
    next to the TTFT percentiles, and ``steady_state_recompiles`` must
    stay 0 with adoption on the measured path (the splice is table data
    + a device_put, never a new shape).

    ``--router N`` measures the FLEET lane instead: N in-process paged
    engines registered on a LocalDirectory behind the serving Router
    (cache-aware placement). The workload interleaves a handful of shared
    system prefixes, so affinity placement keeps each prefix's blocks hot
    on one engine. The best-so-far line reports fleet-summed tokens/s
    plus ``affinity_hit_rate``/``requeues``; ``steady_state_recompiles``
    (summed over engines) must still be 0 with the router in the loop."""
    tpf = _cli_flag(argv, "tp")
    if tpf == "":
        # space-separated form: --tp N (the = form is --tp=N)
        argl = list(argv)
        i = argl.index("--tp")
        tpf = argl[i + 1] if i + 1 < len(argl) \
            and argl[i + 1].isdigit() else ""
        if not tpf:
            raise SystemExit("--tp needs a degree: --tp N or --tp=N")
    tp = int(tpf or 0)
    if tp > 1 and "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        # virtual CPU mesh: must land before jax initializes its backend.
        # The flag only affects the host platform — a real TPU ignores it.
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + f" --xla_force_host_platform_device_"
                                     f"count={tp}")
    _start_jax()
    import jax

    routerf = _cli_flag(argv, "router")
    if routerf == "":
        argl = list(argv)
        i = argl.index("--router")
        routerf = argl[i + 1] if i + 1 < len(argl) \
            and argl[i + 1].isdigit() else ""
        if not routerf:
            raise SystemExit("--router needs a fleet size: "
                             "--router N or --router=N")
    if routerf is not None:
        n = int(routerf)
        if n < 2:
            raise SystemExit(f"--router={n}: a fleet needs >= 2 engines")
        return _decode_router(n)

    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM, shard_gpt_tp
    from paddle_tpu.serving import DecodeEngine

    paged = _cli_flag(argv, "paged") is not None
    chaos = _cli_flag(argv, "chaos") is not None
    pool_flag = _cli_flag(argv, "pool") is not None
    spec = _cli_flag(argv, "spec")
    if spec == "":
        spec = "prompt_lookup"     # bare --spec: the no-model drafter
    if spec is not None and spec not in ("prompt_lookup", "draft_model",
                                         "early_exit"):
        raise SystemExit(f"--spec={spec}: drafter must be prompt_lookup, "
                         f"draft_model or early_exit")
    tiny = bool(os.environ.get("BENCH_TINY"))
    if tp > 1 and not paged:
        print("--tp requires --paged (the row cache is single-chip); "
              "enabling --paged", file=sys.stderr)
        paged = True
    if chaos and not paged:
        print("--chaos requires --paged (the fault seam's alloc site lives "
              "in the BlockPager); enabling --paged", file=sys.stderr)
        paged = True
    if spec and not paged:
        print("--spec requires --paged (speculative K/V lands in pager "
              "blocks); enabling --paged", file=sys.stderr)
        paged = True
    if pool_flag and not paged:
        print("--pool requires --paged (exported blocks live in the "
              "BlockPager); enabling --paged", file=sys.stderr)
        paged = True

    paddle.seed(0)
    size = (dict(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                 max_position_embeddings=128) if tiny else
            dict(vocab_size=50304, hidden_size=1024, num_layers=16,
                 num_heads=8, max_position_embeddings=1024))
    cfg = GPTConfig(hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    **size)
    model = GPTForCausalLM(cfg)
    for _, p in model.named_parameters():
        p._data = p.value().astype("bfloat16")
    if tp > 1:
        from jax.sharding import Mesh
        from paddle_tpu.distributed.env import set_mesh
        devs = np.asarray(jax.devices()[:tp])
        if len(devs) < tp:
            raise SystemExit(f"--tp={tp} but only {len(devs)} devices")
        set_mesh(Mesh(devs.reshape(tp), ("model",)))
        shard_gpt_tp(model)

    slots, horizon = (4, 64) if tiny else (16, 256)
    faults = None
    if chaos:
        from paddle_tpu.serving import FaultSchedule
        # fixed schedule (the whole point: reproducible chaos): slow
        # decodes exercise the stall path, alloc denials inject
        # deterministic pool pressure (preemption), an admission fault
        # fails one request cleanly
        faults = FaultSchedule.parse(
            "slow@decode:3:0.01,slow@decode:11:0.01,"
            "raise@alloc:6,raise@alloc:17,raise@alloc:40,raise@admit:5")
    drafter = None
    if spec == "prompt_lookup":
        from paddle_tpu.serving import PromptLookupDrafter
        drafter = PromptLookupDrafter(max_n=3, min_n=1, max_k=8)
    elif spec == "draft_model":
        from paddle_tpu.serving import DraftModelDrafter
        # a genuinely small draft next to the target (tiny runs halve it)
        dsize = dict(size, num_layers=max(1, size["num_layers"] // 4),
                     hidden_size=size["hidden_size"] // 2,
                     num_heads=max(1, size["num_heads"] // 2))
        dcfg = GPTConfig(hidden_dropout_prob=0.0,
                         attention_dropout_prob=0.0, **dsize)
        dmodel = GPTForCausalLM(dcfg)
        for _, p in dmodel.named_parameters():
            p._data = p.value().astype("bfloat16")
        drafter = DraftModelDrafter(dmodel, ctx_len=horizon // 4, max_k=4)
    elif spec == "early_exit":
        from paddle_tpu.serving import EarlyExitDrafter
        drafter = EarlyExitDrafter(model, interval=2,
                                   ctx_len=horizon // 4, max_k=4)
    kv_pool = None
    if pool_flag:
        from paddle_tpu.serving import LocalPool
        kv_pool = LocalPool()
    if paged:
        engine = DecodeEngine(model, max_slots=slots, max_len=horizon,
                              paged=True, block_size=16,
                              prefill_chunk=16 if tiny else 32,
                              fault_schedule=faults, drafter=drafter,
                              kv_pool=kv_pool)
    else:
        engine = DecodeEngine(model, max_slots=slots, max_len=horizon,
                              paged=False,
                              prefill_buckets=[32 if tiny else 64])
    rng = np.random.RandomState(0)
    # shared-prefix serving workload: a common "system prompt" opens every
    # request (half the prompt) — on --paged the pager serves it from
    # shared blocks, which is the concurrency-at-fixed-bytes story. The
    # pool lane stretches it to cover full 16-token blocks: only whole
    # blocks export/adopt across processes
    sys_prefix = rng.randint(
        0, cfg.vocab_size,
        horizon // 4 if pool_flag else horizon // 8).tolist()
    lo = max(len(sys_prefix) + 4, horizon // 4)
    hi = horizon // 2
    ttfts = []
    if pool_flag:
        # previous incarnation: serve the shared prompt once, export its
        # parked blocks, die. The measured engine below starts with a
        # cold pager and a warm pool — the restart story under a clock.
        prev = DecodeEngine(model, max_slots=2, max_len=horizon,
                            paged=True, block_size=16,
                            prefill_chunk=16 if tiny else 32,
                            kv_pool=kv_pool)
        pr = prev.submit(sys_prefix + rng.randint(
            0, cfg.vocab_size, 4).tolist(), max_new_tokens=4)
        prev.run()
        assert pr.status == "done"
        exported = prev.pool_stats()["exports"]
        assert exported > 0, "pool lane: previous incarnation exported " \
                             "nothing (shared prefix shorter than a block?)"
        del prev

    def refill():
        # staggered prompt lengths and decode budgets: requests finish at
        # different steps, freeing slots the next refill re-admits into
        while engine.queue_depth + engine.active_count < engine.max_slots:
            n = int(rng.randint(lo, hi + 1))
            prompt = sys_prefix + rng.randint(
                0, cfg.vocab_size, n - len(sys_prefix)).tolist()
            kw = {}
            if chaos and n_submitted[0] % mod_e == mod_e - 1:
                kw["deadline_s"] = 0.0     # guaranteed expiry at next step
            r = engine.submit(prompt,
                              max_new_tokens=int(rng.randint(
                                  horizon // 4, horizon // 2)), **kw)
            reqs.append(r)
            all_reqs.append(r)       # never pruned: the drain-gate census
            if chaos and n_submitted[0] % mod_c == mod_c - 1:
                cancel_next.append(r)      # cancelled after the next step
            n_submitted[0] += 1

    def drain_ttfts():
        done = [r for r in reqs if r.t_first_token is not None]
        ttfts.extend(r.t_first_token - r.t_submit for r in done)
        reqs[:] = [r for r in reqs if r.t_first_token is None]

    reqs = []
    all_reqs = []      # every submission (drain_ttfts prunes reqs)
    n_submitted = [0]
    cancel_next = []
    # chaos cadence: every mod_e-th request carries an impossible deadline,
    # every mod_c-th is cancelled mid-flight (tiny runs submit ~5 requests,
    # so the cadence tightens to keep both paths exercised)
    mod_e, mod_c = (3, 4) if tiny else (6, 9)
    # warmup: ONE request through prefill + first decode mints every
    # executable (chunk + decode/verify) — filling all 16 slots first cost
    # a full batch of prefills before the first window could start, which
    # is why a budget-starved round used to die without emitting a line;
    # the remaining slots fill inside the first measured window instead
    n = int(rng.randint(lo, hi + 1))
    r = engine.submit(sys_prefix + rng.randint(
        0, cfg.vocab_size, n - len(sys_prefix)).tolist(),
        max_new_tokens=int(rng.randint(horizon // 4, horizon // 2)))
    reqs.append(r)
    all_reqs.append(r)
    n_submitted[0] += 1
    while engine.decode_steps == 0:
        engine.step()
    warm_compiles = engine.compile_count
    kind = jax.devices()[0].device_kind

    iters, windows = (4, 2) if tiny else (20, 6)
    best = 0.0
    for w in range(windows):
        _heartbeat("decode_window_open", w)
        tok0 = engine.tokens_generated
        t0 = time.time()
        for _ in range(iters):
            refill()
            engine.step()   # host readback of the step's tokens syncs
            while cancel_next:
                engine.cancel(cancel_next.pop())
        dt = time.time() - t0
        drain_ttfts()
        best = max(best, (engine.tokens_generated - tok0) / dt)
        q = (lambda v, p: float(np.percentile(v, p)) if v else None)
        chips = max(tp, 1)
        pager = engine._pager if paged else None
        chaos_fields = ({"chaos": True, "expired": engine.expired,
                         "cancelled": engine.cancelled,
                         "preemptions": engine.preemptions}
                        if chaos else {})
        spec_fields = ({"spec": spec,
                        "accepted_per_step":
                            round(engine.spec_emitted
                                  / max(engine.spec_steps, 1), 3),
                        "draft_hit_rate":
                            round(engine.spec_accepted
                                  / max(engine.spec_drafted, 1), 3)}
                       if spec else {})
        pool_fields = {}
        if pool_flag:
            ps = engine.pool_stats()
            pool_fields = {
                "pool": True,
                "pool_hit_rate": round(pager.pool_hits
                                       / max(n_submitted[0], 1), 3),
                "adopted_tokens": ps["adopted_tokens"],
                "pool_fetch_hits": ps["fetch_hits"],
                "pool_exports": ps["exports"],
            }
        print(json.dumps(dict(_fleet_fields(), **_trace_fields(),
                              **_health_fields(),
                              **chaos_fields, **spec_fields,
                              **pool_fields, **{
            "metric": "gpt_medium_decode_tokens_per_sec_per_chip",
            "value": round(best / chips, 1),
            "unit": "tokens/s (decode)",
            "vs_baseline": (round(best / chips / REF_DECODE_TOKENS_PER_SEC,
                                  3) if REF_DECODE_TOKENS_PER_SEC else None),
            "paged": paged,
            "tp": chips,
            "tokens_per_sec_total": round(best, 1),
            "prefix_hit_rate": (round(pager.prefix_hits
                                      / max(n_submitted[0], 1), 3)
                                if pager is not None else None),
            "prefix_hit_tokens": (pager.prefix_hit_tokens
                                  if pager is not None else None),
            "kv_util": round(engine.kv_util(), 3),
            "ttft_p50_ms": (round(q(ttfts, 50) * 1e3, 2) if ttfts else None),
            "ttft_p95_ms": (round(q(ttfts, 95) * 1e3, 2) if ttfts else None),
            "live_slots": engine.live_count,
            "compiles": engine.compile_count,
            "steady_state_recompiles": engine.compile_count - warm_compiles,
            "nan_logits": engine.nan_logits,
            "device_kind": kind,
            "window": w,
        })))
        sys.stdout.flush()
    if chaos:
        # finish the story: the engine must also DRAIN cleanly after the
        # fault storm (door closes, live slots finish within grace) and
        # the pager's invariants must hold — printed as a final JSON line
        # so the driver sees survival, not just throughput
        t0 = time.time()
        engine.drain(grace_s=30.0)
        engine._pager.check_invariants()
        terminal = sum(r.finished for r in all_reqs)
        print(json.dumps({
            "metric": "decode_chaos_drain",
            "drained": engine.drained,
            "drain_s": round(time.time() - t0, 3),
            "submitted": n_submitted[0],
            "terminal": terminal,
            "expired": engine.expired,
            "cancelled": engine.cancelled,
            "preemptions": engine.preemptions,
            "invariants": "ok",
        }))
        sys.stdout.flush()
        assert terminal == len(all_reqs), \
            f"{len(all_reqs) - terminal} request(s) not terminal after drain"


if __name__ == "__main__":
    sys.exit(main_decode(sys.argv[1:]) if "decode" in sys.argv[1:]
             else main(sys.argv[1:]))
