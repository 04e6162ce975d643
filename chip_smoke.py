#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

One process, holding the chip, drives the two main paths once through the
entry points a user calls, at the FULL width of a GPT-medium
(vocab 50,304, hidden 1024, 16 layers, 8 heads of head_dim 128, 1024
positions, bf16 params with fp32 masters), on random weights from a seed:

  1. device gate   versions + device; anything but a TPU exits non-zero
  2. trainer       Dataset -> DataLoader -> io.DeviceLoader -> jit.TrainStep
                   (AdamW, multi_precision) at B=16, S=1024
  3. kernel        flash_pair_packed and paged_decode_attention compiled by
                   Mosaic vs plain jax.numpy
                   causal attention, forward and d(qkv), at that shape
  4. server        serving.DecodeEngine(paged, chunked prefill) answering 8
                   staggered requests that share a 64-token prefix

Every phase asserts what came out; any failed assertion or exception ends
the process non-zero (no try/except around a phase). Every number printed is
a SMOKE OBSERVATION — one unrepeated run, compilation included where said —
not a benchmark value. The last stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

    python chip_smoke.py                  # one chip (the driver's check)
    python chip_smoke.py --chips 4        # one process, four chips: ZeRO
                                          # os_g trainer, TP=4 server, and
                                          # __graft_entry__._dryrun_body(4)
    python chip_smoke.py --rehearse-cpu   # tiny CPU rehearsal of the same
                                          # command (Pallas interpreted);
                                          # labelled as such, never implied
                                          # by the absence of a chip
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

SEED = 0
FULL = dict(
    model=dict(vocab_size=50304, hidden_size=1024, num_layers=16,
               num_heads=8, max_position_embeddings=1024),
    batch=16, seq=1024, steps=5,
    engine=dict(max_slots=16, max_len=1024, block_size=16, prefill_chunk=32),
    prefix=64,
    prompt_lens=(80, 128, 192, 256, 320, 384, 448, 512),
    new_tokens=(32, 48, 64, 80, 96, 112, 128, 40),
    kernel_shape=(2, 1024, 8, 128),
    # GPT-3 XL's decode step: slots, heads, head width, block, table width
    paged_shape=(32, 16, 128, 16, 128))
# same code, toy sizes: what --rehearse-cpu runs (widths keep head_dim 128
# and S >= 128 so the model still takes the packed-qkv Pallas branch)
REHEARSAL = dict(
    model=dict(vocab_size=512, hidden_size=256, num_layers=2, num_heads=2,
               max_position_embeddings=256),
    batch=4, seq=128, steps=5,
    engine=dict(max_slots=4, max_len=256, block_size=16, prefill_chunk=32),
    prefix=32,
    prompt_lens=(40, 56, 72, 96),
    new_tokens=(8, 12, 16, 10),
    kernel_shape=(1, 256, 2, 128),
    paged_shape=(4, 2, 128, 16, 8))

BF16_ULP = 2.0 ** -8        # relative spacing of bfloat16


def say(msg):
    print(f"[chip_smoke] {msg}", flush=True)


def hbm(jax):
    """{device id: (bytes_in_use, peak_bytes_in_use)}; zeros where the
    backend keeps no allocator stats (CPU rehearsal)."""
    out = {}
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        out[d.id] = (int(st.get("bytes_in_use", 0)),
                     int(st.get("peak_bytes_in_use", 0)))
    return out


def shard_evidence(name, arr, n_dev):
    """Assert FROM THE ARRAY that it lives on n_dev devices in shard-sized
    pieces (code that has only seen a virtual mesh may have put everything
    on jax.devices()[0])."""
    devs = arr.sharding.device_set
    shard = arr.addressable_shards[0].data
    assert len(devs) == n_dev, \
        f"{name}: on {len(devs)} device(s), expected {n_dev}"
    assert shard.size * n_dev == arr.size, \
        f"{name}: shard {shard.shape} of {arr.shape} is not 1/{n_dev}"
    return f"{name} {tuple(arr.shape)} -> {n_dev} x {tuple(shard.shape)}"


# ----------------------------------------------------------------- phases


def build_model(paddle, cfg_kw):
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    paddle.seed(SEED)
    model = GPTForCausalLM(GPTConfig(hidden_dropout_prob=0.0,
                                     attention_dropout_prob=0.0, **cfg_kw))
    # AMP-O2 analog: bf16 working params, fp32 masters in AdamW
    for _, p in model.named_parameters():
        p._data = p.value().astype("bfloat16")
    return model


def phase_trainer(jax, paddle, size, n_dev, on_tpu):
    import numpy as np
    from paddle_tpu.io import DataLoader, Dataset, DeviceLoader, batch_sharding

    vocab, batch, seq, steps = (size["model"]["vocab_size"], size["batch"],
                                size["seq"], size["steps"])

    class SyntheticTokens(Dataset):
        """`steps` identical batches of seeded uniform tokens: sample i is a
        pure function of SEED and i % batch, so the loss must fall as the
        model memorizes the one batch."""

        def __len__(self):
            return batch * steps

        def __getitem__(self, i):
            ids = np.random.RandomState(SEED + i % batch).randint(
                0, vocab, seq).astype("int32")
            return ids, ids

    model = build_model(paddle, size["model"])
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                                 parameters=model.parameters(),
                                 multi_precision=True)
    sharding = None
    evidence = []
    if n_dev > 1:
        import paddle_tpu.distributed as dist
        from paddle_tpu.distributed import fleet
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                                   "pp_degree": 1, "sep_degree": 1,
                                   "sharding_degree": n_dev}
        fleet.init(is_collective=True, strategy=strategy)
        sharding = batch_sharding(dist.get_mesh())
        wrapped, opt, _ = dist.group_sharded_parallel(model, opt,
                                                      level="os_g")
    else:
        wrapped = model
    step = paddle.jit.TrainStep(wrapped, opt)
    loader = DeviceLoader(DataLoader(SyntheticTokens(), batch_size=batch),
                          prefetch_depth=2, sharding=sharding)

    losses, times = [], []
    for ids, labels in loader:
        t0 = time.perf_counter()
        loss = float(step(ids, labels))      # value fetch closes the window
        times.append(time.perf_counter() - t0)
        losses.append(loss)
    loader.close()
    compile_s, step_s = times[0], times[1:]
    say(f"trainer: losses {[round(x, 4) for x in losses]}")
    say(f"trainer: smoke observation — first call (compile + 1 step) "
        f"{compile_s:.1f}s; next {len(step_s)} steps "
        f"{[round(t, 3) for t in step_s]}s each (one run, not a benchmark)")

    assert len(losses) == steps and len(step_s) >= 4
    assert all(math.isfinite(x) for x in losses), losses
    # random init: the first loss sits at ln(vocab) (10.83 at 50,304)
    # within the spread init noise gives it
    assert abs(losses[0] - math.log(vocab)) < 0.5, \
        f"first loss {losses[0]} vs ln({vocab}) = {math.log(vocab):.3f}"
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    assert step.num_compiles == 1, step.num_compiles
    hlo = next(iter(step._fast.values())).as_text()
    n_kernel = hlo.count("tpu_custom_call")
    if on_tpu:
        # the packed-qkv Pallas branch was taken (models/gpt.py ->
        # F.flash_attention_qkv_packed), not the XLA softmax chain
        assert n_kernel > 0, \
            "no tpu_custom_call in the train executable: the attention " \
            "gate routed to the XLA path on a TPU"
    say(f"trainer: num_compiles {step.num_compiles}, tpu_custom_call x"
        f"{n_kernel} in the AOT executable")

    if n_dev > 1:
        some_pid = next(iter(step._opt._master_weights))
        evidence.append(shard_evidence(
            "fp32 master", step._opt._master_weights[some_pid], n_dev))
        for k, v in step._opt._accumulators[some_pid].items():
            if getattr(v, "ndim", 0) >= 1:
                evidence.append(shard_evidence(f"adam {k}", v, n_dev))
        assert len(ids.value().sharding.device_set) == n_dev, \
            "batch not sharded over the mesh"
        for line in evidence:
            say(f"trainer: {line}")
    mem = hbm(jax)
    if on_tpu and n_dev > 1:
        assert all(use > 0 for use, _ in mem.values()), mem
    say(f"trainer: smoke observation — HBM (in_use, peak) bytes per device "
        f"{mem}")

    obs = {"losses": losses, "compile_plus_first_step_s": compile_s,
           "step_s": step_s, "num_compiles": step.num_compiles,
           "tpu_custom_calls": n_kernel, "hbm_in_use_peak": mem,
           "shards": evidence}
    # free the train state (3.7 GB of params, masters and moments on one
    # chip) before the engine allocates its pools
    del step, opt, wrapped, loader, ids, labels, loss
    gc.collect()
    return model, obs


def phase_kernel(jax, size, on_tpu):
    import jax.numpy as jnp
    from paddle_tpu.kernels.pallas.flash_pair import flash_pair_packed

    b, L, heads, d = size["kernel_shape"]
    qkv = jax.random.normal(jax.random.PRNGKey(SEED + 1),
                            (b, L, 3 * heads * d),
                            jnp.float32).astype(jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(SEED + 2), (b, L, heads * d),
                          jnp.float32)

    def reference(x):
        with jax.default_matmul_precision("highest"):
            q, k, v = (t.reshape(b, L, heads, d) for t in
                       jnp.split(x.astype(jnp.float32), 3, axis=-1))
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
            s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -jnp.inf)
            o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
            return o.reshape(b, L, heads * d)

    def kernel(x):          # Mosaic on the chip; interpreted only in rehearsal
        return flash_pair_packed(x, heads, True, interpret=not on_tpu)

    def both(f):
        out, vjp = jax.vjp(lambda x: f(x).astype(jnp.float32), qkv)
        return out, vjp(w)[0].astype(jnp.float32)

    o_k, g_k = jax.jit(lambda: both(kernel))()
    o_r, g_r = jax.jit(lambda: both(reference))()
    obs = {}
    for name, got, want in (("forward", o_k, o_r), ("d(qkv)", g_k, g_r)):
        scale = float(jnp.max(jnp.abs(want)))
        err = float(jnp.max(jnp.abs(got - want)))
        # bf16 outputs of fp32-accumulated dots: a few ulps of the largest
        # magnitude (first chip run: 0.11 and 0.16 of this bound); a wrong
        # tile or mask is O(scale)
        tol = 4 * BF16_ULP * scale
        say(f"kernel: {name} max|err| {err:.4g} (tol {tol:.4g} = 4 bf16 "
            f"ulp of max|ref| {scale:.3g})")
        assert math.isfinite(err) and err <= tol, (name, err, tol)
        obs[name] = {"max_abs_err": err, "tol": tol}
    obs["paged_decode"] = check_paged_decode(jax, size, on_tpu)
    return obs


def check_paged_decode(jax, size, on_tpu):
    """The paged decode kernel (Mosaic on the chip) against plain jax.numpy
    over the same pools: every slot's whole table gathered, float32 scores
    masked past the slot's length, softmax, context."""
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.kernels.pallas.paged_decode import paged_decode_attention

    b, nh, hd, bs, mbs = size["paged_shape"]
    nb = b * mbs // 4
    keys = jax.random.split(jax.random.PRNGKey(SEED + 4), 3)
    q = jax.random.normal(keys[0], (b, 1, nh, hd),
                          jnp.float32).astype(jnp.bfloat16)
    pool_k, pool_v = (jax.random.normal(k, (nb, bs, nh, hd), jnp.float32)
                      .astype(jnp.bfloat16) for k in keys[1:])
    rng = np.random.RandomState(SEED + 4)
    table = jnp.asarray(rng.randint(1, nb, (b, mbs)), jnp.int32)
    top = mbs * bs
    lengths = rng.randint(1, top + 1, b)
    lengths[:4] = (1, bs - 1, bs + 1, top)      # the edges, always
    lengths = jnp.asarray(lengths, jnp.int32)

    def reference(q, pool_k, pool_v):
        with jax.default_matmul_precision("highest"):
            k, v = (jnp.take(p, table, axis=0).reshape(b, top, nh, hd)
                    .astype(jnp.float32) for p in (pool_k, pool_v))
            s = jnp.einsum("bqnd,bknd->bnqk", q.astype(jnp.float32),
                           k) / math.sqrt(hd)
            live = jnp.arange(top)[None, None, None, :] \
                < lengths[:, None, None, None]
            p = jax.nn.softmax(jnp.where(live, s, -1e30), axis=-1)
            return jnp.einsum("bnqk,bknd->bqnd", p, v)

    def kernel(q, pool_k, pool_v):
        return paged_decode_attention(q, pool_k, pool_v, table, lengths,
                                      interpret=not on_tpu)

    got = jax.jit(kernel)(q, pool_k, pool_v).astype(jnp.float32)
    want = jax.jit(reference)(q, pool_k, pool_v)
    scale = float(jnp.max(jnp.abs(want)))
    err = float(jnp.max(jnp.abs(got - want)))
    # a bf16 output of float32 accumulation: its own rounding, half an ulp
    # of the largest magnitude; a wrong block, mask or head is O(scale)
    tol = 2 * BF16_ULP * scale
    say(f"kernel: paged_decode {tuple(size['paged_shape'])} max|err| "
        f"{err:.4g} (tol {tol:.4g} = 2 bf16 ulp of max|ref| {scale:.3g})")
    assert math.isfinite(err) and err <= tol, ("paged_decode", err, tol)
    return {"max_abs_err": err, "tol": tol}


def phase_server(jax, paddle, model, size, n_dev, on_tpu):
    import numpy as np
    from paddle_tpu.serving import DecodeEngine

    vocab = size["model"]["vocab_size"]
    rng = np.random.RandomState(SEED + 3)
    prefix = rng.randint(0, vocab, size["prefix"]).tolist()
    prompts = [prefix + rng.randint(0, vocab, n - len(prefix)).tolist()
               for n in size["prompt_lens"]]
    budgets = list(size["new_tokens"])

    # reference first (and, with TP, before the weights are sharded): ONE
    # plain model(ids) forward over the prompts right-padded to a common
    # length — causal attention makes row n-1 independent of the padding
    model.eval()
    width = max(len(p) for p in prompts)
    padded = np.zeros((len(prompts), width), "int32")
    for i, p in enumerate(prompts):
        padded[i, :len(p)] = p
    with paddle.no_grad():
        logits = model(paddle.to_tensor(padded)).value()
    ref_rows = [np.asarray(logits[i, len(p) - 1], np.float32)
                for i, p in enumerate(prompts)]
    del logits

    if n_dev > 1:
        from jax.sharding import Mesh
        from paddle_tpu.distributed.env import set_mesh
        from paddle_tpu.models import shard_gpt_tp
        set_mesh(Mesh(np.asarray(jax.devices()[:n_dev]), ("model",)))
        shard_gpt_tp(model)
    engine = DecodeEngine(model, **size["engine"])
    pager = engine._pager

    # warm-up request alone: mints the chunk + decode executables, and when
    # it finishes its prompt blocks PARK — the next admission of the shared
    # prefix is then a prefix-cache hit, not just live sharing
    reqs = [engine.submit(prompts[0], max_new_tokens=budgets[0])]
    t0 = time.perf_counter()
    engine.run()
    warm_s = time.perf_counter() - t0
    warm_mints = engine.compile_count
    # staggered arrivals: one submit per scheduler iteration
    t0 = time.perf_counter()
    for p, n in zip(prompts[1:], budgets[1:]):
        reqs.append(engine.submit(p, max_new_tokens=n))
        engine.step()
    engine.run()
    serve_s = time.perf_counter() - t0
    n_tok = sum(len(r.tokens) for r in reqs[1:])
    say(f"server: smoke observation — warm-up request (mints "
        f"{warm_mints} executables) {warm_s:.1f}s; then {len(reqs) - 1} "
        f"requests / {n_tok} tokens in {serve_s:.2f}s (one run, not a "
        f"benchmark)")

    worst = 0.0
    for r, n, ref in zip(reqs, budgets, ref_rows):
        assert r.status == "done", (r.id, r.status, r.error)
        assert len(r.tokens) == n, (r.id, len(r.tokens), n)
        assert all(0 <= t < vocab for t in r.tokens), r.id
        # tie-tolerant numerics: the engine's first token must score, in the
        # plain forward's logits, within a bf16 margin of that forward's
        # maximum — an argmax tie cannot fail it, a wrong cache write
        # (a token that is not near the top of 50k) cannot pass it
        top = float(ref.max())
        margin = 8 * BF16_ULP * max(abs(top), 1.0)
        deficit = top - float(ref[r.tokens[0]])
        worst = max(worst, deficit / margin)
        assert deficit <= margin, \
            f"request {r.id}: first token {r.tokens[0]} scores {deficit:.4f}" \
            f" below the reference max {top:.4f} (margin {margin:.4f})"
    assert engine.nan_logits == 0, engine.nan_logits
    # what the decode executable attended with: the Pallas kernel on one
    # chip, the gathered view under tensor parallelism (pools sharded)
    attention = engine.stats()["decode_attention"]
    say(f"server: decode_attention {attention}")
    assert attention == ("paged_kernel" if n_dev == 1 else "gather"), \
        attention
    # and how its executables wrote the pools: block copies on one chip,
    # XLA's scatter under tensor parallelism
    kv_write = set(engine.stats()["kv_write"].values())
    say(f"server: kv_write {sorted(kv_write)}")
    assert kv_write == {"kernel" if n_dev == 1 else "scatter"}, kv_write
    assert pager.prefix_hits >= 1, pager.prefix_hits
    assert engine.compile_count == warm_mints, \
        f"steady-state recompiles: {engine.compile_count - warm_mints}"
    pager.check_invariants()
    say(f"server: {len(reqs)} requests done with exact budgets; first-token "
        f"deficit at most {worst:.2f} of the bf16 margin; prefix_hits "
        f"{pager.prefix_hits}, shared_hits {pager.shared_hits}, "
        f"steady_state_recompiles 0, nan_logits 0, pager invariants ok")

    evidence = []
    if n_dev > 1:
        assert engine._tp == n_dev, engine._tp
        kpool, vpool = engine._pools[0]
        evidence = [shard_evidence("layer-0 K pool", kpool, n_dev),
                    shard_evidence("layer-0 V pool", vpool, n_dev)]
        for line in evidence:
            say(f"server: {line}")
    mem = hbm(jax)
    if on_tpu and n_dev > 1:
        assert all(use > 0 for use, _ in mem.values()), mem
    say(f"server: smoke observation — HBM (in_use, peak) bytes per device "
        f"{mem}")
    obs = {"requests": len(reqs), "tokens": sum(len(r.tokens) for r in reqs),
           "warm_mints": warm_mints, "warmup_s": warm_s, "serve_s": serve_s,
           "prefix_hits": int(pager.prefix_hits),
           "shared_hits": int(pager.shared_hits),
           "decode_attention": attention, "kv_write": sorted(kv_write),
           "steady_state_recompiles": 0, "first_token_worst_margin": worst,
           "hbm_in_use_peak": mem, "shards": evidence}
    engine.close()
    return obs


# ------------------------------------------------------------------- main


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: one process drives four chips (ZeRO trainer, "
                         "TP=4 server, multichip dryrun body)")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny CPU rehearsal of this command; output is "
                         "labelled a rehearsal on cpu")
    args = ap.parse_args(argv)

    if args.rehearse_cpu:
        # explicit, and decided BEFORE jax starts: never reached by the
        # absence of a chip
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}")
    t_start = time.perf_counter()

    # ---- 1. device gate
    import importlib.metadata as md
    import jax
    import jaxlib
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, libtpu "
        f"{md.version('libtpu')}; device {device}")
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not args.rehearse_cpu:
        print(f"chip_smoke.py: no TPU — jax.devices()[0] is "
              f"{device['platform']}:{device['kind']} (JAX_PLATFORMS="
              f"{os.environ.get('JAX_PLATFORMS')!r}). This smoke only means "
              f"something on the chip and does not continue on a CPU.",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke.py: --chips {args.chips} but jax sees "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 2
    size = REHEARSAL if args.rehearse_cpu else FULL
    if args.rehearse_cpu:
        say("REHEARSAL on cpu at toy sizes: exercises the command, proves "
            "nothing about the chip")

    import paddle_tpu as paddle
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    say(f"compile cache: {enable_compile_cache()}")

    n_dev = args.chips
    if args.rehearse_cpu:
        # rehearsal only: answer the attention gate as the chip would and
        # run the kernel through the Pallas interpreter, so the rehearsal
        # walks the model branch the chip run asserts on (packed-qkv flash
        # and, on the virtual mesh, its partitioning rule)
        import functools
        from paddle_tpu.kernels.pallas import flash_pair
        from paddle_tpu.nn.functional import attention
        attention.flash_path_available = \
            lambda seq_len, head_dim, sample=None: \
            seq_len >= 128 and head_dim >= 64
        flash_pair.flash_pair_packed = functools.partial(
            flash_pair.flash_pair_packed, interpret=True)
        # and the decode step takes the paged kernel as it does on one chip
        from paddle_tpu.kernels.pallas import paged_decode, pool_write
        seam = paged_decode.force_interpret()    # held to the end of main
        seam.__enter__()
        writes = pool_write.force_interpret()    # and the pools' writes
        writes.__enter__()

    phases = {}
    say(f"--- trainer ({n_dev} chip(s))")
    model, phases["trainer"] = phase_trainer(jax, paddle, size, n_dev, on_tpu)
    say("--- kernel vs reference")
    phases["kernel"] = phase_kernel(jax, size, on_tpu)
    if n_dev > 1:
        # the ZeRO-trained weights live replicated on the "sharding" mesh;
        # TP serving gets a fresh model on its own "model" mesh
        del model
        gc.collect()
        model = build_model(paddle, size["model"])
    say(f"--- server ({n_dev} chip(s))")
    phases["server"] = phase_server(jax, paddle, model, size, n_dev, on_tpu)
    if n_dev > 1:
        say(f"--- multichip dryrun body on the {n_dev} real devices")
        del model
        gc.collect()
        import __graft_entry__
        __graft_entry__._dryrun_body(n_dev)
        phases["dryrun_body"] = "ok"

    say("all phases passed")
    print(json.dumps({"smoke_observations": phases,
                      "rehearsal": bool(args.rehearse_cpu),
                      "chips_driven": n_dev,
                      "wall_s": round(time.perf_counter() - t_start, 1)}),
          flush=True)
    result = {"ok": True, "device": device}
    if args.rehearse_cpu:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
