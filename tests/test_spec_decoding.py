"""Speculative decoding through the chunk executable (ISSUE 16).

The contract under test:
  * Bitwise greedy parity: with ANY drafter installed (prompt-lookup,
    draft-model, early-exit) the engine's output equals the eager loop
    token-for-token, for GPT and LLaMA, across prefix sharing, COW,
    chunked prefill and pool-pressure preemption — speculation changes
    latency, never tokens.
  * Zero steady-state recompiles with a drafter on: drafts ride as ids
    DATA through one fixed-width verify executable, and model drafters
    mint exactly one AOT executable of their own (``compile_count`` on
    both sides is the sentinel), single-chip AND on a TP=2 mesh.
  * Paged accept/reject: ``reserve_speculative`` never preempts, stops at
    the first unallocatable block, and ``rollback_speculative`` restores
    the pre-reservation table exactly (COW sources re-referenced, LRU
    revival included, trash for fresh extensions) — ``check_invariants``
    holds through every path, including the randomized property test in
    test_prefix_cache.py.
  * Accounting: serve tokens / tokens_per_s_chip / serve/flops_per_token
    count ACCEPTED tokens only; rejected-draft verify FLOPs ride HFU.
  * Chaos: raise@verify fails the engine loudly with invariants held;
    raise@spec_reserve degrades to a one-token verify with parity intact.
  * Telemetry: serve/spec_* counters + the accepted-per-step gauge are
    live, metrics_summary renders the speculation sub-block with the
    per-drafter breakdown and WARNs on the wasted-work signature.
"""
import io
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.distributed import env as denv
from paddle_tpu.models import GPTConfig, GPTForCausalLM, shard_gpt_tp
from paddle_tpu.serving import (BlockPager, DecodeEngine, DraftModelDrafter,
                                EarlyExitDrafter, FaultSchedule,
                                InjectedFault, PromptLookupDrafter)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny_gpt(seed=0):
    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
                    max_position_embeddings=64, hidden_dropout_prob=0.0,
                    attention_dropout_prob=0.0, use_flash_attention=False)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _tiny_llama(seed=7):
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
    paddle.seed(seed)
    lm = LlamaForCausalLM(llama_tiny(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, max_position_embeddings=64))
    lm.eval()
    return lm


def _eager(m, prompt, n):
    ids = np.asarray([prompt], np.int32)
    return m.generate(paddle.to_tensor(ids),
                      max_new_tokens=n).numpy()[0, len(prompt):]


def _make_drafter(which, target):
    """Fresh drafter per engine. The draft model is a DIFFERENT random
    model (seed 11), so its guesses genuinely disagree with the target
    sometimes — the reject path is exercised, not just the accept path."""
    if which == "prompt_lookup":
        return PromptLookupDrafter(max_n=3, min_n=1, max_k=8)
    if which == "draft_model":
        return DraftModelDrafter(_tiny_gpt(seed=11), ctx_len=32, max_k=4)
    return EarlyExitDrafter(target, interval=2, ctx_len=32, max_k=4)


@pytest.fixture(scope="module")
def tiny():
    return _tiny_gpt()


@pytest.fixture
def model_mesh():
    """Same contract as test_tp_serving: install a "model"-axis mesh,
    restore whatever was there on the way out."""
    import jax
    from jax.sharding import Mesh

    def make(tp):
        devs = np.asarray(jax.devices()[:tp])
        mesh = Mesh(devs.reshape(tp), ("model",))
        denv.set_mesh(mesh)
        return mesh

    old_mesh = denv._env["mesh"]
    old_init = denv._env["initialized"]
    try:
        yield make
    finally:
        denv._env["mesh"] = old_mesh
        denv._env["initialized"] = old_init


# ------------------------------------------------------- drafter unit tests


def test_prompt_lookup_proposes_continuations():
    class R:
        prompt = [1, 2, 3, 4, 2, 3]
        tokens = []

    d = PromptLookupDrafter(max_n=3, min_n=1, max_k=8)
    # trailing 2-gram [2, 3] matched at i=1; the continuation follows it
    assert d.propose(R(), 8) == [4, 2, 3]
    assert d.propose(R(), 2) == [4, 2]          # k clamp
    assert d.propose(R(), 0) == []

    class NoMatch:
        prompt = [1, 2, 3]
        tokens = []

    assert d.propose(NoMatch(), 4) == []

    class Gen:
        prompt = [9, 8]
        tokens = [7, 9, 8]                       # history spans the boundary

    # trailing [9, 8] occurred at the prompt head; continuation crosses
    # into the generated tokens
    assert d.propose(Gen(), 4) == [7, 9, 8]

    with pytest.raises(ValueError):
        PromptLookupDrafter(max_n=1, min_n=2)


def test_spec_requires_greedy(tiny):
    with pytest.raises(NotImplementedError, match="greedy"):
        DecodeEngine(tiny, max_slots=2, max_len=32, block_size=8,
                     prefill_chunk=8, do_sample=True,
                     drafter=PromptLookupDrafter())


# --------------------------------------------------- tentpole: bitwise parity


@pytest.mark.parametrize("which", ["prompt_lookup", "draft_model",
                                   "early_exit"])
def test_spec_parity_gpt_full_machinery(tiny, which):
    """GPT through the speculative engine: greedy tokens equal the eager
    loop across sharing + COW + chunked prefill, for every drafter."""
    drafter = _make_drafter(which, tiny)
    rng = np.random.RandomState(0)
    prefix = rng.randint(1, 64, 10).tolist()
    prompts = [prefix + [50, 51, 52], prefix + [50, 51, 52],  # share + COW
               rng.randint(1, 64, 20).tolist(),               # chunked
               rng.randint(1, 64, 5).tolist()]
    horizons = [8, 8, 6, 10]
    refs = [_eager(tiny, p, h) for p, h in zip(prompts, horizons)]
    eng = DecodeEngine(tiny, max_slots=4, max_len=48, block_size=8,
                       prefill_chunk=8, drafter=drafter)
    lead = eng.submit(prompts[0], max_new_tokens=horizons[0])
    # publish the shared prefix first; a speculative step can take the
    # lead from prefilling straight to done (promote + k accepted drafts
    # in ONE step), so wait on the prefill phases, not on "running"
    while lead.status in ("queued", "prefilling"):
        eng.step()
    reqs = [lead] + [eng.submit(p, max_new_tokens=h)
                     for p, h in zip(prompts[1:], horizons[1:])]
    eng.run()
    for p, r, ref in zip(prompts, reqs, refs):
        assert r.status == "done", r
        np.testing.assert_array_equal(ref, r.output_tokens)
    eng._pager.check_invariants()
    st = eng.stats()
    assert st["paged"]["shared_hits"] >= 1
    spec = st["spec"]
    assert spec["drafter"] == drafter.name
    assert spec["steps"] > 0 and spec["emitted"] >= spec["steps"]
    assert spec["accepted"] <= spec["drafted"]
    # per-request ledgers sum to the engine's
    assert sum(r.spec_drafted for r in reqs) == spec["drafted"]
    assert sum(r.spec_accepted for r in reqs) == spec["accepted"]
    if which == "early_exit":
        # half the layers of a 2-layer model still predict the next token
        # often enough to beat one-token-per-dispatch
        assert spec["accepted_per_step"] > 1.0, spec


@pytest.mark.parametrize("which", ["prompt_lookup", "draft_model",
                                   "early_exit"])
def test_a_drafters_chunks_go_through_the_plan(tiny, which):
    """A drafter's engine admits, plans, launches and collects its prefill
    chunks as every engine does: two slots mid-prefill in one step have
    their chunks launched back to back and read back once, under one
    ``engine/collect``; no step is prepared ahead (``plan`` reads
    ``sync``); and the streams are the no-drafter engine's."""
    import time
    from paddle_tpu.monitor import trace
    rng = np.random.RandomState(21)
    prompts = [rng.randint(1, 64, n).tolist() for n in (20, 19)]
    geo = dict(max_slots=4, max_len=48, block_size=8, prefill_chunk=8)
    plain = DecodeEngine(tiny, **geo)
    want = [plain.submit(p, max_new_tokens=9) for p in prompts]
    plain.run()
    eng = DecodeEngine(tiny, drafter=_make_drafter(which, tiny), **geo)
    reqs = [eng.submit(p, max_new_tokens=9) for p in prompts]
    t0 = time.perf_counter()
    eng.step()
    t1 = time.perf_counter()
    assert [r.status for r in reqs] == ["prefilling"] * 2
    (step,) = trace.spans(t0, t1, "engine/step")
    assert step.attrs["plan"] == "sync"
    (collect,) = trace.spans(t0, t1, "engine/collect")
    calls = trace.spans(t0, t1, "engine/prefill_call")
    assert len(calls) == 2
    assert calls[0].t1 <= calls[1].t0 and calls[1].t1 <= collect.t0
    assert eng._plan is None                 # nothing is prepared ahead
    eng.run()
    assert [r.tokens for r in reqs] == [r.tokens for r in want]
    st = eng.stats()
    assert st["plan"]["prepared"] == st["plan"]["rebuilt"] == 0
    assert st["plan"]["sync"] == 3           # the steps that ran chunks
    assert st["spec"]["steps"] > 0
    eng._pager.check_invariants()


@pytest.mark.parametrize("which", ["prompt_lookup", "draft_model",
                                   "early_exit"])
def test_spec_parity_llama_with_sharing(which):
    """LLaMA (GQA + RoPE) through the speculative engine with prefix
    sharing; the draft-model arm drafts with a GPT — cross-family drafting
    is legal because only token ids cross the interface."""
    lm = _tiny_llama()
    drafter = _make_drafter(which, lm)
    rng = np.random.RandomState(7)
    prefix = rng.randint(1, 64, 10).tolist()
    pa, pb = prefix + [7], prefix + [9]
    refs = [_eager(lm, p, 6) for p in (pa, pb)]
    eng = DecodeEngine(lm, max_slots=2, max_len=32, block_size=4,
                       prefill_chunk=4, drafter=drafter)
    ra = eng.submit(pa, max_new_tokens=6)
    while ra.status in ("queued", "prefilling"):   # spec can skip "running"
        eng.step()
    rb = eng.submit(pb, max_new_tokens=6)
    eng.run()
    assert eng.stats()["paged"]["shared_hits"] >= 1
    for ref, r in zip(refs, (ra, rb)):
        assert r.status == "done"
        np.testing.assert_array_equal(ref, r.output_tokens)
    eng._pager.check_invariants()
    assert eng.spec_steps > 0


def test_spec_parity_across_preemption(tiny):
    """Pool-pressure preemption with speculation on: recompute-on-
    readmission resets the drafter state with the token history, and
    greedy output still equals the eager loop. Speculative reservations
    themselves never preempt (asserted via the pager stats: the
    preemptions that do happen come from admissions/decode extends)."""
    eng = DecodeEngine(tiny, max_slots=4, max_len=48, block_size=8,
                       kv_blocks=9, prefill_chunk=8,
                       drafter=PromptLookupDrafter())
    rng = np.random.RandomState(6)
    prompts = [rng.randint(1, 64, 20).tolist() for _ in range(4)]
    reqs = [eng.submit(p, max_new_tokens=20) for p in prompts]
    eng.run(max_steps=600)
    assert all(r.status == "done" for r in reqs)
    assert eng.preemptions > 0
    for p, r in zip(prompts, reqs):
        np.testing.assert_array_equal(_eager(tiny, p, 20), r.output_tokens)
    eng._pager.check_invariants()


def test_spec_zero_steady_state_recompiles(tiny):
    """The recompile gate with a MODEL drafter on: after warmup, a churn
    wave (sharing, COW, fresh allocs, chunking) mints nothing — on the
    engine's counter AND the drafter's own sentinel (one [1, ctx_len]
    executable, ever)."""
    drafter = EarlyExitDrafter(tiny, interval=2, ctx_len=32, max_k=4)
    eng = DecodeEngine(tiny, max_slots=4, max_len=48, block_size=8,
                       prefill_chunk=8, drafter=drafter)
    warm = eng.submit([1, 2, 3], max_new_tokens=4)
    eng.run()
    assert warm.status == "done"
    assert drafter.compile_count == 1
    base = eng.compile_count
    rng = np.random.RandomState(1)
    shared = rng.randint(1, 64, 12).tolist()
    reqs = []
    for i in range(8):
        p = shared + rng.randint(1, 64, rng.randint(1, 4)).tolist() \
            if i % 2 == 0 else rng.randint(1, 64, rng.randint(2, 20)).tolist()
        reqs.append(eng.submit(p, max_new_tokens=int(rng.randint(2, 8))))
        eng.step()
    eng.run()
    assert all(r.status == "done" for r in reqs)
    assert eng.compile_count == base, \
        f"spec steady state re-minted {eng.compile_count - base} executables"
    assert drafter.compile_count == 1, "drafter re-minted its executable"
    eng._pager.check_invariants()


def test_spec_tp2_parity_and_zero_recompiles(model_mesh):
    """TP=2 on the virtual CPU mesh with the self-speculative drafter
    (its executable compiles SPMD over the same placements as the
    verifier): parity with the single-chip eager loop, zero steady-state
    recompiles on both counters."""
    m = _tiny_gpt()
    rng = np.random.RandomState(2)
    prefix = rng.randint(1, 64, 10).tolist()
    prompts = [prefix + [50, 51], prefix + [60, 61],
               rng.randint(1, 64, 17).tolist()]
    refs = [_eager(m, p, 6) for p in prompts]
    model_mesh(2)
    shard_gpt_tp(m)
    drafter = EarlyExitDrafter(m, interval=2, ctx_len=32, max_k=4)
    eng = DecodeEngine(m, max_slots=4, max_len=48, block_size=8,
                       prefill_chunk=8, drafter=drafter)
    assert eng._tp == 2 and eng._mesh is not None
    lead = eng.submit(prompts[0], max_new_tokens=6)
    while lead.status in ("queued", "prefilling"):  # spec can skip "running"
        eng.step()
    reqs = [lead] + [eng.submit(p, max_new_tokens=6) for p in prompts[1:]]
    eng.run()
    for ref, r in zip(refs, reqs):
        assert r.status == "done"
        np.testing.assert_array_equal(ref, r.output_tokens)
    assert eng.spec_steps > 0
    base, dbase = eng.compile_count, drafter.compile_count
    wave2 = [eng.submit(p, max_new_tokens=4) for p in prompts]
    eng.run()
    assert all(r.status == "done" for r in wave2)
    assert eng.compile_count == base and drafter.compile_count == dbase
    eng._pager.check_invariants()


# -------------------------------------- satellite: pager reserve/rollback unit


class TestSpeculativeReserve:
    def test_private_extend_and_rollback_restores_trash(self):
        pg = BlockPager(9, 8, 4, 6)
        assert pg.ensure_writable(0, 0, 10) == []      # blocks for [0, 10)
        free0 = pg.free_blocks
        # [10, 14) sits in the already-private second block: no allocation
        cov, copies, res = pg.reserve_speculative(0, 10, 14)
        assert cov == 14 and copies == [] and res == []
        # [10, 20) needs a third block: fresh, previous entry was trash
        cov, copies, res = pg.reserve_speculative(0, 10, 20)
        assert cov == 20 and copies == []
        assert res == [(2, None)] and pg.free_blocks == free0 - 1
        pg.check_invariants()
        # verify kept the cursor at 12: the reserved block covered ONLY
        # rejected positions -> freed, table back to trash
        pg.rollback_speculative(0, 12, res)
        assert pg.free_blocks == free0
        assert int(pg.tables[0, 2]) == 0               # TRASH_BLOCK
        pg.check_invariants()

    def test_commit_keeps_accepted_blocks(self):
        pg = BlockPager(9, 8, 4, 6)
        pg.ensure_writable(0, 0, 8)
        cov, copies, res = pg.reserve_speculative(0, 8, 20)
        assert cov == 20 and len(res) == 2
        # cursor landed at 17: both reserved blocks cover accepted
        # positions -> full commit, nothing freed, nothing restored
        free_before = pg.free_blocks
        pg.rollback_speculative(0, 17, res)
        assert pg.free_blocks == free_before
        assert int(pg.tables[0, 1]) != 0 and int(pg.tables[0, 2]) != 0
        pg.check_invariants()

    def test_cow_shared_block_and_restore(self):
        pg = BlockPager(9, 8, 4, 6)
        pg.ensure_writable(0, 0, 16)
        pg.register_prompt(0, list(range(100, 116)))
        assert pg.share_prefix(1, list(range(100, 116))) == 15
        blk1 = int(pg.tables[1][1])
        assert pg._ref[blk1] == 2                      # live-shared
        cov, copies, res = pg.reserve_speculative(1, 15, 17)
        assert cov == 17
        assert len(copies) == 1 and copies[0][0] == blk1
        assert res[0] == (1, blk1) and res[1] == (2, None)
        assert pg._ref[blk1] == 1                      # slot 0 only, for now
        pg.check_invariants()
        # everything rejected (cursor back at 8): COW source re-referenced,
        # the copy and the fresh extension freed
        pg.rollback_speculative(1, 8, res)
        assert int(pg.tables[1][1]) == blk1 and pg._ref[blk1] == 2
        assert int(pg.tables[1][2]) == 0
        pg.check_invariants()

    def test_rollback_revives_parked_cow_source(self):
        """The COW source may PARK between reserve and rollback (its other
        owner released and the block is registered): restoring it must
        revive it from the LRU, not double-own it."""
        pg = BlockPager(9, 8, 4, 6)
        pg.ensure_writable(0, 0, 16)
        toks = list(range(200, 216))
        pg.register_prompt(0, toks)
        assert pg.share_prefix(1, toks) == 15
        blk1 = int(pg.tables[1][1])
        cov, copies, res = pg.reserve_speculative(1, 15, 16)
        assert copies and copies[0][0] == blk1
        pg.release_slot(0)                 # other owner leaves: blk1 parks
        assert blk1 in pg._lru and pg._ref[blk1] == 0
        pg.rollback_speculative(1, 8, res)
        assert int(pg.tables[1][1]) == blk1
        assert pg._ref[blk1] == 1 and blk1 not in pg._lru
        pg.check_invariants()

    def test_reserve_stops_at_exhaustion_never_preempts(self):
        pg = BlockPager(4, 8, 2, 3)                    # 3 usable blocks
        pg.ensure_writable(0, 0, 8)
        pg.ensure_writable(1, 0, 16)                   # pool now empty
        cov, copies, res = pg.reserve_speculative(0, 8, 24)
        assert cov == 8 and copies == [] and res == []
        assert pg.free_blocks == 0                     # nobody was evicted
        pg.check_invariants()


# ---------------------------------------------------------- satellite: chaos


def test_injected_verify_fault_fails_loudly(tiny):
    """raise@verify: the engine fails LOUDLY (InjectedFault out of run,
    in-flight requests terminal) with pager invariants held — speculative
    reservations die with the released slots — and is usable again."""
    eng = DecodeEngine(tiny, max_slots=2, max_len=32, block_size=8,
                       prefill_chunk=8, drafter=PromptLookupDrafter(),
                       fault_schedule=FaultSchedule.parse("raise@verify:1"))
    doomed = eng.submit([5, 6, 5, 6, 5], max_new_tokens=6)
    with pytest.raises(InjectedFault):
        eng.run()
    assert doomed.status == "failed" and doomed.finished
    assert eng.live_count == 0
    eng._pager.check_invariants()
    ok = eng.submit([7, 8, 9], max_new_tokens=2)
    eng.run()
    assert ok.status == "done"
    eng._pager.check_invariants()


def test_injected_reserve_fault_degrades_gracefully(tiny):
    """raise@spec_reserve yields an empty reservation: the engine clips
    its drafts to zero and verifies the one carried token — NO failure,
    and the output is still bitwise the eager loop's."""
    prompt = [5, 6, 7, 5, 6, 7, 5, 6]
    ref = _eager(tiny, prompt, 6)
    eng = DecodeEngine(
        tiny, max_slots=2, max_len=32, block_size=8, prefill_chunk=8,
        drafter=PromptLookupDrafter(),
        fault_schedule=FaultSchedule.parse(
            "raise@spec_reserve:1,raise@spec_reserve:2,"
            "raise@spec_reserve:3"))
    req = eng.submit(prompt, max_new_tokens=6)
    eng.run()
    assert req.status == "done"
    np.testing.assert_array_equal(ref, req.output_tokens)
    assert eng._faults.fired("spec_reserve") >= 3
    assert eng.spec_steps > 0                    # degraded steps still step
    eng._pager.check_invariants()


# ------------------------------------------------ satellite: accounting plane


def test_spec_goodput_counts_accepted_tokens_only(tmp_path):
    """The satellite-2 regression: a width-5 verify dispatch that emitted
    3 tokens bills HFU for all 5 positions but MFU/serve-throughput for
    the 3 emitted — serve/flops_per_token is attributed-FLOPs per
    ACCEPTED token, so rejected drafts can never inflate utilization."""
    class FakeExe:
        def cost_analysis(self):
            return {"flops": 1000.0, "bytes accessed": 0.0}

    monitor.enable(str(tmp_path / "run.jsonl"))
    try:
        mon = monitor.get()
        mon.serve_compiled("verify", 5, 0.01, 1, engine_id=0,
                           compiled=FakeExe(), tokens=5)
        mon.serve_spec_step(0.1, 4, 2, 3, 5, "prompt_lookup", engine_id=0,
                            accepted_per_step=3.0, hit_rate=0.5)
        snap = monitor.snapshot()
        g, c = snap["gauges"], snap["counters"]
        assert mon.goodput._serve_tokens == 3          # emitted only
        assert g["mfu/hw_flops"] == 1000.0             # HFU: full width
        assert g["mfu/model_flops"] == pytest.approx(600.0)   # 3/5 scaled
        assert g["serve/flops_per_token"] == pytest.approx(200.0)
        assert c["serve/spec_steps"] == 1
        assert c["serve/tokens"] == 3
        assert c["serve/spec_drafted"] == 4
        assert c["serve/spec_accepted"] == 2
        assert c["serve/spec_drafted.prompt_lookup"] == 4
        assert g["serve/spec_accepted_per_step"] == 3.0
        assert g["serve/spec_draft_hit_rate"] == 0.5
    finally:
        monitor.disable()


def _load_metrics_summary():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "metrics_summary", os.path.join(REPO, "tools", "metrics_summary.py"))
    ms = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ms)
    return ms


def test_spec_monitor_and_summary(tiny, tmp_path):
    """End-to-end: a real speculative run lands serve/spec_* counters,
    the accepted-per-step gauge is LIVE (acceptance criterion), and
    metrics_summary renders the speculation sub-block with the
    per-drafter breakdown, no WARN."""
    path = str(tmp_path / "spec.jsonl")
    monitor.enable(path)
    try:
        eng = DecodeEngine(tiny, max_slots=2, max_len=48, block_size=8,
                           prefill_chunk=8, drafter=PromptLookupDrafter())
        # a periodic prompt: prompt-lookup's best case, so drafts accept
        req = eng.submit([5, 6, 7, 5, 6, 7, 5, 6], max_new_tokens=12)
        eng.run()
        assert req.status == "done"
        snap = monitor.snapshot()
        c, g = snap["counters"], snap["gauges"]
        assert c["serve/spec_steps"] == eng.spec_steps > 0
        assert c["serve/spec_drafted"] == eng.spec_drafted
        assert c["serve/spec_accepted"] == eng.spec_accepted
        assert g["serve/spec_accepted_per_step"] == pytest.approx(
            eng.spec_emitted / eng.spec_steps)
        # finished-request event carries the whole-lifetime draft ledger
        monitor.get().flush()
        recs = [json.loads(l) for l in open(path)]
        done = [r for r in recs if r.get("kind") == "serve_spec"]
        assert len(done) == 1 and done[0]["drafter"] == "prompt_lookup"
        assert done[0]["drafted"] == req.spec_drafted
    finally:
        monitor.disable()
    ms = _load_metrics_summary()
    out = io.StringIO()
    assert ms.summarize([path], out=out) == 0
    text = out.getvalue()
    assert "speculation:" in text and "accepted/step" in text
    assert "drafter prompt_lookup:" in text
    assert "WARNING" not in text


def test_summary_spec_warn_on_zero_acceptance(tmp_path):
    """Spec enabled with acceptance ~0 is the wasted-work signature the
    summary must WARN on; a healthy acceptance rate stays quiet."""
    ms = _load_metrics_summary()

    def sink(name, accepted):
        eng = {"kind": "serve_engine", "ts": 0.5, "max_slots": 2,
               "max_len": 32, "prefill_buckets": [8], "quantize": None,
               "engine": 0, "kv_blocks": 9, "block_size": 8,
               "prefill_chunk": 8, "drafter": "draft_model"}
        metrics = {"kind": "counters", "ts": 2.0, "metrics": {
            "counters": {"serve/spec_steps": 20, "serve/spec_drafted": 40,
                         "serve/spec_accepted": accepted,
                         "serve/spec_drafted.draft_model": 40,
                         "serve/spec_accepted.draft_model": accepted},
            "gauges": {"serve/spec_accepted_per_step":
                       1.0 + accepted / 40.0},
            "histograms": {}}}
        p = tmp_path / name
        p.write_text("\n".join(json.dumps(r) for r in (eng, metrics)) + "\n")
        return str(p)

    dead = sink("dead.jsonl", accepted=0)
    out = io.StringIO()
    assert ms.summarize([dead], out=out) == 0
    assert "wasted-work signature" in out.getvalue()

    healthy = sink("ok.jsonl", accepted=30)
    out = io.StringIO()
    assert ms.summarize([healthy], out=out) == 0
    assert "WARNING" not in out.getvalue()
    assert "drafter draft_model: drafted 40  accepted 30" in out.getvalue()
