"""Paged-KV serving tests: block page table, prefix sharing, copy-on-write,
chunked prefill, eviction (ISSUE 9 acceptance criteria).

The contract under test:
  * ZERO steady-state recompiles under slot churn, BLOCK churn (allocation,
    sharing, COW, eviction) and chunked prefill — all of it is table data,
    none of it is executable shape.
  * Engine greedy decoding with paging + prefix sharing + chunked prefill
    enabled equals the eager compiled `generate()` loop token-for-token
    (GPT and LLaMA), even across pool-pressure preemptions.
  * A shared-prefix workload admits >= 2x the concurrent requests that a
    row of ``max_len`` positions each would allow at the same KV pool
    bytes (the PagedAttention claim, counted deterministically).
  * Chunked prefill bounds the per-iteration stall: a long prompt admits
    over ceil(n/chunk) iterations while live slots keep decoding; the
    timing gate (max stall <= 0.25x monolithic at >= 0.9x throughput) is
    slow-marked for the 2-CPU host, with the mechanism asserted in tier-1.
  * Copy-on-write never lets one tenant's decode write into a shared block
    (cross-tenant isolation, asserted on raw pool bytes).

Everything tier-1 runs a 2-layer/32-wide GPT on CPU XLA with module-scoped
fixtures sharing compiled executables, same budget discipline as
tests/test_serving.py.
"""
import io
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import BlockPager, DecodeEngine

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
    return m.generate(paddle.to_tensor(ids), max_new_tokens=n).numpy()[0,
                                                                       len(prompt):]


@pytest.fixture(scope="module")
def tiny():
    return _tiny_gpt()


@pytest.fixture(scope="module")
def engine(tiny):
    """Chunked paged engine: block_size 8, prefill_chunk 8 — executables
    minted once and shared by every test in this module."""
    eng = DecodeEngine(tiny, max_slots=4, max_len=48, block_size=8,
                       prefill_chunk=8)
    eng.submit([1, 2, 3], max_new_tokens=2)    # mint chunk-8 + decode
    eng.run()
    return eng


# --------------------------------------------------------------- tentpole


def test_paged_zero_recompile_under_block_churn(engine):
    """The extended acceptance gate: slot churn + block churn (allocation,
    prefix sharing, COW, finish-release) + chunked prefill mints NOTHING
    after the first two executables."""
    rng = np.random.RandomState(0)
    base = engine.compile_count
    shared = rng.randint(1, 64, 12).tolist()
    reqs = []
    for i in range(12):
        if i % 3 == 0:        # same-prefix family: sharing + COW on admit
            p = shared + rng.randint(1, 64, rng.randint(1, 4)).tolist()
        else:                 # fresh prompts: plain block allocation
            p = rng.randint(1, 64, rng.randint(2, 20)).tolist()
        reqs.append(engine.submit(p, max_new_tokens=int(rng.randint(2, 8))))
        engine.step()
    engine.run()
    assert all(r.status == "done" for r in reqs)
    assert engine.compile_count == base, \
        f"paged steady state recompiled: {engine.compile_count - base} mints"
    st = engine.stats()["paged"]
    assert st["shared_hits"] > 0        # the churn really exercised sharing
    assert engine.live_count == 0 and engine.queue_depth == 0


def test_chunked_prefill_spreads_admission(engine, tiny):
    """Mechanism gate (timing-free): a 20-token prompt with chunk 8 admits
    over 3 iterations, and an already-live slot decodes one token in EACH
    of them — the monolithic freeze is gone. Greedy output still equals
    the eager loop. The engine is busy when the prompt arrives, so the
    step under way was prepared without it: it is admitted while that one
    runs, and its chunks are the three steps after."""
    rng = np.random.RandomState(1)
    short = rng.randint(1, 64, 3).tolist()
    long_p = rng.randint(1, 64, 20).tolist()
    a = engine.submit(short, max_new_tokens=12)
    while a.status != "running":
        engine.step()
    tok_before = len(a.tokens)
    b = engine.submit(long_p, max_new_tokens=4)
    progressed = []
    while b.status in ("queued", "prefilling"):
        engine.step()
        progressed.append(len(a.tokens))
    # the step it is admitted under, then 3 chunk iterations ([0,8),
    # [8,16),[16,20)) => first token on the 3rd of those
    assert len(progressed) == 4
    # the live slot advanced one token per iteration, never stalled out
    assert progressed == [tok_before + 1 + i for i in range(4)]
    engine.run()
    np.testing.assert_array_equal(_eager(tiny, long_p, 4), b.output_tokens)
    np.testing.assert_array_equal(_eager(tiny, short, 12), a.output_tokens)


def test_prefix_sharing_shares_blocks(engine, tiny):
    """Same-prefix batch: followers adopt the leader's full prefix blocks
    (pool usage grows by ~1 block per follower, not a full prompt's worth)
    and greedy parity holds for every tenant."""
    rng = np.random.RandomState(2)
    prefix = rng.randint(1, 64, 16).tolist()
    prompts = [prefix + [50 + i] for i in range(3)]
    lead = engine.submit(prompts[0], max_new_tokens=6)
    while lead.status != "running":
        engine.step()
    used_before = engine.stats()["paged"]["blocks_used"]
    followers = [engine.submit(p, max_new_tokens=6) for p in prompts[1:]]
    engine.step()
    st = engine.stats()["paged"]
    # leader: 3 blocks (17 tokens @ bs=8). Followers: prefix 16 shared ->
    # 1 private tail block each; without sharing they'd take 3 each
    assert st["blocks_used"] - used_before <= 2, st
    assert st["blocks_shared"] >= 2 and st["shared_hits"] >= 2, st
    assert st["shared_tokens"] >= 32, st
    engine.run()
    for p, r in zip(prompts, [lead] + followers):
        assert r.status == "done"
        np.testing.assert_array_equal(_eager(tiny, p, 6), r.output_tokens)


def test_cow_isolation_cross_tenant(engine, tiny):
    """Copy-on-write: tenant B shares A's blocks (identical prompt), then
    both decode. A's physical blocks must stay BITWISE untouched by B's
    writes (the engine's cross-tenant invariant, checked on raw pool
    bytes), and both decodes match the eager loop."""
    rng = np.random.RandomState(3)
    prompt = rng.randint(1, 64, 13).tolist()
    a = engine.submit(prompt, max_new_tokens=10)
    while a.status != "running":
        engine.step()
    blocks_a = [int(x) for x in engine._pager.tables[a.slot] if x]
    b = engine.submit(prompt, max_new_tokens=10)
    engine.step()
    st = engine.stats()["paged"]
    assert st["cow_copies"] >= 1, "identical prompt must COW its tail block"
    # snapshot A's blocks mid-flight (A keeps decoding into its OWN copy,
    # so compare only the prompt region it can never rewrite: its first
    # full block is frozen prompt content)
    frozen = blocks_a[0]
    before = np.asarray(engine._pools[0][0][frozen]).copy()
    engine.run()
    after = np.asarray(engine._pools[0][0][frozen])
    np.testing.assert_array_equal(before, after)
    exp = _eager(tiny, prompt, 10)
    np.testing.assert_array_equal(exp, a.output_tokens)
    np.testing.assert_array_equal(exp, b.output_tokens)


def test_refcounts_survive_finish_evict_churn(tiny):
    """Interleaved finish/evict churn over a tight pool: refcounts must
    come back to zero and every block must land in exactly one of
    {free list, prefix-cache LRU} — no leaked or double-freed block, ever.
    (Registered prompt blocks PARK at refcount zero instead of freeing:
    the persistent prefix cache. The registry holds exactly the parked
    blocks once no tenant is live.)"""
    eng = DecodeEngine(tiny, max_slots=4, max_len=48, block_size=8,
                       kv_blocks=9, prefill_chunk=8)   # 8 usable blocks
    rng = np.random.RandomState(4)
    prefix = rng.randint(1, 64, 8).tolist()
    reqs = [eng.submit(prefix + rng.randint(1, 64, 10).tolist(),
                       max_new_tokens=int(rng.randint(6, 18)))
            for _ in range(6)]
    done = eng.run(max_steps=600)
    assert all(r.status == "done" for r in reqs)
    assert eng.preemptions > 0, "pool was sized to force eviction churn"
    pg = eng._pager
    assert pg.free_blocks + pg.lru_blocks == pg.usable_blocks
    assert (pg._ref == 0).all()
    assert set(pg._block_key) == set(pg._lru)   # registry == parked blocks
    pg.check_invariants()
    # the operator flush returns every parked block to the free list
    parked = pg.lru_blocks
    assert pg.drop_prefix_cache() == parked
    assert pg.free_blocks == pg.usable_blocks
    assert not pg._registry and not pg._block_key and not pg._lru
    # parity survived the churn (recompute-style preemption is lossless)
    for r in reqs:
        np.testing.assert_array_equal(
            _eager(tiny, r.prompt, r.max_new_tokens), r.output_tokens)


def test_concurrency_2x_at_fixed_kv_bytes(tiny):
    """The PagedAttention microbench gate: at FIXED KV pool bytes, a
    shared-prefix workload admits >= 2x the concurrent requests a cache
    that gives every request a whole ``max_len`` row could hold. Such a
    row cache (the engine had one until PR 31) holds 256 pooled tokens as
    4 rows of 64 positions, so its concurrency is structurally 4. Paged:
    31 usable blocks x 8 = 248 pooled tokens (strictly fewer bytes),
    prefix sharing stores the common 32 tokens once — 12+ tenants fit
    simultaneously."""
    rng = np.random.RandomState(5)
    prefix = rng.randint(1, 64, 32).tolist()
    prompts = [prefix + [40 + i, 41 + i, 42 + i, 43 + i] for i in range(16)]
    row_peak = 256 // 64                      # slots == bytes/max_len

    paged = DecodeEngine(tiny, max_slots=16, max_len=64, block_size=8,
                         kv_blocks=32, prefill_chunk=16)
    lead = paged.submit(prompts[0], max_new_tokens=4)
    while lead.status != "running":
        paged.step()                          # publish the shared prefix
    for p in prompts[1:]:
        paged.submit(p, max_new_tokens=4)
    paged_peak = 0
    while paged.queue_depth or paged.active_count:
        paged.step()
        paged_peak = max(paged_peak, paged.active_count)
    assert paged_peak >= 2 * row_peak, \
        f"paged admitted {paged_peak} concurrent vs row {row_peak}"
    assert paged.preemptions == 0             # sharing fit them for real


def test_the_row_cache_is_refused_by_name(tiny):
    """``paged`` is kept for its one caller and accepts only True."""
    with pytest.raises(ValueError, match="page table.*chunked prefill"):
        DecodeEngine(tiny, max_slots=4, max_len=64, paged=False)
    assert DecodeEngine(tiny, max_slots=4, max_len=64, paged=True) \
        .stats()["paged"]["block_size"] == 16


def test_eviction_preemption_parity(tiny):
    """Pool pressure evicts the YOUNGEST tenant back to the queue; the
    oldest always progresses (termination), and recompute-on-readmission
    keeps greedy output exactly equal to the eager loop."""
    eng = DecodeEngine(tiny, max_slots=4, max_len=48, block_size=8,
                       kv_blocks=9, prefill_chunk=8)
    rng = np.random.RandomState(6)
    prompts = [rng.randint(1, 64, 20).tolist() for _ in range(4)]
    reqs = [eng.submit(p, max_new_tokens=20) for p in prompts]
    eng.run(max_steps=600)
    assert all(r.status == "done" for r in reqs)
    assert eng.preemptions > 0
    assert any(r.preemptions > 0 for r in reqs)
    for p, r in zip(prompts, reqs):
        np.testing.assert_array_equal(_eager(tiny, p, 20), r.output_tokens)


def test_paged_parity_llama_with_sharing():
    """LLaMA (GQA + RoPE) through the paged chunked engine with prefix
    sharing: greedy tokens equal the eager loop."""
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
    paddle.seed(7)
    lm = LlamaForCausalLM(llama_tiny(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, max_position_embeddings=64))
    lm.eval()
    rng = np.random.RandomState(7)
    prefix = rng.randint(1, 64, 10).tolist()
    pa, pb = prefix + [7], prefix + [9]
    eng = DecodeEngine(lm, max_slots=2, max_len=32, block_size=4,
                       prefill_chunk=4)
    ra = eng.submit(pa, max_new_tokens=6)
    while ra.status != "running":
        eng.step()
    rb = eng.submit(pb, max_new_tokens=6)
    eng.run()
    assert eng.stats()["paged"]["shared_hits"] >= 1
    for p, r in zip((pa, pb), (ra, rb)):
        ids = np.asarray([p], np.int32)
        exp = lm.generate(paddle.to_tensor(ids),
                          max_new_tokens=6).numpy()[0, len(p):]
        np.testing.assert_array_equal(exp, r.output_tokens)


# ------------------------------------- the Pallas paged-decode kernel path


def _tiny_llama():
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
    paddle.seed(7)
    lm = LlamaForCausalLM(llama_tiny(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, max_position_embeddings=64))
    lm.eval()
    return lm


def _sharing_cow_preemption_workload(eng):
    """An identical prompt admitted beside its running twin (its shared
    tail block is copied on the first write), then same-prefix prompts that
    a 8-block pool cannot hold at once (the youngest is preempted)."""
    rng = np.random.RandomState(11)
    twin = rng.randint(1, 64, 13).tolist()
    reqs = [eng.submit(twin, max_new_tokens=10)]
    while reqs[0].status != "running":
        eng.step()
    reqs.append(eng.submit(twin, max_new_tokens=10))
    eng.step()
    prefix = rng.randint(1, 64, 12).tolist()
    for _ in range(3):
        reqs.append(eng.submit(prefix + rng.randint(1, 64, 8).tolist(),
                               max_new_tokens=18))
        eng.step()
    eng.run(max_steps=800)
    return reqs


@pytest.mark.parametrize("make", [_tiny_gpt, _tiny_llama],
                         ids=["gpt", "llama"])
def test_paged_kernel_serves_what_the_gather_path_serves(make):
    """With the decode step traced onto kernels/pallas/paged_decode.py
    (interpreted here; Mosaic on the chip), a paged engine serves token for
    token what the gather path serves, across prefix sharing, copy-on-write
    and preemption; the engine says which path it traced, in ``stats()``
    and on every ``engine/decode_call`` span."""
    import time
    from paddle_tpu.kernels.pallas import paged_decode
    from paddle_tpu.monitor import trace
    model = make()
    served = {}
    for path in ("gather", "paged_kernel"):
        eng = DecodeEngine(model, max_slots=4, max_len=48, block_size=8,
                           kv_blocks=9, prefill_chunk=8)
        assert eng.stats()["decode_attention"] is None    # nothing traced
        t0 = time.perf_counter()
        with paged_decode.force_interpret(path == "paged_kernel"):
            reqs = _sharing_cow_preemption_workload(eng)
        calls = trace.spans(t0, time.perf_counter(), "engine/decode_call")
        st = eng.stats()
        assert st["decode_attention"] == path
        assert all(r.status == "done" for r in reqs)
        assert st["paged"]["shared_hits"] >= 1, st["paged"]
        assert st["paged"]["cow_copies"] >= 1, st["paged"]
        assert eng.preemptions >= 1
        assert eng.compile_count == 2             # one chunk, one decode
        assert len(calls) == eng.decode_steps > 0
        assert {c.attrs["path"] for c in calls} == {path}
        # the twin's first decode step: one live slot at position 13 reads
        # the 2 blocks of 8 that hold positions 0..13
        assert calls[0].attrs["kv_blocks"] == 2
        assert all(1 <= c.attrs["kv_blocks"] <= 4 * 6 for c in calls)
        eng._pager.check_invariants()
        served[path] = [list(r.output_tokens) for r in reqs]
    assert served["paged_kernel"] == served["gather"]


def test_paged_kernel_zero_recompile_under_block_churn(tiny):
    """The block-churn gate on the kernel path: table and lengths are the
    kernel's scalar-prefetch DATA, so allocation, sharing, COW and finish-
    release mint nothing after the first two executables."""
    from paddle_tpu.kernels.pallas import paged_decode
    eng = DecodeEngine(tiny, max_slots=4, max_len=48, block_size=8,
                       prefill_chunk=8)
    rng = np.random.RandomState(0)
    shared = rng.randint(1, 64, 12).tolist()
    with paged_decode.force_interpret():
        eng.submit([1, 2, 3], max_new_tokens=2)
        eng.run()
    assert eng.stats()["decode_attention"] == "paged_kernel"
    base = eng.compile_count
    reqs = []
    for i in range(12):
        p = shared + rng.randint(1, 64, rng.randint(1, 4)).tolist() \
            if i % 3 == 0 else rng.randint(1, 64, rng.randint(2, 20)).tolist()
        reqs.append(eng.submit(p, max_new_tokens=int(rng.randint(2, 8))))
        eng.step()
    eng.run()
    assert all(r.status == "done" for r in reqs)
    assert eng.compile_count == base
    assert eng.stats()["paged"]["shared_hits"] > 0
    for r in reqs[:4]:
        np.testing.assert_array_equal(
            _eager(tiny, r.prompt, len(r.output_tokens)), r.output_tokens)


def test_decode_call_span_counts_the_live_blocks(tiny):
    """``kv_blocks`` is what a decode step has to read: over the live slots,
    the blocks that hold positions 0..pos (dead slots, at pos 0 on the trash
    row, are not in it)."""
    import time
    from paddle_tpu.monitor import trace
    eng = DecodeEngine(tiny, max_slots=4, max_len=48, block_size=8,
                       prefill_chunk=8)
    t0 = time.perf_counter()
    eng.submit(list(range(1, 21)), max_new_tokens=6)     # 20 prompt tokens
    eng.submit(list(range(1, 6)), max_new_tokens=6)      # 5
    eng.run()
    calls = trace.spans(t0, time.perf_counter(), "engine/decode_call")
    assert eng.stats()["decode_attention"] == "gather"   # CPU, no seam
    assert {c.attrs["path"] for c in calls} == {"gather"}
    # the short prompt (one chunk) decodes alone at positions 5 and 6 while
    # the long one is still chunked; then both: positions 20 + 7, 21 + 8,
    # 22 + 9 (blocks of 8: 3 + 1, 3 + 2, 3 + 2); then the long one alone at
    # 23 and 24
    assert [c.attrs["kv_blocks"] for c in calls] == [1, 1, 4, 5, 5, 3, 4]


def test_decode_call_spans_say_which_walk_the_kernel_took(tiny):
    """The kernel sizes a chunk from its input (kernels/pallas/
    paged_decode.py::chunk_pages) and notes what it was traced with; every
    ``engine/decode_call`` span of an engine on the kernel carries it, a
    gather engine's spans carry neither attribute."""
    import time
    from paddle_tpu.kernels.pallas import paged_decode
    from paddle_tpu.monitor import trace
    eng = DecodeEngine(tiny, max_slots=4, max_len=48, block_size=8,
                       prefill_chunk=8)
    t0 = time.perf_counter()
    with paged_decode.force_interpret():
        eng.submit(list(range(1, 12)), max_new_tokens=4)
        eng.run()
        traced = paged_decode.kernel_geometry()
    calls = trace.spans(t0, time.perf_counter(), "engine/decode_call")
    assert eng.stats()["decode_attention"] == "paged_kernel" and calls
    # float32 pools, 2 heads of 16, blocks of 8: 1 KB a page; a chunk of
    # 512 KB would be 512 pages: the table's 6 entries bound it
    assert traced == {"kv_chunk_pages": 6, "kv_page_bytes": 8 * 2 * 16 * 4}
    for c in calls:
        assert {k: c.attrs[k] for k in traced} == traced
    t0 = time.perf_counter()
    gather = DecodeEngine(tiny, max_slots=4, max_len=48, block_size=8,
                          prefill_chunk=8)
    gather.submit(list(range(1, 12)), max_new_tokens=4)
    gather.run()
    calls = trace.spans(t0, time.perf_counter(), "engine/decode_call")
    assert calls and all("kv_chunk_pages" not in c.attrs
                         and "kv_page_bytes" not in c.attrs for c in calls)


# ----------------------------------------------------- satellite: pager unit


class TestBlockPager:
    def test_alloc_release_roundtrip(self):
        pg = BlockPager(9, 8, 4, 6)
        assert pg.usable_blocks == 8 and pg.free_blocks == 8
        copies = pg.ensure_writable(0, 0, 20)     # 3 blocks
        assert copies == [] and pg.free_blocks == 5
        pg.register_prompt(0, list(range(100, 120)))
        cov = pg.share_prefix(1, list(range(100, 120)))
        assert cov == 19                          # n-1 cap: last token redone
        assert pg.free_blocks == 5                # sharing allocates nothing
        # first write of slot 1 hits the shared partial tail -> COW
        copies = pg.ensure_writable(1, cov, 20)
        assert len(copies) == 1 and pg.cow_copies == 1
        assert pg.free_blocks == 4                # the COW took a fresh block
        pg.release_slot(0)
        # slot 0's tail (COW left it sole owner) PARKS — it is registered
        # under the exact-prompt key; the two full prefix blocks survive on
        # slot 1's refs
        assert pg.free_blocks == 4 and pg.lru_blocks == 1
        pg.release_slot(1)
        # every registered block parks in the prefix cache; slot 1's COW
        # tail is unregistered (first registration won) so it frees
        assert pg.free_blocks + pg.lru_blocks == 8
        assert set(pg._block_key) == set(pg._lru)
        pg.check_invariants()

    def test_ensure_rolls_back_on_exhaustion(self):
        pg = BlockPager(4, 8, 2, 3)               # 3 usable blocks
        assert pg.ensure_writable(0, 0, 16) == []  # 2 blocks
        tables_before = pg.tables.copy()
        assert pg.ensure_writable(1, 0, 24) is None  # needs 3, only 1 free
        np.testing.assert_array_equal(tables_before, pg.tables)
        assert pg.free_blocks == 1                # nothing leaked

    def test_share_requires_registration(self):
        pg = BlockPager(9, 8, 4, 6)
        pg.ensure_writable(0, 0, 12)
        # NOT registered yet (prefill incomplete): nothing to adopt
        assert pg.share_prefix(1, list(range(12))) == 0
        pg.register_prompt(0, list(range(12)))
        assert pg.share_prefix(2, list(range(12))) == 11

    def test_blocks_needed_counts_cow(self):
        pg = BlockPager(9, 8, 4, 6)
        pg.ensure_writable(0, 0, 16)
        pg.register_prompt(0, list(range(200, 216)))
        cov = pg.share_prefix(1, list(range(200, 216)))
        assert cov == 15
        # slot 1's write range [15, 16) sits in a shared block: COW = 1 new
        assert pg.blocks_needed(1, cov, 16) == 1


# ------------------------------------------- satellite: queue bound/overload


def test_queue_bound_rejects_overload(tiny):
    eng = DecodeEngine(tiny, max_slots=2, max_len=32, block_size=8,
                       prefill_chunk=8, max_queue=2)
    monitor.enable(None)
    try:
        good = [eng.submit([1 + i, 2, 3], max_new_tokens=2)
                for i in range(2)]
        over = eng.submit([9, 9, 9], max_new_tokens=2)
        assert over.status == "rejected_overload"
        assert "queue full" in over.error
        assert over.finished is False or over.t_done  # terminal, never runs
        snap = monitor.snapshot()
        assert snap["counters"]["serve/rejected_overload"] == 1
        eng.run()
        assert all(r.status == "done" for r in good)
        assert over.status == "rejected_overload"     # untouched by run()
        # queue-wait histogram observed one entry per admission
        snap = monitor.snapshot()
        assert snap["histograms"]["serve/queue_wait_s"]["count"] == 2
    finally:
        monitor.disable()


# --------------------------------------- satellite: engine-cache mint counter


def test_generate_engine_cache_mint_stability(tiny):
    """generate(use_engine=True) keys ONE engine per (slots, max_len
    bucket, quantize, sampling) — mixed caller geometry (prompt lengths
    AND decode horizons) reuses it with ZERO new executable mints (the
    chunk executable serves any prompt length; the regression this
    satellite exists to catch is per-horizon engine thrash)."""
    tiny.__dict__.setdefault("_serving_engines", {}).clear()
    rng = np.random.RandomState(8)
    ids = paddle.to_tensor(rng.randint(1, 64, (2, 5)).astype("int32"))
    tiny.generate(ids, max_new_tokens=4, use_engine=True)
    assert len(tiny._serving_engines) == 1
    eng = next(iter(tiny._serving_engines.values()))
    mints = eng.compile_count
    # different prompt length, different horizon, different batch size —
    # same pow2 bucket => same engine, same executables
    for b, s0, mnt in ((1, 3, 8), (3, 7, 2), (2, 9, 4)):
        ids2 = paddle.to_tensor(rng.randint(1, 64, (b, s0)).astype("int32"))
        tiny.generate(ids2, max_new_tokens=mnt, use_engine=True)
    assert len(tiny._serving_engines) == 1, \
        "mixed-horizon callers minted extra engines"
    assert eng.compile_count == mints, \
        f"mixed geometry re-minted {eng.compile_count - mints} executables"


# ---------------------------------------------------- satellite: telemetry


def _load_metrics_summary():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "metrics_summary", os.path.join(REPO, "tools", "metrics_summary.py"))
    ms = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ms)
    return ms


def test_paged_monitor_and_summary(tmp_path):
    """Paged gauges reach the monitor and metrics_summary renders the pages
    line (occupancy/sharing/COW) for a healthy run WITHOUT the
    fragmentation WARN."""
    path = str(tmp_path / "paged.jsonl")
    m = _tiny_gpt(seed=9)
    monitor.enable(path)
    try:
        eng = DecodeEngine(m, max_slots=2, max_len=32, block_size=8,
                           prefill_chunk=8)
        # 13 tokens: 1 full block + 5-token tail — the identical follower
        # adopts BOTH (tail via the exact-prompt key) and its first write
        # copy-on-writes the shared tail block
        prompt = list(range(5, 18))
        a = eng.submit(prompt, max_new_tokens=4)
        while a.status != "running":
            eng.step()
        eng.submit(prompt, max_new_tokens=4)    # sharing + COW on admit
        eng.step()
        mid = monitor.snapshot()                # both tenants live here
        eng.run()
        snap = monitor.snapshot()
    finally:
        monitor.disable()
    gm, g = mid["gauges"], snap["gauges"]
    assert g["serve/kv_blocks"] == eng.kv_blocks
    assert g["serve/block_size"] == 8
    assert gm["serve/blocks_shared"] >= 1       # shared while co-resident
    assert gm["serve/sharing_ratio"] > 1
    assert g["serve/cow_copies"] >= 1           # cumulative
    assert 0 < gm["serve/kv_util"] <= 1
    ms = _load_metrics_summary()
    out = io.StringIO()
    assert ms.summarize([path], out=out) == 0
    text = out.getvalue()
    assert "paged" in text and "chunked prefill" in text
    assert "pages: occupancy" in text and "sharing ratio" in text
    assert "WARNING" not in text


def test_summary_fragmentation_warn(tmp_path):
    """serve_page_reject with free >= needed is the allocator-bug
    signature the serving section must WARN on; free < needed (real
    saturation) must stay quiet."""
    ms = _load_metrics_summary()

    def sink(name, frees, needed):
        eng = {"kind": "serve_engine", "ts": 0.5, "max_slots": 2,
               "max_len": 16, "prefill_buckets": [8], "quantize": None,
               "engine": 0, "kv_blocks": 9, "block_size": 8,
               "prefill_chunk": 8}
        recs = [eng] + [{"kind": "serve_page_reject", "ts": 1.0 + i,
                         "free_blocks": f, "needed_blocks": n}
                        for i, (f, n) in enumerate(zip(frees, needed))]
        p = tmp_path / name
        p.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
        return str(p)

    healthy = sink("sat.jsonl", [0, 1], [3, 2])       # genuine saturation
    out = io.StringIO()
    assert ms.summarize([healthy], out=out) == 0
    assert "WARNING" not in out.getvalue()

    buggy = sink("frag.jsonl", [6], [2])              # free >= needed
    out = io.StringIO()
    assert ms.summarize([buggy], out=out) == 0
    assert "WARNING" in out.getvalue()
    assert "free blocks >= the slot's need" in out.getvalue()


# --------------------------------------------------- slow: the timing gates


@pytest.mark.slow
def test_chunked_prefill_stall_gate():
    """The ISSUE 9 timing gate, sized for compute dominance on the 2-CPU
    host (hidden 1024, prompt 1024 — a chunk call carries a fixed ~40-60ms
    pool-donation/gather floor, so the chunk's GEMMs must dwarf it): with
    two live slots decoding, admitting the long prompt via chunk=64 keeps
    the max per-iteration stall <= 0.25x the monolithic prefill stall
    (measured ~0.16x), at >= 0.9x the monolithic drain throughput
    (measured ~0.98x: live slots keep earning tokens during the spread
    admission)."""
    import time
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=1024, num_layers=2,
                    num_heads=16, max_position_embeddings=2048,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    use_flash_attention=False)
    m = GPTForCausalLM(cfg)
    m.eval()
    rng = np.random.RandomState(0)
    long_prompt = rng.randint(1, 128, 1024).tolist()
    shorts = [rng.randint(1, 128, 8).tolist() for _ in range(2)]

    def run(chunk):
        eng = DecodeEngine(m, max_slots=4, max_len=1152, block_size=64,
                           prefill_chunk=chunk,
                           prefill_buckets=None if chunk else [1024])
        for p in shorts:
            eng.submit(p, max_new_tokens=60)
        while eng.live_count < 2:
            eng.step()
        warm = eng.submit(long_prompt, max_new_tokens=1)   # mint + warm
        while warm.status != "done":
            eng.step()
        # best-of-2 admission windows: the 2-core host throws occasional
        # 2x scheduler outliers into single steps; the achieved (minimum)
        # max-stall is the honest figure
        best_stall = float("inf")
        for _ in range(2):
            r = eng.submit(long_prompt, max_new_tokens=4)
            stalls = []
            while r.status != "done":
                t0 = time.time()
                eng.step()
                stalls.append(time.time() - t0)
            best_stall = min(best_stall, max(stalls))
            eng.run()
        t0 = time.time()
        reqs = [eng.submit(p, max_new_tokens=24) for p in shorts] \
            + [eng.submit(long_prompt, max_new_tokens=8)]
        eng.run()
        wall = time.time() - t0
        toks = sum(len(q.tokens) for q in reqs)
        return best_stall, toks / wall

    stall_mono, tput_mono = run(None)
    stall_chunk, tput_chunk = run(64)
    ratio = stall_chunk / stall_mono
    assert ratio <= 0.25, \
        f"chunked max stall {stall_chunk * 1e3:.1f}ms vs monolithic " \
        f"{stall_mono * 1e3:.1f}ms = {ratio:.2f}x (> 0.25x)"
    assert tput_chunk >= 0.9 * tput_mono, \
        f"chunked throughput {tput_chunk:.1f} tok/s < 0.9x monolithic " \
        f"{tput_mono:.1f} tok/s"
