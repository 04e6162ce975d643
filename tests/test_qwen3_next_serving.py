"""The serving engine over the hybrid decoder `models/qwen3_next.py` at a
tiny size on the CPU: chunked prefill + decode, with fixed-size recurrent
state per slot beside paged K/V, against the reference's one forward pass;
what a decode step must leave alone; slot reuse, preemption, and what a
state layer cannot do yet."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from test_qwen3_next import CHUNK, family, program  # noqa: F401

from paddle_tpu.kernels.pallas import gdn, moe_grouped, paged_decode
from paddle_tpu.serving import DecodeEngine


@pytest.fixture(scope="module")
def tiny():
    return program()


def engine(prog, **kw):
    geo = dict(max_slots=4, max_len=96, block_size=8, prefill_chunk=CHUNK)
    geo.update(kw)
    return DecodeEngine(prog, **geo)


_REF = {}


def reference_logits(arrays, model, seq):
    """The reference's logits over ``seq``: one forward pass, padded to one
    length so the reference compiles once (padding is causally
    invisible)."""
    _, ref = family()
    if "fn" not in _REF:
        _REF["fn"] = jax.jit(lambda w, ids: ref.logits(w, ids, model))
    ids = np.zeros((1, 96), np.int32)
    ids[0, :len(seq)] = seq
    return np.asarray(_REF["fn"](arrays, jnp.asarray(ids)))[0, :len(seq)]


def reference_gaps(arrays, model, prompt, tokens):
    """How far each served token's reference logit lies under the
    reference's best at its position (0: the reference's own choice),
    teacher-forced over prompt + served tokens."""
    seq = list(prompt) + list(tokens)[:-1]
    at = reference_logits(arrays, model, seq)[len(prompt) - 1:]
    return at.max(-1) - at[np.arange(len(tokens)), np.asarray(tokens)]


def reference_greedy(arrays, model, prompt, n):
    """The ``n`` tokens the reference picks after ``prompt``, one forward
    pass a token and no cache at all."""
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(reference_logits(arrays, model, seq)[-1].argmax()))
    return seq[len(prompt):]


@pytest.fixture(scope="module")
def shared_engine(tiny):
    """One engine for the tests that only need it idle between them."""
    return engine(tiny[0])


PROMPTS = {"inside_a_chunk": 21, "on_a_chunk_boundary": 2 * CHUNK,
           "shorter_than_the_convolution": 2, "one_token": 1,
           "one_past_a_boundary": CHUNK + 1}


@pytest.mark.parametrize("which", sorted(PROMPTS))
def test_chunked_prefill_and_decode_follow_the_reference(tiny, which,
                                                         shared_engine):
    prog, arrays, model = tiny
    n = PROMPTS[which]
    prompt = np.random.default_rng(n).integers(0, 512, n).tolist()
    eng = shared_engine
    req = eng.submit(prompt, max_new_tokens=9)
    eng.run()
    assert req.status == "done" and len(req.tokens) == 9
    assert req.prefill_chunks == -(-n // CHUNK)
    assert float(reference_gaps(arrays, model, prompt, req.tokens).max()) \
        < 1e-5


def test_a_mixed_batch_with_kernels_interpreted_follows_the_reference(tiny):
    """Requests of several lengths admitted together, so decode steps run
    beside prefill chunks; the three Pallas kernels through the
    interpreter. Every served token is the reference's choice."""
    prog, arrays, model = tiny
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 512, n).tolist() for n in (3, 20, 9)]
    eng = engine(prog)
    with paged_decode.force_interpret(), gdn.force_interpret(), \
            moe_grouped.force_interpret():
        reqs = [eng.submit(p, max_new_tokens=3 + i)
                for i, p in enumerate(prompts)]
        eng.run()
    assert eng.stats()["decode_attention"] == "paged_kernel"
    for p, r in zip(prompts, reqs):
        assert r.status == "done"
        assert float(reference_gaps(arrays, model, p, r.tokens).max()) < 1e-5
    st = eng.stats()
    assert st["state"] == {"layers": 3, "slots": 4,
                           "bytes_per_slot": 3 * (4 * 16 * 16 * 4
                                                  + 3 * 128 * 4)}
    moe = st["moe"]
    assert moe["assignments"] == 4 * 4 * sum(len(r.tokens) - 1 for r in reqs)
    assert 0 < moe["local"] < moe["assignments"] and moe["touched"] > 0


def state_rows(eng, slot):
    return [np.asarray(a[slot]) for layer, cache in
            zip(eng.spec.layers, eng._pools) if layer.kind == "state"
            for a in cache]


def test_a_decode_step_leaves_other_slots_state_alone(tiny):
    """A slot that is mid-prefill, and one that is free, keep their state
    rows bit for bit while a neighbour decodes (the state twin of the K/V
    write PERF.md section 7 item 1 was about)."""
    prog, _, _ = tiny
    rng = np.random.default_rng(2)
    short = rng.integers(0, 512, 5).tolist()
    long = rng.integers(0, 512, 3 * CHUNK + 5).tolist()
    eng = engine(prog)
    a = eng.submit(short, max_new_tokens=20)
    eng.step()                                   # a: prefilled, decoding
    b = eng.submit(long, max_new_tokens=4)
    eng.step()                                   # b: first chunk; a decodes
    assert b.status == "prefilling" and a.status == "running"
    free = [s for s in range(4) if s not in (a.slot, b.slot)][0]
    # plant something recognisable in the free slot's rows
    eng._pools = [tuple(x.at[free].set(0.5) for x in c)
                  if layer.kind == "state" else c
                  for layer, c in zip(eng.spec.layers, eng._pools)]
    before_b, before_free = state_rows(eng, b.slot), state_rows(eng, free)
    exe = eng._decode_exe                        # one decode step alone
    eng._pools, _, _ = exe(
        eng._leaf_values(), eng._pools, eng._dev(eng._decode_tables(eng._live)),
        eng._dev(eng._host_tok()), eng._dev(eng._pos),
        *eng._cow_args([]), eng._next_key(),
        eng._dev(eng._pos + eng._live))
    for was, now in zip(before_b + before_free,
                        state_rows(eng, b.slot) + state_rows(eng, free)):
        assert np.array_equal(was, now)
    # and the whole mix still serves what each request serves alone
    eng2 = engine(prog)
    alone = eng2.submit(long, max_new_tokens=4)
    eng2.run()
    eng = engine(prog)
    a = eng.submit(short, max_new_tokens=20)
    eng.step()
    b = eng.submit(long, max_new_tokens=4)
    eng.run()
    assert b.tokens == alone.tokens


def test_a_reused_slot_starts_from_zero_state(tiny):
    prog, _, _ = tiny
    rng = np.random.default_rng(6)
    first, second = (rng.integers(0, 512, n).tolist() for n in (30, 11))
    fresh = engine(prog, max_slots=1, kv_blocks=20)
    want = fresh.submit(second, max_new_tokens=8)
    fresh.run()
    eng = engine(prog, max_slots=1, kv_blocks=20)
    eng.submit(first, max_new_tokens=8)
    eng.run()
    got = eng.submit(second, max_new_tokens=8)
    eng.run()
    assert got.tokens == want.tokens


def test_a_preempted_request_is_served_the_same_tokens(tiny):
    """A pool too small for both tenants: the younger is preempted
    (recompute-style), re-admitted from position 0, its state zeroed by the
    rule, and serves what it serves alone."""
    prog, _, _ = tiny
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 512, 20).tolist() for _ in range(2)]
    alone = []
    for p in prompts:
        eng = engine(prog)
        r = eng.submit(p, max_new_tokens=40)
        eng.run()
        alone.append(r.tokens)
    eng = engine(prog, max_slots=2, max_len=64, kv_blocks=12)
    reqs = [eng.submit(p, max_new_tokens=40) for p in prompts]
    eng.run()
    assert eng.stats()["paged"]["preemptions"] >= 1
    assert [r.tokens for r in reqs] == alone
    eng._pager.check_invariants()


def test_the_prefix_cache_is_not_consulted(tiny, shared_engine):
    prog, _, _ = tiny
    prompt = np.random.default_rng(3).integers(0, 512, 40).tolist()
    eng = shared_engine
    first = eng.submit(prompt, max_new_tokens=4)
    eng.run()
    again = eng.submit(prompt, max_new_tokens=4)
    eng.run()
    pg = eng.stats()["paged"]
    assert pg["prefix_hits"] == 0 and pg["prefix_hit_tokens"] == 0 \
        and pg["shared_tokens"] == 0
    assert again.prefill_chunks == first.prefill_chunks == 3
    assert again.tokens == first.tokens


def test_what_a_state_layer_cannot_do_yet_is_refused_by_name(tiny):
    from paddle_tpu.serving import LocalPool, PromptLookupDrafter
    prog, _, _ = tiny
    with pytest.raises(NotImplementedError, match="state snapshot"):
        engine(prog, drafter=PromptLookupDrafter())
    with pytest.raises(NotImplementedError, match="pool export/adopt"):
        engine(prog, kv_pool=LocalPool())
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu.distributed import env as denv
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("model",))
    prev = denv.get_mesh()
    denv.set_mesh(mesh)
    head = prog.lm_head
    kept = head._data
    try:
        head._data = jax.device_put(kept, NamedSharding(mesh,
                                                        P(None, "model")))
        with pytest.raises(NotImplementedError,
                           match="tensor-parallel serving of recurrent"):
            engine(prog)
    finally:
        head._data = kept
        denv.set_mesh(prev)


def test_spans_carry_the_state_and_the_routing(tiny, shared_engine):
    from paddle_tpu.monitor import trace
    import time
    prog, _, _ = tiny
    eng = shared_engine
    t0 = time.perf_counter()
    eng.submit(list(range(1, 20)), max_new_tokens=3)
    eng.run()
    t1 = time.perf_counter()
    per_slot = eng.stats()["state"]["bytes_per_slot"]
    calls = trace.spans(t0, t1, "engine/decode_call")
    assert calls and all(s.attrs["state_slots"] == 1 and
                         s.attrs["state_bytes"] == per_slot for s in calls)
    chunks = trace.spans(t0, t1, "engine/prefill_call")
    assert chunks and all(s.attrs["state_bytes"] == per_slot for s in chunks)
    fins = trace.spans(t0, t1, "engine/decode_finish")
    assert all(s.attrs["moe_assignments"] == 4 * 4 and
               0 <= s.attrs["moe_local"] <= 16 and
               s.attrs["moe_touched"] <= 4 * 8 for s in fins)
    # a model without state or experts carries none of it
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    gpt = GPTForCausalLM(gpt_tiny())
    gpt.eval()
    e2 = DecodeEngine(gpt, max_slots=2, max_len=32, block_size=8,
                      prefill_chunk=8)
    t0 = time.perf_counter()
    e2.submit([1, 2, 3], max_new_tokens=3)
    e2.run()
    spans = trace.spans(t0, time.perf_counter(), "engine/decode_")
    assert spans and not any(k.startswith(("state_", "moe_"))
                             for s in spans for k in s.attrs)
    assert "state" not in e2.stats() and "moe" not in e2.stats()
