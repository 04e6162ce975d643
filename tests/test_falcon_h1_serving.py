"""The serving engine over the parallel hybrid decoder `models/falcon_h1.py`
at a tiny size on the CPU: every block owns a paged K/V cache AND a
recurrent state, chunked prefill + decode against the reference's one
forward pass; what a decode step must leave alone; slot reuse, preemption,
what a state entry cannot do yet, and that a model with one cache a layer
is handed what it always was."""
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from test_falcon_h1 import CHUNK, family, program  # noqa: F401

from paddle_tpu.kernels.pallas import paged_decode, ssd
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
        < 2e-5


def test_a_mixed_batch_with_kernels_interpreted_follows_the_reference(tiny):
    """Requests of several lengths admitted together, so decode steps run
    beside prefill chunks; both Pallas kernels through the interpreter (the
    paged kernel at 5 query heads a KV head). Every served token is the
    reference's choice."""
    prog, arrays, model = tiny
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 512, n).tolist() for n in (3, 20, 9)]
    eng = engine(prog)
    with paged_decode.force_interpret(), ssd.force_interpret():
        reqs = [eng.submit(p, max_new_tokens=3 + i)
                for i, p in enumerate(prompts)]
        eng.run()
    assert eng.stats()["decode_attention"] == "paged_kernel"
    assert eng.stats()["decode_state"] == "ssd_decode"
    for p, r in zip(prompts, reqs):
        assert r.status == "done"
        assert float(reference_gaps(arrays, model, p, r.tokens).max()) < 2e-5
    # two state entries (one a block), counted as entries
    assert eng.stats()["state"] == {
        "layers": 2, "slots": 4,
        "bytes_per_slot": 2 * (4 * 16 * 8 * 4 + 3 * 96 * 4)}
    fam, _ = family()
    assert eng.stats()["state"]["bytes_per_slot"] == \
        fam.state_bytes_per_slot(model, elem=4)


def test_decode_call_spans_carry_the_kernels_geometry(tiny):
    """The hybrid's attention mixers reach the paged kernel over merged-row
    pools; every ``engine/decode_call`` span says with which walk of the
    table the kernel was traced (1 KV head of 16, float32, blocks of 8:
    512 B a page, a chunk bounded by the table's 12 entries)."""
    import time
    from paddle_tpu.monitor import trace
    prog, _, _ = tiny
    eng = engine(prog)
    t0 = time.perf_counter()
    with paged_decode.force_interpret(), ssd.force_interpret():
        eng.submit(list(range(1, 10)), max_new_tokens=4)
        eng.run()
        traced = paged_decode.kernel_geometry()
    calls = trace.spans(t0, time.perf_counter(), "engine/decode_call")
    assert traced == {"kv_chunk_pages": 12, "kv_page_bytes": 8 * 1 * 16 * 4,
                      "kv_piece_pages": 12}
    assert calls and all(c.attrs["path"] == "paged_kernel" for c in calls)
    for c in calls:
        assert {k: c.attrs[k] for k in traced} == traced


def test_every_block_is_handed_both_of_its_caches(tiny):
    prog, _, model = tiny
    spec = prog.decode_spec()
    assert [[c.kind for c in block] for block in spec.layers] == \
        [["kv", "state"]] * 2
    assert len(spec.state_layers) == len(spec.kv_layers) == 2
    assert (spec.n_kv_heads, spec.head_dim) == (1, 16)
    eng = engine(prog, kv_blocks=20)
    for (k, v), (state, tail) in eng._pools:
        assert k.shape == v.shape == (20, 8 * 1, 16)        # merged rows
        assert state.shape == (4, 4, 16, 8) and state.dtype == jnp.float32
        assert tail.shape == (4, 3, 32 + 2 * 2 * 16)


def cache_rows(eng, slot):
    """A slot's state, tail and the K/V blocks its table names."""
    blocks = np.asarray(eng._pager.tables[slot])
    blocks = blocks[blocks > 0]
    out = []
    for (k, v), (state, tail) in eng._pools:
        out += [np.asarray(state[slot]), np.asarray(tail[slot]),
                np.asarray(k)[blocks], np.asarray(v)[blocks]]
    return out


def test_a_decode_step_leaves_other_slots_caches_alone(tiny):
    """A slot that is mid-prefill, and one that is free, keep their state,
    their convolution tail and their K/V blocks bit for bit while a
    neighbour decodes."""
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
    eng._pools = [(kv, tuple(x.at[free].set(0.5) for x in st))
                  for kv, st in eng._pools]
    before = cache_rows(eng, b.slot) + cache_rows(eng, free)
    state_a = np.asarray(eng._pools[0][1][0][a.slot])
    exe = eng._decode_exe                        # one decode step alone
    eng._pools, _, _ = exe(
        eng._leaf_values(), eng._pools,
        eng._dev(eng._decode_tables(eng._live)),
        eng._dev(eng._host_tok()), eng._dev(eng._pos),
        *eng._cow_args([]), eng._next_key(),
        eng._dev(eng._pos + eng._live))
    for was, now in zip(before,
                        cache_rows(eng, b.slot) + cache_rows(eng, free)):
        assert np.array_equal(was, now)
    # the neighbour did step
    assert not np.array_equal(state_a,
                              np.asarray(eng._pools[0][1][0][a.slot]))
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


def test_what_a_state_entry_cannot_do_yet_is_refused_by_name(tiny):
    """The rules key on `spec.state_layers`, which finds the state entry
    inside a two-entry block."""
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


def test_call_spans_carry_both_caches_bytes(tiny, shared_engine):
    """`state_slots`, `state_bytes` and `kv_bytes` (the live context the
    call reads x the model's K/V bytes a token) on every decode and chunk
    call; the executables hold the three named scopes."""
    from paddle_tpu.monitor import trace
    prog, _, model = tiny
    fam, _ = family()
    eng = shared_engine
    t0 = time.perf_counter()
    eng.submit(list(range(1, 20)), max_new_tokens=3)
    eng.run()
    t1 = time.perf_counter()
    per_slot = fam.state_bytes_per_slot(model, elem=4)
    per_token = fam.kv_bytes_per_token(model, elem=4)
    calls = trace.spans(t0, t1, "engine/decode_call")
    # the prompt's 19 positions and the first token's, then one more a step
    assert [s.attrs["kv_bytes"] for s in calls] == \
        [per_token * n for n in (20, 21)]
    assert all(s.attrs["state_slots"] == 1 and
               s.attrs["state_bytes"] == per_slot for s in calls)
    # which SSD step the decode executable was traced with: off the TPU and
    # outside the test seam the scan, and the span says so
    assert {s.attrs["state_path"] for s in calls} == {"scan"} \
        == {eng.stats()["decode_state"]}
    chunks = trace.spans(t0, t1, "engine/prefill_call")
    assert [s.attrs["kv_bytes"] for s in chunks] == \
        [per_token * n for n in (CHUNK, 19)]
    assert all(s.attrs["state_bytes"] == per_slot for s in chunks)
    text = eng._decode_exe.as_text()
    assert all(scope in text for scope in ("ssm_mixer", "attention_mixer",
                                           "mlp"))


def test_models_with_one_cache_a_layer_build_the_pools_they_built():
    """GPT's K/V pools and the other hybrid's per-layer state rows or
    merged-row pools come out as before a block could own two caches; a
    pure K/V model's call spans gain `kv_bytes` and nothing of state."""
    from paddle_tpu.models import (GPTForCausalLM, Qwen3NextForCausalLM,
                                   gpt_tiny, qwen3_next_tiny)
    from paddle_tpu.monitor import trace
    gpt = GPTForCausalLM(gpt_tiny())
    gpt.eval()
    eng = DecodeEngine(gpt, max_slots=2, max_len=32, block_size=8,
                       prefill_chunk=8, kv_blocks=10)
    assert [tuple(a.shape for a in c) for c in eng._pools] == \
        [((10, 8, 4, 16),) * 2] * 2
    t0 = time.perf_counter()
    eng.submit([1, 2, 3], max_new_tokens=3)
    eng.run()
    spans = trace.spans(t0, time.perf_counter(), "engine/decode_call")
    assert [s.attrs["kv_bytes"] for s in spans] == \
        [2 * 2 * 4 * 16 * 4 * n for n in (4, 5)]
    assert not any(k.startswith("state_") for s in spans for k in s.attrs)
    hybrid = Qwen3NextForCausalLM(qwen3_next_tiny(num_experts=8,
                                                  router_experts=32))
    hybrid.eval()
    eng = DecodeEngine(hybrid, max_slots=2, max_len=32, block_size=8,
                       prefill_chunk=8, kv_blocks=10)
    spec = hybrid.decode_spec()
    assert [c.kind for c in spec.layers] == ["state"] * 3 + ["kv"]
    assert spec.entries == list(spec.layers)
    assert [tuple(a.shape for a in c) for c in eng._pools] == \
        [((2, 4, 16, 16), (2, 3, 128))] * 3 + [((10, 8 * 2, 32),) * 2]
