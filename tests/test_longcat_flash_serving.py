"""The serving engine over the LongCat-Flash decoder
`models/longcat_flash.py` at a tiny size on the CPU: every block owns TWO
paged latent caches (one `[c | rotated k_rope]` row a token a sublayer),
chunked prefill + decode against the reference's one forward pass; what a
decode step must leave alone; the prefix cache, copy-on-write and
preemption working on latent entries BY MECHANISM; the latent decode kernel
under the interpreter against the gathered view; what a latent entry cannot
do yet refused by name; and that the other models are handed what they
always were."""
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from test_longcat_flash import CHUNK, family, program  # noqa: F401

from paddle_tpu.kernels.pallas import paged_decode
from paddle_tpu.serving import DecodeEngine

BLOCK = 8


@pytest.fixture(scope="module")
def tiny():
    return program()


def engine(prog, **kw):
    geo = dict(max_slots=4, max_len=96, block_size=BLOCK,
               prefill_chunk=CHUNK)
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
           "on_a_block_edge": 3 * BLOCK, "one_token": 1,
           "one_past_a_boundary": CHUNK + 1, "one_short_of_a_block": 7}


@pytest.mark.parametrize("which", sorted(PROMPTS))
def test_chunked_prefill_and_decode_follow_the_reference(tiny, which,
                                                         shared_engine):
    """The cursor inside a chunk, on a chunk's and on a block's edge: every
    served token is the reference's choice (float32: a gap under 2e-5)."""
    prog, arrays, model = tiny
    n = PROMPTS[which]
    prompt = np.random.default_rng(n).integers(0, 512, n).tolist()
    eng = shared_engine
    eng.drop_prefix_cache()
    req = eng.submit(prompt, max_new_tokens=11)
    eng.run()
    assert req.status == "done" and len(req.tokens) == 11
    assert req.prefill_chunks == -(-n // CHUNK)
    assert float(reference_gaps(arrays, model, prompt, req.tokens).max()) \
        < 2e-5


def test_a_mixed_batch_with_the_kernel_interpreted_follows_the_reference(
        tiny):
    """Requests of several lengths admitted together, so decode steps run
    beside prefill chunks; the latent decode kernel through the
    interpreter (4 query heads on one row of 24 lanes in 128)."""
    prog, arrays, model = tiny
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 512, n).tolist() for n in (3, 20, 9)]
    eng = engine(prog)
    with paged_decode.force_interpret():
        reqs = [eng.submit(p, max_new_tokens=3 + i)
                for i, p in enumerate(prompts)]
        eng.run()
    assert eng.stats()["decode_attention"] == "mla_decode"
    for p, r in zip(prompts, reqs):
        assert r.status == "done"
        assert float(reference_gaps(arrays, model, p, r.tokens).max()) < 2e-5
    assert "state" not in eng.stats()
    assert set(eng.stats()["moe"]) == {"assignments", "local", "touched",
                                       "zero"}


def test_every_block_is_handed_both_of_its_pools(tiny):
    prog, _, _ = tiny
    eng = engine(prog, kv_blocks=20)
    assert len(eng._pools) == 2
    for first, second in eng._pools:
        for (pool,) in (first, second):
            # one pool an entry: [blocks, block, 24 -> 128 lanes], the pad 0
            assert pool.shape == (20, BLOCK, 128)
    eng.submit(list(range(1, 30)), max_new_tokens=3)
    eng.run()
    for layer in eng._pools:
        for (pool,) in layer:
            pool = np.asarray(pool)
            assert np.abs(pool[..., :24]).max() > 0.1
            assert not pool[..., 24:].any()


def latent_rows(eng, slot):
    """The blocks a slot's table names, in every pool."""
    blocks = np.asarray(eng._pager.tables[slot])
    blocks = blocks[blocks > 0]
    return [np.asarray(pool)[blocks] for layer in eng._pools
            for (pool,) in layer]


def test_a_decode_step_leaves_other_slots_rows_alone(tiny):
    """A slot that is mid-prefill keeps its latent rows bit for bit while
    a neighbour decodes; the neighbour's own blocks gain a row."""
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
    before_b, before_a = latent_rows(eng, b.slot), latent_rows(eng, a.slot)
    exe = eng._decode_exe                        # one decode step alone
    eng._pools, _, _ = exe(
        eng._leaf_values(), eng._pools,
        eng._dev(eng._decode_tables(eng._live)),
        eng._dev(eng._host_tok()), eng._dev(eng._pos),
        *eng._cow_args([]), eng._next_key(),
        eng._dev(eng._pos + eng._live))
    for was, now in zip(before_b, latent_rows(eng, b.slot)):
        assert np.array_equal(was, now)
    assert any(not np.array_equal(was, now)
               for was, now in zip(before_a, latent_rows(eng, a.slot)))
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


def test_a_shared_prefix_is_hit_and_served_what_a_fresh_one_is(tiny):
    """The pager's prefix cache works on latent entries by mechanism: a
    second request with the same two-block prefix adopts the parked
    blocks (one chunk instead of two) and is served the reference's
    tokens, as a fresh engine serves them."""
    prog, arrays, model = tiny
    rng = np.random.default_rng(12)
    shared = rng.integers(0, 512, 2 * BLOCK).tolist()
    tails = [rng.integers(0, 512, 5).tolist() for _ in range(3)]
    eng = engine(prog)
    reqs = []
    for tail in tails:
        r = eng.submit(shared + tail, max_new_tokens=6)
        eng.run()
        reqs.append(r)
    assert reqs[0].prefill_chunks == 2
    assert [r.prefill_chunks for r in reqs[1:]] == [1, 1]
    pg = eng.stats()["paged"]
    assert pg["prefix_hits"] == 2 and pg["prefix_hit_tokens"] == 4 * BLOCK
    for tail, r in zip(tails, reqs):
        fresh = engine(prog)
        want = fresh.submit(shared + tail, max_new_tokens=6)
        fresh.run()
        assert r.tokens == want.tokens
        assert float(reference_gaps(arrays, model, shared + tail,
                                    r.tokens).max()) < 2e-5
    eng._pager.check_invariants()


def test_copy_on_write_at_the_shared_blocks_edge(tiny):
    """Two tenants of one prompt that ends INSIDE a block: the second
    shares the first's blocks, copies the tail block before it writes
    (the copy runs over every latent pool), and both are served what one
    is served alone; the first full block is never rewritten."""
    prog, arrays, model = tiny
    prompt = np.random.default_rng(13).integers(0, 512, 13).tolist()
    alone = engine(prog)
    want = alone.submit(prompt, max_new_tokens=10)
    alone.run()
    eng = engine(prog)
    a = eng.submit(prompt, max_new_tokens=10)
    while a.status != "running":
        eng.step()
    frozen = int(eng._pager.tables[a.slot][0])
    b = eng.submit(prompt, max_new_tokens=10)
    eng.step()
    assert eng.stats()["paged"]["cow_copies"] >= 1
    before = [np.asarray(pool[frozen]).copy() for layer in eng._pools
              for (pool,) in layer]
    eng.run()
    for was, (pool,) in zip(before, [e for layer in eng._pools
                                     for e in layer]):
        assert np.array_equal(was, np.asarray(pool[frozen]))
    assert a.tokens == b.tokens == want.tokens
    assert float(reference_gaps(arrays, model, prompt, b.tokens).max()) \
        < 2e-5
    eng._pager.check_invariants()


def test_a_preempted_request_is_served_the_same_tokens(tiny):
    """A pool too small for both tenants: the younger is preempted
    (recompute-style), re-admitted, and serves what it serves alone."""
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


# ------------------------------------- the latent kernel, interpreted

def gathered_view(q, pool, table, lengths, rank, scale):
    b = q.shape[0]
    rows = jnp.take(pool, table, axis=0).reshape(b, -1, pool.shape[-1])
    s = jnp.einsum("bhd,bmd->bhm", q[:, 0], rows, precision="highest") \
        * scale
    live = jnp.arange(rows.shape[1])[None, None] < lengths[:, None, None]
    p = jax.nn.softmax(jnp.where(live, s, -1e30), -1)
    return jnp.einsum("bhm,bmd->bhd", p, rows[..., :rank],
                      precision="highest")[:, None]


# lengths, pages a chunk (None: derived), query heads, table width, NaN in
# every pool row past each slot's length
CASES = {
    "ragged": ([1, 5, 13, 28, 9], None, 6, 7, False),
    "a_dead_slot_walks_one_page": ([0, 7, 0, 20, 3], None, 6, 7, False),
    "at_a_pages_edge": ([4, 8, 12, 16, 28], None, 6, 7, False),
    "at_a_chunks_edge": ([8, 16, 24, 9, 17], 2, 6, 7, False),
    "chunks_of_three_pages": ([28, 1, 12, 13, 25], 3, 6, 7, False),
    "one_page_a_chunk": ([28, 4, 5, 27, 1], 1, 6, 7, False),
    # 4-position pages in chunks of 24 (96 rows) or 16 (64 rows): pieces of
    # 8 pages (32 rows), the latent walk's unit of stopping at a slot's
    # live rows
    "128_heads_at_a_pieces_edge": ([31, 32, 33, 64, 65], None, 128, 24, True),
    "64_heads_at_a_pieces_edge": ([31, 32, 33, 63, 96], None, 64, 24, True),
    "128_heads_at_a_chunks_edge": ([63, 64, 65, 128, 192], 16, 128, 48,
                                   True),
    "64_heads_at_a_chunks_edge": ([64, 65, 127, 129, 191], 16, 64, 48,
                                  True),
    "one_live_row": ([1, 1, 96, 1, 33], None, 128, 24, True),
    "a_dead_slot_among_pieces": ([0, 96, 0, 40, 1], None, 64, 24, True),
}


@pytest.mark.parametrize("which", sorted(CASES))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_latent_kernel_is_the_gathered_view(which, dtype):
    """4-position pages, query heads on rows of 16 + 8 lanes in 128: lengths
    at a page's, a piece's and a chunk's edge, one live row, 6, 64 and 128
    heads, a dead slot (length 0 walks one page and its output is never
    read); where the case says so, every pool row past a slot's length
    holds NaN, and none reaches the output."""
    lengths, pages, nh, mbs, nan = CASES[which]
    rng = np.random.default_rng(len(which))
    nb, bs, lanes, rank, b = 5 * mbs + 5, 4, 128, 16, 5
    pool = np.zeros((nb, bs, lanes), np.float32)
    pool[..., :24] = rng.normal(size=(nb, bs, 24))
    q = np.zeros((b, 1, nh, lanes), np.float32)
    q[..., :24] = rng.normal(size=(b, 1, nh, 24))
    table = rng.permutation(nb - 1)[:b * mbs].reshape(b, mbs) + 1
    dirty = pool.copy()
    if nan:
        live = np.zeros((nb, bs), bool)
        for row, n in zip(table, lengths):
            for p in range(n):
                live[row[p // bs], p % bs] = True
        dirty[~live] = np.nan
    q = jnp.asarray(q, dtype)
    table = jnp.asarray(table, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    got = paged_decode.latent_decode_attention(
        q, jnp.asarray(dirty, dtype), table, lengths, rank=rank, scale=0.3,
        interpret=True, pages_per_chunk=pages)
    assert got.shape == (b, 1, nh, rank) and got.dtype == q.dtype
    geo = paged_decode.kernel_geometry()
    assert geo["kv_piece_pages"] == min(8, geo["kv_chunk_pages"])
    want = gathered_view(q.astype(jnp.float32),
                         jnp.asarray(pool, dtype).astype(jnp.float32),
                         table, lengths, rank, 0.3)
    live = np.asarray(lengths) > 0
    err = np.abs(np.asarray(got, np.float32) - np.asarray(want))[live]
    assert float(err.max()) < (1e-5 if dtype == "float32" else 3e-2)


def test_the_latent_walk_rounds_its_chunk_up_to_eight_pages():
    """20 KB pages under the 512 KB rule give 25, taken as 32 (the scores'
    columns fill whole lane tiles); a narrow table bounds it."""
    q = jnp.zeros((2, 1, 4, 640), jnp.bfloat16)
    pool = jnp.zeros((300, 16, 640), jnp.bfloat16)
    for width, pages in ((256, 32), (12, 12)):
        table = jnp.ones((2, width), jnp.int32)
        jax.eval_shape(lambda *a: paged_decode.latent_decode_attention(
            *a, rank=512, scale=0.1, interpret=True),
            q, pool, table, jnp.ones((2,), jnp.int32))
        assert paged_decode.kernel_geometry() == {
            "kv_chunk_pages": pages, "kv_page_bytes": 16 * 640 * 2,
            "kv_piece_pages": 8}


# --------------------------------------------------- spans and refusals

def test_call_spans_say_which_attention_and_count_latent_rows(tiny,
                                                              monkeypatch):
    """`path` on every decode and chunk call (the latent kernel names
    itself; the view is `gather`; a chunk walks its slot's key blocks:
    `key_walk`, with the keys it walked beside it), `kv_bytes` = live
    context x 4 rows of 24 numbers, `moe_zero` beside the other three on
    the finish span, and the executables hold the new named scopes."""
    from paddle_tpu.monitor import trace
    prog, _, model = tiny
    fam, _ = family()
    per_token = fam.kv_bytes_per_token(model, elem=4)
    for interpret, path in ((False, "gather"), (True, "mla_decode")):
        eng = engine(prog)
        t0 = time.perf_counter()
        with paged_decode.force_interpret(interpret):
            eng.submit(list(range(1, 20)), max_new_tokens=3)
            eng.run()
        t1 = time.perf_counter()
        calls = trace.spans(t0, t1, "engine/decode_call")
        assert [s.attrs["kv_bytes"] for s in calls] == \
            [per_token * n for n in (20, 21)]
        assert {s.attrs["path"] for s in calls} == {path} \
            == {eng.stats()["decode_attention"]}
        assert all("state_slots" not in s.attrs for s in calls)
        chunks = trace.spans(t0, t1, "engine/prefill_call")
        assert [s.attrs["kv_bytes"] for s in chunks] == \
            [per_token * n for n in (CHUNK, 19)]
        assert {s.attrs["path"] for s in chunks} == {"key_walk"}
        # a trip of the walk holds this engine's whole table row
        assert [s.attrs["kv_walked"] for s in chunks] == [96, 96]
        assert eng.stats()["prefill_attention"] == {
            "path": "key_walk", "kv_walked": 192, "kv_table": 192,
            "share": 1.0}
        fins = trace.spans(t0, t1, "engine/decode_finish")
        for s in fins:
            assert s.attrs["moe_assignments"] == 2 * 4      # layers x top-k
            assert 0 <= s.attrs["moe_zero"] <= 8
            assert s.attrs["moe_local"] + s.attrs["moe_zero"] == 8
        assert eng.stats()["moe"]["zero"] == sum(
            s.attrs["moe_zero"] for s in fins)
    text = eng._decode_exe.as_text()
    assert all(scope in text for scope in (
        "mla_project", "latent_write", "mla_decode", "dense_ffn",
        "zero_experts", "moe_route", "moe_experts"))
    assert "mla_prefill" in eng._prefill_exes[CHUNK].as_text()
    # trips of one chunk's length (4 heads x 16 queries x 16 keys): a
    # chunk walks whole trips up to its end, not the 96 of the row
    from paddle_tpu.models import hybrid
    monkeypatch.setattr(hybrid, "WALK_SCORES", 4 * CHUNK * CHUNK)
    eng = engine(prog)
    assert eng.stats()["prefill_attention"]["share"] is None
    t0 = time.perf_counter()
    eng.submit(list(range(1, 22)), max_new_tokens=2)
    eng.submit(list(range(30, 30 + 2 * CHUNK)), max_new_tokens=2)
    eng.run()
    chunks = trace.spans(t0, time.perf_counter(), "engine/prefill_call")
    assert sorted((s.attrs["kv_bytes"] // per_token, s.attrs["kv_walked"])
                  for s in chunks) == [(CHUNK, CHUNK), (CHUNK, CHUNK),
                                       (21, 2 * CHUNK),
                                       (2 * CHUNK, 2 * CHUNK)]
    assert eng.stats()["prefill_attention"] == {
        "path": "key_walk", "kv_walked": 6 * CHUNK, "kv_table": 4 * 96,
        "share": 0.25}


def test_what_a_latent_entry_cannot_do_yet_is_refused_by_name(tiny):
    from paddle_tpu.serving import LocalPool, PromptLookupDrafter
    prog, _, _ = tiny
    with pytest.raises(NotImplementedError, match="latent-attention entries"):
        engine(prog, drafter=PromptLookupDrafter())
    with pytest.raises(NotImplementedError, match="wire codec"):
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
                           match="head-sharded placement"):
            engine(prog)
    finally:
        head._data = kept
        denv.set_mesh(prev)


def test_the_other_models_are_handed_what_they_were():
    """GPT's and the hybrids' pools keep their shapes, their decode spans
    their `path` values, and a model without zero experts its three
    counters; chunk calls gain `path`."""
    from paddle_tpu.models import (FalconH1ForCausalLM, GPTForCausalLM,
                                   Qwen3NextForCausalLM, falcon_h1_tiny,
                                   gpt_tiny, qwen3_next_tiny)
    from paddle_tpu.monitor import trace
    gpt = GPTForCausalLM(gpt_tiny())
    gpt.eval()
    eng = DecodeEngine(gpt, max_slots=2, max_len=32, block_size=8,
                       kv_blocks=9, prefill_chunk=8)
    cfg = gpt.config
    for k, v in eng._pools:
        assert k.shape == v.shape == (
            9, 8, cfg.num_heads, cfg.hidden_size // cfg.num_heads)
    t0 = time.perf_counter()
    eng.submit([1, 2, 3], max_new_tokens=3)
    eng.run()
    t1 = time.perf_counter()
    assert {s.attrs["path"] for s in trace.spans(
        t0, t1, "engine/decode_call")} == {"gather"}
    assert {s.attrs["path"] for s in trace.spans(
        t0, t1, "engine/prefill_call")} == {"key_walk"}
    assert eng._tok_len == 2 and "moe" not in eng.stats()
    qwen = Qwen3NextForCausalLM(qwen3_next_tiny())
    qwen.eval()
    eng = DecodeEngine(qwen, max_slots=2, max_len=32, block_size=8,
                       kv_blocks=9, prefill_chunk=8)
    assert eng._tok_len == 2 + 3
    eng.submit([1, 2, 3], max_new_tokens=3)
    eng.run()
    assert set(eng.stats()["moe"]) == {"assignments", "local", "touched"}
    falcon = FalconH1ForCausalLM(falcon_h1_tiny())
    falcon.eval()
    eng = DecodeEngine(falcon, max_slots=2, max_len=32, block_size=8,
                       kv_blocks=9, prefill_chunk=8)
    with paged_decode.force_interpret():
        eng.submit([1, 2, 3], max_new_tokens=2)
        eng.run()
    assert eng.stats()["decode_attention"] == "paged_kernel"
