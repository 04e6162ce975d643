"""The serving engine over the DeepSeek-V3 decoder `models/deepseek_v3.py`
at a tiny size on the CPU: one paged latent cache a layer (one `[c |
rotated k_rope]` row a token), chunked prefill + decode against the
reference's one forward pass at every served position, the prefix cache
and preemption working on it by mechanism, the latent decode kernel under
the interpreter, and what the call and finish spans carry (the router's
groups among the counts)."""
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from test_deepseek_v3 import CHUNK, family, program  # noqa: F401

from paddle_tpu.kernels.pallas import paged_decode
from paddle_tpu.serving import DecodeEngine

BLOCK = 8
# float32 program against the float32 reference, teacher-forced: a served
# token is the reference's own choice up to summation order (0 read); a
# bf16 rounding of the cache or of a product moves a logit by 1e-3 and more
GAP = 2e-5


@pytest.fixture(scope="module")
def tiny():
    return program()


def engine(prog, **kw):
    geo = dict(max_slots=4, max_len=96, block_size=BLOCK,
               prefill_chunk=CHUNK)
    geo.update(kw)
    return DecodeEngine(prog, **geo)


_REF = {}


def reference_gaps(arrays, model, prompt, tokens):
    """How far each served token's reference logit lies under the
    reference's best at its position (0: the reference's own choice),
    teacher-forced over prompt + served tokens in one forward pass padded
    to one length (padding is causally invisible)."""
    _, ref = family()
    if "fn" not in _REF:
        _REF["fn"] = jax.jit(lambda w, ids: ref.logits(w, ids, model))
    seq = list(prompt) + list(tokens)[:-1]
    ids = np.zeros((1, 96), np.int32)
    ids[0, :len(seq)] = seq
    at = np.asarray(_REF["fn"](arrays, jnp.asarray(ids)))[
        0, len(prompt) - 1:len(seq)]
    return at.max(-1) - at[np.arange(len(tokens)), np.asarray(tokens)]


@pytest.fixture(scope="module")
def shared_engine(tiny):
    """One engine for the tests that only need it idle between them."""
    return engine(tiny[0])


PROMPTS = {"inside_a_chunk": 21, "on_a_chunk_boundary": 2 * CHUNK,
           "one_token": 1, "past_the_original_context": 40}


@pytest.mark.parametrize("which", sorted(PROMPTS))
def test_chunked_prefill_and_decode_follow_the_reference(tiny, which,
                                                         shared_engine):
    """The cursor inside a chunk, on a chunk's edge, one token, and a
    request that runs past YaRN's 32 original positions: every served
    token is the reference's choice."""
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
        < GAP


def test_a_mixed_batch_with_the_kernel_interpreted_follows_the_reference(
        tiny):
    """Requests of several lengths admitted together, so decode steps run
    beside prefill chunks; the latent decode kernel through the
    interpreter, every call span saying so, the finish spans counting the
    groups each token's choice spans."""
    from paddle_tpu.monitor import trace
    prog, arrays, model = tiny
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 512, n).tolist() for n in (3, 20, 9)]
    eng = engine(prog)
    t0 = time.perf_counter()
    with paged_decode.force_interpret():
        reqs = [eng.submit(p, max_new_tokens=3 + i)
                for i, p in enumerate(prompts)]
        eng.run()
    t1 = time.perf_counter()
    assert eng.stats()["decode_attention"] == "mla_decode"
    for p, r in zip(prompts, reqs):
        assert r.status == "done"
        assert float(reference_gaps(arrays, model, p, r.tokens).max()) < GAP
    calls = trace.spans(t0, t1, "engine/decode_call")
    assert calls and {s.attrs["path"] for s in calls} == {"mla_decode"}
    fins = trace.spans(t0, t1, "engine/decode_finish")
    for s in fins:
        # two routed layers x top-4 a live token; 1 or 2 groups a token
        tokens = s.attrs["moe_assignments"] // 8
        assert tokens >= 1 and s.attrs["moe_assignments"] == 8 * tokens
        assert 2 * tokens <= s.attrs["moe_groups"] <= 2 * 2 * tokens
    moe = eng.stats()["moe"]
    assert set(moe) == {"assignments", "local", "touched", "groups"}
    assert moe["groups"] == sum(s.attrs["moe_groups"] for s in fins)
    text = eng._decode_exe.as_text()
    assert all(scope in text for scope in (
        "mla_project", "latent_write", "mla_decode", "dense_ffn",
        "moe_route", "moe_experts", "shared_expert"))
    assert "mla_prefill" in eng._prefill_exes[CHUNK].as_text()


def test_a_shared_prefix_is_hit_and_a_preempted_request_served_alike(tiny):
    """The pager's prefix cache works on the latent entries by mechanism (a
    second request with the same two-block prefix adopts the parked blocks
    and runs one chunk, not two); a pool too small for two tenants preempts
    the younger, which is served what it is served alone."""
    prog, arrays, model = tiny
    rng = np.random.default_rng(12)
    shared = rng.integers(0, 512, 2 * BLOCK).tolist()
    tails = [rng.integers(0, 512, 5).tolist() for _ in range(2)]
    eng = engine(prog)
    reqs = []
    for tail in tails:
        r = eng.submit(shared + tail, max_new_tokens=6)
        eng.run()
        reqs.append(r)
    assert [r.prefill_chunks for r in reqs] == [2, 1]
    pg = eng.stats()["paged"]
    assert pg["prefix_hits"] == 1 and pg["prefix_hit_tokens"] == 2 * BLOCK
    assert float(reference_gaps(arrays, model, shared + tails[1],
                                reqs[1].tokens).max()) < GAP
    eng._pager.check_invariants()
    prompts = [rng.integers(0, 512, 20).tolist() for _ in range(2)]
    alone = []
    for p in prompts:
        eng.drop_prefix_cache()
        r = eng.submit(p, max_new_tokens=40)
        eng.run()
        alone.append(r.tokens)
    tight = engine(prog, max_slots=2, max_len=64, kv_blocks=12)
    reqs = [tight.submit(p, max_new_tokens=40) for p in prompts]
    tight.run()
    assert tight.stats()["paged"]["preemptions"] >= 1
    assert [r.tokens for r in reqs] == alone
    tight._pager.check_invariants()


def test_the_weights_laid_out_for_serving_give_the_same_logits():
    """The engine lays each ``Wkvb`` out groups first where it is built
    (``serving_layouts``): a decode step over a filled cache, its logits
    compiled against the weights as they were assigned and again after,
    the kernel's walk under the interpreter and the gathered view alike,
    gives the same bits; names, shapes and values stay."""
    from paddle_tpu.kernels.pallas.util import attention_kernels_traced
    from paddle_tpu.serving.engine import lay_out_for_serving
    prog, _, _ = program()
    leaves = [p for _, p in prog.named_parameters()]
    rng = np.random.default_rng(7)
    b, mbs, lanes = 3, 4, 128
    pools = [jnp.asarray(rng.normal(size=(b * mbs + 1, BLOCK, lanes)),
                         jnp.float32) for _ in prog.model.layers]
    table = jnp.asarray(np.arange(1, b * mbs + 1, dtype=np.int32).reshape(
        b, mbs))
    pos = jnp.asarray([5, 17, 30], jnp.int32)
    ids = jnp.asarray(rng.integers(0, 512, (b, 1)), jnp.int32)

    def step(arrays, pools):
        held = [p._data for p in leaves]
        try:
            for p, a in zip(leaves, arrays):
                p._data = a
            hidden, _ = prog.model(ids, kv_caches=[(p, table) for p in pools],
                                   start_pos=pos, write_end=pos + 1)
            return hidden.value()[:, -1] @ prog.lm_head.value()
        finally:
            for p, d in zip(leaves, held):
                p._data = d

    def logits():       # a fresh trace each: the kernel is chosen tracing
        arrays = [p.value() for p in leaves]
        out = [np.asarray(jax.jit(lambda a, q: step(a, q))(arrays, pools))]
        mark = len(attention_kernels_traced())
        with paged_decode.force_interpret():
            out.append(np.asarray(
                jax.jit(lambda a, q: step(a, q))(arrays, pools)))
        assert "mla_decode" in attention_kernels_traced(mark)
        return out

    names = {n: (tuple(p.shape), np.asarray(p.value()))
             for n, p in prog.named_parameters()}
    before = logits()
    lay_out_for_serving(prog)
    wkvb = [blk.self_attn.kv_b_proj.value() for blk in prog.model.layers]
    assert all(w.format.layout.major_to_minor == (1, 0) for w in wkvb)
    after = logits()
    for was, now in zip(before, after):
        np.testing.assert_array_equal(was, now)
    assert float(np.abs(before[0] - before[1]).max()) < 1e-5
    for n, p in prog.named_parameters():
        assert tuple(p.shape) == names[n][0]
        np.testing.assert_array_equal(np.asarray(p.value()), names[n][1])
