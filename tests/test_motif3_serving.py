"""The serving engine over the Motif-3 decoder `models/motif3.py` at a tiny
size on the CPU: a paged latent pool for the full layer, a ring a slot for
each window layer, chunked prefill + decode against the reference's one
forward pass at every served position (prompts longer than the window, a
chunk twice the window, so a ring wraps inside one chunk), preemption
recomputing, no prefix cache, the kernels under the interpreter, and what
the call spans carry (`window_rows`, `mhc_rows`, a per-entry `kv_bytes`,
which reads as it did for models without a ring)."""
import contextlib
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from test_motif3 import CHUNK, family, program  # noqa: F401

from paddle_tpu.kernels.pallas import mhc, moe_grouped, paged_decode, \
    pool_write
from paddle_tpu.serving import DecodeEngine

BLOCK = 8
WINDOW = 8
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
    teacher-forced over prompt + served tokens in one padded pass."""
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
    return engine(tiny[0])


PROMPTS = {"one_token": 1, "inside_the_first_chunk": 5,
           "a_ring_wrapped_in_a_chunk": 21, "on_a_chunk_boundary": 2 * CHUNK,
           "past_the_original_context": 40}


@pytest.mark.parametrize("which", sorted(PROMPTS))
def test_chunked_prefill_and_decode_follow_the_reference(tiny, which,
                                                         shared_engine):
    prog, arrays, model = tiny
    n = PROMPTS[which]
    prompt = np.random.default_rng(n).integers(0, 512, n).tolist()
    eng = shared_engine
    req = eng.submit(prompt, max_new_tokens=13)
    eng.run()
    assert req.status == "done" and len(req.tokens) == 13
    assert req.prefill_chunks == -(-n // CHUNK)
    assert float(reference_gaps(arrays, model, prompt, req.tokens).max()) \
        < GAP


def test_the_rings_and_the_pool_are_what_the_spec_says(tiny, shared_engine):
    eng = shared_engine
    kinds = [c.kind for c in eng.spec.entries]
    assert kinds == ["window", "window", "latent", "window", "window"]
    for kind, (pool,) in zip(kinds, eng._pools):
        if kind == "window":    # trash + 4 slots x one window of blocks
            assert pool.shape == (1 + 4 * WINDOW // BLOCK, BLOCK, 128)
        else:
            assert pool.shape == (eng.kv_blocks, BLOCK, 128)
    assert eng._ring_tables[1].tolist() == [[1], [2], [3], [4]]


@contextlib.contextmanager
def interpreted():
    with contextlib.ExitStack() as stack:
        for m in (paged_decode, pool_write, mhc, moe_grouped):
            stack.enter_context(m.force_interpret())
        yield


def test_a_mixed_batch_with_the_kernels_interpreted_follows_the_reference(
        tiny):
    """Requests of several lengths admitted together, decode beside
    prefill chunks; the latent decode kernel, the pool writes, mhc and the
    PolyNorm grouped kernel through the interpreter; every call span says
    so and carries the rows its window layers read and its boundaries
    mix."""
    from paddle_tpu.monitor import trace
    prog, arrays, model = tiny
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 512, n).tolist() for n in (3, 20, 9)]
    eng = engine(prog)
    t0 = time.perf_counter()
    with interpreted():
        reqs = [eng.submit(p, max_new_tokens=4 + i)
                for i, p in enumerate(prompts)]
        eng.run()
    t1 = time.perf_counter()
    assert eng.stats()["decode_attention"] == "mla_decode"
    for p, r in zip(prompts, reqs):
        assert r.status == "done"
        assert float(reference_gaps(arrays, model, p, r.tokens).max()) < GAP
    calls = trace.spans(t0, t1, "engine/decode_call")
    assert calls and {s.attrs["path"] for s in calls} == {"mla_decode"}
    assert {s.attrs["kv_write"] for s in calls} == {"kernel"}
    for s in calls:         # 10 boundaries of the 4 slots' rows
        assert s.attrs["mhc_rows"] == 10 * 4
        assert 0 < s.attrs["window_rows"] <= 4 * WINDOW * 4
    for s in trace.spans(t0, t1, "engine/prefill_call"):
        assert s.attrs["mhc_rows"] == 10 * CHUNK
        assert "window_rows" in s.attrs
    text = eng._decode_exe.as_text()
    assert all(scope in text for scope in (
        "gdla_project", "window_write", "latent_write", "mla_decode",
        "gdla_combine", "mhc", "dense_ffn", "moe_route", "moe_experts",
        "shared_expert"))
    chunk = eng._prefill_exes[CHUNK].as_text()
    assert "mla_prefill" in chunk and "window_write" in chunk


def test_a_repeated_prompt_is_no_prefix_hit_and_a_preempted_request_is_served_alike(  # noqa: E501
        tiny):
    """A ring cannot be rebuilt from shared blocks: the prefix cache is off
    by the entry's kind, a repeated prompt is prefilled again. A pool too
    small for two tenants preempts the younger, which is recomputed and
    served what it is served alone."""
    prog, arrays, model = tiny
    rng = np.random.default_rng(12)
    eng = engine(prog)
    prompt = rng.integers(0, 512, 2 * BLOCK + 5).tolist()
    reqs = []
    for _ in range(2):
        r = eng.submit(prompt, max_new_tokens=6)
        eng.run()
        reqs.append(r)
    assert [r.prefill_chunks for r in reqs] == [2, 2]
    assert eng.stats()["paged"]["prefix_hits"] == 0
    assert reqs[0].tokens == reqs[1].tokens
    prompts = [rng.integers(0, 512, 20).tolist() for _ in range(2)]
    alone = []
    for p in prompts:
        r = eng.submit(p, max_new_tokens=40)
        eng.run()
        alone.append(r.tokens)
    tight = engine(prog, max_slots=2, max_len=64, kv_blocks=12)
    reqs = [tight.submit(p, max_new_tokens=40) for p in prompts]
    tight.run()
    assert tight.stats()["paged"]["preemptions"] >= 1
    assert [r.tokens for r in reqs] == alone
    for p, r in zip(prompts, reqs):
        assert float(reference_gaps(arrays, model, p, r.tokens).max()) < GAP
    tight._pager.check_invariants()


def test_kv_bytes_counts_what_each_entry_reads(tiny, shared_engine):
    """A full layer's rows are every position a slot holds, a window
    layer's its last `window`: 1 full + 4 window layers of 24 counted
    lanes at 4 bytes."""
    eng = shared_engine
    ctx = np.array([3, 20, 100])
    got = eng._cache_attrs(ctx, 40)
    ring = np.minimum(ctx, WINDOW).sum()
    assert got["kv_bytes"] == 4 * 24 * (ctx.sum() + 4 * ring)
    assert got["window_rows"] == 4 * ring and got["mhc_rows"] == 40
    assert "mhc_rows" not in eng._cache_attrs(ctx)


def _old_kv_bytes(eng):
    """The parent's one count a context token: K and V of every kv entry,
    the row of every latent entry."""
    spec = eng.spec
    return jnp.dtype(eng._cache_dtype).itemsize * (sum(
        2 * c.n_kv_heads * c.head_dim for c in spec.kv_layers)
        + sum(c.head_dim for c in spec.latent_layers))


@pytest.mark.parametrize("which", ["gpt", "longcat", "qwen3_next"])
def test_kv_bytes_reads_as_before_for_a_model_without_a_ring(which):
    if which == "gpt":
        import paddle_tpu as paddle
        from paddle_tpu.models import GPTForCausalLM, gpt_tiny
        paddle.seed(0)
        prog = GPTForCausalLM(gpt_tiny())
    elif which == "longcat":
        from paddle_tpu.models import (LongCatFlashForCausalLM,
                                       longcat_flash_tiny)
        prog = LongCatFlashForCausalLM(longcat_flash_tiny())
    else:
        from paddle_tpu.models import Qwen3NextForCausalLM, qwen3_next_tiny
        prog = Qwen3NextForCausalLM(qwen3_next_tiny(num_experts=8,
                                                    router_experts=32))
    eng = DecodeEngine(prog, max_slots=4, max_len=64, block_size=8,
                       prefill_chunk=16)
    ctx = np.array([1, 17, 60])
    got = eng._cache_attrs(ctx)
    assert got["kv_bytes"] == int(ctx.sum()) * _old_kv_bytes(eng) > 0
    assert "window_rows" not in got and "mhc_rows" not in got
    assert eng._pager.prefix_cache == (which != "qwen3_next")


@pytest.mark.parametrize("kernel", [False, True], ids=["scatter", "kernel"])
def test_a_ring_write_keeps_the_last_window_of_positions(tiny, kernel):
    """Every cursor of a ring of 8 (one block of 8: the block copies' unit,
    so the kernel runs), chunks of 16 rows of which 3, 9 or all are valid:
    the ring holds each of the last 8 positions at its row `p mod 8` and
    nothing else moved, through the block-copy kernel (a run that passes
    the ring's end wraps) and the scatter alike; the other slot's ring
    untouched."""
    prog, _, _ = tiny
    attn = prog.model.layers[0].self_attn
    bs, slots, lanes = 8, 2, 128
    ring = jnp.asarray([[2]], jnp.int32)            # slot 1's one page
    rng = np.random.default_rng(9)
    for p0 in range(0, 24, 3):
        for valid in (3, 9, 16):
            pool = jnp.asarray(rng.normal(size=(1 + slots, bs, lanes)),
                               jnp.float32)
            rows = jnp.asarray(rng.normal(size=(1, 16, lanes)), jnp.float32)
            positions = p0 + jnp.arange(16, dtype=jnp.int32)[None]
            with pool_write.force_interpret(kernel):
                assert (pool_write.kernel_mode(pool, rows) is not None) \
                    == kernel
                got = np.asarray(attn._ring_write(pool, ring, rows, positions,
                                                  jnp.int32(p0 + valid)))
            want = np.asarray(pool).copy()
            for i in range(max(0, valid - 8), valid):
                want[2, (p0 + i) % 8] = np.asarray(rows[0, i])
            # block 0 is trash: the scatter sends what it does not write
            assert np.array_equal(got[1:], want[1:]), (p0, valid)
