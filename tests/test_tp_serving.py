"""Tensor-parallel paged decode over the virtual CPU mesh (ISSUE 13).

The contract under test:
  * With a "model"-axis mesh and a model riding it (shard_gpt_tp /
    shard_llama_tp), the DecodeEngine mints SPMD executables: per-layer KV
    pools sharded on the head axis (head_dim fallback when the GQA head
    count doesn't divide the TP degree), weights on their Column/Row
    placements, block table / cursors / COW pairs replicated host data —
    the BlockPager never learns about the mesh.
  * TP=2 and TP=4 greedy decode equals the single-chip engine and the
    eager loop token-for-token, ACROSS prefix sharing, copy-on-write,
    chunked prefill and pool-pressure preemption.
  * Zero steady-state recompiles holds on the mesh: block churn, sharing,
    COW and chunking never re-mint.
  * A replicated model on a model-axis mesh stays single-chip (the mesh
    alone proves nothing about THIS model).
  * generate(use_engine=True) keys its engine cache on the EFFECTIVE TP
    degree: sharding the model after first use mints a mesh-native engine
    instead of silently serving the stale single-chip one.

Runs on the conftest 8-device virtual CPU platform; every test restores
the global mesh it found, so sibling test files keep their environment.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import env as denv
from paddle_tpu.models import GPTConfig, GPTForCausalLM, shard_gpt_tp
from paddle_tpu.serving import DecodeEngine


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
    return m.generate(paddle.to_tensor(ids),
                      max_new_tokens=n).numpy()[0, len(prompt):]


@pytest.fixture
def model_mesh():
    """Install a tp-degree "model"-axis mesh as the global mesh; restore
    whatever was there on the way out (the mesh is process-global and the
    suite shares one process)."""
    import jax
    from jax.sharding import Mesh

    made = {}

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


def test_tp2_gpt_parity_full_machinery(model_mesh):
    """TP=2 GPT: greedy parity with the eager single-chip loop across
    sharing + COW + chunked prefill + preemption churn, with the KV pool
    head-sharded and ZERO steady-state recompiles on the mesh."""
    m = _tiny_gpt()
    rng = np.random.RandomState(0)
    prefix = rng.randint(1, 64, 10).tolist()    # NOT block-aligned: the
    # leader's 13-token prompt registers one full block + a partial tail.
    # The identical twin adopts the tail (exact-prompt key) and its first
    # write copy-on-writes it; the divergent sibling adopts the full block
    prompts = ([prefix + [50, 51, 52], prefix + [50, 51, 52],
                prefix + [60, 61, 62]]
               + [rng.randint(1, 64, 20).tolist()]            # chunking
               + [rng.randint(1, 64, n).tolist() for n in (5, 13)])
    horizons = [6, 6, 6, 8, 8, 8]
    refs = [_eager(m, p, h) for p, h in zip(prompts, horizons)]

    model_mesh(2)
    shard_gpt_tp(m)
    eng = DecodeEngine(m, max_slots=4, max_len=48, block_size=8,
                       prefill_chunk=8)
    assert eng._tp == 2 and eng._mesh is not None
    assert "model" in str(eng._pools[0][0].sharding.spec)     # head-sharded
    lead = eng.submit(prompts[0], max_new_tokens=horizons[0])
    while lead.status != "running":
        eng.step()                  # publish the shared prefix first
    reqs = [lead] + [eng.submit(p, max_new_tokens=h)
                     for p, h in zip(prompts[1:], horizons[1:])]
    eng.run()
    for p, r, ref in zip(prompts, reqs, refs):
        assert r.status == "done", r
        np.testing.assert_array_equal(ref, r.output_tokens)
    st = eng.stats()["paged"]
    assert st["shared_hits"] >= 2 and st["cow_copies"] >= 1

    # steady state on the mesh: a second wave (sharing, COW, fresh allocs,
    # LRU adoption) mints NOTHING
    base = eng.compile_count
    wave2 = [eng.submit(p, max_new_tokens=4) for p in prompts[:4]]
    eng.run()
    assert all(r.status == "done" for r in wave2)
    assert eng.compile_count == base, \
        f"TP steady state re-minted {eng.compile_count - base} executables"
    assert eng.stats()["paged"]["prefix_hits"] >= 1   # LRU adoption ran too


def test_tp2_parity_across_preemption(model_mesh):
    """Pool-pressure preemption on the mesh: recompute-on-readmission keeps
    greedy output exactly equal to the eager loop (the single-chip
    test_eviction_preemption_parity, now SPMD)."""
    m = _tiny_gpt(seed=3)
    rng = np.random.RandomState(6)
    prompts = [rng.randint(1, 64, 20).tolist() for _ in range(4)]
    refs = [_eager(m, p, 20) for p in prompts]
    model_mesh(2)
    shard_gpt_tp(m)
    eng = DecodeEngine(m, max_slots=4, max_len=48, block_size=8,
                       kv_blocks=9, prefill_chunk=8)
    reqs = [eng.submit(p, max_new_tokens=20) for p in prompts]
    eng.run(max_steps=600)
    assert all(r.status == "done" for r in reqs)
    assert eng.preemptions > 0
    for ref, r in zip(refs, reqs):
        np.testing.assert_array_equal(ref, r.output_tokens)
    eng._pager.check_invariants()


def test_tp4_llama_gqa_hd_fallback_parity(model_mesh):
    """TP=4 LLaMA with 2 KV heads: n_kv % tp != 0, so the pool falls back
    to head_dim sharding — parity with the eager loop still holds, with
    prefix sharing on."""
    from paddle_tpu.models.llama import (LlamaForCausalLM, llama_tiny,
                                         shard_llama_tp)
    paddle.seed(7)
    lm = LlamaForCausalLM(llama_tiny(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, max_position_embeddings=64))
    lm.eval()
    rng = np.random.RandomState(7)
    prefix = rng.randint(1, 64, 10).tolist()
    pa, pb = prefix + [7], prefix + [9]
    refs = [_eager(lm, p, 6) for p in (pa, pb)]
    model_mesh(4)
    shard_llama_tp(lm)
    eng = DecodeEngine(lm, max_slots=2, max_len=32, block_size=4,
                       prefill_chunk=4)
    assert eng._tp == 4
    # n_kv=2 % 4 != 0 -> the sharded axis is head_dim (axis 3)
    spec = eng._pools[0][0].sharding.spec
    assert len(spec) == 4 and spec[3] == "model" and spec[2] is None
    ra = eng.submit(pa, max_new_tokens=6)
    while ra.status != "running":
        eng.step()
    rb = eng.submit(pb, max_new_tokens=6)
    eng.run()
    assert eng.stats()["paged"]["shared_hits"] >= 1
    for ref, r in zip(refs, (ra, rb)):
        np.testing.assert_array_equal(ref, r.output_tokens)


def test_replicated_model_stays_single_chip(model_mesh):
    """A model nobody sharded must NOT go SPMD just because some other
    tenant built a model-axis mesh: the engine requires both the mesh and
    a model that rides it."""
    model_mesh(2)
    m = _tiny_gpt(seed=1)                 # constructed on the mesh, unsharded
    eng = DecodeEngine(m, max_slots=2, max_len=32, block_size=8,
                       prefill_chunk=8)
    assert eng._mesh is None and eng._tp == 1
    r = eng.submit([1, 2, 3], max_new_tokens=4)
    eng.run()
    assert r.status == "done" and len(r.output_tokens) == 4


def test_custom_axis_sharded_model_refused_loudly(model_mesh):
    """A model sharded over a mesh the engine cannot drive (custom axis
    name, or a mesh never installed in distributed.env) must be refused
    with a message naming the "model"-axis contract — not die deep in jit
    with 'incompatible devices'."""
    import jax
    from jax.sharding import Mesh
    m = _tiny_gpt(seed=9)
    denv.set_mesh(Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("mp",)))
    shard_gpt_tp(m, axis="mp")
    with pytest.raises(NotImplementedError, match='"model" axis'):
        DecodeEngine(m, max_slots=2, max_len=32, block_size=8,
                     prefill_chunk=8)


def test_engine_cache_key_includes_tp(model_mesh):
    """Satellite regression: generate(use_engine=True) after a mesh/shard
    change must mint a NEW engine (key carries the effective TP degree) —
    the leaf-identity check can't see a placement-only change, and the
    stale single-chip engine's executables would reject (or silently
    misplace) the now-sharded weights. Counted on the mint counter."""
    m = _tiny_gpt(seed=4)
    m.__dict__.setdefault("_serving_engines", {}).clear()
    rng = np.random.RandomState(8)
    ids = paddle.to_tensor(rng.randint(1, 64, (2, 5)).astype("int32"))
    out1 = m.generate(ids, max_new_tokens=4, use_engine=True).numpy()
    assert len(m._serving_engines) == 1
    (k1, e1), = m._serving_engines.items()
    mints1 = e1.compile_count

    model_mesh(2)
    shard_gpt_tp(m)
    out2 = m.generate(ids, max_new_tokens=4, use_engine=True).numpy()
    assert len(m._serving_engines) == 2, \
        "mesh change after first use served a stale single-chip engine"
    (k2, e2), = ((k, e) for k, e in m._serving_engines.items() if k != k1)
    assert e2 is not e1 and e2._tp == 2
    assert e1.compile_count == mints1     # old engine untouched, not re-mint
    np.testing.assert_array_equal(out1, out2)   # greedy parity across TP

    # same mesh again: the TP engine is REUSED, zero new mints
    mints2 = e2.compile_count
    m.generate(ids, max_new_tokens=4, use_engine=True)
    assert len(m._serving_engines) == 2
    assert e2.compile_count == mints2
