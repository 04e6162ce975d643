"""The paged pools' block-copy writes (kernels/pallas/pool_write.py),
interpreted on the CPU, against the XLA scatters they replace on the chip:
every block but trash block 0 comes out bit for bit what the scatter leaves
(decode cursors of live and dead slots at a block's first and last offset
and at the table's last position; chunks from an unaligned start, with a
padded tail and with positions past the table; the 4-D, merged-row and
latent layouts; copy-on-write with padded pairs and a real pair whose
destination the same call then writes), the trash block is never written,
and engines of three families serve the same tokens either way."""
import numpy as np
import jax.numpy as jnp
import pytest

from paddle_tpu.kernels.pallas import pool_write
from paddle_tpu.models import hybrid
from paddle_tpu.models.gpt import _paged_kv_write

BS, MBS, NB = 8, 6, 40
rng = np.random.RandomState(0)


def table_for(b, dead=()):
    t = np.random.RandomState(b).permutation(np.arange(1, NB))[:b * MBS]
    t = t.reshape(b, MBS).astype(np.int32)
    t[list(dead)] = 0               # what the engine hands a dead slot
    return jnp.asarray(t)


def draw(shape, dtype):
    return jnp.asarray(rng.randn(*shape), jnp.float32).astype(dtype)


def gpt_write(pools, rows, table, pos, end):
    return _paged_kv_write(pools + (table, pos, end), *rows)


def merged_write(pools, rows, table, pos, end):
    return hybrid._write_merged(pools + (table,), *rows,
                                hybrid._positions(pos, rows[0].shape[1]), end)


def latent_write(pools, rows, table, pos, end):
    return (hybrid.write_rows(pools[0], table, rows[0],
                              hybrid._positions(pos, rows[0].shape[1]), end),)


# layout: (write, pool shape, row shape after [B, S], dtype)
LAYOUTS = {
    "gpt_f32": (gpt_write, (NB, BS, 4, 128), (4, 128), jnp.float32),
    "gpt_bf16": (gpt_write, (NB, BS, 2, 128), (2, 128), jnp.bfloat16),
    "merged_4": (merged_write, (NB, BS * 4, 128), (4, 128), jnp.bfloat16),
    "merged_2": (merged_write, (NB, BS * 2, 256), (2, 256), jnp.bfloat16),
    "latent": (latent_write, (NB, BS, 256), (256,), jnp.bfloat16),
}

LAST = MBS * BS - 1
# (cursors or a chunk's start, write ends, S, dead slots)
CALLS = {
    # decode: a block's first and last offset, the next block's first, the
    # table's last position, a slot told it is not live (end == pos) and a
    # dead slot on the trash row
    "decode": ([0, BS - 1, BS, LAST, 3, 5], [1, BS, BS + 1, LAST + 1, 3, 6],
               1, (5,)),
    "chunk_unaligned": (3, 3 + 20, 20, ()),
    "chunk_padded_tail": (BS + 5, BS + 5 + 9, 24, ()),
    "chunk_past_the_table": (LAST - 10, LAST + 30, 24, ()),
    "chunk_aligned_whole_blocks": (2 * BS, 4 * BS, 2 * BS, ()),
}


def call_args(layout, call):
    _, pshape, rtail, dtype = LAYOUTS[layout]
    pos, end, s, dead = CALLS[call]
    b = len(pos) if isinstance(pos, list) else 1
    pools = tuple(draw(pshape, dtype) for _ in range(
        1 if layout == "latent" else 2))
    rows = tuple(draw((b, s) + rtail, dtype) for _ in pools)
    return pools, rows, table_for(b, dead), jnp.asarray(pos, jnp.int32), \
        jnp.asarray(end, jnp.int32)


@pytest.mark.parametrize("call", sorted(CALLS))
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_block_copies_leave_what_the_scatter_leaves(layout, call):
    write = LAYOUTS[layout][0]
    pools, rows, table, pos, end = call_args(layout, call)
    want = write(pools, rows, table, pos, end)
    with pool_write.force_interpret():
        got = write(pools, rows, table, pos, end)
    for p, w, g in zip(pools, want, got):
        w, g = np.asarray(w), np.asarray(g)
        assert np.array_equal(w[1:], g[1:])
        # nothing is written into the trash block any more
        assert np.array_equal(np.asarray(p)[0], g[0])


@pytest.mark.parametrize("layout", ["gpt_f32", "merged_4", "latent"])
def test_layouts_are_read_from_the_shapes(layout):
    """One algorithm: a position on a major axis is copied as it is, on the
    tiled axis a unit of whole tiles of rows is."""
    _, pshape, rtail, dtype = LAYOUTS[layout]
    pool, rows = draw(pshape, dtype), draw((1, 4) + rtail, dtype)
    block, r, u = pool_write._layout(pool, rows)
    assert block == BS and r * u % pool_write.TILE_ROWS == 0 \
        or (r, u) == (1, 1)
    assert pool_write.kernel_mode(pool, rows) is None      # the CPU
    with pool_write.force_interpret():
        assert pool_write.kernel_mode(pool, rows) == "interpret"
        # a block of 6 positions holds no whole unit of 8 latent rows
        assert pool_write.kernel_mode(draw((NB, 6, 256), dtype),
                                      draw((1, 4, 256), dtype)) is None


def xla_cow(pools, src, dst):
    return [p.at[dst].set(p[src]) for p in pools]


@pytest.mark.parametrize("pairs", [
    [],                           # padding only: nothing moves
    [(5, 12)],
    [(5, 12), (9, 3), (7, 30)],
])
def test_copy_on_write_moves_real_pairs_only(pairs):
    pools = [draw((NB, BS, 2, 128), jnp.float32) for _ in range(3)]
    src = np.zeros(6, np.int32)
    dst = np.zeros(6, np.int32)
    for i, (s, d) in enumerate(pairs):
        src[i], dst[i] = s, d
    want = xla_cow(pools, jnp.asarray(src), jnp.asarray(dst))
    got = pool_write.copy_blocks(pools, jnp.asarray(src), jnp.asarray(dst),
                                 interpret=True)
    for p, w, g in zip(pools, want, got):
        assert np.array_equal(np.asarray(w), np.asarray(g))
        assert np.array_equal(np.asarray(p)[0], np.asarray(g)[0])


@pytest.mark.parametrize("layout", ["gpt_bf16", "merged_4", "latent"])
def test_a_copied_block_is_then_written_by_the_same_call(layout):
    """A real pair's destination is the block the call's rows land in: the
    copy is made before the rows are written."""
    write = LAYOUTS[layout][0]
    pools, rows, table, pos, end = call_args(layout, "chunk_unaligned")
    dst = int(table[0, 0])                  # positions 3.. land here
    shared = min(set(range(1, NB)) - set(np.asarray(table).ravel().tolist()))
    src = jnp.asarray([shared, 0, 0, 0], jnp.int32)
    dsts = jnp.asarray([dst, 0, 0, 0], jnp.int32)
    want = write(tuple(xla_cow(pools, src, dsts)), rows, table, pos, end)
    with pool_write.force_interpret():
        copied = pool_write.copy_blocks(list(pools), src, dsts,
                                        interpret=True)
        got = write(tuple(copied), rows, table, pos, end)
    for w, g in zip(want, got):
        assert np.array_equal(np.asarray(w)[1:], np.asarray(g)[1:])
    # the positions before the call's start came from the copied block
    head = np.asarray(got[0])[dst]
    assert np.array_equal(head[:3 * (head.shape[0] // BS)],
                          np.asarray(pools[0])[shared][:3 * (head.shape[0] // BS)])


# ------------------------------------------------------------- engines

def _served(make_engine, workload, with_kernel):
    eng = make_engine()
    with pool_write.force_interpret(with_kernel):
        reqs = workload(eng)
    st = eng.stats()
    assert all(r.status == "done" for r in reqs)
    assert set(st["kv_write"].values()) == \
        {"kernel" if with_kernel else "scatter"}
    eng._pager.check_invariants()
    return [list(r.tokens) for r in reqs], st


def test_gpt_engine_serves_the_same_tokens():
    """Prefix sharing, copy-on-write and preemption (PR 26's workload)."""
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
    from paddle_tpu.serving import DecodeEngine
    from test_paged_serving import _sharing_cow_preemption_workload
    paddle.seed(3)
    model = GPTForCausalLM(gpt_tiny(vocab_size=64, max_position_embeddings=64))
    model.eval()

    def make():
        return DecodeEngine(model, max_slots=4, max_len=48, block_size=8,
                            kv_blocks=9, prefill_chunk=8)

    base, _ = _served(make, _sharing_cow_preemption_workload, False)
    got, st = _served(make, _sharing_cow_preemption_workload, True)
    assert got == base
    assert st["paged"]["cow_copies"] >= 1 and st["paged"]["preemptions"] >= 1


def test_hybrid_engine_serves_the_same_tokens():
    """The grouped-query pools of a hybrid decoder (merged rows), decode
    steps beside chunks."""
    from test_falcon_h1 import CHUNK, program
    from paddle_tpu.serving import DecodeEngine
    prog = program()[0]
    r = np.random.default_rng(4)
    prompts = [r.integers(0, 512, n).tolist() for n in (3, 20, 9)]

    def make():
        return DecodeEngine(prog, max_slots=4, max_len=96, block_size=8,
                            prefill_chunk=CHUNK)

    def workload(eng):
        reqs = [eng.submit(p, max_new_tokens=3 + i)
                for i, p in enumerate(prompts)]
        eng.run()
        return reqs

    base, _ = _served(make, workload, False)
    got, _ = _served(make, workload, True)
    assert got == base


def test_latent_engine_serves_the_same_tokens():
    """The latent pools, through a shared prefix whose tail block is copied
    on write."""
    from test_longcat_flash import CHUNK, program
    from paddle_tpu.serving import DecodeEngine
    prog = program()[0]
    prompt = np.random.default_rng(13).integers(0, 512, 13).tolist()

    def make():
        return DecodeEngine(prog, max_slots=4, max_len=96, block_size=8,
                            prefill_chunk=CHUNK)

    def workload(eng):
        a = eng.submit(prompt, max_new_tokens=6)
        while a.status in ("queued", "prefilling"):
            eng.step()
        b = eng.submit(prompt, max_new_tokens=6)
        eng.run()
        return [a, b]

    base, _ = _served(make, workload, False)
    got, st = _served(make, workload, True)
    assert got == base
    assert st["paged"]["cow_copies"] >= 1
