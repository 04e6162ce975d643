"""Paged decode attention (kernels/pallas/paged_decode.py) against the gather
path it replaces at decode (models/gpt.py::_paged_kv_gather + the masked
float32 softmax of ``_forward_cached``), interpret mode on the CPU: the
lengths round a block edge, mixed batches, tables whose rows share physical
blocks, grouped queries, head widths 64 and 128, float32 and bfloat16
pools; stale garbage past a slot's length and in unreferenced blocks must
not reach the output. The Mosaic compiles at the real decode shapes are at
the end (a described v5e; no chip needed): this kernel's, those of the
other serving kernels (`gdn.py`, `moe_grouped.py`) and the training
attention's (`flash_pair.py`), kept in this ONE file because only one test
process may load the TPU's compiler."""
import math
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.kernels.pallas.paged_decode import (chunk_pages,
                                                    kernel_geometry,
                                                    kernel_mode,
                                                    paged_decode_attention)
from paddle_tpu.models.gpt import _paged_kv_gather

BS, MBS = 16, 8            # block size; table width: max_len 128
MAX_LEN = BS * MBS
# a grouped-query pool as the hybrids keep them: 4 KV heads of 128 in
# bfloat16 is 16 KB a page, for which the kernel derives chunks of 32 pages
# (512 positions); a table of 40 entries holds one whole chunk and a part
NARROW = dict(nh=8, n_kv=4, hd=128, dtype=jnp.bfloat16, mbs=40, nb=200)
EDGE = 32 * BS             # positions of one derived chunk of NARROW
NARROW_MAX = BS * NARROW["mbs"]


def gather_path(q, pool_k, pool_v, table, lengths):
    """What ``_forward_cached`` does with the gathered view at S == 1."""
    b, _, nh, hd = q.shape
    k_buf, v_buf = _paged_kv_gather(pool_k, pool_v, table)
    n_kv = k_buf.shape[2]
    qg = q.reshape(b, 1, n_kv, nh // n_kv, hd).astype(jnp.float32)
    scores = jnp.einsum("bqkgd,bmkd->bkgqm", qg, k_buf.astype(jnp.float32),
                        precision="highest") / math.sqrt(hd)
    key_pos = jnp.arange(k_buf.shape[1])[None, None, None, None, :]
    scores = jnp.where(key_pos < lengths[:, None, None, None, None], scores,
                       -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bkgqm,bmkd->bqkgd", probs, v_buf.astype(jnp.float32),
                     precision="highest")
    return ctx.reshape(b, 1, nh, hd).astype(q.dtype)


def case(lengths, *, nh=4, n_kv=4, hd=64, dtype=jnp.float32, seed=0,
         table=None, nb=40, mbs=MBS):
    rng = np.random.RandomState(seed)
    b = len(lengths)
    q = jnp.asarray(rng.randn(b, 1, nh, hd), jnp.float32).astype(dtype)
    pool_k, pool_v = (jnp.asarray(rng.randn(nb, BS, n_kv, hd) * 0.7,
                                  jnp.float32).astype(dtype)
                      for _ in range(2))
    if table is None:       # block 0 is the engine's trash block
        table = rng.randint(1, nb, (b, mbs))
    return (q, pool_k, pool_v, jnp.asarray(table, jnp.int32),
            jnp.asarray(lengths, jnp.int32))


def check(args, **kw):
    got = paged_decode_attention(*args, interpret=True, **kw)
    want = gather_path(*args)
    assert got.shape == want.shape and got.dtype == want.dtype
    # float32: the same products, another order of the float32 sums;
    # bfloat16: at most the output's own rounding apart
    tol = 2e-6 if args[0].dtype == jnp.float32 else 2.0 ** -8
    scale = float(jnp.max(jnp.abs(want.astype(jnp.float32))))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=0, atol=tol * max(scale, 1.0))
    return got


def derived_pages(args):
    """What the kernel was last traced with, checked against the rule."""
    geo = kernel_geometry()
    page_bytes = BS * args[1].shape[2] * args[1].shape[3] \
        * args[1].dtype.itemsize
    pages = chunk_pages(page_bytes, args[3].shape[1])
    # the K/V walk computes a whole chunk at a time
    assert geo == {"kv_page_bytes": page_bytes, "kv_chunk_pages": pages,
                   "kv_piece_pages": pages}
    return geo["kv_chunk_pages"]


@pytest.mark.parametrize("length", [1, BS - 1, BS, BS + 1])
def test_lengths_round_a_block_edge(length):
    check(case([length, length]))


@pytest.mark.parametrize("length", [1, EDGE - 1, EDGE, EDGE + 1, NARROW_MAX])
def test_lengths_round_an_edge_of_the_derived_chunk(length):
    """16 KB pages: the kernel walks them 32 at a time, and a length one
    under, at and one over that edge, and the table's end, read the same as
    the gathered view."""
    args = case([length, length, 3], **NARROW)
    check(args)
    assert derived_pages(args) == 32


@pytest.mark.parametrize("dtype,geometry", [
    (jnp.float32, None), (jnp.bfloat16, None), (jnp.bfloat16, NARROW)])
def test_mixed_batch_dead_slot_and_full_slot(dtype, geometry):
    """One slot at pos 0 on the trash row (a dead or mid-prefill slot), one
    at max_len - 1, the rest between; several chunks a slot (chunks of 2
    pages, or the 32 the kernel derives for 16 KB pages)."""
    if geometry is None:
        args = case([1, MAX_LEN, 37, 64, 100], dtype=dtype, seed=1)
    else:
        args = case([1, NARROW_MAX, 37, EDGE + 40, 1], seed=1, **geometry)
    table = np.array(args[3])
    table[0] = 0
    args = args[:3] + (jnp.asarray(table),) + args[4:]
    check(args, **({"pages_per_chunk": 2} if geometry is None else {}))


@pytest.mark.parametrize("pages", [1, 3, 8])
def test_chunk_width_does_not_matter(pages):
    check(case([5, 77, MAX_LEN, 16], seed=2), pages_per_chunk=pages)


@pytest.mark.parametrize("lengths,geometry", [
    ([40, 33, 47], {}), ([EDGE + 9, 33, EDGE - 1], NARROW)],
    ids=["wide_pages", "derived_chunk_of_32"])
def test_rows_that_share_physical_blocks(lengths, geometry):
    """Prefix sharing: three slots read the same first two blocks, then
    their own."""
    args = case(lengths, seed=3, **geometry)
    table = np.array(args[3])
    table[:, :2] = table[0, :2]
    check(args[:3] + (jnp.asarray(table),) + args[4:])


@pytest.mark.parametrize("nh,n_kv", [(4, 4), (8, 2), (4, 1)])
def test_query_heads_fold_onto_their_kv_head(nh, n_kv):
    check(case([3, 50, 128], nh=nh, n_kv=n_kv, seed=4), pages_per_chunk=4)


@pytest.mark.parametrize("hd", [64, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_head_widths_and_pool_dtypes(hd, dtype):
    check(case([17, 90, 1], nh=4, n_kv=2, hd=hd, dtype=dtype, seed=5))


@pytest.mark.parametrize("lengths,geometry", [
    ([30, 128], {}), ([30, EDGE + 1, NARROW_MAX],
                      dict(NARROW, dtype=jnp.float32))],
    ids=["wide_pages", "derived_chunk_of_32"])
def test_float32_query_over_bfloat16_pools(lengths, geometry):
    """A query that is not bfloat16-exact meets the stored bfloat16 blocks
    with its float32 value (split in exact bfloat16 terms, not rounded)."""
    q, pk, pv, table, lengths = case(lengths, seed=6, **geometry)
    args = (q, pk.astype(jnp.bfloat16), pv.astype(jnp.bfloat16), table,
            lengths)
    got = paged_decode_attention(*args, interpret=True)
    want = gather_path(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=5e-6)


@pytest.mark.parametrize("dtype,geometry", [
    (jnp.float32, None), (jnp.bfloat16, None), (jnp.bfloat16, NARROW)])
def test_stale_garbage_never_reaches_the_output(dtype, geometry):
    """inf, nan and huge values past each slot's length (the live block's
    tail, the table's dead entries, whose pages the kernel neither fetches
    nor leaves as they lie in its buffers) and in blocks no row references
    change nothing: the output is bit-equal to the clean pools'."""
    if geometry is None:
        lengths, kw = [1, BS - 1, BS + 1, 70], {"pages_per_chunk": 2}
        q, pk, pv, table, L = case(lengths, dtype=dtype, seed=7)
    else:       # a whole chunk and a part; a part alone; the edge itself
        lengths, kw = [1, EDGE + BS + 1, BS - 1, EDGE, NARROW_MAX - 3], {}
        q, pk, pv, table, L = case(lengths, seed=7, **geometry)
    table = np.array(table)
    table[:] = np.arange(1, 1 + table.size).reshape(table.shape) \
        % (pk.shape[0] - 1) + 1
    table[0] = 0
    clean = paged_decode_attention(q, pk, pv, jnp.asarray(table), L,
                                   interpret=True, **kw)
    junk = np.array([np.inf, -np.inf, np.nan, 3e38], np.float32)
    live = np.zeros((pk.shape[0], BS), bool)       # (block, offset) read
    for row, n in zip(table, lengths):
        for p in range(n):
            live[row[p // BS], p % BS] = True
    dirty_k, dirty_v = (np.array(x, np.float32) for x in (pk, pv))
    fill = junk[np.arange((~live).sum()) % 4][:, None, None]
    dirty_k[~live] = fill
    dirty_v[~live] = fill[::-1]
    dirty = paged_decode_attention(
        q, jnp.asarray(dirty_k).astype(dtype),
        jnp.asarray(dirty_v).astype(dtype), jnp.asarray(table), L,
        interpret=True, **kw)
    assert np.isfinite(np.asarray(dirty, np.float32)).all()
    np.testing.assert_array_equal(np.asarray(clean, np.float32),
                                  np.asarray(dirty, np.float32))


@pytest.mark.parametrize("n_kv,hd,mbs,pages", [
    (16, 128, 128, 8),      # GPT-3 XL: 64 KB a page, as before
    (4, 128, 256, 32),      # Falcon-H1: 16 KB
    (2, 256, 256, 32),      # Qwen3-Next: 16 KB
    (8, 128, 128, 16),      # 8 KV heads of 128: 32 KB
    (8, 128, 4, 4),         # a table narrower than a chunk bounds it
])
def test_a_chunk_is_sized_in_bytes(n_kv, hd, mbs, pages):
    """512 KB of one pool a chunk, whatever a page weighs, within the
    table's width: decided from the shapes the kernel is handed."""
    assert chunk_pages(BS * n_kv * hd * 2, mbs) == pages
    shapes = (jax.ShapeDtypeStruct((2, 1, n_kv, hd), jnp.bfloat16),
              jax.ShapeDtypeStruct((64, BS * n_kv, hd), jnp.bfloat16),
              jax.ShapeDtypeStruct((64, BS * n_kv, hd), jnp.bfloat16),
              jax.ShapeDtypeStruct((2, mbs), jnp.int32),
              jax.ShapeDtypeStruct((2,), jnp.int32))
    jax.eval_shape(lambda *a: paged_decode_attention(
        *a, interpret=True, n_kv=n_kv), *shapes)
    assert kernel_geometry() == {"kv_chunk_pages": pages,
                                 "kv_page_bytes": BS * n_kv * hd * 2,
                                 "kv_piece_pages": pages}


@pytest.mark.parametrize("family", ["latent", "gpt"])
def test_decode_call_spans_say_in_what_pieces_the_walk_computes(family):
    """Every ``engine/decode_call`` span of an engine on a kernel carries
    ``kv_piece_pages``: the latent walk stops at a slot's live rows in
    pieces of 8 pages (half its chunk of 16 here: a table of 16 entries),
    the K/V walk computes whole chunks."""
    import time
    from paddle_tpu import models
    from paddle_tpu.monitor import trace
    from paddle_tpu.serving import DecodeEngine
    from paddle_tpu.kernels.pallas import paged_decode
    if family == "latent":
        model = models.LongCatFlashForCausalLM(models.longcat_flash_tiny())
        path, chunk, piece = "mla_decode", 16, 8
    else:
        model = models.GPTForCausalLM(models.gpt_tiny())
        path, chunk, piece = "paged_kernel", 16, 16
    model.eval()
    eng = DecodeEngine(model, max_slots=2, max_len=128, block_size=8,
                       prefill_chunk=16)
    t0 = time.perf_counter()
    with paged_decode.force_interpret():
        eng.submit(list(range(1, 12)), max_new_tokens=3)
        eng.run()
    calls = trace.spans(t0, time.perf_counter(), "engine/decode_call")
    assert calls and {(c.attrs["path"], c.attrs["kv_chunk_pages"],
                       c.attrs["kv_piece_pages"]) for c in calls} == {
        (path, chunk, piece)}


def test_off_the_tpu_the_model_keeps_the_gather_path():
    q, pk = case([1])[:2]
    assert kernel_mode(q, pk) is None


# ------------------------------------------- Mosaic, for a described v5e


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("b,nh,n_kv,mbs,nb,q_dtype", [
    (32, 16, 16, 128, 3000, jnp.bfloat16),   # GPT-3 XL's decode step
    (16, 8, 8, 64, 1100, jnp.bfloat16),      # chip_smoke.py's server
    (32, 32, 8, 128, 3000, jnp.bfloat16),    # grouped queries, 4 a KV head
    (32, 16, 16, 128, 3000, jnp.float32),    # float32 query, bf16 pools
])
def test_mosaic_compiles_the_decode_shapes(one_chip, b, nh, n_kv, mbs, nb,
                                           q_dtype):
    hd = 128

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((nb, BS, n_kv, hd), jnp.bfloat16)
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        exe = jax.jit(paged_decode_attention).lower(
            sds((b, 1, nh, hd), q_dtype), pool, pool,
            sds((b, mbs), jnp.int32), sds((b,), jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    text = exe.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    # the pools reach the kernel as they lie: no pool-sized temporary
    assert exe.memory_analysis().temp_size_in_bytes < (1 << 20)


def compile_for(one_chip, fn, *shapes):
    """`fn` compiled for the described chip from (shape, dtype) pairs, the
    persistent cache off (a described chip's entries cannot be read back)."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return jax.jit(fn).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)


def test_merged_row_pools_reach_the_kernel_without_a_copy(one_chip):
    """2 KV heads of width 256 under 16 query heads, 128 slots, the hybrid
    decoder's full layers: the pools are kept as [blocks, block x heads,
    width] (`cache_spec.kv_layer(merged_rows=True)`) because the 4-D form
    with 2 heads gets small tiles and a pool-sized relayout a call, which
    the second compile shows."""
    b, nh, n_kv, hd, mbs, nb = 128, 16, 2, 256, 256, 20480
    small = [((b, 1, nh, hd), jnp.bfloat16)]
    tail = [((b, mbs), jnp.int32), ((b,), jnp.int32)]
    merged = [((nb, BS * n_kv, hd), jnp.bfloat16)] * 2
    exe = compile_for(
        one_chip, lambda q, k, v, t, n: paged_decode_attention(
            q, k, v, t, n, n_kv=n_kv), *small, *merged, *tail)
    assert exe.as_text().count('custom_call_target="tpu_custom_call"') == 1
    assert exe.memory_analysis().temp_size_in_bytes < (1 << 20)
    four_d = [((nb, BS, n_kv, hd), jnp.bfloat16)] * 2
    exe = compile_for(one_chip, paged_decode_attention, *small, *four_d,
                      *tail)
    assert exe.memory_analysis().temp_size_in_bytes > nb * BS * n_kv * hd * 2


def relaid_parameters(text, params):
    """The copies of the entry computation in ``text`` that re-lay an array
    read from a parameter numbered below ``params``: a ``copy`` or
    ``copy-start`` whose operand traces back to such a parameter through
    bitcasts, slices and their joins, and whose result is laid out
    otherwise than its operand (a move between memory spaces, which keeps
    the layout, is no re-lay)."""
    entry = text[text.index("\nENTRY"):]
    defs = {}
    for line in entry.splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.+?) ([a-z][\w-]*)\(([^)]*)\)",
                     line)
        if m:
            defs[m.group(1)] = (m.group(2), m.group(3), line,
                                re.findall(r"%([\w.-]+)", m.group(4)),
                                m.group(4))
    through = {"bitcast", "reshape", "slice", "slice-start", "slice-done",
               "get-tuple-element", "copy-done"}

    def weight(name, seen=()):
        kind, op, line, operands, arg = defs.get(name, ("", "", "", [], ""))
        if op == "parameter":
            return int(arg) < params
        if op in through or 'custom_call_target="ConcatBitcast"' in line:
            return any(weight(o) for o in operands)
        return False

    def laid(shape):      # dims and layout, the memory space left out
        return re.sub(r"S\(\d+\)", "", shape)

    found = []
    for name, (kind, op, line, operands, _) in defs.items():
        if op not in ("copy", "copy-start") or not weight(operands[0]):
            continue
        if op == "copy":
            src, dst = defs[operands[0]][0], kind
        else:
            dst, src = re.findall(r"\w+\[[\d,]*\]\{[^}]*\}", kind)[:2]
        if laid(src) != laid(dst):
            found.append(line.strip()[:160])
    return found


def latent_layer_call(one_chip, monkeypatch, nh, chunk):
    """One real-width latent layer's decode step (128 slots) or chunk (512
    rows of one slot), weights as arguments laid out as a serving engine
    lays them, compiled for the described chip with the Mosaic kernels in
    it."""
    from paddle_tpu.kernels.pallas import paged_decode as pd, pool_write
    from paddle_tpu.models.deepseek_v3 import YARN, deepseek_v3_tiny
    from paddle_tpu.models.latent_attention import LatentAttention
    from paddle_tpu.models.longcat_flash import longcat_flash_tiny
    from paddle_tpu.nn.initializer import api
    from paddle_tpu.serving.engine import lay_out_for_serving
    from jax.experimental.layout import Format, Layout
    widths = dict(num_attention_heads=nh, q_lora_rank=1536,
                  kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128, dtype="bfloat16")
    monkeypatch.setattr(api, "_host_sample",
                        lambda sampler, shape, dtype: jnp.zeros(shape, dtype))
    cfg = deepseek_v3_tiny(hidden_size=7168, rope_scaling=dict(
        YARN, factor=40, original_max_position_embeddings=4096), **widths) \
        if nh == 128 else longcat_flash_tiny(hidden_size=6144, **widths)
    layer = LatentAttention(cfg)
    lay_out_for_serving(layer)
    params = [p for _, p in layer.named_parameters()]
    for mod in (pd, pool_write):
        monkeypatch.setattr(mod, "tpu_placement", lambda x: True)

    def fn(ws, z, pool, table, pos, end):
        held = [p._data for p in params]
        try:
            for p, w in zip(params, ws):
                p._data = w
            return layer.apply(z, (pool, table), pos, end)
        finally:
            for p, d in zip(params, held):
                p._data = d

    def sds(shape, dtype, sharding=one_chip):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    ws = [sds(p.shape, jnp.bfloat16, Format(Layout(
        tuple(p.value().format.layout.major_to_minor)), one_chip))
        for p in params]
    b, s, cursor = (1, 512, ()) if chunk else (128, 1, (128,))
    args = (ws, sds((b, s, cfg.hidden_size), jnp.bfloat16),
            sds((16384, BS, 640), jnp.bfloat16), sds((b, 256), jnp.int32),
            sds(cursor, jnp.int32), sds(cursor, jnp.int32))
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        exe = jax.jit(fn, donate_argnums=(2,)).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    return exe.as_text(), len(params)


@pytest.mark.parametrize("call", ["decode", "chunk"])
@pytest.mark.parametrize("nh", [128, 64], ids=["deepseek", "longcat"])
def test_latent_attention_reads_its_weights_as_they_lie(one_chip,
                                                        monkeypatch, nh,
                                                        call):
    """DeepSeek-V3's and LongCat-Flash's latent layer at the served widths:
    neither the decode step nor a chunk re-lays a weight. Left to itself,
    the compiler lays ``cq Wqb`` out heads first for the head-batched
    products and copies all of ``Wqb`` for it, and the products batched
    over the heads copy ``Wkvb`` held row-major, every call."""
    text, params = latent_layer_call(one_chip, monkeypatch, nh,
                                     call == "chunk")
    assert "mla_decode" in text if call == "decode" else "while" in text
    assert relaid_parameters(text, params) == []


def test_mosaic_compiles_the_delta_rule_decode_step(one_chip):
    """128 slots x 32 value heads of [128, 128] float32 state: one kernel,
    the state updated in place (aliased, no second copy)."""
    from paddle_tpu.kernels.pallas import gdn
    b, h, d = 128, 32, 128
    row = ((b, h, d), jnp.float32)
    exe = compile_for(
        one_chip, lambda q, k, v, dec, beta, live, s: gdn._decode_call(
            q, k, v, dec, beta, live, s, interpret=False),
        row, row, row, ((b, h), jnp.float32), ((b, h), jnp.float32),
        ((b,), jnp.bool_), ((b, h, d, d), jnp.float32))
    assert exe.as_text().count('custom_call_target="tpu_custom_call"') == 1
    assert "gdn_decode" in exe.as_text()
    assert exe.memory_analysis().temp_size_in_bytes < (1 << 20)


def test_mosaic_compiles_five_query_heads_a_kv_head(one_chip):
    """Falcon-H1's attention mixer: 20 query heads on 4 KV heads of 128 (a
    group of 5: not a power of two, under a sublane tile), 128 slots,
    merged-row pools of 64 rows a block. One kernel, no pool-sized copy."""
    b, nh, n_kv, hd, mbs, nb = 128, 20, 4, 128, 256, 16384
    exe = compile_for(
        one_chip, lambda q, k, v, t, n: paged_decode_attention(
            q, k, v, t, n, n_kv=n_kv),
        ((b, 1, nh, hd), jnp.bfloat16),
        ((nb, BS * n_kv, hd), jnp.bfloat16),
        ((nb, BS * n_kv, hd), jnp.bfloat16), ((b, mbs), jnp.int32),
        ((b,), jnp.int32))
    assert exe.as_text().count('custom_call_target="tpu_custom_call"') == 1
    assert exe.memory_analysis().temp_size_in_bytes < (1 << 20)


@pytest.mark.parametrize("q_dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16_query", "f32_query"])
@pytest.mark.parametrize("nh,n_kv,hd,nb", [
    (20, 4, 128, 16384), (16, 2, 256, 20480)],
    ids=["falcon_h1", "qwen3_next"])
def test_mosaic_compiles_the_derived_chunk_of_16_kb_pages(one_chip, nh, n_kv,
                                                          hd, nb, q_dtype):
    """The two hybrids' decode shapes, 128 slots over merged-row pools of
    16 KB a page: the kernel derives chunks of 32 pages (three buffers of
    512 KB a pool, 64 page copies a chunk in straight-line code), also with
    a float32 query, whose three bfloat16 terms triple the score rows."""
    b, mbs = 128, 256
    exe = compile_for(
        one_chip, lambda q, k, v, t, n: paged_decode_attention(
            q, k, v, t, n, n_kv=n_kv),
        ((b, 1, nh, hd), q_dtype), ((nb, BS * n_kv, hd), jnp.bfloat16),
        ((nb, BS * n_kv, hd), jnp.bfloat16), ((b, mbs), jnp.int32),
        ((b,), jnp.int32))
    assert kernel_geometry() == {"kv_chunk_pages": 32,
                                 "kv_page_bytes": 16384,
                                 "kv_piece_pages": 32}
    assert exe.as_text().count('custom_call_target="tpu_custom_call"') == 1
    assert exe.memory_analysis().temp_size_in_bytes < (1 << 20)


def test_mosaic_compiles_the_state_space_decode_step(one_chip):
    """128 slots x 32 heads of [256, 128] float32 state in 2 groups: one
    kernel, a group's 16 heads a grid step, the state updated in place
    (aliased, no second copy)."""
    from paddle_tpu.kernels.pallas import ssd
    b, h, n, p, g = 128, 32, 256, 128, 2
    exe = compile_for(
        one_chip, lambda x, dec, dt, bm, cm, live, forget, s:
        ssd._decode_call(x, dec, dt, bm, cm, live, forget, s,
                         interpret=False),
        ((b, h, p), jnp.float32), ((b, h), jnp.float32),
        ((b, h), jnp.float32), ((b, g, n), jnp.float32),
        ((b, g, n), jnp.float32), ((b,), jnp.bool_), ((b,), jnp.bool_),
        ((b, h, n, p), jnp.float32))
    assert exe.as_text().count('custom_call_target="tpu_custom_call"') == 1
    assert "ssd_decode" in exe.as_text()
    # not donated here, so one copy of the state is the most there may be
    assert exe.memory_analysis().temp_size_in_bytes <= b * h * n * p * 4 \
        + (1 << 20)


@pytest.mark.parametrize("tokens", [128, 512])
def test_mosaic_compiles_the_grouped_expert_matmul(one_chip, tokens):
    """128 held experts of 2048 x 512, top-10: a decode step's 128 tokens
    and a prefill chunk's 512, each expert's three matrices one block."""
    from paddle_tpu.kernels.pallas import moe_grouped as M
    k, e, h, i = 10, 128, 2048, 512
    tiles = -(-tokens * k // M.TILE) + e
    exe = compile_for(
        one_chip, lambda xs, ex, used, wg, wu, wd: M._grouped_ffn(
            xs, ex, used, wg, wu, wd, tile=M.TILE, interpret=False),
        ((tiles * M.TILE, h), jnp.bfloat16), ((tiles,), jnp.int32),
        ((), jnp.int32), ((e, h, i), jnp.bfloat16), ((e, h, i), jnp.bfloat16),
        ((e, i, h), jnp.bfloat16))
    assert exe.as_text().count('custom_call_target="tpu_custom_call"') == 1
    assert "moe_grouped" in exe.as_text()
    assert exe.memory_analysis().temp_size_in_bytes < (1 << 20)


@pytest.mark.parametrize("b,L,heads,d,causal,max_fused_bwd", [
    (8, 2048, 16, 64, True, None),     # gpt3-350m.pretrain_2k's 48 calls
    (4, 2048, 16, 128, True, None),    # GPT-3 XL's width, one head a block
    (16, 1024, 16, 64, True, None),    # one major tile, four sub-tiles
    (32, 512, 12, 64, False, None),    # BERT: no mask is ever built
    (2, 1900, 16, 64, True, None),     # padded tail under the diagonal
    (2, 4096, 16, 64, True, None),     # the longest the fused backward holds
    (2, 4096, 16, 64, True, 0),        # the split backward's two kernels
    (2, 2048, 2, 256, True, None),     # a 256-lane head block
])
def test_mosaic_compiles_the_packed_flash_attention(one_chip, b, L, heads, d,
                                                    causal, max_fused_bwd):
    """`flash_pair`'s forward and backward at the geometries its tests and
    callers name: the dynamic trip counts, the transposed score pieces and
    the full-length dk/dv scratch all inside Mosaic's VMEM."""
    from paddle_tpu.kernels.pallas import flash_pair as fp
    hpb = fp._heads_per_block(d)
    scale, pad = 1.0 / math.sqrt(d), -(-L // 128) * 128
    qkv = ((b, L, 3 * heads * d), jnp.bfloat16)
    ctx = ((b, L, heads * d), jnp.bfloat16)
    seed = ((1,), jnp.int32)
    fwd = compile_for(
        one_chip, lambda x, s: fp._pair_fwd(x, s, heads, d, causal, scale,
                                            512), qkv, seed)
    assert fwd.as_text().count('custom_call_target="tpu_custom_call"') == 1
    bwd = compile_for(
        one_chip, lambda x, o, lse, g, s: fp._pair_bwd(
            x, o, lse, g, s, heads, d, causal, scale, 512,
            max_fused_bwd=max_fused_bwd),
        qkv, ctx, ((b, heads // hpb, hpb, pad), jnp.float32), ctx, seed)
    form = fp.pair_schedule(L, causal, hpb, d, "bwd",
                            max_fused_bwd=max_fused_bwd)["form"]
    assert (bwd.as_text().count('custom_call_target="tpu_custom_call"')
            == {"fused": 1, "split": 2}[form])


@pytest.mark.parametrize("nh", [64, 128], ids=["longcat", "deepseek"])
def test_mosaic_compiles_the_latent_geometry(one_chip, nh):
    """LongCat-Flash's and DeepSeek-V3's decode steps: 64 or 128 query heads
    on ONE cached row of 512 + 64 lanes in 640, 128 slots, 20 KB pages in
    chunks of 32 computed in pieces of 8: one kernel named `mla_decode`,
    one pool, the context 512 wide, no pool-sized copy."""
    from paddle_tpu.kernels.pallas.paged_decode import (
        kernel_geometry, latent_decode_attention)
    b, lanes, rank, mbs, nb = 128, 640, 512, 256, 16384
    exe = compile_for(
        one_chip, lambda q, pool, t, n: latent_decode_attention(
            q, pool, t, n, rank=rank, scale=192 ** -0.5),
        ((b, 1, nh, lanes), jnp.bfloat16), ((nb, BS, lanes), jnp.bfloat16),
        ((b, mbs), jnp.int32), ((b,), jnp.int32))
    text = exe.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "mla_decode" in text
    assert exe.memory_analysis().temp_size_in_bytes < (1 << 20)
    assert kernel_geometry() == {"kv_chunk_pages": 32,
                                 "kv_page_bytes": BS * lanes * 2,
                                 "kv_piece_pages": 8}


def test_mosaic_compiles_an_expert_walked_in_blocks(one_chip):
    """LongCat-Flash's experts (6144 x 2048: 75 MB, more than the kernel's
    fast memory holds twice) in blocks of 512 columns on a second grid
    axis, at the decode step's 128 tokens x top-12 over 16 held."""
    from paddle_tpu.kernels.pallas import moe_grouped as mg
    tiles = -(-128 * 12 // mg.TILE) + 16
    bf = jnp.bfloat16
    exe = compile_for(
        one_chip, lambda xs, e, u, g, up, d: mg._grouped_ffn(
            xs, e, u, g, up, d, tile=mg.TILE, interpret=False),
        ((tiles * mg.TILE, 6144), bf), ((tiles,), jnp.int32),
        ((), jnp.int32), ((16, 6144, 2048), bf), ((16, 6144, 2048), bf),
        ((16, 2048, 6144), bf))
    assert exe.as_text().count('custom_call_target="tpu_custom_call"') == 1
    assert exe.memory_analysis().temp_size_in_bytes < (1 << 20)


# the pools' block-copy writes (kernels/pallas/pool_write.py) at the served
# shapes: GPT-3 XL's 4-D pools, Falcon-H1's merged rows (4 KV heads) and
# LongCat's latent rows, a decode step's rows and a chunk's
@pytest.mark.parametrize("pool,rows,b,mbs", [
    ((3000, 16, 16, 128), (32, 1, 16, 128), 32, 128),
    ((3000, 16, 16, 128), (1, 256, 16, 128), 1, 128),
    ((16384, 64, 128), (128, 1, 4, 128), 128, 256),
    ((16384, 64, 128), (1, 512, 4, 128), 1, 256),
    ((16384, 16, 640), (128, 1, 640), 128, 256),
    ((16384, 16, 640), (1, 512, 640), 1, 256),
], ids=["gpt_decode", "gpt_chunk", "merged_decode", "merged_chunk",
        "latent_decode", "latent_chunk"])
def test_mosaic_compiles_the_pool_writes_in_place(one_chip, pool, rows, b,
                                                  mbs):
    """One kernel, no scatter, and the donated pools written where they
    lie: no copy of a pool (a lost alias copies it whole every call)."""
    from paddle_tpu.kernels.pallas import pool_write
    one = (b,) if rows[1] == 1 else ()
    shapes = [(pool, jnp.bfloat16)] * 2 + [(rows, jnp.bfloat16)] * 2 + [
        ((b, mbs), jnp.int32), (one, jnp.int32), (one, jnp.int32)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        exe = jax.jit(lambda pk, pv, k, v, t, p, e: pool_write.write_rows(
            (pk, pv), (k, v), t, p, e), donate_argnums=(0, 1)).lower(
                *args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    text = exe.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert " scatter(" not in text
    dims = ",".join(map(str, pool))
    assert not [l for l in text.splitlines()
                if f"[{dims}]" in l and (" copy(" in l or "copy-start" in l)]
    assert exe.memory_analysis().temp_size_in_bytes < (4 << 20)


def test_mosaic_compiles_the_copy_on_write_of_48_pools(one_chip):
    from paddle_tpu.kernels.pallas import pool_write
    pool = jax.ShapeDtypeStruct((3000, 16, 16, 128), jnp.bfloat16,
                                sharding=one_chip)
    pair = jax.ShapeDtypeStruct((32,), jnp.int32, sharding=one_chip)
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        exe = jax.jit(pool_write.copy_blocks, donate_argnums=(0,)).lower(
            [pool] * 48, pair, pair).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    text = exe.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert " copy(" not in text and " gather(" not in text
    assert exe.memory_analysis().temp_size_in_bytes < (1 << 20)


@pytest.mark.parametrize("t", [128, 512, 130],
                         ids=["decode", "chunk", "ragged"])
@pytest.mark.parametrize("mix", [True, False], ids=["write_and_read",
                                                    "first_read"])
def test_mosaic_compiles_the_hyper_connection_boundary(one_chip, t, mix):
    """Motif-3-Beta's mHC boundary (4 streams of 4,096, bf16) at a decode
    step's 128 rows, a chunk's 512 and a ragged 130: the tile of up to a
    lane width of tokens, its blocks double-buffered and the walk's
    float32 pieces, fit the kernel's fast memory."""
    from paddle_tpu.kernels.pallas import mhc
    n, c, bf, f32 = 4, 4096, jnp.bfloat16, jnp.float32
    exe = compile_for(
        one_chip, lambda *a: mhc._fused(*a, eps=1e-6, clamp=100.0, iters=20,
                                        mix=mix, interpret=False),
        ((t, n, c), bf), ((t, c), bf), ((t, n), f32), ((t, n * n), f32),
        ((n * c,), bf), ((24, n * c), bf), ((3,), bf), ((24,), bf))
    assert exe.as_text().count('custom_call_target="tpu_custom_call"') == 1
    assert exe.memory_analysis().temp_size_in_bytes < (1 << 20)
