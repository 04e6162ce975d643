"""`models/hybrid.py::walk_keys`: a prefill chunk's queries walk the key
blocks their slot holds before `end`, with a running softmax, and never the
rest of the table row. The three call sites (GPT's `[NB, BS, n_kv, hd]`
pools, the hybrids' merged rows `[NB, BS * n_kv, hd]`, the latent pool
`[NB, BS, lanes]`) against the dense view they replace, on a permuted block
table, on the CPU in float32; every block the walk must not read holds NaN.
The engines' side (served tokens, the span's `path` and `kv_walked`) is in
the serving tests of each family."""
import math
import os
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from paddle_tpu.models import gpt, hybrid                       # noqa: E402
from paddle_tpu.models.longcat_flash import (                   # noqa: E402
    LatentAttention, longcat_flash_tiny)

BS, MBS, NB = 4, 16, 40           # a table row of 64 positions
WIDTH = BS * MBS
KB = 16                           # the key block the tests walk in
NH, NKV, HD = 4, 2, 8             # NH: `longcat_flash_tiny()`'s heads too
GEOMETRIES = ["gpt", "merged_rows", "latent"]


class Case:
    """Pools behind a permuted table, clean for the dense view and with NaN
    in every block the walk of `end` must not read: the table's entries
    past the walked width and the blocks no entry names (block 0, the
    trash block, stays finite: a table that is no multiple of a trip reads
    it)."""

    def __init__(self, geometry, s, p0, end, kb=KB, mbs=MBS, seed=0):
        rng = np.random.default_rng(seed)
        self.geometry, self.s, self.p0, self.end, self.kb = \
            geometry, s, p0, end, kb
        self.table = rng.permutation(np.arange(1, NB))[:mbs][None] \
            .astype(np.int32)
        walked = min(-(-end // kb) * kb, mbs * BS) // BS
        self.unread = np.setdiff1d(np.arange(1, NB), self.table[0, :walked])
        self.positions = (p0 + jnp.arange(s, dtype=jnp.int32))[None]
        self.rng = rng

    def pools(self, *shape):
        clean = self.rng.normal(size=(NB,) + shape).astype(np.float32)
        holed = clean.copy()
        holed[self.unread] = np.nan
        return jnp.asarray(clean), jnp.asarray(holed)

    def q(self, *shape):
        return jnp.asarray(self.rng.normal(size=(1, self.s) + shape),
                           jnp.float32)


def dense_grouped(q, k_view, v_view, positions, precision):
    """What `grouped_attention` and `GPTAttention._forward_cached` compute
    over the gathered view `[B, M, n_kv, hd]`."""
    b, s, nh, hd = q.shape
    nkv = k_view.shape[2]
    qh = q.reshape(b, s, nkv, nh // nkv, hd)
    sc = jnp.einsum("bqkgd,bmkd->bkgqm", qh, k_view,
                    precision=precision) / math.sqrt(hd)
    live = jnp.arange(k_view.shape[1])[None, None, None, None, :] \
        <= positions[:, None, None, :, None]
    probs = jax.nn.softmax(jnp.where(live, sc, -1e30), axis=-1)
    return jnp.einsum("bkgqm,bmkd->bqkgd", probs, v_view,
                      precision=precision).reshape(b, s, nh, hd)


def both(case, monkeypatch):
    """(the walk over the pools with NaN, the dense view over the clean
    ones), each [1, S, ...], for `case`'s geometry."""
    table, positions, end = jnp.asarray(case.table), case.positions, \
        jnp.int32(case.end)
    monkeypatch.setattr(hybrid, "WALK_SCORES", NH * case.s * case.kb)
    if case.geometry == "latent":
        attn = LatentAttention(longcat_flash_tiny())
        attn.kv_b_proj._data = jnp.asarray(case.rng.normal(
            size=attn.kv_b_proj.shape), jnp.float32) * 0.3
        clean, holed = case.pools(BS, 128)
        q_nope, q_rope = case.q(attn.nh, attn.nope), case.q(attn.nh, attn.rot)
        view = jnp.take(clean, table, axis=0).reshape(1, -1, 128)
        want = attn._expanded(q_nope, q_rope, view, positions)
        got = jax.jit(attn._walked)(q_nope, q_rope, holed, table, positions,
                                    end)
    else:
        shape = (BS, NKV, HD) if case.geometry == "gpt" else (BS * NKV, HD)
        (k, k_holed), (v, v_holed) = case.pools(*shape), case.pools(*shape)
        q = case.q(NH, HD)
        k_view, v_view = (jnp.take(p, table, axis=0).reshape(1, -1, NKV, HD)
                          for p in (k, v))
        if case.geometry == "gpt":
            want = dense_grouped(q, k_view, v_view, positions, None)
            got = jax.jit(lambda *a: gpt._paged_chunk_attend(
                (None, None) + a[:3], a[3], a[4:]))(
                table, jnp.int32(case.p0), end, q, k_holed, v_holed)
        else:
            want = dense_grouped(q, k_view, v_view, positions, "highest")
            got = jax.jit(lambda q, t, e, *p: hybrid.walk_grouped(
                q, p, t, positions, e, NKV, precision="highest"))(
                q, table, end, k_holed, v_holed)
    assert hybrid.walk_geometry() == {
        "key_block": min(case.kb, case.table.shape[1] * BS)}
    return np.asarray(got), np.asarray(want)


def check(case, monkeypatch):
    got, want = both(case, monkeypatch)
    n = case.end - case.p0                  # the queries before `end`
    assert got.shape == want.shape and 0 < n <= case.s
    assert np.isfinite(got).all()           # the padded queries too
    assert float(np.abs(want[:, :n]).max()) > 0.1
    assert float(np.abs(got[:, :n] - want[:, :n]).max()) < 2e-6


@pytest.mark.parametrize("end", [1, KB - 1, KB, KB + 1, 2 * KB + 3, WIDTH])
@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_the_walk_is_the_view_up_to_end(geometry, end, monkeypatch):
    """A chunk of 8 positions whose last 3 are padding, ending at `end`:
    inside the first trip, on its edge, one past it, mid-table (`p0 > 0`)
    and at the table's width."""
    check(Case(geometry, 8, max(0, end - 5), end, seed=end), monkeypatch)


@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_a_trip_in_a_querys_future_changes_nothing(geometry, monkeypatch):
    """`KB < S`: 24 queries from position 8 in trips of 8 keys, so the
    later trips lie wholly in the earlier queries' future."""
    check(Case(geometry, 24, 8, 32, kb=8, seed=7), monkeypatch)


@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_a_table_that_is_no_multiple_of_a_trip(geometry, monkeypatch):
    """14 entries in trips of 4: the last trip's tail reads the trash
    block, which no live query sees."""
    check(Case(geometry, 8, 47, 55, mbs=14, seed=3), monkeypatch)


@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_one_trip_where_a_trip_holds_the_table(geometry, monkeypatch):
    """A key block as wide as the table (what the tiny engines of the
    serving tests trace): one trip, the view's own arithmetic."""
    check(Case(geometry, 8, 20, 26, kb=4 * WIDTH, seed=5), monkeypatch)


def test_the_trip_count_is_data(monkeypatch):
    """One program whatever `end`: the same jitted walk, traced once,
    serves every `end` (the chunk executable never recompiles for it)."""
    monkeypatch.setattr(hybrid, "WALK_SCORES", NH * 8 * KB)
    case = Case("merged_rows", 8, 0, WIDTH)
    (k, _), (v, _) = case.pools(BS * NKV, HD), case.pools(BS * NKV, HD)
    q, table = case.q(NH, HD), jnp.asarray(case.table)
    k_view, v_view = (jnp.take(p, table, axis=0).reshape(1, -1, NKV, HD)
                      for p in (k, v))
    traces = []

    @jax.jit
    def walk(q, p0, end):
        traces.append(1)
        positions = (p0 + jnp.arange(8, dtype=jnp.int32))[None]
        return hybrid.walk_grouped(q, (k, v), table, positions, end, NKV,
                                   precision="highest"), positions

    for end in (3, 17, 40, WIDTH):
        got, positions = walk(q, jnp.int32(max(0, end - 8)), jnp.int32(end))
        want = dense_grouped(q, k_view, v_view, positions, "highest")
        n = min(end, 8)
        assert float(jnp.abs(got - want)[:, :n].max()) < 2e-6, end
    assert len(traces) == 1


def test_per_row_cursors_are_refused():
    with pytest.raises(ValueError, match="one cursor a call"):
        hybrid.walk_keys(jnp.zeros((2, 4), jnp.int32),
                         jnp.zeros((2, 3), jnp.int32), 3, 4, 12,
                         None, None, None)


@pytest.mark.parametrize("rows, block, width, want", [
    (64 * 512, 16, 4096, 256),      # LongCat-Flash: 64 heads x 512 queries
    (20 * 512, 16, 4096, 512),      # Falcon-H1: 4 KV heads x 5
    (16 * 512, 16, 4096, 1024),     # Qwen3-Next
    (16 * 256, 16, 2048, 2048),     # GPT-3 XL: one trip holds the row
    (4 * 16, 8, 96, 96),            # the tiny engines: one trip
    (1 << 30, 16, 4096, 16),        # never under a block
])
def test_a_trip_is_sized_by_its_scores(rows, block, width, want):
    assert hybrid.key_block_for(rows, block, width) == want


# ------------------------------------------------ through the engines

def _engine_of(which):
    """(model, engine keywords, heads) of a family's tiny model; GPT comes
    with a drafter, so that the verify executable walks too."""
    from paddle_tpu.serving import PromptLookupDrafter
    from test_falcon_h1 import program as falcon
    from test_longcat_flash import program as longcat
    from test_spec_decoding import _tiny_gpt, _tiny_llama
    geo = dict(max_slots=2, max_len=64, block_size=4, prefill_chunk=8)
    if which == "gpt_verify":
        return _tiny_gpt(), dict(geo, drafter=PromptLookupDrafter(
            max_n=3, min_n=1, max_k=8)), 2
    if which == "llama":
        return _tiny_llama(), geo, 4
    if which == "longcat_flash":
        return longcat()[0], geo, 4
    return falcon()[0], geo, 5


@pytest.mark.parametrize("which", ["gpt_verify", "llama", "longcat_flash",
                                   "falcon_h1"])
def test_engines_serve_the_same_tokens_in_trips_of_a_block(which,
                                                           monkeypatch):
    """Chunks (and speculative verify) that walk their slot's keys in
    trips of two blocks serve what one trip over the whole row serves, and
    their spans count the trips: a periodic prompt of 21 tokens in chunks
    of 8 walks 8, 16 and 24 of the row's 64 keys."""
    from paddle_tpu.monitor import trace
    from paddle_tpu.serving import DecodeEngine
    model, kw, heads = _engine_of(which)
    prompt = ([5, 9, 3, 7] * 6)[:21]
    served = {}
    for kb in (64, 8):
        monkeypatch.setattr(hybrid, "WALK_SCORES", heads * 8 * kb)
        eng = DecodeEngine(model, **kw)
        t0 = time.perf_counter()
        req = eng.submit(prompt, max_new_tokens=6)
        eng.run()
        assert req.status == "done"
        served[kb] = list(req.output_tokens)
        chunks = trace.spans(t0, time.perf_counter(), "engine/prefill_call")
        assert {s.attrs["path"] for s in chunks} == {"key_walk"}
        assert [s.attrs["kv_walked"] for s in chunks] == \
            ([64] * 3 if kb == 64 else [8, 16, 24])
        assert eng.stats()["prefill_attention"]["share"] == \
            (1.0 if kb == 64 else 0.25)
        if "drafter" in kw:
            assert eng.spec_steps > 0
    assert served[8] == served[64] and len(served[8]) == 6
