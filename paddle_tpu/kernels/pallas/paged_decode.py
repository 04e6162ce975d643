"""Paged decode attention: one query position per slot, read straight from
the block pools through the slot's block table.

Why this exists: the gather path (``models/gpt.py::_paged_kv_gather``)
materialises every slot's WHOLE table as a dense ``[B, mbs*BS, n_kv, hd]``
view, widens it to float32 and masks it — the same bytes whatever the live
length. At decode (``S == 1``, per-slot cursors) that view was nine tenths
of the step's device time. This kernel walks each slot's table instead and
streams only the ``ceil(length / BS)`` blocks that hold live positions from
HBM through VMEM, in the pools' own dtype; nothing past a slot's length is
fetched or computed on, and no view ever exists in HBM.

Layout contract: the pools stay ``[NB, BS, n_kv, hd]`` (the pager, the kv
pool's wire codec and the reshard snapshot all speak it). A block is viewed
as a ``[BS*n_kv, hd]`` matrix whose row ``t*n_kv + h`` is position ``t`` of
KV head ``h`` (a free reshape), so BOTH products run on the MXU with every
head at once and no relayout:

  scores  S = q [nh, hd] . K2[R, hd]^T  ->  [nh, R]   (R = chunk rows)
          row i keeps the columns of ITS KV head (``col % n_kv == i //
          group``) at live positions; every other column is masked
  context acc += P [nh, R] . V2 [R, hd]               (masked P is exactly
          zero off its own head's columns, so the other heads' rows of V2
          add nothing)

Same arithmetic as the gather path, not less: K and V travel as stored and
meet float32-exact operands — a float32 factor is split into three
bfloat16 terms (8 + 8 + 8 mantissa bits, exact) stacked on the M axis of
ONE matmul against the bfloat16 block, whose products are exact in the
MXU's float32 accumulator; float32 pools take a float32 matmul at the
highest precision. Scores, the online softmax (running max / sum) and the
context accumulator are float32.

Grid: one step per slot. Inside, a slot's live blocks arrive in chunks,
each table entry one async copy per pool. A chunk is sized in BYTES
(``chunk_pages``: 512 KB of one pool, 8 pages of 16 KV heads x 128 in
bfloat16, 32 of 4 KV heads), because what a page costs to start and to
await is scalar work that does not shrink with the page. The chunks of
all slots are one sequence through a ring of ``RING`` buffers: while one
is computed on, the next two are in flight (a cursor, carried across grid
steps, names the chunk whose copies start next), so the copy engine always
has a chunk queued behind the one it is moving. A chunk's trip is one basic
block: one wait a pool for a whole chunk (the semaphore counts bytes; a
part of a chunk is awaited a power of two of pages at a time), the copies of
the chunk two ahead started in straight-line code, each under its own
predicate (no loop, no branch, none past a slot's length), then the two
products. What a short chunk leaves stale in V's buffer is zeroed page by
page, and only the pages the buffer's last chunk had fetched.
"""
from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .util import tpu_placement

_NEG = -1e30                  # the gather path's mask value
CHUNK_BYTES = 512 << 10       # one pool's share of a chunk
MAX_PAGES = 64                # each copy of a chunk is straight-line code
RING = 3                      # chunk buffers: one computed on, two in flight

# Test seam, shaped like models/gpt.py::set_paged_kv_sharding: entered round
# an engine's trace, it makes the model take this kernel off the TPU too,
# through the Pallas interpreter.
_FORCE = {"interpret": False}
# How often the kernel was traced into a program: the engine reads it round
# its decode trace to say which path that executable took.
_TRACES = {"n": 0}
# ... and with which walk: table entries a chunk, bytes a page of one pool
_GEOMETRY = {"kv_chunk_pages": None, "kv_page_bytes": None}


@contextlib.contextmanager
def force_interpret(on: bool = True):
    prev, _FORCE["interpret"] = _FORCE["interpret"], bool(on)
    try:
        yield
    finally:
        _FORCE["interpret"] = prev


def kernel_traces() -> int:
    return _TRACES["n"]


def kernel_geometry() -> dict:
    """The geometry of the last trace: `kv_chunk_pages`, `kv_page_bytes`."""
    return dict(_GEOMETRY)


def chunk_pages(page_bytes: int, table_width: int) -> int:
    """Table entries a chunk holds: `CHUNK_BYTES` of one pool, whatever a
    page weighs (8 pages of 64 KB, 32 of 16 KB), within the table."""
    return max(1, min(CHUNK_BYTES // page_bytes, MAX_PAGES, table_width))


def kernel_mode(q, pool_k, n_kv=None):
    """How the paged decode step should attend: ``"mosaic"`` on a TPU whose
    tiling the shapes fit, ``"interpret"`` inside ``force_interpret``, None
    for the gather path. Decided from the input's shapes and placement.
    ``n_kv`` says a 3-D pool's rows are (position, KV head) pairs."""
    if _FORCE["interpret"]:
        return "interpret"
    if not tpu_placement(q):
        return None
    if n_kv is None:
        bs, n_kv, hd = pool_k.shape[1:]
    else:
        bs, hd = pool_k.shape[1] // n_kv, pool_k.shape[2]
    sublanes = 32 // jnp.dtype(pool_k.dtype).itemsize    # rows of one tile
    if hd % 128 or (bs * n_kv) % sublanes or q.shape[-2] % n_kv:
        return None
    return "mosaic"


def _split3(x):
    """float32 -> three bfloat16 terms whose sum is x exactly."""
    hi = x.astype(jnp.bfloat16)
    r = x - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    lo = (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _dot_exact(a, b, dims):
    """``a`` (small, [M, *]) against a pool chunk ``b`` as stored, float32
    out, with ``a``'s float32 value: bfloat16 blocks meet ``a`` split in
    exact bfloat16 terms stacked on M; anything else a float32 matmul."""
    dn = (dims, ((), ()))
    if b.dtype != jnp.bfloat16:
        return jax.lax.dot_general(
            a.astype(jnp.float32), b.astype(jnp.float32), dn,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    if a.dtype == jnp.bfloat16:
        return jax.lax.dot_general(a, b, dn,
                                   preferred_element_type=jnp.float32)
    m = a.shape[0]
    out = jax.lax.dot_general(jnp.concatenate(_split3(a), axis=0), b, dn,
                              preferred_element_type=jnp.float32)
    return out[:m] + out[m:2 * m] + out[2 * m:]


def _kernel(len_ref, tab_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sem, ring, *,
            pages, block, n_kv, group, mbs, n_slots, scale):
    # grid (slot,), sequential. `ring` carries across steps: [0] the ring
    # slot that holds the chunk computed on next, [1] and [2] the slot and
    # the chunk whose copies are started next (the cursor), [3 + r] the
    # pages of ring slot r that the chunk computed on there last had fetched
    b = pl.program_id(0)
    nh, hd = q_ref.shape
    depth, nb = kbuf.shape[0], k_hbm.shape[0]
    page_rows = block * n_kv             # rows of one page: (t, head)
    rows = pages * page_rows             # rows of one chunk
    span = pages * block                 # positions of one chunk

    def n_pages(slot):
        return jnp.clip((len_ref[slot] + block - 1) // block, 1, mbs)

    def issue(to, unrolled=True):
        """Start the copies of the chunk at the cursor into ring slot `to`,
        one per pool per LIVE table entry, and move the cursor on. Inside a
        trip: straight line, each copy under its own predicate, so no loop
        and no branch parts it from the chunk's compute. (The call's first
        chunks, once a call, take a loop: less to trace and to lower.)"""
        slot, c = ring[1], ring[2]
        s = jnp.minimum(slot, n_slots - 1)
        live, first = n_pages(s), c * pages
        cnt = jnp.where(slot < n_slots, live - first, 0)

        def start(j):
            entry = first + j if mbs % pages == 0 \
                else jnp.minimum(first + j, mbs - 1)
            # (the copies are not bounds-checked: the check was two thirds
            # of a start's scalar work)
            page = jnp.clip(tab_ref[s * mbs + entry], 0, nb - 1)

            @pl.when(j < cnt)
            def _():
                pltpu.make_async_copy(k_hbm.at[page], kbuf.at[to, j],
                                      sem.at[to, 0]).start()
                pltpu.make_async_copy(v_hbm.at[page], vbuf.at[to, j],
                                      sem.at[to, 1]).start()

        if unrolled:
            for j in range(pages):
                start(j)
        else:
            jax.lax.fori_loop(0, pages, lambda j, _: start(j), None)
        more = first + pages < live
        ring[1] = jnp.where(more, slot, slot + 1)
        ring[2] = jnp.where(more, c + 1, 0)

    def await_(cnt, at):
        """Wait for `cnt` pages of both pools in ring slot `at`. A copy's
        semaphore counts bytes, so the pages are awaited a power of two at
        a time: one wait a pool for a whole chunk."""
        bit = 1 << (pages.bit_length() - 1)
        while bit:
            @pl.when((cnt & bit) != 0)
            def _(bit=bit):
                for pool, buf, i in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                    pltpu.make_async_copy(
                        pool.at[pl.ds(0, bit)], buf.at[at, pl.ds(0, bit)],
                        sem.at[at, i]).wait()
            bit >>= 1

    @pl.when(b == 0)
    def _():
        ring[0] = ring[1] = ring[2] = 0
        for to in range(depth):
            ring[3 + to] = pages         # the buffers may hold anything
        for to in range(depth - 1):
            issue(to, unrolled=False)

    length = len_ref[b]
    live = n_pages(b)
    n_chunks = (live + pages - 1) // pages
    q = q_ref[...]

    def chunk(c, carry):
        m, l, acc = carry
        at = ring[0]
        ring[0] = jnp.where(at + 1 == depth, 0, at + 1)
        left = (length - c * span) * n_kv        # live rows from here on
        cnt = jnp.minimum(live - c * pages, pages)

        # the pages of V this chunk did not fetch may hold anything, inf
        # and nan too, and 0 * that is nan in the context product: those
        # the last chunk in this ring slot fetched are zeroed, the ones
        # past them were zeroed then
        def zero(j, _):
            vbuf[at, j] = jnp.zeros(vbuf.shape[2:], vbuf.dtype)

        jax.lax.fori_loop(cnt, ring[3 + at], zero, None)
        ring[3 + at] = cnt
        await_(cnt, at)
        # the page the length ends in: its stale tail is zeroed like the
        # pages past it (a chunk that is live throughout rewrites a page)
        edge = jnp.clip((left - 1) // page_rows, 0, pages - 1)
        tail = edge * page_rows + jax.lax.broadcasted_iota(
            jnp.int32, (page_rows, 1), 0)
        v_edge = vbuf[at, edge]
        vbuf[at, edge] = jnp.where(tail < left, v_edge,
                                   jnp.zeros_like(v_edge))
        issue(jnp.where(at == 0, depth - 1, at - 1))

        k2 = kbuf[at].reshape(rows, -1)
        v2 = vbuf[at].reshape(rows, -1)
        s = _dot_exact(q, k2, ((1,), (1,))) * scale          # [nh, rows]
        # column r of a chunk is position r // n_kv of KV head r % n_kv;
        # row i of the scores is query head i, which reads KV head
        # i // group
        col = jax.lax.broadcasted_iota(jnp.int32, (nh, rows), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (nh, rows), 0)
        col_head = col & (n_kv - 1) if n_kv & (n_kv - 1) == 0 \
            else jax.lax.rem(col, n_kv)
        s = jnp.where((col_head == row // group) & (col < left), s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)           # masked columns: exactly 0
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc = alpha * acc + _dot_exact(p, v2, ((1,), (0,)))
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(
        0, n_chunks, chunk,
        (jnp.full((nh, 1), _NEG, jnp.float32),
         jnp.zeros((nh, 1), jnp.float32),
         jnp.zeros((nh, hd), jnp.float32)))
    o_ref[...] = (acc / l).astype(o_ref.dtype)


def paged_decode_attention(q, pool_k, pool_v, table, lengths, *,
                           interpret=False, pages_per_chunk=None,
                           n_kv=None):
    """Attention of one query position per slot over that slot's live KV.

    ``q`` [B, 1, nh, hd]; ``pool_k`` / ``pool_v`` [NB, BS, n_kv, hd] in
    their stored dtype (``nh`` a multiple of ``n_kv``: query head ``i``
    reads KV head ``i // (nh // n_kv)``), or with ``n_kv`` given already
    [NB, BS * n_kv, hd] (``cache_spec.kv_layer(merged_rows=True)``: the
    matrix a block the kernel works on; for few KV heads the 4-D form is
    tiled so that viewing it this way copies the pool); ``table`` [B, mbs]
    int32 block ids; ``lengths`` [B] int32, the live positions of each slot
    (cursor + 1, at least 1: a slot always walks one page). A chunk of the
    walk is ``chunk_pages`` table entries, from the page's bytes;
    ``pages_per_chunk`` overrides that for the tests. Returns the context
    [B, 1, nh, hd] in ``q``'s dtype.
    """
    if n_kv is None:
        nb, block, n_kv, hd = pool_k.shape
        pool_k = pool_k.reshape(nb, block * n_kv, hd)
        pool_v = pool_v.reshape(nb, block * n_kv, hd)
    assert q.shape[1] == 1 and q.shape[2] % n_kv == 0 \
        and pool_v.shape == pool_k.shape and pool_k.shape[1] % n_kv == 0
    page_bytes = pool_k.shape[1] * pool_k.shape[2] * pool_k.dtype.itemsize
    pages = min(pages_per_chunk, table.shape[1]) if pages_per_chunk \
        else chunk_pages(page_bytes, table.shape[1])
    _TRACES["n"] += 1
    _GEOMETRY.update(kv_chunk_pages=pages, kv_page_bytes=page_bytes)
    return _attend(q, pool_k, pool_v, table, lengths, interpret=interpret,
                   pages=pages, n_kv=n_kv)


# jitted so that a model's layers share ONE trace and ONE Mosaic lowering of
# the kernel (it is the same program in each; lowered once a layer, it was
# most of a minute of every start on the chip's host, cache hit or not)
@functools.partial(jax.jit, static_argnames=("interpret", "pages", "n_kv"))
def _attend(q, pool_k, pool_v, table, lengths, *, interpret, pages, n_kv):
    b, _, nh, hd = q.shape
    nb, block = pool_k.shape[0], pool_k.shape[1] // n_kv
    mbs = table.shape[1]
    kernel = functools.partial(
        _kernel, pages=pages, block=block, n_kv=n_kv, group=nh // n_kv,
        mbs=mbs, n_slots=b, scale=1.0 / math.sqrt(hd))
    head_block = pl.BlockSpec((None, nh, hd), lambda i, *_: (i, 0, 0))
    buf = pltpu.VMEM((RING, pages, block * n_kv, hd), pool_k.dtype)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[head_block,
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=head_block,
            scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((RING, 2)),
                            pltpu.SMEM((3 + RING,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((b, nh, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            disable_bounds_checks=True),
        interpret=interpret,
        name="paged_decode",
    )(lengths.astype(jnp.int32), table.reshape(-1).astype(jnp.int32),
      q.reshape(b, nh, hd), pool_k, pool_v)
    return out.reshape(b, 1, nh, hd)
