"""Paged decode attention: one query position per slot, read straight from
the block pools through the slot's block table.

Why this exists: the gather path (``models/gpt.py::_paged_kv_gather``)
materialises every slot's WHOLE table as a dense ``[B, mbs*BS, n_kv, hd]``
view, widens it to float32 and masks it — the same bytes whatever the live
length. At decode (``S == 1``, per-slot cursors) that view was nine tenths
of the step's device time. This kernel walks each slot's table instead and
streams only the ``ceil(length / BS)`` blocks that hold live positions from
HBM through VMEM, in the pools' own dtype; nothing past a slot's length is
fetched or computed on, and no view ever exists in HBM. (Who still attends
over a dense view: tensor-parallel serving, the decode step off the chip,
and ``generate()``'s contiguous cache, which has no table. A prefill chunk
or a speculative verify walks its slot's key blocks in ``jax.numpy``:
``models/hybrid.py::walk_keys``.)

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

The latent geometry (``latent_decode_attention``: ONE pool whose rows every
head reads, the kernel ``mla_decode``) walks the same chunks with the same
copies, chunk rule and waits, in a body of its own, ``_latent_kernel``. At
128 heads its products bound it, not its copies (a chunk's products pop
576 results from the MXU whichever operand streams), so its trip does what
the K/V walk's need not: (1) each ring slot is an allocation of its own
and the trip is compiled once a ring slot, so that the starts of the chunk
two ahead, which write another allocation than the products read, are
scheduled under the products (with one allocation for the ring, their
scalar work ran before the first product); (2) the context accumulates in
a VMEM scratch, not in the loop's carry (a copy of 64 vregs a trip); (3) a
slot's last chunk is computed only as far as its live rows, in pieces of
``PIECE_PAGES``: the trip for the chunk's first k pieces is chosen at run
time among static shapes. The queries stream past the chunk's rows as in
the K/V walk: the other way round (the rows streaming past the queries
held still) read the same at 128 heads and a third slower at 64 heads,
which fill half the MXU's columns (PERF.md §3).
"""
from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .util import note_attention_kernel, tpu_placement

_NEG = -1e30                  # the gather path's mask value
CHUNK_BYTES = 512 << 10       # one pool's share of a chunk
MAX_PAGES = 64                # each copy of a chunk is straight-line code
RING = 3                      # chunk buffers: one computed on, two in flight
# bfloat16 terms the latent kernel's float32 probabilities meet the values
# in (1: rounded once, as the context itself is when it leaves in bfloat16;
# 3: exact, as the K/V kernel). 64 heads a row put the latent kernel at the
# ridge (121 FLOP/B in one pass), so three terms would make it compute-bound:
# 0.415 / 0.473 / 0.534 ms with one / two / three at the served shape
LATENT_P_TERMS = 1
# pages of the latent walk's unit of stopping at a slot's live rows: 8 (128
# rows) against 16 and a whole chunk, 0.406 / 0.410 / 0.429 ms a call at 128
# heads on one TPU v5e
PIECE_PAGES = 8

# Test seam, shaped like models/gpt.py::set_paged_kv_sharding: entered round
# an engine's trace, it makes the model take this kernel off the TPU too,
# through the Pallas interpreter.
_FORCE = {"interpret": False}
# With which walk the kernel was last traced (that it WAS traced, it notes
# in ``util.note_attention_kernel``: the engine reads that round its traces
# to say which path an executable took): table entries a chunk, bytes a page
# of one pool, and the table entries of the walk's unit of computing (a whole
# chunk in the K/V walk; a piece of the chunk in the latent walk, which
# stops at a slot's live rows)
_GEOMETRY = {"kv_chunk_pages": None, "kv_page_bytes": None,
             "kv_piece_pages": None}


@contextlib.contextmanager
def force_interpret(on: bool = True):
    prev, _FORCE["interpret"] = _FORCE["interpret"], bool(on)
    try:
        yield
    finally:
        _FORCE["interpret"] = prev


def kernel_geometry() -> dict:
    """The geometry of the last trace: `kv_chunk_pages`, `kv_page_bytes`,
    `kv_piece_pages`."""
    return dict(_GEOMETRY)


def chunk_pages(page_bytes: int, table_width: int) -> int:
    """Table entries a chunk holds: `CHUNK_BYTES` of one pool, whatever a
    page weighs (8 pages of 64 KB, 32 of 16 KB), within the table."""
    return max(1, min(CHUNK_BYTES // page_bytes, MAX_PAGES, table_width))


def _seam(q):
    """``"interpret"`` inside ``force_interpret``, ``"mosaic"`` where ``q``
    will run on a TPU (if the shapes fit: the caller's check), else None."""
    if _FORCE["interpret"]:
        return "interpret"
    return "mosaic" if tpu_placement(q) else None


def kernel_mode(q, pool_k, n_kv=None):
    """How the paged decode step should attend: ``"mosaic"`` on a TPU whose
    tiling the shapes fit, ``"interpret"`` inside ``force_interpret``, None
    for the gather path. Decided from the input's shapes and placement.
    ``n_kv`` says a 3-D pool's rows are (position, KV head) pairs."""
    mode = _seam(q)
    if mode != "mosaic":
        return mode
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


def _dot_exact(a, b, dims, terms=3):
    """``a`` (small, [M, *]) against a pool chunk ``b`` as stored, float32
    out, with ``a``'s float32 value: bfloat16 blocks meet ``a`` split in
    exact bfloat16 terms stacked on M (the first ``terms`` of the three);
    anything else a float32 matmul."""
    dn = (dims, ((), ()))
    if b.dtype != jnp.bfloat16:
        return jax.lax.dot_general(
            a.astype(jnp.float32), b.astype(jnp.float32), dn,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    if a.dtype == jnp.bfloat16:
        return jax.lax.dot_general(a, b, dn,
                                   preferred_element_type=jnp.float32)
    if terms == 1:
        return jax.lax.dot_general(a.astype(jnp.bfloat16), b, dn,
                                   preferred_element_type=jnp.float32)
    m = a.shape[0]
    out = jax.lax.dot_general(
        jnp.concatenate(_split3(a)[:terms], axis=0), b, dn,
        preferred_element_type=jnp.float32)
    acc = out[:m]
    for i in range(1, terms):
        acc = acc + out[i * m:(i + 1) * m]
    return acc


def _walk(len_ref, tab_ref, hbm, sem, ring, *, pages, block, mbs, n_slots,
          dst):
    """The copies of the walk that both kernels make: ``n_pages(slot)``,
    ``issue(to)``, ``await_(cnt, at)`` and ``begin(b)``, the call's first
    chunks (at grid step ``b`` 0). ``dst(i, r, idx)`` is pool ``i``'s
    buffer of ring slot ``r`` at ``idx``. `ring` carries across grid steps:
    [0] the ring slot that holds the chunk computed on next, [1] and [2]
    the slot and the chunk whose copies are started next (the cursor),
    [3 + r] the pages of ring slot r that the chunk computed on there last
    had fetched."""
    nb = hbm[0].shape[0]

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
                for i, pool in enumerate(hbm):
                    pltpu.make_async_copy(pool.at[page], dst(i, to, j),
                                          sem.at[to, i]).start()

        if unrolled:
            for j in range(pages):
                start(j)
        else:
            jax.lax.fori_loop(0, pages, lambda j, _: start(j), None)
        more = first + pages < live
        ring[1] = jnp.where(more, slot, slot + 1)
        ring[2] = jnp.where(more, c + 1, 0)

    def await_(cnt, at):
        """Wait for `cnt` pages of every pool in ring slot `at`. A copy's
        semaphore counts bytes, so the pages are awaited a power of two at
        a time: one wait a pool for a whole chunk."""
        bit = 1 << (pages.bit_length() - 1)
        while bit:
            @pl.when((cnt & bit) != 0)
            def _(bit=bit):
                for i, pool in enumerate(hbm):
                    pltpu.make_async_copy(
                        pool.at[pl.ds(0, bit)], dst(i, at, pl.ds(0, bit)),
                        sem.at[at, i]).wait()
            bit >>= 1

    def begin(b):
        @pl.when(b == 0)
        def _():
            ring[0] = ring[1] = ring[2] = 0
            for to in range(RING):
                ring[3 + to] = pages     # the buffers may hold anything
            for to in range(RING - 1):
                issue(to, unrolled=False)

    return n_pages, issue, await_, begin


def _kernel(len_ref, tab_ref, q_ref, *refs,
            pages, block, n_kv, group, mbs, n_slots, scale):
    # refs: the pools in HBM (K and V), the output, a ring buffer a pool,
    # the semaphores, the ring's state (`_walk`)
    hbm, o_ref = refs[:2], refs[2]
    bufs, (sem, ring) = refs[3:5], refs[5:]
    kbuf, vbuf = bufs
    # grid (slot,), sequential
    b = pl.program_id(0)
    nh, hd = o_ref.shape
    depth = kbuf.shape[0]
    page_rows = block * n_kv             # rows of one page: (t, head)
    rows = pages * page_rows             # rows of one chunk
    span = pages * block                 # positions of one chunk
    n_pages, issue, await_, begin = _walk(
        len_ref, tab_ref, hbm, sem, ring, pages=pages, block=block, mbs=mbs,
        n_slots=n_slots, dst=lambda i, r, idx: bufs[i].at[r, idx])
    begin(b)

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
        if n_kv == 1:
            own = col < left
        else:
            row = jax.lax.broadcasted_iota(jnp.int32, (nh, rows), 0)
            col_head = col & (n_kv - 1) if n_kv & (n_kv - 1) == 0 \
                else jax.lax.rem(col, n_kv)
            own = (col_head == row // group) & (col < left)
        s = jnp.where(own, s, _NEG)
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


def _switch(index, branches, *args):
    """``lax.switch`` as a balanced tree of two-way conds: Mosaic lowers a
    switch to a chain of conds as deep as it has branches, and a chain of
    12 overflows its layout pass's stack."""
    if len(branches) == 1:
        return branches[0](*args)
    half = len(branches) // 2
    return jax.lax.cond(
        index < half, lambda *a: _switch(index, branches[:half], *a),
        lambda *a: _switch(index - half, branches[half:], *a), *args)


def _latent_kernel(len_ref, tab_ref, q_ref, hbm, o_ref, *refs, pages, block,
                   mbs, n_slots, scale, v_lanes, p_terms):
    # refs: a buffer a ring slot (three allocations), the semaphores, the
    # ring's state (`_walk`), the context accumulator [nh, v_lanes] float32
    bufs, (sem, ring, acc_ref) = refs[:RING], refs[RING:]
    b = pl.program_id(0)
    nh, lanes = q_ref.shape
    rows = pages * block                 # rows (positions) of one chunk
    piece = min(PIECE_PAGES, pages)
    n_k = -(-pages // piece)             # pieces of a chunk
    n_pages, issue, await_, begin = _walk(
        len_ref, tab_ref, (hbm,), sem, ring, pages=pages, block=block,
        mbs=mbs, n_slots=n_slots, dst=lambda i, r, idx: bufs[r].at[idx])
    begin(b)

    length = len_ref[b]
    live = n_pages(b)
    n_chunks = (live + pages - 1) // pages
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def trip(at, k):
        """A chunk in ring slot `at`, computed over its first `k` pieces:
        both static, so that the copies started here (into another
        allocation) may be scheduled under the products."""
        buf, r = bufs[at], min(k * piece, pages) * block

        def body(c, m, l):
            ring[0] = (at + 1) % RING
            left = length - c * rows             # live rows from here on
            cnt = jnp.minimum(live - c * pages, pages)

            # the pages this chunk did not fetch may hold anything, inf and
            # nan too, and 0 * that is nan in the context product: those
            # the last chunk in this ring slot fetched are zeroed, the ones
            # past them were zeroed then
            def zero(j, _):
                buf[j] = jnp.zeros(buf.shape[1:], buf.dtype)

            jax.lax.fori_loop(cnt, ring[3 + at], zero, None)
            ring[3 + at] = cnt
            await_(cnt, at)
            # the page the length ends in: its stale tail is zeroed too
            edge = jnp.clip((left - 1) // block, 0, pages - 1)
            tail = edge * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, 1), 0)
            v_edge = buf[edge]
            buf[edge] = jnp.where(tail < left, v_edge, jnp.zeros_like(v_edge))
            issue((at - 1) % RING)

            x = buf[:r // block].reshape(r, lanes)
            s = _dot_exact(q_ref[...], x, ((1,), (1,))) * scale  # [nh, r]
            col = jax.lax.broadcasted_iota(jnp.int32, (nh, r), 1)
            s = jnp.where(col < left, s, _NEG)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)       # masked columns: exactly 0
            alpha = jnp.exp(m - m_new)
            v = buf[:r // block, :, :v_lanes].reshape(r, v_lanes)
            acc_ref[...] = alpha * acc_ref[...] + _dot_exact(
                p, v, ((1,), (0,)), p_terms)
            return m_new, alpha * l + jnp.sum(p, axis=1, keepdims=True)
        return body

    branches = [trip(at, k) for at in range(RING) for k in range(1, n_k + 1)]

    def chunk(c, carry):
        k = jnp.clip((length - c * rows + piece * block - 1)
                     // (piece * block), 1, n_k)
        return _switch(ring[0] * n_k + k - 1, branches, c, *carry)

    _, l = jax.lax.fori_loop(0, n_chunks, chunk,
                             (jnp.full((nh, 1), _NEG, jnp.float32),
                              jnp.zeros((nh, 1), jnp.float32)))
    o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


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
    note_attention_kernel("paged_kernel")
    _GEOMETRY.update(kv_chunk_pages=pages, kv_page_bytes=page_bytes,
                     kv_piece_pages=pages)
    return _attend(q, pool_k, pool_v, table, lengths, interpret=interpret,
                   pages=pages, n_kv=n_kv)


def latent_mode(q, pool, rank: int):
    """``kernel_mode`` for the latent geometry: ``q [B, 1, nh, lanes]``,
    ``pool [NB, BS, lanes]``, the values the first ``rank`` lanes."""
    mode = _seam(q)
    sublanes = 32 // jnp.dtype(pool.dtype).itemsize
    if mode == "mosaic" and (pool.shape[2] % 128 or rank % 128
                             or pool.shape[1] % sublanes):
        return None
    return mode


def latent_decode_attention(q, pool, table, lengths, *, rank, scale,
                            interpret=False, pages_per_chunk=None):
    """Latent attention's decode step, absorbed form: one query position a
    slot, ``q [B, 1, nh, lanes]`` = ``[q_nope W_k^T (rank) | q_rope | 0]``
    against the rows ``[latent | rotated key | 0]`` of ``pool [NB, BS,
    lanes]`` that ``table`` / ``lengths`` say are live (as
    ``paged_decode_attention``), scores times ``scale``, the context over
    the rows' first ``rank`` lanes. Returns [B, 1, nh, rank] in ``q``'s
    dtype. The walk's copies are ``paged_decode``'s with one pool; a
    chunk's pages are rounded up to a multiple of 8, so that the scores'
    columns fill whole lane tiles (25 pages of 20 KB become 32: 0.415 ms
    against 24's 0.440 at the served shape), and a slot's last chunk is
    computed in pieces of ``PIECE_PAGES`` up to its live rows
    (``_latent_kernel``)."""
    assert q.shape[1] == 1 and q.shape[3] == pool.shape[2] >= rank
    page_bytes = pool.shape[1] * pool.shape[2] * pool.dtype.itemsize
    pages = pages_per_chunk or min(
        -(-chunk_pages(page_bytes, table.shape[1]) // 8) * 8, MAX_PAGES)
    pages = min(pages, table.shape[1])
    note_attention_kernel("mla_decode")
    _GEOMETRY.update(kv_chunk_pages=pages, kv_page_bytes=page_bytes,
                     kv_piece_pages=min(PIECE_PAGES, pages))
    return _attend(q, pool, None, table, lengths, interpret=interpret,
                   pages=pages, n_kv=1, v_lanes=int(rank),
                   scale=float(scale), p_terms=LATENT_P_TERMS)


# jitted so that a model's layers share ONE trace and ONE Mosaic lowering of
# the kernel (it is the same program in each; lowered once a layer, it was
# most of a minute of every start on the chip's host, cache hit or not)
@functools.partial(jax.jit, static_argnames=(
    "interpret", "pages", "n_kv", "v_lanes", "scale", "p_terms"))
def _attend(q, pool_k, pool_v, table, lengths, *, interpret, pages, n_kv,
            v_lanes=None, scale=None, p_terms=3):
    """``pool_v`` None: the latent geometry, the values ``pool_k``'s first
    ``v_lanes`` lanes (the kernel ``mla_decode``)."""
    b, _, nh, hd = q.shape
    block, mbs = pool_k.shape[1] // n_kv, table.shape[1]
    state = [pltpu.SemaphoreType.DMA((RING, 1 if pool_v is None else 2)),
             pltpu.SMEM((3 + RING,), jnp.int32)]
    if pool_v is None:
        pools, out_hd = (pool_k,), v_lanes
        kernel = functools.partial(
            _latent_kernel, pages=pages, block=block, mbs=mbs, n_slots=b,
            scale=scale, v_lanes=v_lanes, p_terms=p_terms)
        scratch = [pltpu.VMEM((pages, block, hd), pool_k.dtype)] * RING \
            + state + [pltpu.VMEM((nh, v_lanes), jnp.float32)]
    else:
        pools, out_hd = (pool_k, pool_v), hd
        kernel = functools.partial(
            _kernel, pages=pages, block=block, n_kv=n_kv, group=nh // n_kv,
            mbs=mbs, n_slots=b, scale=1.0 / math.sqrt(hd))
        scratch = [pltpu.VMEM((RING, pages, block * n_kv, hd),
                              pool_k.dtype)] * 2 + state
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[pl.BlockSpec((None, nh, hd), lambda i, *_: (i, 0, 0))]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
            out_specs=pl.BlockSpec((None, nh, out_hd),
                                   lambda i, *_: (i, 0, 0)),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((b, nh, out_hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            disable_bounds_checks=True),
        interpret=interpret,
        name="mla_decode" if pool_v is None else "paged_decode",
    )(lengths.astype(jnp.int32), table.reshape(-1).astype(jnp.int32),
      q.reshape(b, nh, hd), *pools)
    return out.reshape(b, 1, nh, out_hd)
