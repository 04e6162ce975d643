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

Grid: one step per slot. Inside, a slot's live blocks arrive in chunks of
``pages_per_chunk`` table entries, each entry one async copy per pool, into
a double buffer; the next chunk (the next slot's first chunk at a slot's
end) is in flight while the current one is computed on.
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
PAGES_PER_CHUNK = 8           # table entries fetched per compute chunk

# Test seam, shaped like models/gpt.py::set_paged_kv_sharding: entered round
# an engine's trace, it makes the model take this kernel off the TPU too,
# through the Pallas interpreter.
_FORCE = {"interpret": False}
# How often the kernel was traced into a program: the engine reads it round
# its decode trace to say which path that executable took.
_TRACES = {"n": 0}


@contextlib.contextmanager
def force_interpret(on: bool = True):
    prev, _FORCE["interpret"] = _FORCE["interpret"], bool(on)
    try:
        yield
    finally:
        _FORCE["interpret"] = prev


def kernel_traces() -> int:
    return _TRACES["n"]


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
            kbuf, vbuf, sem, first_buf, *,
            pages, block, n_kv, group, mbs, n_slots, scale):
    # grid (slot,), sequential: the double buffer and `first_buf` (which
    # half holds this slot's first chunk) carry across steps
    b = pl.program_id(0)
    nh, hd = q_ref.shape
    rows = pages * block * n_kv          # rows of one chunk: (page, t, head)
    span = pages * block                 # positions of one chunk

    def n_pages(slot):
        return jnp.minimum((len_ref[slot] + block - 1) // block, mbs)

    def copies(slot, c, buf, fn):
        """Apply `fn` (start or wait) to the async copies of chunk `c` of
        `slot`: one per pool per LIVE table entry, none past the length."""
        first = c * pages

        def one(j, _):
            page = tab_ref[slot * mbs + first + j]
            fn(pltpu.make_async_copy(k_hbm.at[page], kbuf.at[buf, j],
                                     sem.at[buf, 0]))
            fn(pltpu.make_async_copy(v_hbm.at[page], vbuf.at[buf, j],
                                     sem.at[buf, 1]))

        jax.lax.fori_loop(0, jnp.minimum(n_pages(slot) - first, pages), one,
                          None)

    start = functools.partial(copies, fn=lambda dma: dma.start())
    wait = functools.partial(copies, fn=lambda dma: dma.wait())

    @pl.when(b == 0)
    def _():
        first_buf[0] = 0
        start(0, 0, 0)

    base = first_buf[0]
    length = len_ref[b]
    n_chunks = (n_pages(b) + pages - 1) // pages

    # column r of a chunk is position r // n_kv of KV head r % n_kv; row i
    # of the scores is query head i, which reads KV head i // group
    col = jax.lax.broadcasted_iota(jnp.int32, (nh, rows), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (nh, rows), 0)
    col_pos = col // n_kv
    own_head = (col - col_pos * n_kv) == row // group
    row_pos = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // n_kv
    q = q_ref[...]

    def chunk(c, carry):
        m, l, acc = carry
        buf = (base + c) % 2

        @pl.when(c + 1 < n_chunks)
        def _():
            start(b, c + 1, 1 - buf)

        @pl.when((c + 1 == n_chunks) & (b + 1 < n_slots))
        def _():
            start(b + 1, 0, 1 - buf)

        wait(b, c, buf)

        @pl.when(c + 1 == n_chunks)
        def _():
            # rows past the length (the block's stale tail, entries never
            # fetched) may hold anything, inf and nan too: 0 * that is nan
            # in the context product, so they are zeroed where they lie
            v_all = vbuf[buf].reshape(rows, -1)
            vbuf[buf] = jnp.where(c * span + row_pos < length, v_all,
                                  jnp.zeros_like(v_all)).reshape(
                                      vbuf.shape[1:])

        k2 = kbuf[buf].reshape(rows, -1)
        v2 = vbuf[buf].reshape(rows, -1)
        s = _dot_exact(q, k2, ((1,), (1,))) * scale          # [nh, rows]
        s = jnp.where(own_head & (c * span + col_pos < length), s, _NEG)
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
    first_buf[0] = (base + n_chunks) % 2
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
    (cursor + 1, at least 1). Returns the context [B, 1, nh, hd] in ``q``'s
    dtype.
    """
    if n_kv is None:
        nb, block, n_kv, hd = pool_k.shape
        pool_k = pool_k.reshape(nb, block * n_kv, hd)
        pool_v = pool_v.reshape(nb, block * n_kv, hd)
    assert q.shape[1] == 1 and q.shape[2] % n_kv == 0 \
        and pool_v.shape == pool_k.shape and pool_k.shape[1] % n_kv == 0
    _TRACES["n"] += 1
    return _attend(q, pool_k, pool_v, table, lengths, interpret=interpret,
                   pages=min(pages_per_chunk or PAGES_PER_CHUNK,
                             table.shape[1]), n_kv=n_kv)


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
    buf = pltpu.VMEM((2, pages, block * n_kv, hd), pool_k.dtype)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[head_block,
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=head_block,
            scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((b, nh, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode",
    )(lengths.astype(jnp.int32), table.reshape(-1).astype(jnp.int32),
      q.reshape(b, nh, hd), pool_k, pool_v)
    return out.reshape(b, 1, nh, hd)
