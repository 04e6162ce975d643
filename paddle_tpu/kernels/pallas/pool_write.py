"""Writes into the paged block pools, in place, as DMA block copies.

Why this exists: a step's new K/V rows went into the pools by XLA scatters
(``pool.at[block, offset].set(rows)``), one per pool per layer, that update
one row at a time: 11 us for GPT-3 XL's 32 decode rows and 1.35 ms for a
256-row chunk, 1.5 % and 4.5 % of what the bytes need; and the pager's
copy-on-write pairs went in as a gather and a scatter of ``max_slots``
blocks on every pool in every call, real pairs or padding. Here both are
async copies that start one after another and are waited for together:

* ``write_rows``: a call's rows, one copy for each contiguous run of valid
  positions inside one block (a decode row is one copy a pool, a 256-row
  chunk of GPT-3 XL at most 17). No copy is made for a position at or past
  ``end`` or past the table's width, nor into the trash block (a slot that
  is not live has the trash row): nothing is written there any more. Every
  other byte of every pool is what the scatter leaves.
* ``copy_blocks``: one whole-block copy a pool for each real pair (a pair
  into the trash block is padding and costs a predicate), all of a call's
  pools in one kernel. The pools it returns are what the call's rows are
  then written into: every copy is made before any row is.

A block is seen as rows. Where a position's rows lie on a major axis of the
pool (``[NB, BS, n_kv, hd]``: GPT, LLaMA) a position is whole tiles and is
copied as it is. Where they lie on the tiled axis (``[NB, BS * r, lanes]``
with ``r`` rows a position: the grouped-query pools of the hybrids,
``cache_spec.kv_layer(merged_rows=True)``, and the latent pool) a copy
moves whole tiles of ``TILE_ROWS`` rows: the positions are taken ``u`` at a
time (``u * r`` rows, a multiple of the tile), and the first and the last
such unit of a call, which hold rows the call does not write, are staged
first with the pool's own rows round the new ones. The layout is read from
the shapes; one algorithm serves all three.

The pools are aliased in and out (``input_output_aliases``); the engine's
executables donate them, so nothing is copied whole.
"""
from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .util import note_pool_write, tpu_placement

TRASH = 0          # serving/pager.py::TRASH_BLOCK: never copied into
TILE_ROWS = 8      # rows of one HBM tile on the tiled axis ((8, 128) tiling)

# Test seam, as paged_decode.force_interpret: the writes take the kernel off
# the TPU too, through the Pallas interpreter.
_FORCE = {"interpret": False}


@contextlib.contextmanager
def force_interpret(on: bool = True):
    prev, _FORCE["interpret"] = _FORCE["interpret"], bool(on)
    try:
        yield
    finally:
        _FORCE["interpret"] = prev


def copy_mode(pool):
    """``"interpret"`` inside ``force_interpret``, ``"mosaic"`` where
    ``pool`` lives on a TPU, else None: how ``copy_blocks`` runs (whole
    blocks fit any layout). The caller keeps XLA's copies under a serving
    mesh (sharded pools)."""
    if _FORCE["interpret"]:
        return "interpret"
    return "mosaic" if tpu_placement(pool) else None


def _layout(pool, rows):
    """``(block, r, u)``: positions a block, rows a position on the pool's
    second axis, positions a copied unit; None where the shapes do not
    fit. ``rows [B, S, *unit]`` against ``pool [NB, BS, *unit]`` (a
    position's rows on a major axis, or the 3-D latent pool: one row a
    position) or ``pool [NB, BS * r, lanes]`` for ``rows [B, S, r,
    lanes]`` (merged rows)."""
    if pool.shape[-1] != rows.shape[-1]:
        return None
    if pool.ndim == rows.ndim and pool.shape[2:] == rows.shape[2:]:
        if pool.ndim > 3:
            return pool.shape[1], 1, 1
        r = 1
    elif pool.ndim == 3 and rows.ndim == 4:
        r = rows.shape[2]
    else:
        return None
    if pool.shape[1] % r:
        return None
    block = pool.shape[1] // r
    u = TILE_ROWS // math.gcd(TILE_ROWS, r)
    return (block, r, u) if block % u == 0 else None


def kernel_mode(pool, rows):
    """How ``rows`` should be written into ``pool``: ``"mosaic"`` on a TPU
    where the layout fits, ``"interpret"`` inside ``force_interpret``,
    None for the XLA scatter. The caller keeps the scatter under a serving
    mesh (sharded pools)."""
    mode = copy_mode(pool)
    if mode is None or _layout(pool, rows) is None:
        return None
    if mode == "mosaic" and pool.ndim == 3 \
            and jnp.dtype(pool.dtype).itemsize not in (2, 4):
        return None
    return mode


def _each_copy(fn, b, runs, bits, per, tab_ref, lo_ref, hi_ref, mbs):
    """Call ``fn(slot, block, src, dst, n)`` for every piece of every run:
    slot ``b``'s units ``[lo, hi)`` cut at block edges (``per`` units a
    block), each run's length in powers of two (``bits``), under a
    predicate: no piece past the table, none of an empty run, none into
    the trash block."""
    def slot(i, _):
        lo, hi = lo_ref[i], hi_ref[i]
        first = lo // per

        def run(j, _):
            lb = first + j
            a = jnp.maximum(lo, lb * per)
            n = jnp.minimum(hi, (lb + 1) * per) - a
            blk = tab_ref[i * mbs + jnp.minimum(lb, mbs - 1)]
            ok = (n > 0) & (lb < mbs) & (blk != TRASH)
            done = jnp.int32(0)
            for bit in bits:
                @pl.when(ok & ((n & bit) != 0))
                def _(bit=bit, done=done):
                    fn(i, blk, a - lo + done, a - lb * per + done, bit)
                done = done + (n & bit)
            return 0

        jax.lax.fori_loop(0, runs, run, 0)
        return 0

    jax.lax.fori_loop(0, b, slot, 0)


def _rows_kernel(tab_ref, lo_ref, hi_ref, *refs, n_pools, b, runs, bits, per,
                 mbs):
    # refs: the staged rows a pool (HBM), the pools in (aliased), the pools
    # out, a DMA semaphore a pool
    staged, outs, sem = refs[:n_pools], refs[2 * n_pools:3 * n_pools], \
        refs[3 * n_pools]

    def copies(i, blk, src, dst, n):
        return [pltpu.make_async_copy(staged[k].at[i, pl.ds(src, n)],
                                      outs[k].at[blk, pl.ds(dst, n)],
                                      sem.at[k]) for k in range(n_pools)]

    for act in ("start", "wait"):
        _each_copy(lambda *a, act=act: [getattr(c, act)()
                                        for c in copies(*a)],
                   b, runs, bits, per, tab_ref, lo_ref, hi_ref, mbs)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _write_units(pools, staged, table, lo, hi, *, interpret):
    """Copy ``staged[k][b, j]`` (units ``[B, NU, *unit]``) into
    ``pools[k]`` (``[NB, per, *unit]``) at units ``lo[b] + j < hi[b]`` of
    slot ``b``'s table row. Jitted: a model's layers share one trace and
    one lowering."""
    n_pools = len(pools)
    b, nu = staged[0].shape[:2]
    per, mbs = pools[0].shape[1], table.shape[1]
    runs = 1 if nu == 1 else (nu - 1) // per + 2
    bits = tuple(1 << k for k in
                 range(min(per, nu).bit_length() - 1, -1, -1))
    kernel = functools.partial(_rows_kernel, n_pools=n_pools, b=b, runs=runs,
                               bits=bits, per=per, mbs=mbs)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(),
            in_specs=[hbm] * (2 * n_pools), out_specs=[hbm] * n_pools,
            scratch_shapes=[pltpu.SemaphoreType.DMA((n_pools,))]),
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        input_output_aliases={3 + n_pools + k: k for k in range(n_pools)},
        interpret=interpret, name="pool_write",
    )(table.reshape(-1).astype(jnp.int32), lo, hi, *staged, *pools)


def _per_slot(x, b):
    return jnp.broadcast_to(jnp.asarray(x, jnp.int32).reshape(-1), (b,))


def write_rows(pools, rows, table, start, end, *, interpret=False):
    """Write ``rows[k] [B, S, ...]`` into ``pools[k]`` (one layout, as
    ``kernel_mode`` accepted it) at positions ``start + i`` of each slot's
    ``table [B, mbs]`` row, those before ``end`` (``start``/``end``
    scalars or ``[B]``). Returns the pools after the write."""
    block, r, u = _layout(pools[0], rows[0])
    b, s = rows[0].shape[:2]
    mbs = table.shape[1]
    p0 = _per_slot(start, b)
    hi = jnp.clip(jnp.minimum(p0 + s, _per_slot(end, b)), p0, mbs * block)
    lo_u, hi_u = p0 // u, -(-hi // u)
    if u == 1:
        staged = [x.astype(p.dtype).reshape((b, s) + p.shape[2:]
                                            if p.ndim > 3 else
                                            (b, s, r, p.shape[-1]))
                  for x, p in zip(rows, pools)]
        views = [p if p.ndim > 3 else p.reshape(p.shape[0], block, r,
                                                p.shape[-1])
                 for p in pools]
    else:
        nu = (2 * u - 2 + s) // u          # units a call can touch
        per = block // u
        lanes = pools[0].shape[-1]
        views = [p.reshape(p.shape[0] * per, u * r, lanes) for p in pools]
        # the units' positions, and which of them the call writes
        q = lo_u[:, None] * u + jnp.arange(nu * u, dtype=jnp.int32)
        fresh = ((q >= p0[:, None]) & (q < hi[:, None]))[..., None, None]
        at = jnp.clip(q - p0[:, None], 0, s - 1)
        unit = lo_u[:, None] + jnp.arange(nu, dtype=jnp.int32)
        lb = jnp.minimum(unit // per, mbs - 1)
        held = jnp.take_along_axis(table, lb, axis=1) * per + unit % per
        staged = []
        for x, v in zip(rows, views):
            new = jnp.take_along_axis(
                x.reshape(b, s, r, lanes).astype(v.dtype),
                at[..., None, None], axis=1)          # [B, NU * u, r, lanes]
            old = jnp.take(v, held, axis=0, mode="clip").reshape(
                b, nu * u, r, lanes)
            staged.append(jnp.where(fresh, new, old).reshape(
                b, nu, u * r, lanes))
        views = [v.reshape(p.shape[0], per, u * r, lanes)
                 for v, p in zip(views, pools)]
    note_pool_write("kernel")
    out = _write_units(views, staged, table, lo_u, hi_u, interpret=interpret)
    return [o.reshape(p.shape) for o, p in zip(out, pools)]


def _copy_kernel(src_ref, dst_ref, *refs, n_pools, n_pairs):
    outs, sem = refs[n_pools:2 * n_pools], refs[2 * n_pools]

    for act in ("start", "wait"):
        def pair(i, _, act=act):
            @pl.when(dst_ref[i] != TRASH)
            def _():
                for k in range(n_pools):
                    c = pltpu.make_async_copy(outs[k].at[src_ref[i]],
                                              outs[k].at[dst_ref[i]],
                                              sem.at[k])
                    getattr(c, act)()
            return 0

        jax.lax.fori_loop(0, n_pairs, pair, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def copy_blocks(pools, src, dst, *, interpret=False):
    """``pools[k][dst[i]] = pools[k][src[i]]`` for every pair whose ``dst``
    is not the trash block (padding), every pool in one kernel, each copy
    whole blocks. The pairs' destinations are fresh blocks, never a
    source of the same call. Returns the pools after the copies."""
    n_pools = len(pools)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    kernel = functools.partial(_copy_kernel, n_pools=n_pools,
                               n_pairs=src.shape[0])
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(),
            in_specs=[hbm] * n_pools, out_specs=[hbm] * n_pools,
            scratch_shapes=[pltpu.SemaphoreType.DMA((n_pools,))]),
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        input_output_aliases={2 + k: k for k in range(n_pools)},
        interpret=interpret, name="pool_copy",
    )(src.astype(jnp.int32), dst.astype(jnp.int32), *pools)
