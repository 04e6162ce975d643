"""Grouped expert matmul: the SwiGLU experts a chip HOLDS, applied to the
(token, expert) assignments that fall on them, group by group.

A routed layer's step is thousands of small matrix products against
different weights. Batched naively it either drops tokens at a capacity or
multiplies every token by every expert. Here the step's assignments to held
experts are SORTED by expert into one row buffer, each expert's group padded
to whole tiles of ``tile`` rows, and one pass walks the tiles: a tile
multiplies its rows by ITS expert's three matrices. Nothing is dropped, and
an expert no token chose is never touched.

  plan           from the router's choice: where each assignment's row goes,
                 which token each row of the buffer holds, which expert each
                 tile belongs to, how many tiles are used, how many held
                 experts got a token. Plain ``jax.numpy`` (a cumulative sum
                 over a one-hot: no sort primitive, no host round trip).
  moe_grouped    on a TPU the Pallas kernel ``moe_grouped``: grid over the
                 tiles, the tile's expert index scalar-prefetched into the
                 weight BlockSpecs' index maps, so the pipeline fetches the
                 weights of experts WITH tokens only, and once each (tiles
                 of one expert are adjacent; an unchanged block index is not
                 fetched again). Tiles past the used count keep the last
                 expert's index (no fetch) and write zeros. An expert
                 whose three matrices do not fit the kernel's fast memory
                 twice (6144 x 2048: 75 MB) is walked in blocks of its
                 intermediate width on a second grid axis, the down
                 projection's partial products summed in float32
                 (``inter_block``); a tile past the used count keeps the
                 last block too.
  dense_masked   the plain form: every held expert over every token, times
                 the assignment's weight or 0. The CPU path, and the oracle.

Weights are ``[E, H, I]`` (gate, up) and ``[E, I, H]`` (down) for the ``E``
experts held; expert ids are the router's GLOBAL ids, ``offset`` the first
one held. An assignment to an absent expert contributes nothing here: its
owner adds it, on another chip.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .util import tpu_placement

TILE = 16                     # rows of one tile: a bfloat16 sublane tile
VMEM_LIMIT = 48 * 1024 * 1024  # two experts' weights in flight + the tiles

_FORCE = {"interpret": False}


@contextlib.contextmanager
def force_interpret(on: bool = True):
    prev, _FORCE["interpret"] = _FORCE["interpret"], bool(on)
    try:
        yield
    finally:
        _FORCE["interpret"] = prev


def kernel_mode(x, w_gate):
    if _FORCE["interpret"]:
        return "interpret"
    if not tpu_placement(x):
        return None
    _, h, i = w_gate.shape
    if h % 128 or i % 128:
        return None
    return "mosaic"


def held_ids(ids, valid, offset: int, count: int):
    """``ids`` relative to the first held expert; ``count`` where the
    expert is not held here or the token is not valid."""
    local = ids - offset
    return jnp.where(valid[:, None] & (local >= 0) & (local < count), local,
                     count)


def plan(ids, valid, offset: int, count: int, tile: int = TILE):
    """``ids [T, k]`` int32 global expert ids, ``valid [T]`` bool (a padded
    or dead token routes nowhere). Returns a dict:

      row      [T, k]  the buffer row of each assignment (R: none, not held)
      token    [R]     the token each buffer row holds (T: an empty row)
      expert   [tiles] the held expert each tile multiplies by
      used     []      tiles that hold at least one row
      counts   [count] assignments per held expert

    with R = tiles * tile rows, tiles = ceil(T k / tile) + count: room for
    every assignment landing here, each group padded to whole tiles."""
    t, k = ids.shape
    n_tiles = -(-t * k // tile) + count
    rows = n_tiles * tile
    flat = held_ids(ids, valid, offset, count).reshape(-1)      # [T k]
    onehot = (flat[:, None] == jnp.arange(count)[None, :]).astype(jnp.int32)
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1)
    counts = jnp.sum(onehot, axis=0)                            # [count]
    tiles_of = -(-counts // tile)
    first_tile = jnp.cumsum(tiles_of) - tiles_of
    used = jnp.sum(tiles_of)
    start = jnp.concatenate([first_tile * tile,
                             jnp.full((1,), rows, jnp.int32)])
    row = jnp.where(flat < count, start[flat] + rank, rows)
    token = jnp.full((rows,), t, jnp.int32).at[row].set(
        jnp.arange(t * k, dtype=jnp.int32) // k, mode="drop")
    # tile i belongs to the expert whose tile range holds it; tiles past
    # the used count repeat the last used tile's expert
    ends = jnp.cumsum(tiles_of)
    at = jnp.minimum(jnp.arange(n_tiles), jnp.maximum(used - 1, 0))
    expert = jnp.minimum(jnp.searchsorted(ends, at, side="right"),
                         count - 1).astype(jnp.int32)
    return {"row": row.reshape(t, k), "token": token, "expert": expert,
            "used": used.astype(jnp.int32), "counts": counts}


def inter_block(h: int, inter: int, elem: int) -> int:
    """Columns of the intermediate width a grid step multiplies by: all of
    them where two experts' matrices fit ``VMEM_LIMIT`` (one computed on,
    one in flight), else the widest 128-multiple divisor of ``inter`` that
    does."""
    def fits(cols):
        return 2 * 3 * h * cols * elem <= VMEM_LIMIT - (4 << 20)
    if fits(inter):
        return inter
    for cols in range(inter - 128, 0, -128):
        if inter % cols == 0 and fits(cols):
            return cols
    return 128


def _kernel_blocked(expert_ref, used_ref, x_ref, wg_ref, wu_ref, wd_ref,
                    o_ref, acc_ref):
    """`_kernel` over one block of the intermediate width a step: grid
    (tile, block), the down projection's partial products summed in
    `acc_ref`."""
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i < used_ref[0])
    def _():
        x = x_ref[...]
        prec = jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 else None
        dot = functools.partial(jnp.dot, precision=prec,
                                preferred_element_type=jnp.float32)
        gate, up = dot(x, wg_ref[...]), dot(x, wu_ref[...])
        hid = (jax.nn.silu(gate) * up).astype(x.dtype)
        acc_ref[...] += dot(hid, wd_ref[...])

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _kernel(expert_ref, used_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref):
    i = pl.program_id(0)

    @pl.when(i < used_ref[0])
    def _():
        x = x_ref[...]
        prec = jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 else None
        dot = functools.partial(jnp.dot, precision=prec,
                                preferred_element_type=jnp.float32)
        gate, up = dot(x, wg_ref[...]), dot(x, wu_ref[...])
        hid = (jax.nn.silu(gate) * up).astype(x.dtype)
        o_ref[...] = dot(hid, wd_ref[...]).astype(o_ref.dtype)

    @pl.when(i >= used_ref[0])
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _grouped_ffn(xs, expert, used, w_gate, w_up, w_down, *, tile, interpret):
    rows, h = xs.shape
    _, _, inter = w_gate.shape
    cols = inter_block(h, inter, w_gate.dtype.itemsize)
    if cols < inter:
        n_j = inter // cols

        def at(i, j, u):        # a tile past the used ones: no new block
            return jnp.where(i < u[0], j, n_j - 1)

        x_block = pl.BlockSpec((tile, h), lambda i, j, *_: (i, 0))
        return pl.pallas_call(
            _kernel_blocked,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(rows // tile, n_j),
                in_specs=[x_block,
                          pl.BlockSpec((None, h, cols), lambda i, j, e, u:
                                       (e[i], 0, at(i, j, u))),
                          pl.BlockSpec((None, h, cols), lambda i, j, e, u:
                                       (e[i], 0, at(i, j, u))),
                          pl.BlockSpec((None, cols, h), lambda i, j, e, u:
                                       (e[i], at(i, j, u), 0))],
                out_specs=x_block,
                scratch_shapes=[pltpu.VMEM((tile, h), jnp.float32)]),
            out_shape=jax.ShapeDtypeStruct((rows, h), xs.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT),
            interpret=interpret,
            name="moe_grouped",
        )(expert, used.reshape(1), xs, w_gate, w_up, w_down)
    x_block = pl.BlockSpec((tile, h), lambda i, *_: (i, 0))
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(rows // tile,),
            in_specs=[x_block,
                      pl.BlockSpec((None, h, inter),
                                   lambda i, e, u: (e[i], 0, 0)),
                      pl.BlockSpec((None, h, inter),
                                   lambda i, e, u: (e[i], 0, 0)),
                      pl.BlockSpec((None, inter, h),
                                   lambda i, e, u: (e[i], 0, 0))],
            out_specs=x_block),
        out_shape=jax.ShapeDtypeStruct((rows, h), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="moe_grouped",
    )(expert, used.reshape(1), xs, w_gate, w_up, w_down)


def dense_masked(x, ids, weights, valid, w_gate, w_up, w_down, offset: int):
    """Every held expert over every token, each result times the weight of
    that token's assignment to it (0 where it has none). ``x [T, H]``;
    returns [T, H] float32."""
    count = w_gate.shape[0]
    local = held_ids(ids, valid, offset, count)
    prec = "highest" if x.dtype == jnp.float32 else None

    def one(acc, e):
        wg, wu, wd = w_gate[e], w_up[e], w_down[e]
        hid = jax.nn.silu(jnp.dot(x, wg, precision=prec,
                                  preferred_element_type=jnp.float32)) \
            * jnp.dot(x, wu, precision=prec,
                      preferred_element_type=jnp.float32)
        y = jnp.dot(hid.astype(x.dtype), wd, precision=prec,
                    preferred_element_type=jnp.float32)
        w = jnp.sum(jnp.where(local == e, weights, 0.0), axis=1)
        return acc + y * w[:, None], None

    acc, _ = jax.lax.scan(one, jnp.zeros(x.shape, jnp.float32),
                          jnp.arange(count))
    return acc


def moe_grouped(x, ids, weights, valid, w_gate, w_up, w_down, offset: int,
                tile: int = TILE):
    """The held experts' part of a routed layer. ``x [T, H]`` in the model's
    dtype, ``ids weights [T, k]`` the router's choice (global ids, float32
    weights), ``valid [T]``. Returns (out [T, H] float32, counts [count]:
    assignments per held expert)."""
    count = w_gate.shape[0]
    mode = kernel_mode(x, w_gate)
    if mode is None:
        counts = jnp.sum((held_ids(ids, valid, offset, count)[..., None]
                          == jnp.arange(count)).astype(jnp.int32), (0, 1))
        return dense_masked(x, ids, weights, valid, w_gate, w_up, w_down,
                            offset), counts
    p = plan(ids, valid, offset, count, tile)
    t = x.shape[0]
    x_pad = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])
    ys = _grouped_ffn(x_pad[p["token"]], p["expert"], p["used"], w_gate,
                      w_up, w_down, tile=tile, interpret=mode == "interpret")
    # back to the tokens: each assignment reads its row (an absent one the
    # zero row past the end) and scales it by its weight
    ys = jnp.concatenate([ys, jnp.zeros((1, ys.shape[1]), ys.dtype)])
    picked = ys[p["row"]].astype(jnp.float32)                  # [T, k, H]
    w = jnp.where(p["row"] < ys.shape[0] - 1, weights, 0.0)
    return jnp.sum(picked * w[..., None], axis=1), p["counts"]
