"""Head-PAIR flash attention over the packed qkv layout, for head_dim 64.

Why this exists: at head_dim 64 (GPT-medium, BERT-base, most 64-dim-head
models) the flat [B*H, L, D] kernels read half-empty 128-lane tiles AND the
[B,L,H,D] <-> [B*H,L,D] relayout around them is pure HBM transposes. This
path instead reads 128-wide column blocks straight out of the fused
projection output [B, L, 3*H*D] — TWO adjacent 64-wide heads per block — and
writes the context back pre-packed [B, L, H*D]. Zero layout copies, full
lanes.

Shape contract: head-BLOCKS of hpb = max(1, 128 // head_dim) adjacent heads
fill the 128-lane quantum (hpb*d % 128 == 0; hpb=2 at d=64, hpb=1 at d=128)
and num_heads % hpb == 0. Any sequence length.

The schedule (PR 32). A grid step holds one q tile and one MAJOR K/V tile
(``block_k``: the whole padded length up to 2048, so K and V are fetched
once a head block) and walks the K/V tile in SUB-tiles of ``sub_k`` rows
with two loops whose bounds come from ``_sub_tile_bounds``:

  - interior sub-tiles (wholly under the diagonal, wholly inside kv_len)
    run a body with no iota, compare or select;
  - sub-tiles the diagonal crosses, or that hold padded columns, run the
    same body under a mask, and where the pieces are square (the one masked
    piece of a q tile then lies ON the diagonal) as ``split`` row slabs,
    each cut after its last visible column; sub-tiles above the diagonal
    are never visited, and a major tile wholly above it is not fetched
    either (its index map is clamped to the last one needed).

A non-causal call whose length is its padded length builds no mask at all.
``pair_schedule`` gives the counts (tiles computed, the causal minimum,
tiles masked) from the same bounds, and every trace of ``_pair_fwd`` /
``_pair_bwd`` leaves them on a ``flash_pair/schedule`` span.

Every operand is a full 128-lane block: a head's q (and do) is the pair's
block with the other head's lanes zeroed, so no kernel slices 64 lanes out
of a ref, and the two heads' results are merged into one full-lane store.
The forward keeps scores as [q rows, K/V rows] with the running max / sum
lane-replicated ([block_q, 128], as jax's own TPU kernel does) and turns
them into the [hpb, L] ``lse`` rows once, at the last K/V step. The backward
keeps scores TRANSPOSED ([K/V rows, q rows] = k . q^T, as jax's splash
kernel does): the ``lse`` and ``delta`` rows broadcast along sublanes as
they lie, dv = ``pT . do`` and dk = ``dsT . q`` are plain products, and dq
is accumulated transposed too (``k^T . dsT``, head h's d rows of k^T), so
no score-sized array is ever transposed: dq^T is, once a q tile. It picks
between two forms by VMEM budget:

  - FUSED (kv_pad <= 4096): one kernel, s/p computed once per piece for dq,
    dk AND dv; dk/dv accumulate in full-length VMEM scratch across both
    grid dims (the scratch is what bounds the length).
  - SPLIT (longer): the classic two-kernel flash backward — a dq kernel
    (q-parallel, kv streamed) and a dkv kernel (kv-parallel, q streamed),
    each with only tile-sized scratch, so any length fits; s/p recomputed
    per kernel.

Both write d(qkv) parts directly in the packed layout — zero relayouts at
every length. Dropout draws one hardware-PRNG tile per (head, q tile,
GLOBAL sub-tile), the same unit in all four kernels.

What it costs on one TPU v5e (PR 32's chip runs; PERF.md §6 has the sweep):
``[8, 2048, 3*16*64]`` causal bf16, forward 1.30 ms and backward 2.63 ms a
call (3.06 and 3.62 before this schedule), against 0.79 + 1.97 ms of MXU
time at head width 64, where every product half-fills the array. 512-row
pieces beat 256-row ones (forward 1.30 against 2.19 ms) although a
256 x 256 float32 piece is the register file and a 512 x 512 one four of
them: a piece's fixed costs (statistics, accumulator updates, weight
loads) outweigh its round trips through VMEM. There is no single-tile
special case any more: at ``[16, 1024]`` causal and ``[32, 512]``
non-causal the same walk is faster than the old one-tile body was.

Reference analog: phi/kernels/fusion/fused_attention — the reference fuses
qkv-projection-adjacent attention exactly to avoid these relayouts.
"""
from __future__ import annotations

import functools
import math
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...monitor import trace as _trace
from .flash_attention import _NEG_INF, _dropout_mask, _pad_len, _round_up

_NT = (((1,), (1,)), ((), ()))      # a . b^T
_NN = (((1,), (0,)), ((), ()))      # a . b


def _heads_per_block(head_dim: int) -> int:
    """How many adjacent heads fill the 128-lane quantum (2 at d=64, 1 at
    d>=128-multiples)."""
    return max(1, 128 // head_dim)


# longest kv_pad the FUSED backward's full-length dk/dv scratch fits in VMEM
# (2 x kv_pad x (hpb*d) lanes x 4 B = 4 MB at kv_pad=4096, hpb*d=128; beside
# it the 2048-row K/V tiles, the full-length dk/dv output blocks and the
# temporaries of a 512 x 512 piece: it compiles and runs at 4096 on a v5e,
# PR 32, while 4096-row K/V tiles beside it do not; the split form takes
# over beyond). The budget was sized at hpb*d == 128 lanes: head_dim=256 passes pair_layout_supported
# (256 % 128 == 0) with hpb*d == 256, doubling the scratch — so the cutoff
# scales down by the same lane factor instead of blowing past VMEM at
# kv_pad=4096 (ADVICE r5).
_MAX_FUSED_BWD_LANE_BUDGET = 4096 * 128


def _max_fused_bwd(hpb: int, d: int, override=None) -> int:
    """Fused-bwd kv_pad cutoff. The heuristic (lane budget / lane width)
    loses to reality on chips with other VMEM headroom — override with the
    ``max_fused_bwd=`` kwarg (flash_pair_packed) or env
    ``PADDLE_FLASH_FUSED_BWD_MAX=<kv_pad>`` (0 forces the split form).
    The env fallback here runs when a backward first TRACES a static
    signature; like anything read into a compiled program, a mid-process
    env change only affects new signatures (flash_pair_packed resolves the
    env at the call site instead, so its callers re-trace on change —
    direct flash_pair callers wanting a per-call value must pass the
    kwarg)."""
    if override is None:
        env = os.environ.get("PADDLE_FLASH_FUSED_BWD_MAX")
        if env:
            override = int(env)
    if override is not None:
        return int(override)
    return _MAX_FUSED_BWD_LANE_BUDGET // (hpb * d)


def pair_layout_supported(head_dim: int, num_heads: int,
                          seq_len: int = 0) -> bool:
    """The gate for this path: whole head-blocks fill the 128-lane quantum.
    Any sequence length (round 5: multi-tile online-softmax kernels; the
    seq_len parameter remains for call-site compatibility)."""
    hpb = _heads_per_block(head_dim)
    return ((hpb * head_dim) % 128 == 0 and head_dim % 8 == 0
            and num_heads % hpb == 0)


# ----------------------------------------------------------------- geometry


class _Geometry(NamedTuple):
    kv_pad: int      # L rounded up to 128: rows of q, k, v as the kernels see
    block_q: int     # q rows a grid step holds
    block_k: int     # K/V rows a grid step holds (the major tile)
    sub_k: int       # K/V rows one piece of the inner loops computes on
    split: int = 1   # a masked piece is computed as this many row slabs


def _largest_divisor(n: int, cap: int) -> int:
    """The largest of cap, cap/2, cap/4, ... that divides n."""
    cap = min(cap, n)
    while n % cap:
        cap //= 2
    return cap


def _norm_pair_blocks(L, block_q, causal, hpb, d) -> _Geometry:
    """The ONE place sizes are decided, for the forward and every backward
    kernel alike (the dropout PRNG seeds per (q tile, sub-tile), so their
    pieces must match or the keep masks desynchronize). A pure function of
    what the call can see; ``block_q`` is the caller's cap. The sizes are
    what a v5e measured fastest at head widths 64 and 128, causal and not,
    512 to 4096 tokens (PERF.md §6, PR 32): 512-row pieces; 256 and 128
    are slower by a quarter and more, 1024 does not fit VMEM."""
    kv_pad = _round_up(L, 128)
    # a head block wider than the 128-lane quantum (d = 256) holds that many
    # fewer rows: every block, scratch and temporary scales with the width
    wide = hpb * d // 128
    block_q = _largest_divisor(kv_pad, min(block_q, 512 // wide))
    # K and V stay resident per head block up to 2048 rows (0.5 MB each,
    # double-buffered): fetched once, not once a q tile
    block_k = _largest_divisor(kv_pad, 2048 // wide)
    sub_k = _largest_divisor(block_k, 512 // wide)
    # a square piece on the diagonal is computed as two row slabs, each up
    # to its own last visible column: three quarters of the piece
    split = 2 if causal and block_q == sub_k and block_q % 256 == 0 else 1
    return _Geometry(kv_pad, block_q, block_k, sub_k, split)


def _slabs(g: _Geometry, masked):
    """(first q row, q rows, K/V rows) of the slabs a piece is computed as:
    the whole piece, or for a masked piece of a ``split`` geometry (causal,
    square pieces: the one masked piece of a q tile then lies ON the
    diagonal) one slab per row chunk, cut after its last visible column."""
    if not masked or g.split == 1:
        return [(0, g.block_q, g.sub_k)]
    rq, rk = g.block_q // g.split, g.sub_k // g.split
    return [(r * rq, rq, (r + 1) * rk) for r in range(g.split)]


def _sub_tile_bounds(qi, ki, g: _Geometry, causal, kv_len, lo=jnp.maximum,
                     hi=jnp.minimum):
    """(n_int, n_run) for q tile ``qi`` over major K/V tile ``ki``, counted
    in sub-tiles from the major tile's start: [0, n_int) lie wholly under
    the diagonal and inside kv_len (no mask), [n_int, n_run) are crossed by
    the diagonal or hold padded columns (masked), the rest are above the
    diagonal and never run. Works on traced scalars in the kernels and on
    ints (``lo=max, hi=min``) in ``pair_schedule``."""
    per_major = g.block_k // g.sub_k
    total = g.kv_pad // g.sub_k
    if causal:
        top = qi * g.block_q                      # the tile's first row
        run_end = (top + g.block_q - 1) // g.sub_k + 1
        int_end = (top + 1) // g.sub_k            # (j+1)*sub_k - 1 <= top
    else:
        run_end = int_end = total
    if kv_len < g.kv_pad:
        int_end = hi(int_end, kv_len // g.sub_k)
    first = ki * per_major
    return (hi(lo(int_end - first, 0), per_major),
            hi(lo(run_end - first, 0), per_major))


def pair_schedule(L, causal, hpb, d, kernel="fwd", block_q=512,
                  max_fused_bwd=None) -> dict:
    """What one trace of ``_pair_fwd`` / ``_pair_bwd`` will walk, from the
    same pure functions the kernels take their sizes and loop bounds from.
    The geometry (``block_q``, ``block_k``, ``sub_k``, ``split``), and
    counts of [block_q / split, sub_k / split] tiles over one head block:
    ``tiles_run`` (computed in one pass), ``tiles_min`` (those that hold at
    least one visible entry: the least any schedule at this granularity can
    run), ``tiles_square`` (all of them), ``tiles_masked`` (computed under a
    mask: a slab is masked whole). ``form``: ``fused`` (one kernel, one
    pass) or ``split`` (the backward's two kernels, each of which walks
    ``tiles_run``)."""
    g = _norm_pair_blocks(L, block_q, causal, hpb, d)
    n_q, n_k = g.kv_pad // g.block_q, g.kv_pad // g.block_k
    tq, tk = g.block_q // g.split, g.sub_k // g.split

    def tiles(masked):
        return sum((nr // tq) * (nc // tk) for _, nr, nc in _slabs(g, masked))

    run = masked = 0
    for qi in range(n_q):
        for ki in range(n_k):
            n_int, n_run = _sub_tile_bounds(qi, ki, g, causal, L, max, min)
            run += n_int * tiles(False) + (n_run - n_int) * tiles(True)
            masked += (n_run - n_int) * tiles(True)
    cols = g.kv_pad // tk
    least = sum(min(cols, (row + tq - 1) // tk + 1) if causal else cols
                for row in range(0, g.kv_pad, tq))
    fused = (kernel == "fwd"
             or g.kv_pad <= _max_fused_bwd(hpb, d, max_fused_bwd))
    return {"kernel": kernel, "block_q": g.block_q, "block_k": g.block_k,
            "sub_k": g.sub_k, "split": g.split, "tiles_run": run,
            "tiles_min": least,
            "tiles_square": (g.kv_pad // tq) * cols,
            "tiles_masked": masked, "form": "fused" if fused else "split"}


# ------------------------------------------------------- shared kernel pieces


def _lane(shape):
    return jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)


def _head_only(x, h, hpb, d):
    """float32 ``x`` [rows, hpb*d] with every lane outside head ``h``
    zeroed: a full-lane operand that contracts (or produces) that head
    alone. The whole block where a block is one head."""
    if hpb == 1:
        return x
    lane = _lane(x.shape)
    return jnp.where((lane >= h * d) & (lane < (h + 1) * d), x, 0.0)


def _head_merge(parts, d):
    """One [rows, hpb*d] array whose head ``h`` lanes come from
    ``parts[h]`` (each full-width): what lets the heads share one full-lane
    accumulate and store."""
    out = parts[-1]
    if len(parts) > 1:
        lane = _lane(out.shape)
        for h in range(len(parts) - 2, -1, -1):
            out = jnp.where(lane < (h + 1) * d, parts[h], out)
    return out


def _rep(x, width):
    """A lane-replicated [rows, 128] statistic as [rows, width]."""
    return x if width == 128 else jnp.tile(x, (1, width // 128))


def _sub_mask(qi, sub, slab, g: _Geometry, causal, kv_len, transposed):
    """Validity of one slab of a masked piece ([q rows, K/V rows], or
    transposed): padded columns off, entries above the diagonal off.
    Shared by all four kernels so forward and backward probabilities can
    never desynchronize."""
    r0, nr, nc = slab
    shape = (nc, nr) if transposed else (nr, nc)
    cols = sub * g.sub_k + jax.lax.broadcasted_iota(
        jnp.int32, shape, 0 if transposed else 1)
    valid = None
    if kv_len < g.kv_pad:
        valid = cols < kv_len
    if causal:
        rows = qi * g.block_q + r0 + jax.lax.broadcasted_iota(
            jnp.int32, shape, 1 if transposed else 0)
        below = rows >= cols
        valid = below if valid is None else valid & below
    return valid


def _walk(n_int, n_run, piece, ever_masked):
    """Run ``piece(j, masked)`` over the interior sub-tiles, then over the
    masked ones."""
    def loop(a, b, masked):
        def body(j, carry):
            piece(j, masked)
            return carry
        jax.lax.fori_loop(a, b, body, 0)
    loop(0, n_int, False)
    if ever_masked:
        loop(n_int, n_run, True)


# ------------------------------------------------------------------ forward

def _pair_fwd_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                     m_sc, l_sc, acc_sc, *, g, sm_scale, causal, d, kv_len,
                     dropout_rate, n_heads, hpb):
    # grid (b, head_block, q_blocks, kv_blocks); kv innermost/sequential —
    # m/l/acc carry the online softmax across sub-tiles and kv steps in
    # scratch: m/l lane-replicated [hpb, block_q, 128], acc [block_q, hpb*d]
    # for the hpb heads side by side.
    b, h2 = pl.program_id(0), pl.program_id(1)
    qi, ki = pl.program_id(2), pl.program_id(3)
    n_k = g.kv_pad // g.block_k
    per_major = g.block_k // g.sub_k
    width = hpb * d

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    qs = q_ref[...].astype(jnp.float32) * sm_scale
    qz = [_head_only(qs, h, hpb, d).astype(q_ref.dtype) for h in range(hpb)]

    def piece(j, masked):
        sub = ki * per_major + j
        start = pl.multiple_of(j * g.sub_k, g.sub_k)
        for slab in _slabs(g, masked):
            r0, nr, nc = slab
            qr = slice(r0, r0 + nr)
            k, v = k_ref[pl.ds(start, nc), :], v_ref[pl.ds(start, nc), :]
            valid = (_sub_mask(qi, sub, slab, g, causal, kv_len, False)
                     if masked else None)
            alphas, pvs = [], []
            for h in range(hpb):
                s = jax.lax.dot_general(qz[h][qr], k, _NT,
                                        preferred_element_type=jnp.float32)
                if masked:
                    # no second select on p: every row's first sub-tile
                    # holds column 0, which every row sees, so m is a real
                    # maximum before any wholly masked slab and
                    # exp(-1e30 - m) is 0
                    s = jnp.where(valid, s, _NEG_INF)
                m_prev = m_sc[h, qr, :]
                m_next = jnp.maximum(m_prev,
                                     jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_next)
                p = jnp.exp(s - _rep(m_next, nc))
                l_sc[h, qr, :] = (alpha * l_sc[h, qr, :]
                                  + jnp.sum(p, axis=1, keepdims=True))
                m_sc[h, qr, :] = m_next
                if dropout_rate > 0.0:
                    bh = b * n_heads + hpb * h2 + h
                    keep = _dropout_mask(seed_ref, bh, qi, sub,
                                         (g.block_q, g.sub_k), dropout_rate)
                    p = jnp.where(keep[qr, :nc], p / (1.0 - dropout_rate),
                                  0.0)
                pvs.append(jax.lax.dot_general(
                    p.astype(v.dtype), v, _NN,
                    preferred_element_type=jnp.float32))
                alphas.append(alpha)
            acc_sc[qr, :] = (acc_sc[qr, :]
                             * _rep(_head_merge(alphas, d), width)
                             + _head_merge(pvs, d))

    n_int, n_run = _sub_tile_bounds(qi, ki, g, causal, kv_len)
    _walk(n_int, n_run, piece, causal or kv_len < g.kv_pad)

    @pl.when(ki == n_k - 1)
    def _finalize():
        ls = [jnp.maximum(l_sc[h], 1e-30) for h in range(hpb)]
        o_ref[...] = (acc_sc[...] / _rep(_head_merge(ls, d), width)
                      ).astype(o_ref.dtype)
        for h in range(hpb):
            # the one relayout of the statistics: lane-replicated columns
            # to the lse row, once a q tile
            lse_ref[h:h + 1, :] = (m_sc[h] + jnp.log(ls[h])).T[0:1, :]


def _seq_specs(g: _Geometry, h2, hpb, d, causal, order="qk"):
    """BlockSpecs over [B, L, 3HD] and its [B, L, HD] / [B, h2, hpb, L]
    companions for a grid (b, head block, i, j) whose last two dims are
    (q tile, kv tile) — or (kv tile, q tile) with ``order="kq"``. Column
    maps: q block at hpb*h2*d, k at (H + hpb*h2)*d, v at (2H + hpb*h2)*d."""
    width = hpb * d
    if order == "qk":
        qi_of = lambda i, j: i                                   # noqa: E731

        def kj_of(i, j):
            # a causal step wholly above the diagonal names the last K/V
            # tile that was needed, so nothing new is fetched for it
            if not causal or g.block_k == g.kv_pad:
                return j
            return jnp.minimum(j, (i * g.block_q + g.block_q - 1)
                               // g.block_k)
    else:
        # (kv tile i, q tile j): a causal q tile wholly above the kv tile
        # names the first one that is needed instead
        def qi_of(i, j):
            if not causal:
                return j
            return jnp.maximum(j, i * g.block_k // g.block_q)
        kj_of = lambda i, j: i                                   # noqa: E731
    q_rows = pl.BlockSpec((None, g.block_q, width),
                          lambda bb, hh, i, j, *_: (bb, qi_of(i, j), hh))
    k_rows = pl.BlockSpec((None, g.block_k, width),
                          lambda bb, hh, i, j, *_: (bb, kj_of(i, j),
                                                    h2 + hh))
    v_rows = pl.BlockSpec((None, g.block_k, width),
                          lambda bb, hh, i, j, *_: (bb, kj_of(i, j),
                                                    2 * h2 + hh))
    stat_rows = pl.BlockSpec((None, None, hpb, g.block_q),
                             lambda bb, hh, i, j, *_: (bb, hh, 0,
                                                       qi_of(i, j)))
    return q_rows, k_rows, v_rows, stat_rows


def _schedule_span(kernel, L, causal, hpb, d, block_q, max_fused_bwd=None):
    """The schedule of this trace, on the span layer: static per traced
    signature, so one span a trace says how often it engages."""
    return _trace.span("flash_pair/schedule", **pair_schedule(
        L, causal, hpb, d, kernel, block_q, max_fused_bwd))


@functools.partial(jax.jit, static_argnames=("heads", "d", "causal",
                                             "sm_scale", "block_q",
                                             "dropout_rate", "interpret"))
def _pair_fwd(qkv, seed, heads, d, causal, sm_scale, block_q,
              dropout_rate=0.0, interpret=False):
    b, L, width = qkv.shape
    hpb = _heads_per_block(d)
    h2 = heads // hpb
    g = _norm_pair_blocks(L, block_q, causal, hpb, d)
    with _schedule_span("fwd", L, causal, hpb, d, block_q):
        qkvp = _pad_len(qkv, g.kv_pad)
        qs, ks, vs, ls = _seq_specs(g, h2, hpb, d, causal)
        out, lse = pl.pallas_call(
            functools.partial(_pair_fwd_kernel, g=g, sm_scale=sm_scale,
                              causal=causal, d=d, kv_len=L,
                              dropout_rate=dropout_rate, n_heads=heads,
                              hpb=hpb),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(b, h2, g.kv_pad // g.block_q, g.kv_pad // g.block_k),
                in_specs=[qs, ks, vs],
                out_specs=[qs, ls],       # o as q lies, lse [B, h2, hpb, L]
                scratch_shapes=[
                    pltpu.VMEM((hpb, g.block_q, 128), jnp.float32),
                    pltpu.VMEM((hpb, g.block_q, 128), jnp.float32),
                    pltpu.VMEM((g.block_q, hpb * d), jnp.float32)],
            ),
            out_shape=[
                jax.ShapeDtypeStruct((b, g.kv_pad, heads * d), qkv.dtype),
                jax.ShapeDtypeStruct((b, h2, hpb, g.kv_pad), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary")),
            interpret=interpret,
        )(seed, qkvp, qkvp, qkvp)
    return out[:, :L], lse


# ------------------------------------------------------------------ backward


def _bwd_q_side(q_ref, do_ref, lse_ref, delta_ref, *, sm_scale, d, hpb):
    """What a grid step's q tile gives every piece of its walk, per head:
    scaled q and do with the other heads' lanes zeroed (full-lane operands
    that contract one head), the unscaled q likewise (dk's operand:
    sm_scale multiplies the float32 accumulators where they are written
    out), and loaders of the lse / delta rows: a slab loads its own columns
    as they lie."""
    q = q_ref[...].astype(jnp.float32)
    do = do_ref[...].astype(jnp.float32)
    dt = q_ref.dtype
    return [dict(qs=_head_only(q * sm_scale, h, hpb, d).astype(dt),
                 q=_head_only(q, h, hpb, d).astype(dt),
                 do=_head_only(do, h, hpb, d).astype(dt),
                 lse=lambda qr, h=h: lse_ref[h:h + 1, qr],
                 delta=lambda qr, h=h: delta_ref[h:h + 1, qr])
            for h in range(hpb)]


def _bwd_tile_core(seed_ref, k, v, side, qr, valid, bh, qi, sub, *, g,
                   dropout_rate):
    """Recompute p and the shared ds for one (head, q rows ``qr`` of the
    tile, K/V rows of a sub-tile), both TRANSPOSED ([K/V rows, q rows]);
    returns (pT for dv, dsT) in the operand dtype for the caller's dq/dk/dv
    matmuls. Identical math in the fused and split kernels so their
    gradients can never diverge."""
    sT = jax.lax.dot_general(k, side["qs"][qr], _NT,
                             preferred_element_type=jnp.float32)
    if valid is not None:
        sT = jnp.where(valid, sT, _NEG_INF)      # exp(-1e30 - lse) is 0
    pT = jnp.exp(sT - side["lse"](qr))
    dpT = jax.lax.dot_general(v, side["do"][qr], _NT,
                              preferred_element_type=jnp.float32)
    pT_dv = pT
    if dropout_rate > 0.0:
        # the forward drew this piece as [block_q, sub_k]
        keep = _dropout_mask(seed_ref, bh, qi, sub, (g.block_q, g.sub_k),
                             dropout_rate)
        keep_scale = jnp.where(keep, 1.0 / (1.0 - dropout_rate),
                               0.0)[qr, :k.shape[0]].T
        pT_dv = pT * keep_scale
        dpT = dpT * keep_scale
    dsT = pT * (dpT - side["delta"](qr))
    return pT_dv.astype(k.dtype), dsT.astype(k.dtype)


def _bwd_walk(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
              qi, ki, dq_acc, dkv_acc, kv_row0, *, g, sm_scale, causal, d,
              kv_len, dropout_rate, n_heads, hpb):
    """The backward kernels' common walk over the sub-tiles q tile ``qi``
    sees of major K/V tile ``ki``. Each slab's contribution for the hpb
    heads of the block, float32 and before sm_scale, is added to ``dq_acc``
    (dq TRANSPOSED, [hpb*d, block_q]) and to ``dkv_acc`` = (dk, dv)
    accumulators whose row ``kv_row0`` is the major tile's first; a kernel
    that has no use for one passes None and its products are never
    formed."""
    b, h2 = pl.program_id(0), pl.program_id(1)
    per_major = g.block_k // g.sub_k
    sides = _bwd_q_side(q_ref, do_ref, lse_ref, delta_ref,
                        sm_scale=sm_scale, d=d, hpb=hpb)

    def piece(j, masked):
        sub = ki * per_major + j
        start = pl.multiple_of(j * g.sub_k, g.sub_k)
        for slab in _slabs(g, masked):
            r0, nr, nc = slab
            qr = slice(r0, r0 + nr)
            k, v = k_ref[pl.ds(start, nc), :], v_ref[pl.ds(start, nc), :]
            valid = (_sub_mask(qi, sub, slab, g, causal, kv_len, True)
                     if masked else None)
            kT = k.T if dq_acc is not None else None      # [hpb*d, rows]
            dqs, dk, dv = [], None, None
            for h in range(hpb):
                pT, dsT = _bwd_tile_core(
                    seed_ref, k, v, sides[h], qr, valid,
                    b * n_heads + hpb * h2 + h, qi, sub, g=g,
                    dropout_rate=dropout_rate)
                if dkv_acc is not None:
                    # the zeroed lanes of do / q leave the other heads'
                    # columns 0: the heads' products add up to the block's
                    dv_h = jax.lax.dot_general(
                        pT, sides[h]["do"][qr], _NN,
                        preferred_element_type=jnp.float32)
                    dk_h = jax.lax.dot_general(
                        dsT, sides[h]["q"][qr], _NN,
                        preferred_element_type=jnp.float32)
                    dv = dv_h if dv is None else dv + dv_h
                    dk = dk_h if dk is None else dk + dk_h
                if dq_acc is not None:
                    # dq TRANSPOSED: head h's d rows of k^T against dsT. The
                    # score piece is never transposed (k's [rows, 128] is),
                    # and the heads' rows stack with no select
                    dqs.append(jax.lax.dot_general(
                        kT[h * d:(h + 1) * d, :], dsT, _NN,
                        preferred_element_type=jnp.float32))
            if dq_acc is not None:
                dq_acc[:, qr] += jnp.concatenate(dqs, axis=0)
            if dkv_acc is not None:
                rows = pl.ds(pl.multiple_of(kv_row0 + start, g.sub_k), nc)
                dkv_acc[0][rows, :] += dk
                dkv_acc[1][rows, :] += dv

    n_int, n_run = _sub_tile_bounds(qi, ki, g, causal, kv_len)
    _walk(n_int, n_run, piece, causal or kv_len < g.kv_pad)


def _pair_bwd_fused_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                           delta_ref, dq_ref, dk_ref, dv_ref,
                           dq_acc, dk_acc, dv_acc, *, g, sm_scale, **kw):
    # grid (b, h2, q_blocks, kv_blocks), both inner dims sequential. s/p
    # computed ONCE per (pair, q tile, sub-tile) for dq, dk AND dv: dq
    # accumulates across sub-tiles in a small scratch, dk/dv accumulate
    # across BOTH dims in full-length scratch (what bounds kv_pad <= 4 k).
    qi, ki = pl.program_id(2), pl.program_id(3)
    n_q, n_k = g.kv_pad // g.block_q, g.kv_pad // g.block_k

    @pl.when(jnp.logical_and(qi == 0, ki == 0))
    def _init_kv():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(ki == 0)
    def _init_q():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    _bwd_walk(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
              qi, ki, dq_acc, (dk_acc, dv_acc), ki * g.block_k, g=g,
              sm_scale=sm_scale, **kw)

    @pl.when(ki == n_k - 1)
    def _write_dq():
        dq_ref[...] = (dq_acc[...].T * sm_scale).astype(dq_ref.dtype)

    @pl.when(jnp.logical_and(qi == n_q - 1, ki == n_k - 1))
    def _finalize():
        dk_ref[...] = (dk_acc[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _pair_bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                        delta_ref, dq_ref, dq_acc, *, g, sm_scale, **kw):
    # split form, kernel 1: grid (b, h2, q_blocks, kv_blocks), kv streamed —
    # only tile-sized scratch, so any sequence length fits
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    _bwd_walk(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
              qi, ki, dq_acc, None, 0, g=g, sm_scale=sm_scale, **kw)

    @pl.when(ki == g.kv_pad // g.block_k - 1)
    def _write():
        dq_ref[...] = (dq_acc[...].T * sm_scale).astype(dq_ref.dtype)


def _pair_bwd_dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                         delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, g,
                         sm_scale, **kw):
    # split form, kernel 2: grid (b, h2, kv_blocks, q_blocks), q streamed
    ki, qi = pl.program_id(2), pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    _bwd_walk(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
              qi, ki, None, (dk_acc, dv_acc), 0, g=g, sm_scale=sm_scale,
              **kw)

    @pl.when(qi == g.kv_pad // g.block_q - 1)
    def _write():
        dk_ref[...] = (dk_acc[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("heads", "d", "causal",
                                             "sm_scale", "block_q",
                                             "dropout_rate", "interpret",
                                             "max_fused_bwd"))
def _pair_bwd(qkv, o, lse, g, seed, heads, d, causal, sm_scale, block_q,
              dropout_rate=0.0, interpret=False, max_fused_bwd=None):
    b, L, width = qkv.shape
    hpb = _heads_per_block(d)
    h2 = heads // hpb
    geo = _norm_pair_blocks(L, block_q, causal, hpb, d)
    kv_pad = geo.kv_pad
    n_q, n_k = kv_pad // geo.block_q, kv_pad // geo.block_k
    with _schedule_span("bwd", L, causal, hpb, d, block_q,
                        max_fused_bwd) as span:
        qkvp = _pad_len(qkv, kv_pad)
        gp = _pad_len(g, kv_pad)
        delta = jnp.sum((g.astype(jnp.float32) * o.astype(jnp.float32))
                        .reshape(b, L, heads, d), axis=-1)       # [B, L, H]
        delta = jnp.transpose(delta, (0, 2, 1)).reshape(b, h2, hpb, L)
        delta = _pad_len(delta, kv_pad, axis=3)
        lsep = _pad_len(lse, kv_pad, axis=3)

        qs, ks, vs, ls = _seq_specs(geo, h2, hpb, d, causal)
        common = dict(g=geo, sm_scale=sm_scale, causal=causal, d=d, kv_len=L,
                      dropout_rate=dropout_rate, n_heads=heads, hpb=hpb)
        out = jax.ShapeDtypeStruct((b, kv_pad, heads * d), qkv.dtype)
        args = (seed, qkvp, qkvp, qkvp, gp, lsep, delta)
        dq_shape = (hpb * d, geo.block_q)          # dq^T, see _bwd_walk

        if span.attrs["form"] == "fused":
            # FUSED: s/p once per piece for all three grads
            gpart = pl.BlockSpec((None, kv_pad, hpb * d),
                                 lambda bb, hh, i, j, *_: (bb, 0, hh))
            dq, dk, dv = pl.pallas_call(
                functools.partial(_pair_bwd_fused_kernel, **common),
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=1,
                    grid=(b, h2, n_q, n_k),
                    in_specs=[qs, ks, vs, qs, ls, ls],
                    out_specs=[qs, gpart, gpart],
                    scratch_shapes=[
                        pltpu.VMEM(dq_shape, jnp.float32),
                        pltpu.VMEM((kv_pad, hpb * d), jnp.float32),
                        pltpu.VMEM((kv_pad, hpb * d), jnp.float32)],
                ),
                out_shape=[out, out, out],
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("parallel", "parallel", "arbitrary",
                                         "arbitrary")),
                interpret=interpret,
            )(*args)
        else:
            # SPLIT: tile-sized scratch only — any length; s/p recomputed
            # per kernel (the same trade the flat long-context kernels make)
            dq, = pl.pallas_call(
                functools.partial(_pair_bwd_dq_kernel, **common),
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=1,
                    grid=(b, h2, n_q, n_k),
                    in_specs=[qs, ks, vs, qs, ls, ls],
                    out_specs=[qs],
                    scratch_shapes=[pltpu.VMEM(dq_shape, jnp.float32)],
                ),
                out_shape=[out],
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("parallel", "parallel", "parallel",
                                         "arbitrary")),
                interpret=interpret,
            )(*args)
            qs2, ks2, vs2, ls2 = _seq_specs(geo, h2, hpb, d, causal, "kq")
            kv_tile = pl.BlockSpec((None, geo.block_k, hpb * d),
                                   lambda bb, hh, j, i, *_: (bb, j, hh))
            dk, dv = pl.pallas_call(
                functools.partial(_pair_bwd_dkv_kernel, **common),
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=1,
                    grid=(b, h2, n_k, n_q),
                    in_specs=[qs2, ks2, vs2, qs2, ls2, ls2],
                    out_specs=[kv_tile, kv_tile],
                    scratch_shapes=[
                        pltpu.VMEM((geo.block_k, hpb * d), jnp.float32),
                        pltpu.VMEM((geo.block_k, hpb * d), jnp.float32)],
                ),
                out_shape=[out, out],
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("parallel", "parallel", "parallel",
                                         "arbitrary")),
                interpret=interpret,
            )(*args)
    # d(qkv) column order [q | k | v]; the concat feeds qkv_proj's backward
    # matmul and fuses there
    return jnp.concatenate([dq[:, :L], dk[:, :L], dv[:, :L]], axis=-1)


# ------------------------------------------------------------------ custom_vjp


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7, 8, 9))
def flash_pair(qkv, seed, heads, d, causal, sm_scale, block_q, dropout_rate,
               interpret, max_fused_bwd=None):
    out, _ = _pair_fwd(qkv, seed, heads, d, causal, sm_scale, block_q,
                       dropout_rate, interpret)
    return out


def _pair_vjp_fwd(qkv, seed, heads, d, causal, sm_scale, block_q,
                  dropout_rate, interpret, max_fused_bwd=None):
    out, lse = _pair_fwd(qkv, seed, heads, d, causal, sm_scale, block_q,
                         dropout_rate, interpret)
    return out, (qkv, out, lse, seed)


def _pair_vjp_bwd(heads, d, causal, sm_scale, block_q, dropout_rate,
                  interpret, max_fused_bwd, res, g):
    qkv, out, lse, seed = res
    dqkv = _pair_bwd(qkv, out, lse, g, seed, heads, d, causal, sm_scale,
                     block_q, dropout_rate, interpret,
                     max_fused_bwd=max_fused_bwd)
    return dqkv, None


flash_pair.defvjp(_pair_vjp_fwd, _pair_vjp_bwd)


def flash_pair_packed(qkv, num_heads, causal, dropout_rate=0.0, seed=0,
                      block_q=512, interpret=False, max_fused_bwd=None):
    """Keyword front door for the pair path: derives head_dim/scale/seed form
    so call sites don't hand-assemble the positional custom_vjp call.
    ``max_fused_bwd`` overrides the fused-backward kv_pad cutoff (see
    _max_fused_bwd; env PADDLE_FLASH_FUSED_BWD_MAX works everywhere)."""
    d = qkv.shape[-1] // (3 * num_heads)
    if not pair_layout_supported(d, num_heads, qkv.shape[1]):
        # fail fast: a truncating heads // hpb would leave trailing heads'
        # output columns unwritten (silent NaN/garbage)
        raise ValueError(
            f"flash_pair: unsupported shape (head_dim={d}, "
            f"num_heads={num_heads}); requires "
            f"num_heads % max(1, 128 // head_dim) == 0 and hpb*d % 128 == 0 "
            f"— use flash_attention_blhd/packed instead")
    if max_fused_bwd is None:
        # resolve the env HERE, outside any jit: max_fused_bwd is a static
        # argname of the jitted _pair_bwd, so an env read at trace time
        # would be frozen into the cached executable — resolving at the
        # front door makes a changed env a new static value (fresh trace)
        env = os.environ.get("PADDLE_FLASH_FUSED_BWD_MAX")
        if env:
            max_fused_bwd = int(env)
    seed_arr = jnp.atleast_1d(jnp.asarray(seed, jnp.int32))
    return flash_pair(qkv, seed_arr, int(num_heads), int(d), bool(causal),
                      1.0 / math.sqrt(d), int(block_q), float(dropout_rate),
                      bool(interpret),
                      None if max_fused_bwd is None else int(max_fused_bwd))
