"""Head-PAIR flash attention over the packed qkv layout, for head_dim 64.

Why this exists: at head_dim 64 (GPT-medium, BERT-base, most 64-dim-head
models) the flat [B*H, L, D] kernels read half-empty 128-lane tiles AND the
[B,L,H,D] <-> [B*H,L,D] relayout around them is pure HBM transposes (their
cost on the chip: not measured on the current stack). This path instead
reads 128-wide column blocks straight out of the fused projection output
[B, L, 3*H*D] — TWO adjacent 64-wide heads per block — and writes the
context back pre-packed [B, L, H*D]. Zero layout copies, full lanes.

Shape contract: head-BLOCKS of hpb = max(1, 128 // head_dim) adjacent heads
fill the 128-lane quantum (hpb*d % 128 == 0; hpb=2 at d=64, hpb=1 at d=128)
and num_heads % hpb == 0. Any sequence length: the forward streams KV tiles
with online-softmax carries (m/l/acc scratch across the kv grid dim), and
the backward picks between two forms by VMEM budget:

  - FUSED (kv_pad <= 4096): one kernel, s/p computed once per tile for dq,
    dk AND dv; dk/dv accumulate in full-length VMEM scratch across both
    grid dims (the scratch is what bounds the length).
  - SPLIT (longer): the classic two-kernel flash backward — a dq kernel
    (q-parallel, kv streamed) and a dkv kernel (kv-parallel, q streamed),
    each with only tile-sized scratch, so any length fits; s/p recomputed
    per kernel.

Both write d(qkv) parts directly in the packed layout — zero relayouts at
every length.

Reference analog: phi/kernels/fusion/fused_attention — the reference fuses
qkv-projection-adjacent attention exactly to avoid these relayouts.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import (_NEG_INF, _dropout_mask, _pad_len, _round_up,
                              _valid_mask)


def _heads_per_block(head_dim: int) -> int:
    """How many adjacent heads fill the 128-lane quantum (2 at d=64, 1 at
    d>=128-multiples)."""
    return max(1, 128 // head_dim)


# longest kv_pad the FUSED backward's full-length dk/dv scratch fits in VMEM
# (2 x kv_pad x (hpb*d) lanes x 4 B = 4 MB at kv_pad=4096, hpb*d=128, which
# fits with the reduced 256/512 tiles — see _pair_bwd; the split form takes
# over beyond). The budget was sized at hpb*d == 128 lanes: head_dim=256
# passes pair_layout_supported (256 % 128 == 0) with hpb*d == 256, doubling
# the scratch — so the cutoff scales down by the same lane factor instead of
# blowing past VMEM at kv_pad=4096 (ADVICE r5).
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


# ------------------------------------------------------------------ forward


def _pair_fwd_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                     m_sc, l_sc, acc_sc, *,
                     sm_scale, causal, d, kv_len, block_q, block_k, n_k,
                     dropout_rate, n_heads, hpb):
    # grid (b, head_block, q_blocks, kv_blocks); kv innermost/sequential —
    # m/l/acc carry the online softmax across kv tiles in scratch. Refs hold
    # hpb heads side by side [*, hpb*d].
    b, h2 = pl.program_id(0), pl.program_id(1)
    qi, ki = pl.program_id(2), pl.program_id(3)

    if n_k == 1:
        # single-KV-tile fast path (the pre-round-5 kernel): softmax in
        # registers, no online-softmax scratch round trips — this is the
        # production config for L <= 1024 (GPT-medium bench, BERT-512)
        for which in range(hpb):
            sl = slice(which * d, (which + 1) * d)
            qs = (q_ref[:, sl].astype(jnp.float32)
                  * sm_scale).astype(q_ref.dtype)
            s = jax.lax.dot_general(qs, k_ref[:, sl],
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            valid = None
            if causal or kv_len < block_k:
                valid = _valid_mask(qi, 0, causal=causal, block_q=block_q,
                                    block_k=block_k, kv_len=kv_len,
                                    causal_offset=0)
                s = jnp.where(valid, s, _NEG_INF)
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp(s - m)
            if valid is not None:
                p = jnp.where(valid, p, 0.0)
            l = jnp.sum(p, axis=-1, keepdims=True)
            if dropout_rate > 0.0:
                bh = b * n_heads + hpb * h2 + which
                keep = _dropout_mask(seed_ref, bh, qi, jnp.int32(0),
                                     (block_q, block_k), dropout_rate)
                p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
            o = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[:, sl],
                                    (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            o_ref[:, sl] = (o / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
            lse_ref[which, :] = (m[:, 0]
                                 + jnp.log(jnp.maximum(l[:, 0], 1e-30)))
        return

    @pl.when(ki == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    # causal: tiles fully above the diagonal contribute nothing
    def _body():
        for which in range(hpb):
            sl = slice(which * d, (which + 1) * d)
            qs = (q_ref[:, sl].astype(jnp.float32)
                  * sm_scale).astype(q_ref.dtype)
            s = jax.lax.dot_general(qs, k_ref[:, sl],
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            valid = None
            if causal or kv_len < n_k * block_k:
                valid = _valid_mask(qi, ki, causal=causal, block_q=block_q,
                                    block_k=block_k, kv_len=kv_len,
                                    causal_offset=0)
                s = jnp.where(valid, s, _NEG_INF)
            m_prev = m_sc[which, :]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            corr = jnp.exp(m_prev - m_cur)
            p = jnp.exp(s - m_cur[:, None])
            if valid is not None:
                p = jnp.where(valid, p, 0.0)
            l_sc[which, :] = l_sc[which, :] * corr + jnp.sum(p, axis=-1)
            m_sc[which, :] = m_cur
            if dropout_rate > 0.0:
                bh = b * n_heads + hpb * h2 + which
                keep = _dropout_mask(seed_ref, bh, qi, ki,
                                     (block_q, block_k), dropout_rate)
                p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
            pv = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[:, sl],
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            acc_sc[:, sl] = acc_sc[:, sl] * corr[:, None] + pv

    if causal:
        # tiles fully above the diagonal contribute nothing — skip them
        pl.when(ki * block_k <= qi * block_q + block_q - 1)(_body)
    else:
        _body()

    @pl.when(ki == n_k - 1)
    def _finalize():
        for which in range(hpb):
            sl = slice(which * d, (which + 1) * d)
            l = jnp.maximum(l_sc[which, :], 1e-30)
            o_ref[:, sl] = (acc_sc[:, sl] / l[:, None]).astype(o_ref.dtype)
            lse_ref[which, :] = m_sc[which, :] + jnp.log(l)


def _norm_pair_blocks(L, block_q, block_k):
    kv_pad = _round_up(L, 128)
    if kv_pad > 2048:
        # ONE tile geometry shared by forward and backward at every length:
        # the dropout PRNG seeds per (q-tile, kv-tile), so fwd/bwd tile
        # shapes must match or the keep masks desynchronize. The 256/512
        # tiles are what lets the fused backward's full-length scratch fit
        # VMEM at 4096 (512/1024 measured 16.52 MB vs the 16 MB budget).
        block_q = min(block_q, 256)
        block_k = min(block_k, 512)
    block_q = min(block_q, kv_pad)
    while kv_pad % block_q:      # q blocks must tile the padded row count
        block_q //= 2
    block_k = min(block_k, kv_pad)
    while kv_pad % block_k:
        block_k //= 2
    return kv_pad, block_q, block_k


@functools.partial(jax.jit, static_argnames=("heads", "d", "causal",
                                             "sm_scale", "block_q",
                                             "dropout_rate", "interpret"))
def _pair_fwd(qkv, seed, heads, d, causal, sm_scale, block_q,
              dropout_rate=0.0, interpret=False):
    b, L, width = qkv.shape
    hpb = _heads_per_block(d)
    h2 = heads // hpb
    kv_pad, block_q, block_k = _norm_pair_blocks(L, block_q, 1024)
    q_pad = kv_pad
    n_k = kv_pad // block_k
    qkvp = _pad_len(qkv, kv_pad)
    grid = (b, h2, q_pad // block_q, n_k)
    # column maps into [B, L, 3HD]: q block at hpb*h2*d, k at (H + hpb*h2)*d
    qs = pl.BlockSpec((None, block_q, hpb * d),
                      lambda bb, hh, i, j, *_: (bb, i, hh))
    ks = pl.BlockSpec((None, block_k, hpb * d),
                      lambda bb, hh, i, j, *_: (bb, j, h2 + hh))
    vs = pl.BlockSpec((None, block_k, hpb * d),
                      lambda bb, hh, i, j, *_: (bb, j, 2 * h2 + hh))
    out, lse = pl.pallas_call(
        functools.partial(_pair_fwd_kernel, sm_scale=sm_scale, causal=causal,
                          d=d, kv_len=L, block_q=block_q, block_k=block_k,
                          n_k=n_k, dropout_rate=dropout_rate, n_heads=heads,
                          hpb=hpb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[qs, ks, vs],
            out_specs=[
                pl.BlockSpec((None, block_q, hpb * d),
                             lambda bb, hh, i, j, *_: (bb, i, hh)),
                pl.BlockSpec((None, None, hpb, block_q),
                             lambda bb, hh, i, j, *_: (bb, hh, 0, i)),
            ],
            scratch_shapes=[pltpu.VMEM((hpb, block_q), jnp.float32),
                            pltpu.VMEM((hpb, block_q), jnp.float32),
                            pltpu.VMEM((block_q, hpb * d), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, kv_pad, heads * d), qkv.dtype),
            jax.ShapeDtypeStruct((b, h2, hpb, q_pad), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(seed, qkvp, qkvp, qkvp)
    return out[:, :L], lse


# ------------------------------------------------------------------ backward


def _bwd_tile_core(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   which, qi, ki, *, sm_scale, causal, d, kv_len, block_q,
                   block_k, dropout_rate, n_heads, hpb, b, h2):
    """Recompute p and the shared ds for one (head, q-tile, kv-tile); returns
    (p_dv, do, dsc) for the caller's dq/dk/dv matmuls. Identical math in the
    fused and split kernels so their gradients can never diverge."""
    sl = slice(which * d, (which + 1) * d)
    qs = (q_ref[:, sl].astype(jnp.float32) * sm_scale).astype(q_ref.dtype)
    s = jax.lax.dot_general(qs, k_ref[:, sl], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    lse = lse_ref[which, :][:, None]
    p = jnp.exp(s - lse)
    valid = _valid_mask(qi, ki, causal=causal, block_q=block_q,
                        block_k=block_k, kv_len=kv_len, causal_offset=0)
    p = jnp.where(valid, p, 0.0)
    keep_scale = None
    if dropout_rate > 0.0:
        bh = b * n_heads + hpb * h2 + which
        keep = _dropout_mask(seed_ref, bh, qi, ki, (block_q, block_k),
                             dropout_rate)
        keep_scale = jnp.where(keep, 1.0 / (1.0 - dropout_rate), 0.0)
    do = do_ref[:, sl]
    p_dv = p * keep_scale if keep_scale is not None else p
    dp = jax.lax.dot_general(do, v_ref[:, sl], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if keep_scale is not None:
        dp = dp * keep_scale
    ds = p * (dp - delta_ref[which, :][:, None])
    return sl, p_dv, do, ds.astype(q_ref.dtype)


def _pair_bwd_fused_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                           delta_ref, dq_ref, dk_ref, dv_ref,
                           dq_acc, dk_acc, dv_acc, *,
                           sm_scale, causal, d, kv_len, block_q, block_k,
                           dropout_rate, n_heads, n_q, n_k, hpb):
    # grid (b, h2, q_blocks, kv_blocks), both inner dims sequential. s/p
    # computed ONCE per (pair, q-tile, kv-tile) for dq, dk AND dv: dq
    # accumulates across kv tiles in a small scratch, dk/dv accumulate
    # across BOTH dims in full-length scratch (what bounds kv_pad <= 4 k).
    b, h2 = pl.program_id(0), pl.program_id(1)
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(jnp.logical_and(qi == 0, ki == 0))
    def _init_kv():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    if n_k > 1:
        @pl.when(ki == 0)
        def _init_q():
            dq_acc[:] = jnp.zeros_like(dq_acc)

    def _body():
        for which in range(hpb):
            sl, p_dv, do, dsc = _bwd_tile_core(
                seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                which, qi, ki, sm_scale=sm_scale, causal=causal, d=d,
                kv_len=kv_len, block_q=block_q, block_k=block_k,
                dropout_rate=dropout_rate, n_heads=n_heads, hpb=hpb,
                b=b, h2=h2)
            dq = jax.lax.dot_general(
                dsc, k_ref[:, sl], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            if n_k == 1:
                # single KV tile: dq complete in this step — write direct,
                # no accumulator round trip (the pre-round-5 form)
                dq_ref[pl.ds(qi * block_q, block_q), sl] = \
                    dq.astype(dq_ref.dtype)
            else:
                dq_acc[:, sl] += dq
            rows = pl.ds(ki * block_k, block_k)
            dv_acc[rows, sl] += jax.lax.dot_general(
                p_dv.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk_acc[rows, sl] += jax.lax.dot_general(
                dsc, q_ref[:, sl], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale

    if causal:
        pl.when(ki * block_k <= qi * block_q + block_q - 1)(_body)
    else:
        _body()

    if n_k > 1:
        @pl.when(ki == n_k - 1)
        def _write_dq():
            dq_ref[pl.ds(qi * block_q, block_q), :] = \
                dq_acc[:].astype(dq_ref.dtype)

    @pl.when(jnp.logical_and(qi == n_q - 1, ki == n_k - 1))
    def _finalize():
        dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


def _pair_bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                        delta_ref, dq_ref, dq_acc, *,
                        sm_scale, causal, d, kv_len, block_q, block_k,
                        dropout_rate, n_heads, n_k, hpb):
    # split form, kernel 1: grid (b, h2, q_blocks, kv_blocks), kv streamed —
    # only tile-sized scratch, so any sequence length fits
    b, h2 = pl.program_id(0), pl.program_id(1)
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _body():
        for which in range(hpb):
            sl, _p_dv, _do, dsc = _bwd_tile_core(
                seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                which, qi, ki, sm_scale=sm_scale, causal=causal, d=d,
                kv_len=kv_len, block_q=block_q, block_k=block_k,
                dropout_rate=dropout_rate, n_heads=n_heads, hpb=hpb,
                b=b, h2=h2)
            dq_acc[:, sl] += jax.lax.dot_general(
                dsc, k_ref[:, sl], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale

    if causal:
        pl.when(ki * block_k <= qi * block_q + block_q - 1)(_body)
    else:
        _body()

    @pl.when(ki == n_k - 1)
    def _write():
        dq_ref[:] = dq_acc[:].astype(dq_ref.dtype)


def _pair_bwd_dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                         delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                         sm_scale, causal, d, kv_len, block_q, block_k,
                         dropout_rate, n_heads, n_q, hpb):
    # split form, kernel 2: grid (b, h2, kv_blocks, q_blocks), q streamed
    b, h2 = pl.program_id(0), pl.program_id(1)
    ki, qi = pl.program_id(2), pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _body():
        for which in range(hpb):
            sl, p_dv, do, dsc = _bwd_tile_core(
                seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                which, qi, ki, sm_scale=sm_scale, causal=causal, d=d,
                kv_len=kv_len, block_q=block_q, block_k=block_k,
                dropout_rate=dropout_rate, n_heads=n_heads, hpb=hpb,
                b=b, h2=h2)
            dv_acc[:, sl] += jax.lax.dot_general(
                p_dv.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk_acc[:, sl] += jax.lax.dot_general(
                dsc, q_ref[:, sl], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale

    if causal:
        pl.when(ki * block_k <= qi * block_q + block_q - 1)(_body)
    else:
        _body()

    @pl.when(qi == n_q - 1)
    def _write():
        dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("heads", "d", "causal",
                                             "sm_scale", "block_q",
                                             "dropout_rate", "interpret",
                                             "max_fused_bwd"))
def _pair_bwd(qkv, o, lse, g, seed, heads, d, causal, sm_scale, block_q,
              dropout_rate=0.0, interpret=False, max_fused_bwd=None):
    b, L, width = qkv.shape
    hpb = _heads_per_block(d)
    h2 = heads // hpb
    kv_pad, block_q, block_k = _norm_pair_blocks(L, block_q, 1024)
    q_pad = kv_pad
    n_q, n_k = q_pad // block_q, kv_pad // block_k
    qkvp = _pad_len(qkv, kv_pad)
    gp = _pad_len(g, kv_pad)
    delta = jnp.sum((g.astype(jnp.float32) * o.astype(jnp.float32))
                    .reshape(b, L, heads, d), axis=-1)       # [B, L, H]
    delta = jnp.transpose(delta, (0, 2, 1)).reshape(b, h2, hpb, L)
    delta = _pad_len(delta, q_pad, axis=3)
    lsep = _pad_len(lse, q_pad, axis=3)

    qs = pl.BlockSpec((None, block_q, hpb * d),
                      lambda bb, hh, i, j, *_: (bb, i, hh))
    ks = pl.BlockSpec((None, block_k, hpb * d),
                      lambda bb, hh, i, j, *_: (bb, j, h2 + hh))
    vs = pl.BlockSpec((None, block_k, hpb * d),
                      lambda bb, hh, i, j, *_: (bb, j, 2 * h2 + hh))
    gs = pl.BlockSpec((None, block_q, hpb * d),
                      lambda bb, hh, i, j, *_: (bb, i, hh))
    ls = pl.BlockSpec((None, None, hpb, block_q),
                      lambda bb, hh, i, j, *_: (bb, hh, 0, i))
    common = dict(sm_scale=sm_scale, causal=causal, d=d, kv_len=L,
                  block_q=block_q, block_k=block_k,
                  dropout_rate=dropout_rate, n_heads=heads, hpb=hpb)

    if kv_pad <= _max_fused_bwd(hpb, d, max_fused_bwd):
        # FUSED: s/p once per tile for all three grads
        gpart = pl.BlockSpec((None, kv_pad, hpb * d),
                             lambda bb, hh, i, j, *_: (bb, 0, hh))
        dq, dk, dv = pl.pallas_call(
            functools.partial(_pair_bwd_fused_kernel, n_q=n_q, n_k=n_k,
                              **common),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(b, h2, n_q, n_k),
                in_specs=[qs, ks, vs, gs, ls, ls],
                out_specs=[gpart, gpart, gpart],
                scratch_shapes=[
                    pltpu.VMEM((block_q, hpb * d), jnp.float32),
                    pltpu.VMEM((kv_pad, hpb * d), jnp.float32),
                    pltpu.VMEM((kv_pad, hpb * d), jnp.float32)],
            ),
            out_shape=[jax.ShapeDtypeStruct((b, kv_pad, heads * d),
                                            qkv.dtype) for _ in range(3)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary",
                                     "arbitrary")),
            interpret=interpret,
        )(seed, qkvp, qkvp, qkvp, gp, lsep, delta)
    else:
        # SPLIT: tile-sized scratch only — any length; s/p recomputed per
        # kernel (the same trade the flat long-context kernels make)
        dq, = pl.pallas_call(
            functools.partial(_pair_bwd_dq_kernel, n_k=n_k, **common),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(b, h2, n_q, n_k),
                in_specs=[qs, ks, vs, gs, ls, ls],
                out_specs=[pl.BlockSpec((None, block_q, hpb * d),
                                        lambda bb, hh, i, j, *_: (bb, i, hh))],
                scratch_shapes=[pltpu.VMEM((block_q, hpb * d), jnp.float32)],
            ),
            out_shape=[jax.ShapeDtypeStruct((b, kv_pad, heads * d),
                                            qkv.dtype)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary")),
            interpret=interpret,
        )(seed, qkvp, qkvp, qkvp, gp, lsep, delta)
        qs2 = pl.BlockSpec((None, block_q, hpb * d),
                           lambda bb, hh, j, i, *_: (bb, i, hh))
        ks2 = pl.BlockSpec((None, block_k, hpb * d),
                           lambda bb, hh, j, i, *_: (bb, j, h2 + hh))
        vs2 = pl.BlockSpec((None, block_k, hpb * d),
                           lambda bb, hh, j, i, *_: (bb, j, 2 * h2 + hh))
        gs2 = pl.BlockSpec((None, block_q, hpb * d),
                           lambda bb, hh, j, i, *_: (bb, i, hh))
        ls2 = pl.BlockSpec((None, None, hpb, block_q),
                           lambda bb, hh, j, i, *_: (bb, hh, 0, i))
        dkv_spec = pl.BlockSpec((None, block_k, hpb * d),
                                lambda bb, hh, j, i, *_: (bb, j, hh))
        dk, dv = pl.pallas_call(
            functools.partial(_pair_bwd_dkv_kernel, n_q=n_q, **common),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(b, h2, n_k, n_q),
                in_specs=[qs2, ks2, vs2, gs2, ls2, ls2],
                out_specs=[dkv_spec, dkv_spec],
                scratch_shapes=[
                    pltpu.VMEM((block_k, hpb * d), jnp.float32),
                    pltpu.VMEM((block_k, hpb * d), jnp.float32)],
            ),
            out_shape=[jax.ShapeDtypeStruct((b, kv_pad, heads * d),
                                            qkv.dtype) for _ in range(2)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary")),
            interpret=interpret,
        )(seed, qkvp, qkvp, qkvp, gp, lsep, delta)
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
