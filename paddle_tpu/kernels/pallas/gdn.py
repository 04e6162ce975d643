"""Gated DeltaNet (Yang et al. 2024, "Gated Delta Networks"): the delta rule
with a per-head scalar decay, in the three forms a server needs.

Per position and value head, with a float32 state ``S [dk, dv]``::

    S <- exp(g) * S
    S <- S + k (x) beta * (v - S^T k)
    o  = S^T q

``q`` and ``k`` arrive L2-normalised (``q`` scaled too) and already repeated
to one per value head; ``g <= 0`` and ``beta`` in (0, 1) are per head. A
position with ``g = 0`` and ``beta = 0`` leaves the state as it found it:
that is how callers switch off the padded tail of a prefill chunk.

* ``gdn_recurrent``: the rule as written, a ``lax.scan`` over positions. The
  CPU path of the decode step and the oracle of the tests.
* ``gdn_chunked``: the same result for a run of positions with its products
  on the MXU (the WY form of the delta rule over chunks of 64 positions:
  inside a chunk the rank-one updates collapse to one unit-lower-triangular
  solve, across chunks the state is carried). Plain ``jax.numpy``; what a
  prefill chunk runs, on every backend.
* ``gdn_decode_step``: one position for every slot of a decode batch. On a
  TPU a Pallas kernel (``gdn_decode``: each slot's state is read once and
  written once, in place; the update is VPU work on [128, 128] tiles, the
  contractions over ``dk`` are sublane reductions); elsewhere the scan above.
  A slot that is not live gets its state back bit for bit.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .util import note_state_kernel, tpu_placement

CHUNK = 64                    # positions per chunk of the chunked form
HEADS_PER_STEP = 8            # value heads one grid step of the kernel takes

_FORCE = {"interpret": False}


@contextlib.contextmanager
def force_interpret(on: bool = True):
    """Test seam: the decode step takes the Pallas kernel off the TPU too,
    through the interpreter (as ``paged_decode.force_interpret``)."""
    prev, _FORCE["interpret"] = _FORCE["interpret"], bool(on)
    try:
        yield
    finally:
        _FORCE["interpret"] = prev


def kernel_mode(state):
    """``"mosaic"`` on a TPU whose tiling the state fits, ``"interpret"``
    inside ``force_interpret``, None for the ``jax.numpy`` step."""
    if _FORCE["interpret"]:
        return "interpret"
    if not tpu_placement(state):
        return None
    _, h, dk, dv = state.shape
    if dk != 128 or dv != 128 or h % HEADS_PER_STEP \
            or state.dtype != jnp.float32:
        return None
    return "mosaic"


# ------------------------------------------------------------ the rule

def gdn_recurrent(q, k, v, g, beta, state):
    """``q k [B, S, H, dk]``, ``v [B, S, H, dv]``, ``g beta [B, S, H]``, all
    float32; ``state [B, H, dk, dv]`` float32. Returns (o [B, S, H, dv],
    the state after the last position)."""
    def step(s, x):
        qt, kt, vt, gt, bt = x
        s = s * jnp.exp(gt)[..., None, None]
        kv = jnp.sum(s * kt[..., None], axis=-2)
        s = s + kt[..., None] * ((vt - kv) * bt[..., None])[..., None, :]
        return s, jnp.sum(s * qt[..., None], axis=-2)

    xs = tuple(jnp.moveaxis(a.astype(jnp.float32), 1, 0)
               for a in (q, k, v, g, beta))
    state, o = jax.lax.scan(step, state.astype(jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1), state


def gdn_chunked(q, k, v, g, beta, state, chunk: int = CHUNK):
    """The same as ``gdn_recurrent`` with the products as matrix products
    over chunks of ``chunk`` positions (``S`` is padded to a whole number
    of chunks with switched-off positions)."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    pad = -s % chunk
    if pad:
        q, k, v, g, beta = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] *
                                    (a.ndim - 2))
                            for a in (q, k, v, g, beta))
    n = (s + pad) // chunk
    f32 = jnp.float32

    def chunks(a):                      # [B, S, H, ...] -> [N, B, H, C, ...]
        a = a.astype(f32).reshape((b, n, chunk) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    qc, kc, vc = chunks(q), chunks(k), chunks(v)
    gc = jnp.cumsum(chunks(g), axis=-1)            # decay since chunk start
    bc = chunks(beta)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    eye = jnp.eye(chunk, dtype=f32)

    def mm(spec, x, y):
        return jnp.einsum(spec, x, y, precision="highest",
                          preferred_element_type=f32)

    def body(st, x):
        qi, ki, vi, gi, bi = x          # [B,H,C,d], gi bi [B,H,C]
        # decay from position j (exclusive) to position i, for j <= i
        diff = gi[..., :, None] - gi[..., None, :]
        decay = jnp.exp(jnp.where(causal, diff, 0.0)) * causal
        kb = ki * bi[..., None]
        a = jnp.where(lower, mm("bhid,bhjd->bhij", kb, ki) * decay, 0.0)
        # (I + A)^-1 applied to [beta v | beta k exp(g)] in one solve
        rhs = jnp.concatenate([vi * bi[..., None],
                               kb * jnp.exp(gi)[..., None]], axis=-1)
        sol = jax.scipy.linalg.solve_triangular(
            eye + a, rhs, lower=True, unit_diagonal=True)
        u, w = sol[..., :dv], sol[..., dv:]
        v_new = u - mm("bhck,bhkv->bhcv", w, st)
        inter = mm("bhck,bhkv->bhcv", qi * jnp.exp(gi)[..., None], st)
        attn = jnp.where(causal, mm("bhid,bhjd->bhij", qi, ki) * decay, 0.0)
        o = inter + mm("bhij,bhjv->bhiv", attn, v_new)
        last = gi[..., -1]
        st = st * jnp.exp(last)[..., None, None] + mm(
            "bhck,bhcv->bhkv",
            ki * jnp.exp(last[..., None] - gi)[..., None], v_new)
        return st, o

    state, o = jax.lax.scan(body, state.astype(f32), (qc, kc, vc, gc, bc))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 3, 2).reshape(b, n * chunk, h,
                                                          dv)
    return o[:, :s], state


# ------------------------------------------------------ the decode step

def _decode_kernel(decay_ref, beta_ref, live_ref, q_ref, k_ref, v_ref,
                   s_ref, o_ref, so_ref, *, hb, n_heads):
    b, j = pl.program_id(0), pl.program_id(1)
    live = live_ref[b] > 0
    dk = q_ref.shape[-1]
    # the contractions run over dk, the state's sublane axis, so k and q
    # are needed as columns: one padded [128, 128] transpose each a step
    pad = jnp.zeros((dk - hb, dk), jnp.float32)
    kt = jnp.concatenate([k_ref[...], pad], axis=0).T
    qt = jnp.concatenate([q_ref[...], pad], axis=0).T
    for h in range(hb):
        at = b * n_heads + j * hb + h
        s0 = s_ref[h]
        kc, qc = kt[:, h:h + 1], qt[:, h:h + 1]
        s1 = s0 * decay_ref[at]
        kv = jnp.sum(s1 * kc, axis=0, keepdims=True)           # [1, dv]
        s2 = s1 + kc * ((v_ref[h:h + 1, :] - kv) * beta_ref[at])
        o_ref[h:h + 1, :] = jnp.sum(s2 * qc, axis=0, keepdims=True)
        so_ref[h] = jnp.where(live, s2, s0)


# jitted so the linear layers of a model share one trace and one Mosaic
# lowering (as paged_decode._attend); the caller's executable donates
@functools.partial(jax.jit, static_argnames=("interpret",))
def _decode_call(q, k, v, decay, beta, live, state, *, interpret):
    b, h, dk, dv = state.shape
    hb = min(HEADS_PER_STEP, h, dk)        # smaller only at test sizes
    assert h % hb == 0
    row = pl.BlockSpec((None, hb, dk), lambda i, j, *_: (i, j, 0))
    mat = pl.BlockSpec((None, hb, dk, dv), lambda i, j, *_: (i, j, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_decode_kernel, hb=hb, n_heads=h),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, h // hb),
            in_specs=[row, row, row, mat], out_specs=[row, mat]),
        out_shape=[jax.ShapeDtypeStruct((b, h, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="gdn_decode",
    )(decay.reshape(-1), beta.reshape(-1), live.astype(jnp.int32),
      q, k, v, state)
    return o, state


def gdn_decode_step(q, k, v, g, beta, state, live):
    """One position for every slot: ``q k [B, H, dk]``, ``v [B, H, dv]``,
    ``g beta [B, H]`` float32, ``state [B, H, dk, dv]`` float32, ``live
    [B]`` bool. Returns (o [B, H, dv] float32, the new state); the state of
    a slot that is not live comes back unchanged, its ``o`` is unused."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    mode = kernel_mode(state)
    if mode is None:
        o, new = gdn_recurrent(q[:, None], k[:, None], v[:, None],
                               g[:, None], beta[:, None], state)
        keep = live[:, None, None, None]
        return o[:, 0], jnp.where(keep, new, state)
    note_state_kernel("gdn_decode")
    return _decode_call(q, k, v, jnp.exp(g), beta, live, state,
                        interpret=mode == "interpret")
