"""Mamba-2's state-space duality layer (Dao & Gu 2024, "Transformers are
SSMs", arXiv:2405.21060): a selective state-space recurrence with a scalar
decay a head, in the three forms a server needs.

Per position, head ``h`` of group ``g = h // (H / G)``, with a float32 state
``S [N, P]`` (``N`` the state size, ``P`` the head's width)::

    S <- exp(dt * A) * S + B_g (x) (dt * x_h)
    y  = C_g^T S + D * x_h

``dt > 0`` (after softplus) and ``A < 0`` are per head, ``B`` and ``C`` per
group. A position with ``dt = 0`` leaves the state as it found it: that is
how callers switch off the padded tail of a prefill chunk. The state is kept
``[N, P]`` (the paper's ``S^T``): both contractions of the decode step run
over ``N``, the sublane axis, and ``x``, ``y`` stay rows.

* ``ssd_recurrent``: the rule as written, a ``lax.scan`` over positions. The
  CPU path of the decode step and the oracle of the tests.
* ``ssd_chunked``: the same result for a run of positions in the paper's
  block-decomposed form (Listing 1): inside a chunk of ``chunk`` positions
  the outputs are masked ``(C B^T) . L`` products on the MXU, one state a
  chunk is carried between chunks; a state comes in and one goes out. Plain
  ``jax.numpy``; what a prefill chunk runs, on every backend.
* ``ssd_decode_step``: one position for every slot of a decode batch. On a
  TPU a Pallas kernel (``ssd_decode``: each slot's state is read once and
  written once, in place; one grid step takes a group's heads, so ``B`` and
  ``C`` are turned into columns once a step); elsewhere the scan above. A
  slot that is not live gets its state back bit for bit.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .util import note_state_kernel, tpu_placement

CHUNK = 128                   # positions per chunk (``mamba_chunk_size``)

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


def kernel_mode(state, groups: int):
    """``"mosaic"`` on a TPU whose tiling the state fits, ``"interpret"``
    inside ``force_interpret``, None for the ``jax.numpy`` step."""
    if _FORCE["interpret"]:
        return "interpret"
    if not tpu_placement(state):
        return None
    _, h, n, p = state.shape
    if p % 128 or n % 128 or (h // groups) % 8 or h % groups \
            or state.dtype != jnp.float32:
        return None
    return "mosaic"


def _per_head(a, heads: int):
    """``[..., G, N]`` -> ``[..., H, N]``: a group's row for each head."""
    return jnp.repeat(a, heads // a.shape[-2], axis=-2)


# ------------------------------------------------------------ the rule

def ssd_recurrent(x, dt, a, b, c, d, state):
    """``x [B, S, H, P]``, ``dt [B, S, H]``, ``a d [H]``, ``b c [B, S, G,
    N]``, ``state [B, H, N, P]``; float32 throughout. Returns (y [B, S, H,
    P], the state after the last position)."""
    f32 = jnp.float32
    h = x.shape[2]
    a, d = a.astype(f32), d.astype(f32)

    def step(s, at):
        xt, dtt, bt, ct = at
        s = s * jnp.exp(dtt * a)[..., None, None] \
            + _per_head(bt, h)[..., None] * (dtt[..., None] * xt)[..., None, :]
        y = jnp.sum(s * _per_head(ct, h)[..., None], axis=-2)
        return s, y + d[:, None] * xt

    xs = tuple(jnp.moveaxis(t.astype(f32), 1, 0) for t in (x, dt, b, c))
    state, y = jax.lax.scan(step, state.astype(f32), xs)
    return jnp.moveaxis(y, 0, 1), state


def ssd_chunked(x, dt, a, b, c, d, state, chunk: int = CHUNK):
    """The same as ``ssd_recurrent`` with the products as matrix products
    over chunks of ``chunk`` positions (``S`` is padded to a whole number
    of chunks with switched-off positions)."""
    bsz, s, h, p = x.shape
    f32 = jnp.float32
    pad = -s % chunk
    if pad:
        x, dt, b, c = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] *
                               (t.ndim - 2)) for t in (x, dt, b, c))
    n_chunks = (s + pad) // chunk
    a, d = a.astype(f32), d.astype(f32)

    def chunks(t):                      # [B, S, ...] -> [n, B, L, ...]
        t = t.astype(f32).reshape((bsz, n_chunks, chunk) + t.shape[2:])
        return jnp.moveaxis(t, 1, 0)

    causal = jnp.tril(jnp.ones((chunk, chunk), bool))[None, :, :, None]

    def mm(spec, u, v):
        return jnp.einsum(spec, u, v, precision="highest",
                          preferred_element_type=f32)

    def body(st, at):
        xi, dti, bi, ci = at            # [B,L,H,P] [B,L,H] [B,L,G,N] x2
        cum = jnp.cumsum(dti * a, axis=1)          # log decay since start
        # decay from position j (exclusive) to position i, for j <= i
        diff = cum[:, :, None, :] - cum[:, None, :, :]         # [B,Li,Lj,H]
        decay = jnp.where(causal, jnp.exp(jnp.where(causal, diff, 0.0)), 0.0)
        cb = _per_head(jnp.moveaxis(mm("blgn,bmgn->blmg", ci, bi), -1, -2),
                       h)                                      # [B,Li,H,Lj]
        w = cb * jnp.moveaxis(decay, -1, -2) \
            * jnp.moveaxis(dti, 1, -1)[:, None]
        y = mm("blhm,bmhp->blhp", w, xi)
        # what the state the chunk started from still contributes
        y = y + mm("blhn,bhnp->blhp", _per_head(ci, h), st) \
            * jnp.exp(cum)[..., None]
        last = cum[:, -1]                                      # [B,H]
        wj = jnp.exp(last[:, None] - cum) * dti
        st = st * jnp.exp(last)[..., None, None] + mm(
            "blhn,blhp->bhnp", _per_head(bi, h) * wj[..., None], xi)
        return st, y + d[:, None] * xi

    state, y = jax.lax.scan(body, state.astype(f32),
                            tuple(chunks(t) for t in (x, dt, b, c)))
    y = jnp.moveaxis(y, 0, 1).reshape(bsz, n_chunks * chunk, h, p)
    return y[:, :s], state


# ------------------------------------------------------ the decode step

def _decode_kernel(decay_ref, dt_ref, live_ref, forget_ref, x_ref, b_ref,
                   c_ref, s_ref, y_ref, so_ref, *, hb, n_heads):
    i, j = pl.program_id(0), pl.program_id(1)
    live, forget = live_ref[i] > 0, forget_ref[i] > 0
    n = b_ref.shape[-1]
    # both contractions run over N, the state's sublane axis, so B and C
    # are needed as columns: one padded [N, N] transpose a step
    bc = jnp.concatenate([b_ref[...], c_ref[...],
                          jnp.zeros((n - 2, n), jnp.float32)], axis=0).T
    bcol, ccol = bc[:, 0:1], bc[:, 1:2]
    for h in range(hb):
        at = i * n_heads + j * hb + h
        held = s_ref[h]
        # a select, not a decay of 0: whatever the slot's last tenant left
        # (a NaN too) is gone, as in the scan and in the chunked form
        s0 = jnp.where(forget, 0.0, held)
        s1 = s0 * decay_ref[at] + bcol * (x_ref[h:h + 1, :] * dt_ref[at])
        y_ref[h:h + 1, :] = jnp.sum(s1 * ccol, axis=0, keepdims=True)
        so_ref[h] = jnp.where(live, s1, held)


# jitted so a model's layers share one trace and one Mosaic lowering (as
# paged_decode._attend); the caller's executable donates the state
@functools.partial(jax.jit, static_argnames=("interpret",))
def _decode_call(x, decay, dt, b, c, live, forget, state, *, interpret):
    bsz, h, n, p = state.shape
    g = b.shape[1]
    hb = h // g                     # a group's heads a grid step
    row = pl.BlockSpec((None, hb, p), lambda i, j, *_: (i, j, 0))
    vec = pl.BlockSpec((None, 1, n), lambda i, j, *_: (i * g + j, 0, 0))
    mat = pl.BlockSpec((None, hb, n, p), lambda i, j, *_: (i, j, 0, 0))
    y, state = pl.pallas_call(
        functools.partial(_decode_kernel, hb=hb, n_heads=h),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(bsz, g),
            in_specs=[row, vec, vec, mat], out_specs=[row, mat]),
        out_shape=[jax.ShapeDtypeStruct((bsz, h, p), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssd_decode",
    )(decay.reshape(-1), dt.reshape(-1), live.astype(jnp.int32),
      forget.astype(jnp.int32), x, b.reshape(bsz * g, 1, n),
      c.reshape(bsz * g, 1, n), state)
    return y, state


def ssd_decode_step(x, dt, a, b, c, d, state, live, forget=None):
    """One position for every slot: ``x [B, H, P]``, ``dt [B, H]``, ``a d
    [H]``, ``b c [B, G, N]``, ``state [B, H, N, P]`` float32, ``live [B]``
    bool. Returns (y [B, H, P] float32, the new state); the state of a slot
    that is not live comes back unchanged, its ``y`` is unused. A slot in
    ``forget [B]`` (a sequence's first position) steps from a zero state:
    in the kernel that is a select on the block it has read anyway, and no
    second pass over the states."""
    f32 = jnp.float32
    x, dt, b, c = (t.astype(f32) for t in (x, dt, b, c))
    mode = kernel_mode(state, b.shape[1])
    if mode is None:
        start = state if forget is None else jnp.where(
            forget[:, None, None, None], 0.0, state)
        y, new = ssd_recurrent(x[:, None], dt[:, None], a, b[:, None],
                               c[:, None], d, start)
        return y[:, 0], jnp.where(live[:, None, None, None], new, state)
    if forget is None:
        forget = jnp.zeros_like(live)
    note_state_kernel("ssd_decode")
    y, new = _decode_call(x, jnp.exp(dt * a.astype(f32)), dt, b, c, live,
                          forget, state, interpret=mode == "interpret")
    return y + d.astype(f32)[:, None] * x, new
