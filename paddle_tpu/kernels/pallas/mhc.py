"""One boundary of manifold-constrained hyper-connections (mHC), fused.

A model with hyper-connections carries ``n`` residual streams a token
(``X [n, C]``) instead of one. Every sublayer ``F`` reads a mix of them and
writes back through a doubly stochastic ``n x n`` matrix (mHC:
Manifold-Constrained Hyper-Connections, DeepSeek-AI)::

    x^      = N(vec X)                   # RMS over n * C values, weighted
    p       = phi x^                     # [n + n + n * n]; phi is stored
                                         #   [2n + n n, n C], a row an
                                         #   output (lane-dense in HBM)
    H_pre   = sigmoid(a_pre  p_pre  + b_pre)          # [n]
    H_post  = 2 sigmoid(a_post p_post + b_post)       # [n]
    H_res   = Sinkhorn(exp(a_res p_res + b_res))      # [n, n]
    u       = H_pre X                    # what F reads (its own norm next)
    X      <- clamp(H_res X + H_post^T F(u), +-clamp)

Sinkhorn is ``iters`` (20) rounds of a row normalisation then a column
normalisation, in float32, always all of them (stopping early on a
tolerance would change the model's math).

A boundary between two sublayers is ONE call: the previous sublayer's write
(``y``, its ``H_post`` and ``H_res``; none before the first) and the next
one's read. On a TPU it is one Pallas kernel, ``mhc``; off the chip (and as
the oracle) the same equations in ``jax.numpy`` (``mhc_plain``).
``force_interpret`` runs the kernel through the Pallas interpreter, as
``paged_decode.force_interpret`` does.

The kernel's schedule follows the shape (``schedule``): a grid step holds a
tile of up to a lane width (128) of tokens, at least two tiles a call where
there are rows for them (a decode step's 128 rows are two tiles of 64, a
prefill chunk's 512 four of 128), and walks each stream's columns in pieces
of ``PIECE`` float32 values, three times: the write and the square sums,
the normed row against ``phi``, then ``u``. The projection comes out
transposed, ``p^T [2n + n n, tile]`` (``phi`` against the normed tile), so
the coefficients' per-token work (the sigmoids, the max-shifted exp, all
Sinkhorn rounds) runs with the tokens on lanes, a handful of vector
operations a round for the whole tile. One small transpose a tile brings
the coefficients back to a token a sublane, for ``H_pre X`` and the whole
``[tile, n]`` and ``[tile, n n]`` stores; the incoming ``H_post`` and
``H_res`` already lie so. Each trace leaves its schedule on an
``mhc/schedule`` span.

Every call notes the rows it mixes (``util.note_mhc``): the serving
engine's call spans carry them as ``mhc_rows``.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...monitor import trace as _trace
from .util import note_mhc, tpu_placement

LANES = 128            # the most tokens a grid step holds: a lane width
SUBLANES = 16          # a tile is whole bfloat16 sublane tiles
PIECE = 64 * 1024      # float32 values of one stream's piece of columns
VMEM_LIMIT = 32 * 1024 * 1024

_FORCE = {"interpret": False}


@contextlib.contextmanager
def force_interpret(on: bool = True):
    prev, _FORCE["interpret"] = _FORCE["interpret"], bool(on)
    try:
        yield
    finally:
        _FORCE["interpret"] = prev


def kernel_mode(x):
    """``"interpret"`` inside ``force_interpret``, ``"mosaic"`` where ``x``
    lives on a TPU and its width is whole lane tiles, else None (the
    ``jax.numpy`` form)."""
    if _FORCE["interpret"]:
        return "interpret"
    if not tpu_placement(x) or x.shape[-1] % 128:
        return None
    return "mosaic"


def sinkhorn(m, iters: int):
    """``iters`` rounds of rows then columns normalised, over the last two
    axes of ``m`` (float32, positive)."""
    for _ in range(iters):
        m = m / jnp.sum(m, axis=-1, keepdims=True)
        m = m / jnp.sum(m, axis=-2, keepdims=True)
    return m


def _coefficients(p, alpha, bias, n, iters):
    """(H_pre [T, n], H_post [T, n], H_res [T, n, n]) from the projection
    ``p [T, 2n + n*n]`` float32."""
    t = p.shape[0]
    pre = jax.nn.sigmoid(alpha[0] * p[:, :n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * p[:, n:2 * n] + bias[n:2 * n])
    logits = (alpha[2] * p[:, 2 * n:] + bias[2 * n:]).reshape(t, n, n)
    res = sinkhorn(jnp.exp(logits - jnp.max(logits, axis=(1, 2),
                                            keepdims=True)), iters)
    return pre, post, res


def mhc_plain(x, y, post, res, norm_w, phi, alpha, bias, *, eps, clamp,
              iters):
    """The boundary in ``jax.numpy``: ``x [T, n, C]`` the streams, ``y [T,
    C]`` the previous sublayer's output with its ``post [T, n]`` and ``res
    [T, n, n]`` (float32), or ``y`` None at the first boundary. Returns
    (streams after the write [T, n, C] in ``x``'s dtype, ``u [T, C]`` in
    ``x``'s dtype, the next ``H_post``, the next ``H_res``)."""
    t, n, c = x.shape
    f32 = jnp.float32
    prec = "highest" if x.dtype == f32 else None
    xf = x.astype(f32)
    if y is not None:
        xf = jnp.einsum("tij,tjc->tic", res, xf, precision="highest") \
            + post[..., None] * y.astype(f32)[:, None, :]
        xf = jnp.clip(xf, -clamp, clamp)
        x = xf.astype(x.dtype)
        xf = x.astype(f32)
    flat = xf.reshape(t, n * c)
    normed = flat * jax.lax.rsqrt(jnp.mean(jnp.square(flat), -1,
                                           keepdims=True) + eps) \
        * norm_w.astype(f32)
    p = jnp.einsum("tk,jk->tj", normed.astype(x.dtype), phi.astype(x.dtype),
                   precision=prec, preferred_element_type=f32)
    pre, post_n, res_n = _coefficients(p, alpha.astype(f32),
                                       bias.astype(f32), n, iters)
    u = jnp.einsum("ti,tic->tc", pre, xf, precision="highest")
    return x, u.astype(x.dtype), post_n, res_n


def tile(t: int) -> int:
    """Tokens a grid step for ``t`` rows: a lane width at most, whole
    bfloat16 sublane tiles, and at least two grid steps where ``t`` has
    more than one sublane tile, so that one step's copies run under
    another's work."""
    steps = max(2, pl.cdiv(t, LANES))
    return min(LANES, pl.cdiv(pl.cdiv(t, steps), SUBLANES) * SUBLANES)


def piece(tb: int, c: int) -> int:
    """Columns of a stream one step of the walk holds: ``C`` halved while
    a float32 ``[tb, piece]`` holds more than ``PIECE`` values and the
    halves stay whole lane tiles."""
    w = c
    while w * tb > PIECE and w % (2 * LANES) == 0:
        w //= 2
    return w


def schedule(t: int, n: int, c: int) -> dict:
    """What ``_fused`` runs for ``t`` rows of ``n`` streams of width
    ``c``: the tile, its grid steps, and the column pieces a step walks."""
    tb = tile(t)
    return {"T": t, "tokens": tb, "grid_steps": pl.cdiv(t, tb),
            "column_pieces": c // piece(tb, c)}


def _kernel(x_ref, y_ref, post_ref, res_ref, w_ref, phi_ref, a_ref, b_ref,
            xo_ref, u_ref, po_ref, ro_ref, *, n, cw, eps, clamp, iters, mix):
    f32 = jnp.float32
    dt = x_ref.dtype
    prec = jax.lax.Precision.HIGHEST if dt == f32 else None
    tb = x_ref.shape[0]
    c = x_ref.shape[1] // n
    width = phi_ref.shape[0]

    def cols(j, q):                 # stream j's q-th piece of columns
        return pl.ds(pl.multiple_of(j * c + q * cw, cw), cw)

    # 1. the write, a piece of every stream at a time: streams rounded to
    # their dtype after the clamp, their square sums from what was stored
    post, res = post_ref[...], res_ref[...]

    def write(q, sq):
        xs = [x_ref[:, cols(j, q)] for j in range(n)]
        if mix:
            y = y_ref[:, cols(0, q)].astype(f32)
            xf = [v.astype(f32) for v in xs]
            new = []
            for i in range(n):
                acc = post[:, i:i + 1] * y
                for j in range(n):
                    acc = acc + res[:, i * n + j:i * n + j + 1] * xf[j]
                new.append(jnp.clip(acc, -clamp, clamp).astype(dt))
            xs = new
        for j in range(n):
            xo_ref[:, cols(j, q)] = xs[j]
        for v in xs:
            vf = v.astype(f32)
            sq = sq + jnp.sum(vf * vf, axis=1, keepdims=True)
        return sq

    sq = jax.lax.fori_loop(0, c // cw, write, jnp.zeros((tb, 1), f32))
    inv = jax.lax.rsqrt(sq / (n * c) + eps)                     # [tb, 1]

    # 2. the projection transposed, tokens on lanes: p^T = phi x^^T
    def project(q, pt):
        for j in range(n):
            normed = (xo_ref[:, cols(j, q)].astype(f32) * inv
                      * w_ref[:, cols(j, q)].astype(f32)).astype(dt)
            pt = pt + jax.lax.dot_general(
                phi_ref[:, cols(j, q)], normed, (((1,), (1,)), ((), ())),
                precision=prec, preferred_element_type=f32)
        return pt

    pt = jax.lax.fori_loop(0, c // cw, project,
                           jnp.zeros((width, tb), f32))         # [P, tb]

    # 3. the coefficients on [k, tb] rows; H_res row i is the n sublanes
    # [2n + i n, 2n + (i + 1) n)
    a = [a_ref[k] for k in range(3)]
    b = b_ref[...]                                              # [P, 1]
    pre = jax.nn.sigmoid(a[0] * pt[:n] + b[:n])
    post_n = 2.0 * jax.nn.sigmoid(a[1] * pt[n:2 * n] + b[n:2 * n])
    logit = a[2] * pt[2 * n:] + b[2 * n:]                       # [n n, tb]
    m = jnp.exp(logit - jnp.max(logit, axis=0, keepdims=True))
    rows = [m[i * n:(i + 1) * n] for i in range(n)]
    for _ in range(iters):
        rows = [r / jnp.sum(r, axis=0, keepdims=True) for r in rows]
        s = functools.reduce(jnp.add, rows)
        rows = [r / s for r in rows]
    # back to a token a sublane, once: [tb, P]
    coef = jnp.concatenate([pre, post_n] + rows, axis=0).T
    po_ref[...] = coef[:, n:2 * n]
    ro_ref[...] = coef[:, 2 * n:]

    # 4. what the sublayer reads, u = H_pre X
    def read(q, carry):
        u = coef[:, 0:1] * xo_ref[:, cols(0, q)].astype(f32)
        for j in range(1, n):
            u = u + coef[:, j:j + 1] * xo_ref[:, cols(j, q)].astype(f32)
        u_ref[:, cols(0, q)] = u.astype(dt)
        return carry

    jax.lax.fori_loop(0, c // cw, read, 0)


@functools.partial(jax.jit, static_argnames=("eps", "clamp", "iters", "mix",
                                             "interpret"))
def _fused(x, y, post, res, norm_w, phi, alpha, bias, *, eps, clamp, iters,
           mix, interpret):
    t, n, c = x.shape
    width = phi.shape[0]
    plan = schedule(t, n, c)
    tb = plan["tokens"]
    kernel = functools.partial(_kernel, n=n, cw=c // plan["column_pieces"],
                               eps=eps, clamp=clamp, iters=iters, mix=mix)
    row = lambda i: (i, 0)                                      # noqa: E731
    whole = lambda i: (0, 0)                                    # noqa: E731
    with _trace.span("mhc/schedule", **plan):
        xo, u, po, ro = pl.pallas_call(
            kernel,
            grid=(plan["grid_steps"],),
            in_specs=[pl.BlockSpec((tb, n * c), row),
                      pl.BlockSpec((tb, c), row),
                      pl.BlockSpec((tb, n), row),
                      pl.BlockSpec((tb, n * n), row),
                      pl.BlockSpec((1, n * c), whole),
                      pl.BlockSpec((width, n * c), whole),
                      pl.BlockSpec(memory_space=pltpu.SMEM),
                      pl.BlockSpec((width, 1), whole)],
            out_specs=[pl.BlockSpec((tb, n * c), row),
                       pl.BlockSpec((tb, c), row),
                       pl.BlockSpec((tb, n), row),
                       pl.BlockSpec((tb, n * n), row)],
            out_shape=[jax.ShapeDtypeStruct((t, n * c), x.dtype),
                       jax.ShapeDtypeStruct((t, c), x.dtype),
                       jax.ShapeDtypeStruct((t, n), jnp.float32),
                       jax.ShapeDtypeStruct((t, n * n), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",),
                vmem_limit_bytes=VMEM_LIMIT),
            interpret=interpret,
            name="mhc",
        )(x.reshape(t, n * c), y, post, res, norm_w.reshape(1, n * c),
          phi.astype(x.dtype), alpha.astype(jnp.float32),
          bias.astype(jnp.float32).reshape(width, 1))
    return xo.reshape(t, n, c), u, po, ro


def mhc(x, y, post, res, norm_w, phi, alpha, bias, *, eps, clamp,
        iters=20):
    """One boundary (``mhc_plain``'s arguments and results): the Pallas
    kernel ``mhc`` on a TPU or under ``force_interpret``, else the
    ``jax.numpy`` form."""
    t, n, c = x.shape
    note_mhc(t)
    mode = kernel_mode(x)
    if mode is None:
        return mhc_plain(x, y, post, res, norm_w, phi, alpha, bias, eps=eps,
                         clamp=clamp, iters=iters)
    mix = y is not None
    pad = -t % tile(t)
    f32 = jnp.float32
    if not mix:
        y = jnp.zeros((t, c), x.dtype)
        post, res = jnp.zeros((t, n), f32), jnp.zeros((t, n, n), f32)

    def padded(a):
        return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)) \
            if pad else a

    xo, u, po, ro = _fused(
        padded(x), padded(y), padded(post.astype(f32)),
        padded(res.astype(f32).reshape(t, n * n)), norm_w, phi, alpha, bias,
        eps=float(eps), clamp=float(clamp), iters=int(iters), mix=mix,
        interpret=mode == "interpret")
    return xo[:t], u[:t], po[:t], ro[:t].reshape(t, n, n)
