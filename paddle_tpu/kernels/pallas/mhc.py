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
one's read. Per token that is tiny, latency-bound work (a 24-wide
projection, 20 rounds on 4 x 4 numbers, two small mixes), so on a TPU it is
one Pallas kernel, ``mhc``, over blocks of ``TOKENS`` tokens held in VMEM;
off the chip (and as the oracle) the same equations in ``jax.numpy``
(``mhc_plain``). ``force_interpret`` runs the kernel through the Pallas
interpreter, as ``paged_decode.force_interpret`` does.

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

from .util import note_mhc, tpu_placement

TOKENS = 16            # tokens a grid step: a bfloat16 sublane tile
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


def _kernel(x_ref, y_ref, post_ref, res_ref, w_ref, phi_ref, a_ref, b_ref,
            xo_ref, u_ref, po_ref, ro_ref, *, n, eps, clamp, iters, mix):
    f32 = jnp.float32
    dt = x_ref.dtype
    prec = jax.lax.Precision.HIGHEST if dt == f32 else None
    c = x_ref.shape[1] // n
    xs = [x_ref[:, j * c:(j + 1) * c].astype(f32)
          for j in range(n)]                                    # n x [tb, C]
    if mix:
        y = y_ref[...].astype(f32)
        post, res = post_ref[...], res_ref[...]
        new = []
        for i in range(n):
            acc = post[:, i:i + 1] * y
            for j in range(n):
                acc = acc + res[:, i * n + j:i * n + j + 1] * xs[j]
            new.append(jnp.clip(acc, -clamp, clamp).astype(dt))
        for i in range(n):
            xo_ref[:, i * c:(i + 1) * c] = new[i]
        xs = [v.astype(f32) for v in new]
    else:
        xo_ref[...] = x_ref[...]
    sq = sum(jnp.sum(v * v, axis=1, keepdims=True) for v in xs)
    inv = jax.lax.rsqrt(sq / (n * c) + eps)                     # [tb, 1]
    p = None
    for j in range(n):
        normed = (xs[j] * inv
                  * w_ref[:, j * c:(j + 1) * c].astype(f32)).astype(dt)
        part = jax.lax.dot_general(
            normed, phi_ref[:, j * c:(j + 1) * c],
            (((1,), (1,)), ((), ())), precision=prec,
            preferred_element_type=f32)                         # [tb, P]
        p = part if p is None else p + part
    a = [a_ref[k] for k in range(3)]
    b = b_ref[...].astype(f32)                                  # [1, P]
    col = [p[:, k:k + 1] for k in range(2 * n + n * n)]
    pre = [jax.nn.sigmoid(a[0] * col[k] + b[:, k:k + 1]) for k in range(n)]
    for k in range(n):
        po_ref[:, k:k + 1] = 2.0 * jax.nn.sigmoid(
            a[1] * col[n + k] + b[:, n + k:n + k + 1])
    logit = [a[2] * col[2 * n + k] + b[:, 2 * n + k:2 * n + k + 1]
             for k in range(n * n)]
    top = functools.reduce(jnp.maximum, logit)
    m = [jnp.exp(v - top) for v in logit]
    for _ in range(iters):
        for i in range(n):
            s = sum(m[i * n + j] for j in range(n))
            for j in range(n):
                m[i * n + j] = m[i * n + j] / s
        for j in range(n):
            s = sum(m[i * n + j] for i in range(n))
            for i in range(n):
                m[i * n + j] = m[i * n + j] / s
    for k in range(n * n):
        ro_ref[:, k:k + 1] = m[k]
    u = sum(pre[j] * xs[j] for j in range(n))
    u_ref[...] = u.astype(u_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "clamp", "iters", "mix",
                                             "interpret"))
def _fused(x, y, post, res, norm_w, phi, alpha, bias, *, eps, clamp, iters,
           mix, interpret):
    t, n, c = x.shape
    width = phi.shape[0]
    tb = TOKENS
    kernel = functools.partial(_kernel, n=n, eps=eps, clamp=clamp,
                               iters=iters, mix=mix)
    row = lambda i: (i, 0)                                      # noqa: E731
    xo, u, po, ro = pl.pallas_call(
        kernel,
        grid=(t // tb,),
        in_specs=[pl.BlockSpec((tb, n * c), row),
                  pl.BlockSpec((tb, c), row),
                  pl.BlockSpec((tb, n), row),
                  pl.BlockSpec((tb, n * n), row),
                  pl.BlockSpec((1, n * c), lambda i: (0, 0)),
                  pl.BlockSpec((width, n * c), lambda i: (0, 0)),
                  pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((1, width), lambda i: (0, 0))],
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
      bias.astype(jnp.float32).reshape(1, width))
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
    pad = -t % TOKENS
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
