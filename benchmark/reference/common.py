"""What every family's plain reference shares, in one copy: the matmul
every product goes through (`hi_dot`, and the control's `fp8_dot`), the
optimizer the training comparison follows, and the per-leaf norms. Float32
`jax.numpy`; imports nothing of the program or of the harness.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F8_MAX = 448.0            # largest finite float8_e4m3fn


def hi_dot(spec, a, b):
    return jnp.einsum(spec, a, b, precision="highest",
                      preferred_element_type=jnp.float32)


def _round_fp8(x):
    """Round to float8_e4m3fn under a per-tensor scale (amax -> 448), the
    usual fp8 recipe; gradients pass straight through the rounding."""
    scale = F8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    return x + jax.lax.stop_gradient(q - x)


def fp8_dot(spec, a, b):
    """The control's matmul: both operands rounded to fp8, product and
    accumulation as the reference's."""
    return hi_dot(spec, _round_fp8(a), _round_fp8(b))


def adamw_step(params, m, v, grads, t, lr, beta1, beta2, eps, wd):
    """Decoupled-weight-decay Adam (Loshchilov & Hutter), every leaf
    decayed, bias-corrected moments; t counts from 1."""
    bc1 = 1.0 - beta1 ** t
    bc2 = 1.0 - beta2 ** t
    out_p, out_m, out_v = {}, {}, {}
    for k in params:
        g = grads[k]
        m1 = beta1 * m[k] + (1.0 - beta1) * g
        m2 = beta2 * v[k] + (1.0 - beta2) * jnp.square(g)
        upd = lr * (m1 / bc1) / (jnp.sqrt(m2 / bc2) + eps) \
            + lr * wd * params[k]
        out_p[k], out_m[k], out_v[k] = params[k] - upd, m1, m2
    return out_p, out_m, out_v


def split_fused(tree, fused):
    """A fused leaf as the leaves it is made of: `fused` is {stacked key:
    part names}, the parts equal slices of the last axis (GPT's qkv bias:
    the key third has no gradient under softmax, a constant added to every
    score of a row, so it must be judged apart from the other two)."""
    out = dict(tree)
    for key, parts in fused.items():
        b = out.pop(key)
        h = b.shape[-1] // len(parts)
        for i, part in enumerate(parts):
            out[f"{key}.{part}"] = b[..., i * h:(i + 1) * h]
    return out


def leaf_norms(tree, layer_keys, fused):
    """Per-leaf L2 norms, one per LAYER for the stacked keys: {key: [L] or
    []} float32, fused leaves split into their parts."""
    out = {}
    for k, a in split_fused(tree, fused).items():
        a = a.astype(jnp.float32)
        axes = tuple(range(1, a.ndim)) if k.split(".")[0] in layer_keys \
            else None
        out[k] = jnp.sqrt(jnp.sum(jnp.square(a), axis=axes))
    return out
