"""A 64-number signed sketch of a tensor, the same on both sides of a
comparison: bucket sums under a fixed +-1 pattern that depends on the
tensor's shape alone. The norm of the difference of two sketches
estimates the norm of the difference of the tensors (to about 9 %), so a
gradient can be compared in direction, not only in length, without either
side holding the other's tensor.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

K = 64


def signs(shape) -> jax.Array:
    """[rows, K] of +-1.0 for a tensor of this shape (threefry, whatever
    the process's default generator is)."""
    n = math.prod(shape)
    key = jax.random.fold_in(jax.random.key(0x5EED, impl="threefry2x32"),
                             n % (1 << 31))
    return jax.random.rademacher(key, (-(-n // K), K)).astype(jnp.float32)


def sketch(x, pattern=None) -> jax.Array:
    """x of any shape -> [K] float32."""
    pattern = signs(x.shape) if pattern is None else pattern
    flat = x.reshape(-1).astype(jnp.float32)
    flat = jnp.pad(flat, (0, pattern.size - flat.size))
    return jnp.sum(flat.reshape(pattern.shape) * pattern, axis=0)


def sketch_layers(stacked) -> jax.Array:
    """[L, ...] -> [L, K]: every layer under the pattern of ONE layer's
    shape, as the program's per-layer leaves are sketched."""
    pattern = signs(stacked.shape[1:])
    return jax.vmap(lambda a: sketch(a, pattern))(stacked)
