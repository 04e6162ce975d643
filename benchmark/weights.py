"""The benchmark's own weights: every array of a configuration made on the
device from `--seed` in ONE jitted call, in the type they are served or
trained in. The program's model and the plain reference are both handed
these arrays; neither makes any of its own. Which arrays a configuration
has, and how each is scaled, is its family's to say
(`families/<family>.py::shapes`, `make`); the seed's key and the drawing
are here, once.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any whole number (seeds run past 2**31). The
    generator is named: the program switches jax's DEFAULT one to "rbg"
    on a TPU when it first makes a key, and weights made before and after
    that switch must be the same weights."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="threefry2x32"), seed >> 31)


def draw(shapes: dict, recipe, seed: int, dtype):
    """{key: array} on the device: array `key` is mean + std x a standard
    normal drawn under `fold_in(seed's key, n)`, n the key's place in
    sorted order; `recipe(key)` gives (mean, std)."""
    def build(key):
        out = {}
        for n, (name, shape) in enumerate(sorted(shapes.items())):
            mean, std = recipe(name)
            a = std * jax.random.normal(jax.random.fold_in(key, n), shape,
                                        jnp.float32)
            # no `0.0 +` where the mean is 0: tests pin the arrays' bits
            out[name] = (mean + a if mean else a).astype(dtype)
        return out

    return jax.jit(build)(seed_key(seed))
