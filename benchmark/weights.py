"""The benchmark's own weights: every array of a GPT configuration made on
the device from `--seed` in ONE jitted call, in the type they are served or
trained in. The program's model and the plain reference are both handed
these arrays; neither makes any of its own.

GPT-3 recipe: N(0, 0.02) matrices and embeddings, residual-out projections
scaled by 1/sqrt(2L). Biases and LayerNorm parameters are given small
random values too (the program initialises them to 0 and 1): a check on
all-zero biases would not see a bias that is dropped.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def shapes(model: dict) -> dict:
    """{stacked key: shape} for a configuration's `model` group."""
    v, h, n_l = model["vocab_size"], model["hidden_size"], model["num_layers"]
    p, i = model["max_position_embeddings"], 4 * model["hidden_size"]
    return {
        "wte": (v, h), "wpe": (p, h), "lnf_w": (h,), "lnf_b": (h,),
        "ln1_w": (n_l, h), "ln1_b": (n_l, h), "qkv_w": (n_l, h, 3 * h),
        "qkv_b": (n_l, 3 * h), "proj_w": (n_l, h, h), "proj_b": (n_l, h),
        "ln2_w": (n_l, h), "ln2_b": (n_l, h), "fc1_w": (n_l, h, i),
        "fc1_b": (n_l, i), "fc2_w": (n_l, i, h), "fc2_b": (n_l, h),
    }


def n_params(model: dict) -> int:
    return sum(math.prod(s) for s in shapes(model).values())


def seed_key(seed: int):
    """A PRNG key from any whole number (seeds run past 2**31). The
    generator is named: the program switches jax's DEFAULT one to "rbg"
    on a TPU when it first makes a key, and weights made before and after
    that switch must be the same weights."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="threefry2x32"), seed >> 31)


def make(model: dict, seed: int, dtype="bfloat16"):
    """All arrays of the configuration, on the device, from the seed."""
    shp = shapes(model)
    std = 0.02
    resid = std / math.sqrt(2.0 * model["num_layers"])

    def build(key):
        out = {}
        for n, (name, shape) in enumerate(sorted(shp.items())):
            z = jax.random.normal(jax.random.fold_in(key, n), shape,
                                  jnp.float32)
            if name in ("proj_w", "fc2_w"):
                a = resid * z
            elif name.endswith("_w") and name.startswith("ln"):
                a = 1.0 + std * z
            else:
                a = std * z
            out[name] = a.astype(dtype)
        return out

    return jax.jit(build)(seed_key(seed))
