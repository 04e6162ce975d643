"""Global PRNG state.

Reference analog: per-generator Philox state (`paddle.seed`, phi Generator) and Fleet's
``RNGStatesTracker`` for tensor-parallel-deterministic dropout
(/root/reference/python/paddle/distributed/fleet/meta_parallel/parallel_layers/random.py).

TPU-idiomatic design: a single functional jax.random key chain. Every consumer splits from
the global chain; named tracker states support the TP local/global dropout pattern.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager

import jax

_lock = threading.Lock()
_state = {"seed": 0}
_rng_tensor = None  # the single source of truth for the key, once materialized

# TPU-native PRNG: the default threefry key chain costs ~10 VPU ops/element
# wherever jax.random draws inside a kernel (dropout masks, init); the "rbg"
# impl rides the hardware generator (reference analog: curand Philox states in
# phi dropout/init kernels). CPU keeps threefry (exact, splittable). Deferred
# to first key creation: jax.default_backend() initializes XLA, which must not
# happen at import time (launcher workers call jax.distributed.initialize()
# first).
_prng_impl_chosen = False


def _ensure_prng_impl():
    global _prng_impl_chosen
    if _prng_impl_chosen:
        return
    _prng_impl_chosen = True
    if jax.default_backend() == "tpu":
        # raw key data is 4 words under rbg (2 under threefry): nothing may
        # hard-code a key width
        jax.config.update("jax_default_prng_impl", "rbg")


def rng_state_tensor():
    """The global key as a Tensor, so to_static can thread it as program state.

    Traced programs take it as an input and return its advanced value as an update
    (like BN running stats) — this keeps dropout patterns fresh per step in compiled
    programs instead of baking the trace-time mask in as a constant.
    """
    global _rng_tensor
    if _rng_tensor is None:
        from .tensor import Tensor
        _ensure_prng_impl()
        _rng_tensor = Tensor(jax.random.PRNGKey(_state["seed"]))
        _rng_tensor.name = "__global_rng_state__"
        _rng_tensor.persistable = True
    return _rng_tensor


def seed(value: int):
    import numpy as _np
    _ensure_prng_impl()
    with _lock:
        _state["seed"] = int(value)
        rng_state_tensor()._data = jax.random.PRNGKey(int(value))
        _host["gen"] = _np.random.default_rng(int(value))
    return value


def get_seed() -> int:
    return _state["seed"]


def int32_seed():
    """Fresh int32 scalar from the global key chain — THE seed recipe for
    in-kernel hardware-PRNG ops (pallas flash dropout, pallas dropout).
    Kept in one place so every kernel's RNG stream derives identically."""
    return jax.random.key_data(split_key()).ravel()[0].astype("int32")


def split_key():
    """Return a fresh subkey, advancing the global chain (traced or eager)."""
    from .dispatch import in_trace, trace_ctx
    t = rng_state_tensor()
    if in_trace():
        new_key, sub = jax.random.split(t._data)
        ctx = trace_ctx()
        if ctx is not None:
            # record BEFORE mutating so TraceContext.saved_data snapshots the
            # pre-trace key (ctx.restore() must never put a tracer back)
            ctx.record_buffer_update(t, new_key)
        t._data = new_key  # chain within the trace
        return sub
    with _lock:
        new_key, sub = jax.random.split(t._data)
        t._data = new_key
    return sub


_host = {"gen": None}


def host_generator():
    """Host-side numpy Generator seeded with the global seed.

    Weight INITIALIZATION samples here (reference inits are host-side too): a device
    round-trip + XLA compile per parameter shape is pure overhead at build time.
    The device key chain (split_key) stays the source for runtime randomness
    (dropout), where values must be drawable inside compiled programs.
    """
    import numpy as _np
    if _host["gen"] is None:
        _host["gen"] = _np.random.default_rng(_state["seed"])
    return _host["gen"]


def get_rng_state():
    return rng_state_tensor()._data


def set_rng_state(key):
    with _lock:
        rng_state_tensor()._data = key


class RNGStatesTracker:
    """Named RNG state chains, for TP-deterministic dropout.

    Mirrors fleet's RNGStatesTracker: 'global' dropout must agree across model-parallel
    ranks, 'local' must differ. With a functional key chain this is just separate named
    chains seeded from rank-dependent or rank-independent seeds.
    """

    def __init__(self):
        self.states_ = {}

    def add(self, name: str, seed_val: int):
        if name in self.states_:
            raise ValueError(f"rng state {name!r} already exists")
        self.states_[name] = jax.random.PRNGKey(int(seed_val))

    def reset(self):
        self.states_ = {}

    def split(self, name: str):
        if name not in self.states_:
            raise KeyError(f"rng state {name!r} not registered")
        self.states_[name], sub = jax.random.split(self.states_[name])
        return sub

    @contextmanager
    def rng_state(self, name: str = "global"):
        """Within the context, the global chain is swapped for the named chain."""
        if name not in self.states_:
            raise KeyError(f"rng state {name!r} not registered")
        t = rng_state_tensor()
        with _lock:
            saved = t._data
            t._data = self.states_[name]
        try:
            yield
        finally:
            with _lock:
                self.states_[name] = t._data
                t._data = saved


_TRACKER = RNGStatesTracker()


def get_rng_state_tracker() -> RNGStatesTracker:
    return _TRACKER
