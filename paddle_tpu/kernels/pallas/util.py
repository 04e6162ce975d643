"""Shared gates for the Pallas TPU kernel family."""
from __future__ import annotations

import jax

# The Pallas kernels of a recurrent state's decode step (``gdn_decode``,
# ``ssd_decode``) traced into programs, by name and in order: the engine reads
# it round its decode trace to say which step that executable took.
_STATE_KERNELS: list = []


def note_state_kernel(name: str) -> None:
    _STATE_KERNELS.append(name)


def state_kernels_traced(since: int = 0) -> list:
    return _STATE_KERNELS[since:]


# ... and what a call's attention went through, noted the same way
# (``paged_kernel``: ``paged_decode``'s K/V walk; ``mla_decode``: its latent
# geometry; ``key_walk``: a chunk's ``jax.numpy`` walk of its slot's key
# blocks, ``models/hybrid.py::walk_keys``), so that a silent fallback to the
# gathered view shows.
_ATTENTION_KERNELS: list = []


def note_attention_kernel(name: str) -> None:
    _ATTENTION_KERNELS.append(name)


def attention_kernels_traced(since: int = 0) -> list:
    return _ATTENTION_KERNELS[since:]


# ... and how a call wrote its new rows into the paged pools (``kernel``:
# ``pool_write``'s block copies; ``scatter``: XLA's), so that the spans of
# the calls say which write an executable was traced with.
_POOL_WRITES: list = []


def note_pool_write(name: str) -> None:
    _POOL_WRITES.append(name)


def pool_writes_traced(since: int = 0) -> list:
    return _POOL_WRITES[since:]


def tpu_placement(x) -> bool:
    """True when `x` will execute on a real TPU. Must NOT observe the value:
    under deferred eager a .value() here would flush the pending graph at
    every availability check. Concrete arrays answer from their devices;
    tracers and LazyArrays answer from where the program will run."""
    arr = getattr(x, "_data", x)
    if isinstance(arr, jax.Array) and not isinstance(arr, jax.core.Tracer):
        try:
            return any(d.platform == "tpu" for d in arr.devices())
        except Exception:
            pass
    return jax.default_backend() == "tpu"
