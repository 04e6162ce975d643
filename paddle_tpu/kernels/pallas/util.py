"""Shared gates for the Pallas TPU kernel family."""
from __future__ import annotations

import jax


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
