"""Device/place management.

Reference analog: ``paddle.CPUPlace`` / ``paddle.CUDAPlace`` and the phi DeviceContext pool
(/root/reference/paddle/phi/backends/context_pool.h). On TPU there are no user-visible
streams — XLA executables are dispatched asynchronously by the runtime — so a "place" is
just a JAX device handle. The default place is the first accelerator if present.
"""
from __future__ import annotations

import functools
import threading

import jax

_state = threading.local()


class Place:
    """A device place. Wraps a jax.Device."""

    __slots__ = ("_device",)

    def __init__(self, device):
        self._device = device

    @property
    def jax_device(self):
        return self._device

    @property
    def device_type(self) -> str:
        return self._device.platform

    @property
    def device_id(self) -> int:
        return self._device.id

    def is_cpu_place(self) -> bool:
        return self._device.platform == "cpu"

    def is_tpu_place(self) -> bool:
        return self._device.platform == "tpu"

    def __eq__(self, other):
        return isinstance(other, Place) and self._device == other._device

    def __hash__(self):
        return hash(self._device)

    def __repr__(self):
        return f"Place({self._device.platform}:{self._device.id})"


def CPUPlace() -> Place:
    return Place(jax.devices("cpu")[0])


def TPUPlace(dev_id: int = 0) -> Place:
    accels = _accelerators()
    if not accels:
        raise RuntimeError(
            "no TPU/accelerator devices visible: jax.devices() is "
            f"{jax.devices()} (a CPU is never handed back as an accelerator "
            "place — use CPUPlace()/set_device('cpu') to run on the host)")
    return Place(accels[dev_id])


@functools.lru_cache(maxsize=None)
def _accelerators():
    return tuple(d for d in jax.devices() if d.platform != "cpu")


def set_device(device: str) -> Place:
    """set_device('tpu') / set_device('tpu:0') / set_device('cpu')."""
    if ":" in device:
        kind, _, idx = device.partition(":")
        idx = int(idx)
    else:
        kind, idx = device, 0
    if kind in ("tpu", "gpu", "xpu", "accel"):  # accept reference spellings
        place = TPUPlace(idx)
    elif kind == "cpu":
        place = CPUPlace()
    else:
        raise ValueError(f"unknown device string {device!r}")
    _state.place = place
    return place


def get_device() -> str:
    p = get_default_place()
    kind = "tpu" if p.is_tpu_place() else p.device_type
    return f"{kind}:{p.device_id}"


def get_default_place() -> Place:
    place = getattr(_state, "place", None)
    if place is None:
        place = Place(jax.devices()[0])
        _state.place = place
    return place


def device_count() -> int:
    return len(_accelerators())


def is_compiled_with_tpu() -> bool:  # parity: paddle.is_compiled_with_cuda
    return any(d.platform != "cpu" for d in jax.devices())
