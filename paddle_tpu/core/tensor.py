"""The eager Tensor.

Reference analog: `paddle::Tensor` over phi::DenseTensor (phi/core/dense_tensor.h:38) plus
the eager autograd meta (fluid/eager/eager_tensor.h). Here the storage is a jax.Array
living in HBM; autograd metadata (`_grad_node`, `_out_index`) wires it into the GradNode
reverse graph built by core.dispatch.

Paddle semantics preserved:
  - `stop_gradient` defaults to True for user-created tensors, False for Parameters.
  - `.grad` populated on leaves after backward(); `retain_grads()` for intermediates.
  - in-place mutation bumps `_version`; backward detects stale saved tensors.
Most math methods are monkey-patched on by `paddle_tpu.ops` (mirroring the reference's
monkey_patch_math_varbase pattern) to keep this module cycle-free.
"""
from __future__ import annotations

import sys
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..monitor import trace as _trace
from . import dtype as dtypes
from .device import Place, get_default_place
from .lazy import LazyArray

# what the not-ready waits of ``Tensor.numpy()`` have been taking
# (``trace.book``), a mean for each line of the caller's that waits (a
# loop's ``float(loss)`` and the fetch of an evaluation that takes a
# second are two lines, and neither is judged by the other): a wait far
# over its own line's mean is sealed as a host/stall record
_SYNC_MEANS: dict = {}


class Tensor:
    __slots__ = ("_data", "stop_gradient", "_grad", "_grad_node", "_out_index",
                 "name", "persistable", "trainable", "_version", "_retain_grad_flag",
                 "_grad_sharding", "_hooks", "__weakref__")

    def __init__(self, data, dtype=None, place: Optional[Place] = None,
                 stop_gradient: bool = True, name: Optional[str] = None):
        dt = dtypes.convert_dtype(dtype)
        if isinstance(data, Tensor):
            arr = data.value()
            if dt is not None and arr.dtype != dt:
                arr = arr.astype(dt)
        elif isinstance(data, (jax.Array, LazyArray)):
            arr = data if dt is None or data.dtype == dt else data.astype(dt)
        else:
            np_arr = np.asarray(data)
            if dt is not None:
                np_arr = np_arr.astype(dt)
            elif np_arr.dtype == np.float64:
                np_arr = np_arr.astype(np.float32)  # paddle default fp32
            elif np_arr.dtype == np.int64:
                # TPU-native deviation: int32 is the canonical integer dtype (XLA
                # default); the reference uses int64. String dtype "int64" is accepted
                # everywhere and maps here.
                np_arr = np_arr.astype(np.int32)
            arr = jnp.asarray(np_arr)
        if place is not None:
            arr = jax.device_put(arr, place.jax_device)
        self._data = arr
        self.stop_gradient = stop_gradient
        self._grad = None          # raw jax.Array accumulation
        self._grad_node = None
        self._out_index = 0
        self.name = name or ""
        self.persistable = False
        self.trainable = not stop_gradient
        self._version = 0
        self._retain_grad_flag = False

    # ------------------------------------------------------------- storage access

    def value(self) -> jax.Array:
        # the public boundary out of deferred-eager land: everything holding a
        # .value() result (optimizers, jit entry, collectives, user code) gets
        # a real jax.Array; internals that can stay lazy read ._data
        d = self._data
        if type(d) is LazyArray:
            d = d.force()
            self._data = d
        return d

    def numpy(self) -> np.ndarray:
        d = self.value()  # force + cache any pending lazy computation
        ready = getattr(d, "is_ready", None)
        if ready is None or ready():
            return np.asarray(d)
        # the host blocks here until the device has made the value: the
        # one place a training loop waits (``float(loss)``)
        with _trace.span("tensor/sync", bytes=d.nbytes) as sync:
            out = np.asarray(d)
        f = sys._getframe(1)
        while f.f_back is not None and f.f_code.co_filename == __file__:
            f = f.f_back          # out of item() / __float__ / tolist()
        at = f"{f.f_code.co_filename}:{f.f_lineno}"
        _trace.book(sync, [(at, sync.t1 - sync.t0)], _SYNC_MEANS, at=at)
        return out

    def item(self, *args):
        if args:
            return self.numpy().item(*args)
        return self.numpy().item()

    def tolist(self):
        return self.numpy().tolist()

    def __array__(self, dtype=None):
        a = self.numpy()
        return a.astype(dtype) if dtype is not None else a

    # ------------------------------------------------------------- metadata

    @property
    def shape(self):
        return list(self._data.shape)

    @property
    def dtype(self):
        return self._data.dtype

    @property
    def ndim(self) -> int:
        return self._data.ndim

    def dim(self) -> int:
        return self._data.ndim

    def rank(self) -> int:
        return self._data.ndim

    @property
    def size(self) -> int:
        return int(self._data.size)

    def numel(self) -> int:
        return int(self._data.size)

    @property
    def place(self) -> Place:
        devs = list(self._data.devices())
        return Place(devs[0]) if devs else get_default_place()

    @property
    def is_leaf(self) -> bool:
        return self._grad_node is None

    def __len__(self):
        if self._data.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self._data.shape[0]

    def __bool__(self):
        if self._data.size != 1:
            raise ValueError("The truth value of a multi-element Tensor is ambiguous")
        return bool(self.numpy())

    def __float__(self):
        return float(self.numpy())

    def __int__(self):
        return int(self.numpy())

    def __index__(self):
        return int(self.numpy())

    def __format__(self, spec):
        if self._data.size == 1:
            return format(self.item(), spec)
        return str(self)

    def __repr__(self):
        grad_info = "" if self.stop_gradient else ", stop_gradient=False"
        return (f"Tensor(shape={self.shape}, dtype={dtypes.dtype_name(self.dtype)}"
                f"{grad_info},\n       {np.array2string(self.numpy(), prefix='       ')})")

    # ------------------------------------------------------------- autograd surface

    @property
    def grad(self) -> Optional["Tensor"]:
        if self._grad is None:
            return None
        from .selected_rows import SelectedRows
        if isinstance(self._grad, SelectedRows):
            return self._grad  # sparse grads surface as SelectedRows
        return Tensor(self._grad, stop_gradient=True)

    @grad.setter
    def grad(self, value):
        from .selected_rows import SelectedRows
        if value is None or isinstance(value, SelectedRows):
            self._grad = value
        else:
            self._grad = value.value() if isinstance(value, Tensor) else jnp.asarray(value)

    def _apply_grad_hooks(self, g):
        """Run registered backward hooks on a flowing gradient; hooks fire when
        this tensor's grad is PRODUCED (leaf or intermediate) and a returned
        value replaces the cotangent for everything downstream — reference
        Tensor.register_hook semantics."""
        hooks = getattr(self, "_hooks", None)
        if not hooks:
            return g
        from .selected_rows import SelectedRows
        if isinstance(g, SelectedRows):
            # hooks see the dense view (reference hooks receive a Tensor);
            # a hook on a sparse-grad param forfeits the sparsity
            g = g.to_dense()
        for hook in list(hooks.values()):
            t_in = g if isinstance(g, Tensor) else Tensor(g)
            r = hook(t_in)
            if r is not None:
                g = r if isinstance(g, Tensor) else \
                    (r.value() if isinstance(r, Tensor) else r)
        return g

    def _accumulate_grad(self, g):
        # GradNodeAccumulation analog (reference: eager/accumulation/)
        from .selected_rows import SelectedRows
        sh = getattr(self, "_grad_sharding", None)
        if sh is not None and isinstance(g, SelectedRows):
            g = g.to_dense()  # sharded-grad params keep the dense contract
        if sh is not None and not isinstance(g, Tensor):
            # ZeRO stage-2 semantics: the gradient is sharded AT accumulation
            # (reduce-scatter), never held replicated on the tape — reference
            # GroupShardedStage2's slice-reduce hooks. lazy_device_put keeps
            # a pending deferred-eager grad lazy when device sets allow.
            from .lazy import lazy_device_put
            g = lazy_device_put(g, sh)
        if self._grad is None:
            self._grad = g
        else:
            self._grad = self._grad + g

    def register_hook(self, hook):
        """Backward hook on this tensor's gradient (reference
        Tensor.register_hook); returns a removable handle."""
        hooks = getattr(self, "_hooks", None)
        if hooks is None:
            hooks = {}
            self._hooks = hooks
        hid = max(hooks, default=-1) + 1
        hooks[hid] = hook

        class _Handle:
            def remove(_self):
                hooks.pop(hid, None)

        return _Handle()

    def backward(self, grad_tensor=None, retain_graph: bool = False):
        from .autograd import run_backward
        run_backward([self], [grad_tensor], retain_graph=retain_graph)

    def retain_grads(self):
        self._retain_grad_flag = True

    def clear_grad(self):
        self._grad = None

    def clear_gradient(self, set_to_zero: bool = False):
        if set_to_zero and self._grad is not None:
            from .selected_rows import SelectedRows
            if isinstance(self._grad, SelectedRows):
                self._grad = None  # sparse grads have no zero-filled form
            else:
                self._grad = jnp.zeros_like(self._grad)
        else:
            self._grad = None

    def detach(self) -> "Tensor":
        t = Tensor(self._data, stop_gradient=True)
        t.name = self.name
        return t

    def detach_(self) -> "Tensor":
        self._grad_node = None
        self.stop_gradient = True
        return self

    # ------------------------------------------------------------- mutation

    def _set_value_inplace(self, arr: jax.Array):
        """In-place value replacement; bumps version so stale autograd saves error out."""
        if tuple(arr.shape) != tuple(self._data.shape):
            raise ValueError(f"in-place shape mismatch {arr.shape} vs {self._data.shape}")
        from .dispatch import in_trace, trace_ctx
        if in_trace():
            ctx = trace_ctx()
            if ctx is not None:
                # inside a to_static trace: capture as a functional update; also set
                # _data so later in-trace reads chain off the new value (TraceContext
                # .restore() un-leaks the tracer when the trace ends)
                ctx.record_buffer_update(self, arr)
                self._data = arr
                return
        self._data = arr
        self._version += 1

    def set_value(self, value):
        if isinstance(value, Tensor):
            arr = value.value()
        elif isinstance(value, (jax.Array, LazyArray)):
            arr = value  # keep on device — np.asarray here would round-trip HBM→host
        else:
            arr = jnp.asarray(np.asarray(value))
        if arr.dtype != self._data.dtype:
            arr = arr.astype(self._data.dtype)
        self._set_value_inplace(arr)

    def copy_(self, other, blocking: bool = True):
        self.set_value(other)
        return self

    # ------------------------------------------------------------- device movement

    def to(self, *args, **kwargs):
        device = kwargs.get("device")
        dtype = kwargs.get("dtype")
        for a in args:
            if isinstance(a, Place) or (isinstance(a, str) and a.split(":")[0] in
                                        ("cpu", "tpu", "gpu", "xpu")):
                device = a
            else:
                dtype = a
        arr = self._data
        if dtype is not None:
            arr = arr.astype(dtypes.convert_dtype(dtype))
        if device is not None:
            from .device import set_device
            place = device if isinstance(device, Place) else _parse_place(device)
            arr = jax.device_put(arr, place.jax_device)
        t = Tensor(arr, stop_gradient=self.stop_gradient)
        t.name = self.name
        return t

    def cpu(self):
        from .device import CPUPlace
        return self.to(device=CPUPlace())

    def pin_memory(self):
        return self  # host pinning is a CUDA concept; no-op on TPU runtime

    def cuda(self, *a, **kw):
        from .device import TPUPlace
        return self.to(device=TPUPlace())


def _parse_place(device: str) -> Place:
    from .device import CPUPlace, TPUPlace
    if device.startswith("cpu"):
        return CPUPlace()
    idx = int(device.split(":")[1]) if ":" in device else 0
    return TPUPlace(idx)


class Parameter(Tensor):
    """Trainable tensor (reference: paddle.ParamBase / EagerParamBase)."""

    __slots__ = ("optimize_attr", "regularizer", "is_distributed")

    def __init__(self, data, dtype=None, name: Optional[str] = None, trainable: bool = True):
        super().__init__(data, dtype=dtype, stop_gradient=not trainable, name=name)
        self.persistable = True
        self.trainable = trainable
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.is_distributed = False

    def __repr__(self):
        return "Parameter containing:\n" + super().__repr__()


def wrap_outputs(outs_t, single, node):
    """Wrap raw arrays from dispatch into Tensors, wiring autograd edges."""
    import weakref
    tensors = []
    refs = []
    for i, o in enumerate(outs_t):
        diff = node is not None and jnp.issubdtype(o.dtype, jnp.inexact)
        t = Tensor(o, stop_gradient=not diff)
        if diff:
            t._grad_node = node
            t._out_index = i
            refs.append(weakref.ref(t))
        else:
            refs.append(None)
        tensors.append(t)
    if node is not None:
        # backward needs the output tensors to apply their hooks / retain-grad
        # on the FULLY ACCUMULATED cotangent (weakrefs: no cycle)
        node._out_refs = refs
    return tensors[0] if single else tuple(tensors)


def to_tensor(data, dtype=None, place=None, stop_gradient: bool = True) -> Tensor:
    """paddle.to_tensor analog."""
    return Tensor(data, dtype=dtype, place=place, stop_gradient=stop_gradient)
