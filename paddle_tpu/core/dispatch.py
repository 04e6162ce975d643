"""Eager op dispatch: the TPU-native replacement for the reference's PHI kernel machinery.

Reference analog: `phi/core/kernel_factory.h` (KernelKey select) + generated dygraph
`*_ad_func` forwards (`fluid/eager/auto_code_generator/generator/eager_gen.py:209`). There,
every op resolves to a hand-written CUDA kernel; here, every op is a small jax-traceable
function compiled once per (op, attrs, shapes, dtypes) into a cached XLA executable — the
idiomatic way to get "eager" dispatch on an AOT-compiled device (SURVEY.md §7 hard part a).

Backward rules come for free: the generic backward executable is `jit(vjp(fwd))`, where XLA
dead-code-eliminates whatever part of the recomputed forward the cotangent doesn't need
(e.g. matmul's vjp needs only the primal inputs, so the forward matmul is DCE'd away). Ops
may still register an explicit bwd for cases where recompute-vjp is wrong or wasteful.
"""
from __future__ import annotations

import functools
import threading
import time as _time
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import dtype as dtypes
from . import lazy
from .flags import flag


class OpDef:
    __slots__ = ("name", "fwd", "bwd", "nondiff_inputs", "no_jit")

    def __init__(self, name: str, fwd: Callable, bwd: Optional[Callable] = None,
                 nondiff_inputs: Sequence[int] = (), no_jit: bool = False):
        self.name = name
        self.fwd = fwd
        self.bwd = bwd  # explicit backward: bwd(primals, outs, cotangents, **attrs) -> grads tuple
        self.nondiff_inputs = frozenset(nondiff_inputs)
        # no_jit: execute fwd directly in eager (host ops that cannot live
        # inside an XLA executable, e.g. cpp_extension custom kernels)
        self.no_jit = no_jit


_REGISTRY: Dict[str, OpDef] = {}

# profiler host-tracer hook: fn(op_name, t_start, t_end) or None (see
# paddle_tpu.profiler; reference platform/profiler/host_tracer.cc)
_PROFILER_HOOK: Optional[Callable[[str, float, float], None]] = None


def set_profiler_hook(hook: Optional[Callable[[str, float, float], None]]):
    global _PROFILER_HOOK
    _PROFILER_HOOK = hook


# monitor hooks (paddle_tpu.monitor): op-mix counter fn(op_name) invoked per
# dispatch, and fn(op_name, attr_key) invoked once per NEW per-op executable
# (lru miss in the caches below). Both None when the monitor is disabled —
# the hot path pays one global read + None check, same deal as the profiler.
_MONITOR_OP: Optional[Callable[[str], None]] = None
_MONITOR_COMPILE: Optional[Callable[[str, Tuple], None]] = None


def set_monitor_hooks(op_hook: Optional[Callable[[str], None]],
                      compile_hook: Optional[Callable[[str, Tuple], None]]):
    global _MONITOR_OP, _MONITOR_COMPILE
    _MONITOR_OP = op_hook
    _MONITOR_COMPILE = compile_hook


# (name, attr_key, diff_idx, n_in) -> registered vjp-op name (double grad)
_VJP_NAMES: Dict[Tuple, str] = {}


def register_op(name: str, fwd: Callable, bwd: Optional[Callable] = None,
                nondiff_inputs: Sequence[int] = (), no_jit: bool = False) -> OpDef:
    op = OpDef(name, fwd, bwd, nondiff_inputs, no_jit)
    _REGISTRY[name] = op
    return op


def get_op(name: str) -> OpDef:
    return _REGISTRY[name]


# ---------------------------------------------------------------- grad / trace mode

_tls = threading.local()


def is_grad_enabled() -> bool:
    return getattr(_tls, "grad_enabled", True)


def set_grad_enabled(value: bool):
    _tls.grad_enabled = bool(value)


class no_grad:
    """Context manager + decorator disabling autograd recording (paddle.no_grad)."""

    def __enter__(self):
        self._prev = is_grad_enabled()
        set_grad_enabled(False)
        return self

    def __exit__(self, *exc):
        set_grad_enabled(self._prev)
        return False

    def __call__(self, fn):
        @functools.wraps(fn)
        def wrapper(*a, **kw):
            with no_grad():
                return fn(*a, **kw)
        return wrapper


class enable_grad:
    def __enter__(self):
        self._prev = is_grad_enabled()
        set_grad_enabled(True)
        return self

    def __exit__(self, *exc):
        set_grad_enabled(self._prev)
        return False


def in_trace() -> bool:
    """True while tracing a to_static program (dispatch must not re-jit per op)."""
    return getattr(_tls, "trace_depth", 0) > 0


def push_trace(ctx=None):
    stack = getattr(_tls, "trace_stack", None)
    if stack is None:
        stack = _tls.trace_stack = []
    stack.append(ctx)
    _tls.trace_depth = len(stack)
    _tls.trace_ctx = ctx


def pop_trace():
    # restore the ENCLOSING context (nested traces: e.g. jax.checkpoint
    # capture inside a TrainStep trace) — clearing only at depth 0 would
    # leave trace_ctx() pointing at the popped context
    stack = getattr(_tls, "trace_stack", [])
    if stack:
        stack.pop()
    _tls.trace_depth = len(stack)
    _tls.trace_ctx = stack[-1] if stack else None


def trace_ctx():
    return getattr(_tls, "trace_ctx", None)


def program_mesh():
    """The device mesh the program being traced is laid over, or None (eager,
    a single-device program, or a tracer that did not say). Inner contexts
    (recompute regions, pipeline stages) inherit the enclosing one's."""
    for ctx in reversed(getattr(_tls, "trace_stack", None) or ()):
        mesh = getattr(ctx, "mesh", None)
        if mesh is not None:
            return mesh
    return None


class TraceContext:
    """Collects functional side effects during a to_static trace.

    Reference analog: dy2static captures buffer writes (e.g. BN running stats) as
    program state vars; here they become extra outputs of the traced pure function,
    assigned back to the live buffers after each execution.
    """

    def __init__(self, mesh=None):
        self.buffer_updates = []  # list of (Tensor, traced_array)
        self.saved_data = {}      # id(Tensor) -> (tensor, pre-trace concrete array)
        # the mesh the traced program's own arrays live on, when the tracer
        # knows it (TrainStep reads it off its params and batch): what ops
        # that XLA cannot partition by itself need — see program_mesh()
        self.mesh = mesh

    def record_buffer_update(self, tensor, array):
        if id(tensor) not in self.saved_data:
            self.saved_data[id(tensor)] = (tensor, tensor._data)
        for i, (t, _) in enumerate(self.buffer_updates):
            if t is tensor:
                self.buffer_updates[i] = (t, array)
                return
        self.buffer_updates.append((tensor, array))

    def restore(self):
        """Undo in-trace mutations so no tracer leaks into live eager state."""
        for t, original in self.saved_data.values():
            t._data = original


# ---------------------------------------------------------------- executable caches


def _hashable(v):
    if isinstance(v, (list, tuple)):
        return tuple(_hashable(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _hashable(x)) for k, x in v.items()))
    if isinstance(v, np.dtype):
        return str(v)
    return v


def _attr_key(attrs: dict) -> Tuple:
    return tuple(sorted((k, _hashable(v)) for k, v in attrs.items()))


@functools.lru_cache(maxsize=None)
def _fwd_exec(name: str, attr_key: Tuple):
    op = _REGISTRY[name]
    attrs = dict((k, v) for k, v in attr_key)
    fn = functools.partial(op.fwd, **attrs) if attrs else op.fwd
    ch = _MONITOR_COMPILE
    if ch is not None:
        ch(name, attr_key)
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _raw_fwd(name: str, attr_key: Tuple):
    """Unjitted fwd with attrs baked — the lazy-graph node function."""
    op = _REGISTRY[name]
    attrs = dict((k, v) for k, v in attr_key)
    return functools.partial(op.fwd, **attrs) if attrs else op.fwd


@functools.lru_cache(maxsize=None)
def _bwd_exec(name: str, attr_key: Tuple, diff_idx: Tuple[int, ...], n_in: int):
    """Generic backward executable: recompute-vjp of fwd w.r.t. diff_idx inputs."""
    op = _REGISTRY[name]
    attrs = dict((k, v) for k, v in attr_key)

    def bwd(primals, cotangents):
        def f(*diff_primals):
            full = list(primals)
            for slot, p in zip(diff_idx, diff_primals):
                full[slot] = p
            out = op.fwd(*full, **attrs)
            return out if isinstance(out, (tuple, list)) else (out,)

        _, vjp_fn = jax.vjp(f, *[primals[i] for i in diff_idx])
        return vjp_fn(tuple(cotangents))

    ch = _MONITOR_COMPILE
    if ch is not None:
        ch(f"{name}@grad", attr_key)
    return jax.jit(bwd)


@functools.lru_cache(maxsize=None)
def _bwd_raw(name: str, attr_key: Tuple, diff_idx: Tuple[int, ...], n_in: int):
    """Flat-args unjitted generic vjp — the lazy-graph node function."""
    op = _REGISTRY[name]
    attrs = dict((k, v) for k, v in attr_key)

    def raw(*flat):
        primals, cts = flat[:n_in], flat[n_in:]

        def f(*diff_primals):
            full = list(primals)
            for slot, p in zip(diff_idx, diff_primals):
                full[slot] = p
            out = op.fwd(*full, **attrs)
            return out if isinstance(out, (tuple, list)) else (out,)

        _, vjp_fn = jax.vjp(f, *[primals[i] for i in diff_idx])
        return vjp_fn(tuple(cts))

    return raw


@functools.lru_cache(maxsize=None)
def _bwd_call(name: str, attr_key: Tuple, diff_idx: Tuple[int, ...], n_in: int):
    """Mode-agnostic generic-backward entry: records lazily when deferred-eager
    is active (the whole bwd walk fuses into the flush executable), otherwise
    runs the cached jitted vjp."""

    def call(primals, cotangents):
        hook = _PROFILER_HOOK
        t0 = _time.perf_counter() if hook is not None else 0.0
        if lazy.enabled():
            raw = _bwd_raw(name, attr_key, diff_idx, n_in)
            out = lazy.record(("gbwd", name, attr_key, diff_idx, n_in), raw,
                              tuple(primals) + tuple(cotangents))
        else:
            primals = tuple(lazy.concrete(p) for p in primals)
            cotangents = tuple(lazy.concrete(c) for c in cotangents)
            out = _bwd_exec(name, attr_key, diff_idx, n_in)(primals,
                                                            cotangents)
        if hook is not None:
            # backward dispatch event under the op's own name (the reference
            # host tracer records *_grad ops; profilers and coverage gates
            # see the backward under "name@grad")
            hook(f"{name}@grad", t0, _time.perf_counter())
        mon = _MONITOR_OP
        if mon is not None:
            mon(f"{name}@grad")
        return out

    return call


@functools.lru_cache(maxsize=None)
def _ebwd_raw(name: str, attr_key: Tuple, n_p: int, n_o: int):
    op = _REGISTRY[name]
    attrs = dict((k, v) for k, v in attr_key)

    def raw(*flat):
        ps, os_, cts = flat[:n_p], flat[n_p:n_p + n_o], flat[n_p + n_o:]
        return op.bwd(ps, os_, cts, **attrs)

    return raw


@functools.lru_cache(maxsize=None)
def _explicit_bwd_call(name: str, attr_key: Tuple):
    op = _REGISTRY[name]

    def call(primals, outs, cotangents):
        hook = _PROFILER_HOOK
        t0 = _time.perf_counter() if hook is not None else 0.0
        if lazy.enabled() and not op.no_jit:
            raw = _ebwd_raw(name, attr_key, len(primals), len(outs))
            res = lazy.record(
                ("ebwd", name, attr_key, len(primals), len(outs)), raw,
                tuple(primals) + tuple(outs) + tuple(cotangents))
        else:
            primals = tuple(lazy.concrete(p) for p in primals)
            outs = tuple(lazy.concrete(o) for o in outs)
            cotangents = tuple(lazy.concrete(c) for c in cotangents)
            res = _explicit_bwd_exec(name, attr_key)(primals, outs,
                                                     cotangents)
        if hook is not None:
            hook(f"{name}@grad", t0, _time.perf_counter())
        mon = _MONITOR_OP
        if mon is not None:
            mon(f"{name}@grad")
        return res

    return call


@functools.lru_cache(maxsize=None)
def _explicit_bwd_exec(name: str, attr_key: Tuple):
    op = _REGISTRY[name]
    attrs = dict((k, v) for k, v in attr_key)
    fn = functools.partial(op.bwd, **attrs) if attrs else op.bwd
    if op.no_jit:
        return fn   # host kernels (plugin C backwards) cannot live in jit
    return jax.jit(fn)


def clear_executable_cache():
    _fwd_exec.cache_clear()
    _bwd_exec.cache_clear()
    _explicit_bwd_exec.cache_clear()


# ---------------------------------------------------------------- dispatch entry


def _check_nan_inf(name, outs):
    for o in outs:
        if isinstance(o, jax.Array) and jnp.issubdtype(o.dtype, jnp.inexact):
            if bool(jnp.any(~jnp.isfinite(o))):
                raise FloatingPointError(
                    f"Operator {name} output contains NaN/Inf "
                    f"(FLAGS_check_nan_inf is enabled)")


def apply_op(name: str, tensor_args: Sequence, attrs: Optional[dict] = None):
    """Execute a registered op on Tensor/array inputs; record autograd if needed.

    Returns raw output(s) wrapped into Tensors by the caller-side helper in
    paddle_tpu.core.tensor (kept separate to avoid an import cycle).
    """
    from .tensor import Tensor, wrap_outputs  # local: cycle with tensor.py

    attrs = attrs or {}
    arrays = []
    requires = []
    in_tensors = []
    for a in tensor_args:
        if isinstance(a, Tensor):
            arrays.append(a._data)  # lazy-capable (value() would force)
            requires.append((not a.stop_gradient) and dtypes.is_differentiable(a.dtype))
            in_tensors.append(a)
        else:
            if isinstance(a, (jax.Array, lazy.LazyArray)):
                arrays.append(a)
            elif isinstance(a, (bool, int, float)) and not in_trace():
                # device constants, transferred once — a bare jnp.asarray(2.0)
                # is a host→device transfer of its own (milliseconds on a
                # TPU host), and scalar operands (BN momentum, scale
                # factors) appear on every op
                arrays.append(lazy.scalar_const(a))
            else:
                arrays.append(jnp.asarray(a))
            requires.append(False)
            in_tensors.append(None)

    from .amp_state import maybe_cast_inputs
    arrays = maybe_cast_inputs(name, arrays)

    op = _REGISTRY[name]
    key = _attr_key(attrs)
    record = is_grad_enabled() and any(requires)

    hook = _PROFILER_HOOK
    t0 = _time.perf_counter() if hook is not None else 0.0
    if in_trace() or op.no_jit:
        # Inside a to_static trace: call the raw function so everything inlines into the
        # enclosing jit; no per-op executables, no autograd tape (grad via whole-graph vjp).
        # no_jit ops (host kernels) also run raw: they cannot live in an executable.
        if op.no_jit:
            arrays = [lazy.concrete(a) for a in arrays]
        outs = op.fwd(*arrays, **attrs)
    elif lazy.enabled():
        # deferred eager: record into the lazy graph; one fused executable
        # materializes the whole pending stream on first observation
        outs = lazy.record(("fwd", name, key), _raw_fwd(name, key), arrays)
    else:
        arrays = [lazy.concrete(a) for a in arrays]
        outs = _fwd_exec(name, key)(*arrays)
    if hook is not None:
        # host-side dispatch cost (the reference host tracer's op event analog;
        # device time lives in the jax profiler trace)
        hook(name, t0, _time.perf_counter())
    mon = _MONITOR_OP
    if mon is not None:
        mon(name)

    single = not isinstance(outs, (tuple, list))
    outs_t = (outs,) if single else tuple(outs)

    if flag("FLAGS_check_nan_inf") and not in_trace():
        _check_nan_inf(name, outs_t)

    node = None
    if record and not in_trace():
        from .autograd import GradNode
        diff_idx = tuple(i for i, r in enumerate(requires)
                         if r and i not in op.nondiff_inputs)
        if diff_idx:
            if op.bwd is not None:
                bwd_fn = _explicit_bwd_call(name, key)
                mode = "explicit"
            else:
                bwd_fn = _bwd_call(name, key, diff_idx, len(arrays))
                mode = "generic"
            node = GradNode(name=name, bwd_fn=bwd_fn, mode=mode,
                            saved_primals=tuple(arrays),
                            saved_outs=outs_t if mode == "explicit" else None,
                            diff_idx=diff_idx,
                            input_tensors=tuple(in_tensors[i] for i in diff_idx),
                            out_metas=tuple((o.shape, o.dtype) for o in outs_t))
            # double-grad support: keep what record_bwd_call needs to replay
            # this node's vjp THROUGH the dispatcher (create_graph=True)
            node._attr_key = key
            node._in_items = tuple(t if t is not None else a
                                   for t, a in zip(in_tensors, arrays))

    return wrap_outputs(outs_t, single, node)


def record_bwd_call(name: str, attr_key: Tuple, diff_idx: Tuple[int, ...],
                    in_items: Tuple, cotangents: Tuple):
    """Run an op's generic vjp AS a dispatched op, so the backward computation
    is itself recorded on the tape — the mechanism behind create_graph=True
    (reference analog: GradNodes emitting ops with their own GradNodes,
    enabling eager double grad / GeneralGrad higher-order paths).

    The vjp op's own backward is jit(vjp(vjp_fwd)) — nested jax.vjp gives the
    second-order derivative. Returns grad Tensors aligned with diff_idx.
    """
    op = _REGISTRY[name]
    attrs = dict((k, v) for k, v in attr_key)
    n_in = len(in_items)
    # full-key map (not a truncated hash): a collision would silently run a
    # vjp with someone else's baked-in attrs/diff_idx
    vkey = (name, attr_key, diff_idx, n_in)
    vname = _VJP_NAMES.get(vkey)
    if vname is None:
        vname = f"vjp~{name}~{len(_VJP_NAMES)}"
        _VJP_NAMES[vkey] = vname
    if vname not in _REGISTRY:
        def vjp_fwd(*args):
            primals, cts = args[:n_in], args[n_in:]

            def f(*diff_primals):
                full = list(primals)
                for slot, p in zip(diff_idx, diff_primals):
                    full[slot] = p
                out = op.fwd(*full, **attrs)
                return out if isinstance(out, (tuple, list)) else (out,)

            _, vjp_fn = jax.vjp(f, *[primals[i] for i in diff_idx])
            grads = vjp_fn(tuple(cts))
            return grads if len(grads) > 1 else grads[0]

        register_op(vname, vjp_fwd)
    outs = apply_op(vname, tuple(in_items) + tuple(cotangents))
    return outs if isinstance(outs, tuple) else (outs,)
