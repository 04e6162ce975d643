"""Custom C++ op extension.

Reference analog: python/paddle/utils/cpp_extension (CppExtension / load —
JIT-compile user C++ against the custom-op registry,
framework/custom_operator.cc). There, user kernels register into PHI and run
on device; here the TPU compute path is XLA, so custom C++ runs as a HOST
op: the user writes a C function over raw buffers, `load()` compiles it with
the native build harness, and the op enters the dispatcher via
jax.pure_callback — tape autograd, jit embedding and vmap come for free (a
host round-trip per call; custom DEVICE kernels belong in Pallas instead).

User C ABI (one function per op — unary elementwise over float32):
    extern "C" void <name>(const float* in, float* out, int64_t n);
(multi-input/attr-carrying signatures are future work; for device-side custom
kernels write Pallas instead.)
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dispatch import register_op
from ..core.tensor import Tensor
from ..ops._helpers import _op

__all__ = ["load", "CppExtension", "load_kernel_plugin",
           "plugin_include_dir"]

_BUILD_DIR = os.path.join(tempfile.gettempdir(), "paddle_tpu_extensions")


def _compile(name: str, sources: Sequence[str],
             extra_cxx_flags: Sequence[str] = ()) -> ctypes.CDLL:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    blobs = []
    for src in sources:
        if os.path.exists(src):
            with open(src) as f:
                blobs.append(f.read())
        else:
            blobs.append(src)  # inline source string
    digest = hashlib.sha256(("\x00".join(blobs) + "\x01"
                             + " ".join(extra_cxx_flags)).encode()
                            ).hexdigest()[:16]
    out = os.path.join(_BUILD_DIR, f"{name}_{digest}.so")
    if not os.path.exists(out):
        src_path = os.path.join(_BUILD_DIR, f"{name}_{digest}.cpp")
        src_tmp = f"{src_path}.tmp.{os.getpid()}"
        with open(src_tmp, "w") as f:
            f.write("\n".join(blobs))
        os.replace(src_tmp, src_path)   # atomic: parallel workers never read a
        # truncated translation unit
        tmp = f"{out}.tmp.{os.getpid()}"   # unique: fleet workers build in parallel
        cmd = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC",
               *extra_cxx_flags, src_path, "-o", tmp]
        proc = subprocess.run(cmd, capture_output=True, text=True)
        if proc.returncode != 0:
            raise RuntimeError(f"cpp_extension build of {name} failed:\n"
                               f"{proc.stderr[-2000:]}")
        os.replace(tmp, out)
    return ctypes.CDLL(out)


def load(name: str, sources: Sequence[str], functions: Sequence[str] = None,
         extra_cxx_flags: Sequence[str] = (), verbose: bool = False):
    """Compile + register custom ops; returns a module-like namespace whose
    attributes are the op entry points (reference cpp_extension.load)."""
    lib = _compile(name, sources, extra_cxx_flags)
    functions = list(functions or [name])
    ns = type(f"{name}_ops", (), {})()
    for fn_name in functions:
        setattr(ns, fn_name, _bind_unary(lib, fn_name, name))
    return ns


def _bind_unary(lib: ctypes.CDLL, fn_name: str, ext_name: str) -> Callable:
    cfn = getattr(lib, fn_name)
    cfn.restype = None
    cfn.argtypes = [ctypes.POINTER(ctypes.c_float),
                    ctypes.POINTER(ctypes.c_float), ctypes.c_int64]

    def host_kernel(x: np.ndarray) -> np.ndarray:
        x = np.ascontiguousarray(x, np.float32)
        out = np.empty_like(x)
        cfn(x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            ctypes.c_int64(x.size))
        return out

    # namespaced per extension: two extensions may export the same C symbol
    op_name = f"custom::{ext_name}::{fn_name}"

    def fwd(x):
        if not isinstance(x, jax.core.Tracer):
            # eager: run the C kernel directly on host memory (concrete array
            # round-trips through numpy)
            return jnp.asarray(host_kernel(np.asarray(x)))
        # traced (jit/to_static): embed as a host callback (works on CPU and
        # on the TPU runtime); device kernels belong in Pallas.
        return jax.pure_callback(
            host_kernel, jax.ShapeDtypeStruct(x.shape, jnp.float32),
            x.astype(jnp.float32), vmap_method="sequential")

    register_op(op_name, fwd, no_jit=True)

    def api(x, name=None):
        return _op(op_name, x)

    api.__name__ = fn_name
    api.__doc__ = f"Custom C++ op '{fn_name}' (host kernel via cpp_extension)."
    return api


class CppExtension:
    """Build-spec holder for setuptools-style usage (reference CppExtension)."""

    def __init__(self, sources: Sequence[str], name: Optional[str] = None,
                 extra_compile_args: Sequence[str] = ()):
        self.sources = list(sources)
        self.name = name
        self.extra_compile_args = list(extra_compile_args)

    def load(self, name: Optional[str] = None, functions=None):
        return load(name or self.name or "custom", self.sources,
                    functions=functions,
                    extra_cxx_flags=self.extra_compile_args)


# ------------------------------------------------------- kernel-plugin C API

_PTK_DTYPES = {0: np.float32, 1: np.float64, 2: np.int32, 3: np.int64,
               4: np.uint8, 5: np.bool_}
_PTK_CODES = {np.dtype(v): k for k, v in _PTK_DTYPES.items()}
PTK_MAX_NDIM = 8


class _PTKTensor(ctypes.Structure):
    _fields_ = [("data", ctypes.c_void_p),
                ("ndim", ctypes.c_int64),
                ("shape", ctypes.c_int64 * PTK_MAX_NDIM),
                ("dtype", ctypes.c_int32)]


def _as_ptk(arr: np.ndarray) -> "_PTKTensor":
    if arr.ndim > PTK_MAX_NDIM:
        raise ValueError(f"plugin ABI supports at most {PTK_MAX_NDIM} dims "
                         f"(plugin.h PTK_MAX_NDIM); got {arr.ndim}")
    if arr.dtype not in _PTK_CODES:
        raise ValueError(
            f"plugin ABI supports dtypes "
            f"{sorted(str(np.dtype(d)) for d in _PTK_CODES)}; got "
            f"{arr.dtype} (cast before the call — e.g. bfloat16 has no "
            f"stable C layout here)")
    t = _PTKTensor()
    t.data = arr.ctypes.data_as(ctypes.c_void_p)
    t.ndim = arr.ndim
    for i, s in enumerate(arr.shape):
        t.shape[i] = s
    t.dtype = _PTK_CODES[arr.dtype]
    return t


def plugin_include_dir() -> str:
    """Directory holding plugin.h (pass as -I to the plugin's build)."""
    return os.path.dirname(os.path.abspath(__file__))


def load_kernel_plugin(name: str, sources: Sequence[str], kernels: dict,
                       extra_cxx_flags: Sequence[str] = ()):
    """Kernel-plugin C API loader (reference analog: phi/capi — out-of-tree
    kernels against a stable C ABI; see plugin.h for the contract).

    kernels: {c_symbol: spec} where spec has
      n_in:  number of input tensors
      out:   fn(*(shape, np.dtype) specs) -> list of (shape, np.dtype)
             output specs — the InferMeta role
      grad:  optional c_symbol of a gradient kernel taking
             (inputs..., upstream-grads...) and writing input grads.

    Returns an object with one Python function per kernel, each also
    registered as a dispatch op (host/no_jit — the TPU path for custom
    device kernels is Pallas). With `grad`, the op is differentiable.
    """
    flags = ["-I" + plugin_include_dir()] + list(extra_cxx_flags)
    lib = _compile(name, sources, flags)
    ns = type("KernelPlugin", (), {})()

    def bind(sym: str, spec: dict):
        cfn = getattr(lib, sym)
        cfn.restype = ctypes.c_int
        cfn.argtypes = [ctypes.POINTER(_PTKTensor), ctypes.c_int,
                        ctypes.POINTER(_PTKTensor), ctypes.c_int]
        n_in = int(spec["n_in"])
        out_fn = spec["out"]

        def run_c(*arrays):
            if len(arrays) != n_in:
                raise TypeError(f"plugin kernel {sym!r} takes {n_in} "
                                f"tensors, got {len(arrays)}")
            ins = [np.ascontiguousarray(a) for a in arrays]
            out_specs = out_fn(*[(tuple(a.shape), a.dtype) for a in ins])
            outs = [np.empty(shape, dtype) for shape, dtype in out_specs]
            in_c = (_PTKTensor * len(ins))(*[_as_ptk(a) for a in ins])
            out_c = (_PTKTensor * len(outs))(*[_as_ptk(a) for a in outs])
            rc = cfn(in_c, len(ins), out_c, len(outs))
            if rc != 0:
                raise RuntimeError(f"plugin kernel {sym!r} failed (rc={rc})")
            return outs[0] if len(outs) == 1 else tuple(outs)

        op_name = f"plugin::{name}::{sym}"

        def _wrap_out(r):
            out = jnp.asarray(r)
            if out.dtype != r.dtype:
                raise TypeError(
                    f"plugin kernel {sym!r} declared a {r.dtype} output, "
                    f"which jax would silently downcast to {out.dtype} "
                    f"(enable x64 or declare a 32-bit output spec)")
            return out

        def fwd(*arrays):
            if any(isinstance(a, jax.core.Tracer) for a in arrays):
                # under jit/to_static: embed as a host computation with the
                # spec-declared output shapes (same pattern as _bind_unary);
                # backends without host callbacks reject this loudly
                specs = out_fn(*[(tuple(a.shape), np.dtype(a.dtype))
                                 for a in arrays])
                structs = [jax.ShapeDtypeStruct(sh, dt) for sh, dt in specs]
                res = jax.pure_callback(
                    run_c, structs[0] if len(structs) == 1 else tuple(structs),
                    *arrays, vmap_method="sequential")
                return res
            res = run_c(*[np.asarray(a) for a in arrays])
            if isinstance(res, tuple):
                return tuple(_wrap_out(r) for r in res)
            return _wrap_out(res)

        bwd = None
        gsym = spec.get("grad")
        if gsym is not None:
            gfn = getattr(lib, gsym)
            gfn.restype = ctypes.c_int
            gfn.argtypes = cfn.argtypes

            def bwd(primals, outs_saved, cotangents):
                ins = [np.ascontiguousarray(np.asarray(a)) for a in primals]
                cts = [np.ascontiguousarray(np.asarray(c))
                       for c in cotangents]
                grads = [np.empty_like(a) for a in ins]
                in_c = (_PTKTensor * (len(ins) + len(cts)))(
                    *[_as_ptk(a) for a in ins + cts])
                out_c = (_PTKTensor * len(grads))(
                    *[_as_ptk(g) for g in grads])
                rc = gfn(in_c, len(ins) + len(cts), out_c, len(grads))
                if rc != 0:
                    raise RuntimeError(
                        f"plugin grad kernel {gsym!r} failed (rc={rc})")
                return tuple(jnp.asarray(g) for g in grads)

        register_op(op_name, fwd, bwd=bwd, no_jit=True)

        def api(*tensors, name=None):
            return _op(op_name, *tensors)

        api.__name__ = sym
        api.__doc__ = (f"Plugin kernel '{sym}' ({n_in} inputs; host C ABI, "
                       f"see utils/plugin.h)")
        return api

    for sym, spec in kernels.items():
        setattr(ns, sym, bind(sym, spec))
    return ns
