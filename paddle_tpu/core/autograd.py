"""Tape-free eager autograd engine.

Reference analog: `fluid/eager/grad_node_info.h:168` (GradNodeBase with slots/edges),
`eager/backward.cc:104` (RunBackward: in-degree map + topological queue walk) and
`eager/accumulation/` (leaf grad accumulation). The structure here is the same — a reverse
graph of GradNodes discovered at dispatch time — but each node's backward is a cached XLA
executable produced by `jit(vjp(fwd))` rather than a generated CUDA grad kernel.
"""
from __future__ import annotations

import collections
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp


_MULTI_DEVICE = None  # lazily cached: device-set checks are per-op bwd overhead


class GradNode:
    """One node of the reverse graph: knows how to turn output cotangents into input grads."""

    __slots__ = ("name", "bwd_fn", "mode", "saved_primals", "saved_outs", "diff_idx",
                 "input_tensors", "out_metas", "released", "_saved_versions",
                 "_attr_key", "_in_items", "_out_refs")

    def __init__(self, name, bwd_fn, mode, saved_primals, saved_outs, diff_idx,
                 input_tensors, out_metas):
        self.name = name
        self.bwd_fn = bwd_fn
        self.mode = mode  # "generic" (recompute-vjp over diff_idx) | "explicit"
        self.saved_primals = saved_primals
        self.saved_outs = saved_outs
        self.diff_idx = diff_idx
        self.input_tensors = input_tensors  # Tensors at diff_idx positions
        self.out_metas = out_metas  # [(shape, dtype)] per output slot
        self.released = False
        # inplace-safety: snapshot input tensor versions (reference: eager/tensor_wrapper.h)
        self._saved_versions = tuple(t._version for t in input_tensors)

    def check_versions(self):
        for t, v in zip(self.input_tensors, self._saved_versions):
            if t._version != v:
                raise RuntimeError(
                    f"tensor used by {self.name} backward was modified in-place "
                    f"(version {t._version} != saved {v}); this would produce wrong "
                    f"gradients (reference analog: TensorWrapper inplace version check)")

    def _align_cotangent_devices(self, cotangents: Tuple) -> Tuple:
        """Pipeline backward p2p: when this node's saved primals live on a different
        device set than an incoming cotangent (stage boundary), re-place the
        cotangent onto the primals' devices — the reverse of the forward's
        activation transfer (reference: p2p_communication send_backward)."""
        import jax as _jax
        from jax.sharding import NamedSharding, PartitionSpec as _P

        global _MULTI_DEVICE
        if _MULTI_DEVICE is None:
            _MULTI_DEVICE = _jax.device_count() > 1
        if not _MULTI_DEVICE:
            return cotangents  # stage boundaries cannot exist on one device

        from .lazy import LazyArray, _placement_key

        def place_key(x):
            # deferred-eager aware: a pending LazyArray's placement is its
            # graph's routing key; forcing here would break fusion for the
            # common single-placement multi-device case
            if type(x) is LazyArray:
                if x._concrete is not None:
                    return _placement_key(x._concrete)
                return x._graph.pkey
            if isinstance(x, _jax.Array):
                return _placement_key(x)
            return None

        ref = None
        ref_key = None
        all_devs = set()
        try:
            for p in (self.saved_primals or ()):
                k = place_key(p)
                if k is not None:
                    all_devs |= set(k)
                    if ref_key is None or len(k) > len(ref_key):
                        ref_key = k
                        ref = p
        except Exception:
            return cotangents
        if ref is None:
            return cotangents
        out = []
        for c in cotangents:
            # create_graph cotangents are Tensors: align the inner array
            # in-place (placement doesn't affect the recorded history)
            inner = c._data if hasattr(c, "_data") else c
            ck = place_key(inner)
            # only a DISJOINT device set marks a stage boundary; overlapping
            # sets (e.g. single-device input + mesh-wide weight) are
            # jit-compatible
            if ck is not None and not (set(ck) & all_devs):
                if type(inner) is LazyArray:
                    inner = inner.force()  # stage boundary: flush the source
                if type(ref) is LazyArray:
                    ref = ref.force()
                sh = ref.sharding
                target = (NamedSharding(sh.mesh, _P())
                          if isinstance(sh, NamedSharding) else sh)
                aligned = _jax.device_put(inner, target)
                if hasattr(c, "_data"):
                    c._data = aligned
                else:
                    c = aligned
            out.append(c)
        return tuple(out)

    def run(self, cotangents: Tuple, create_graph: bool = False) -> List:
        """Returns list of (input_tensor, grad) pairs for diff inputs.

        create_graph=True replays the vjp through the dispatcher so the grads
        carry their own GradNodes (double-grad); cotangents are then Tensors."""
        if self.released:
            raise RuntimeError(
                f"trying to run backward of {self.name} a second time "
                f"(specify retain_graph=True the first time)")
        self.check_versions()
        if create_graph:
            if self.mode == "explicit":
                raise NotImplementedError(
                    f"double grad through op '{self.name}' (explicit backward) "
                    f"is not supported; use the generic-vjp form of the op")
            from . import dispatch
            cotangents = self._align_cotangent_devices(cotangents)
            grads = dispatch.record_bwd_call(
                self.name, self._attr_key, self.diff_idx, self._in_items,
                cotangents)
            return list(zip(self.input_tensors, grads))
        cotangents = self._align_cotangent_devices(cotangents)
        if self.mode == "explicit":
            grads = self.bwd_fn(self.saved_primals, self.saved_outs, cotangents)
            grads = [grads[i] for i in self.diff_idx]
        else:
            grads = self.bwd_fn(self.saved_primals, cotangents)
        return list(zip(self.input_tensors, grads))

    def release(self):
        self.saved_primals = None
        self.saved_outs = None
        self.released = True

    def __repr__(self):
        return f"GradNode({self.name})"


_FILL_CACHE: "collections.OrderedDict" = collections.OrderedDict()
_FILL_CACHE_BYTES = 0
_FILL_CACHE_BUDGET = 64 << 20  # total pinned HBM for seed constants
_FILL_CACHE_LOCK = threading.Lock()


def _cached_fill_small(shape, dt, v):
    global _FILL_CACHE_BYTES
    key = (shape, dt, v)
    with _FILL_CACHE_LOCK:
        arr = _FILL_CACHE.get(key)
        if arr is not None:
            _FILL_CACHE.move_to_end(key)
            return arr
    arr = jnp.full(shape, v, dt)
    with _FILL_CACHE_LOCK:
        if key not in _FILL_CACHE:
            # account by arr.nbytes on BOTH insert and evict: under x64
            # disabled, jnp.full canonicalizes 64-bit requests down to 32-bit
            # and the requested-dtype size would drift the counter upward
            _FILL_CACHE[key] = arr
            _FILL_CACHE_BYTES += arr.nbytes
            while _FILL_CACHE_BYTES > _FILL_CACHE_BUDGET and _FILL_CACHE:
                _, old = _FILL_CACHE.popitem(last=False)
                _FILL_CACHE_BYTES -= old.nbytes
    return arr


def _cached_fill(shape, dt, v):
    # zero/one cotangent seeds are immutable constants; each uncached
    # jnp.zeros is a device op of its own and the backward walk seeds one
    # per unused output slot (e.g. BN's mean/var outputs).
    # Only SMALL seeds are cached, and the cache is byte-budgeted (LRU
    # eviction at 64 MiB total) — an entry-count bound alone would let a
    # shape-diverse workload pin GiBs of constants for the process lifetime.
    n = dt.itemsize
    for s in shape:
        n *= s
    if n <= (1 << 20):
        return _cached_fill_small(shape, dt, v)
    return jnp.full(shape, v, dt)


def _ones_like_meta(meta):
    shape, dt = meta
    return _cached_fill(tuple(shape), jnp.dtype(dt), 1)


def _zeros_like_meta(meta):
    shape, dt = meta
    return _cached_fill(tuple(shape), jnp.dtype(dt), 0)


def _build_indegree(roots: Sequence[GradNode]) -> Dict[GradNode, int]:
    """BFS the reverse graph; in-degree of P = #consumer nodes reachable that feed P.

    Reference: getInDegreeMap, eager/backward.cc:22.
    """
    indeg: Dict[GradNode, int] = {}
    seen = set()
    queue = collections.deque(roots)
    for r in roots:
        indeg.setdefault(r, 0)
        seen.add(r)
    while queue:
        node = queue.popleft()
        for t in node.input_tensors:
            p = t._grad_node
            if p is None:
                continue
            indeg[p] = indeg.get(p, 0) + 1
            if p not in seen:
                seen.add(p)
                queue.append(p)
    return indeg


def run_backward(tensors: Sequence, grad_tensors: Optional[Sequence] = None,
                 retain_graph: bool = False, create_graph: bool = False,
                 accumulate_into: Optional[set] = None):
    """Reference analog: egr::RunBackward (eager/backward.cc:104).

    create_graph=True keeps cotangents as Tensors and records every vjp on the
    tape (higher-order grads). accumulate_into (a set of tensor ids) restricts
    which leaves receive .grad — paddle.grad's only_inputs semantics."""
    from .tensor import Tensor

    def _may_acc(t):
        return accumulate_into is None or id(t) in accumulate_into

    grad_tensors = grad_tensors or [None] * len(tensors)
    if len(grad_tensors) != len(tensors):
        raise ValueError("grad_tensors length must match tensors")

    # Per-node cotangent buffers, keyed by output slot (GradTensorHolder analog).
    buffers: Dict[GradNode, List] = {}
    roots: List[GradNode] = []

    def _acc(buf, slot, g):
        if buf[slot] is None:
            buf[slot] = g
        else:
            buf[slot] = buf[slot] + g

    def _zero_ct(meta):
        z = _zeros_like_meta(meta)
        return Tensor(z) if create_graph else z

    # leaf grads buffer until the walk ends so hooks fire ONCE on the fully
    # accumulated gradient (not per consumer partial)
    leaf_acc: Dict[int, list] = {}

    def _leaf_add(t, g):
        from .selected_rows import SelectedRows
        sh = getattr(t, "_grad_sharding", None)
        if sh is not None and isinstance(g, SelectedRows):
            g = g.to_dense()  # ZeRO-sharded params keep the dense contract
        if sh is not None and not isinstance(g, Tensor):
            # ZeRO stage-2 invariant: grads shard the moment they're produced,
            # even while buffered here — never a full replicated copy per
            # param. lazy_device_put records the re-placement into the lazy
            # graph when possible (a force here would flush per parameter
            # and undo the backward's fusion).
            from .lazy import lazy_device_put
            g = lazy_device_put(g, sh)
        ent = leaf_acc.get(id(t))
        if ent is None:
            leaf_acc[id(t)] = [t, g]
        else:
            ent[1] = ent[1] + g

    for t, g in zip(tensors, grad_tensors):
        if t.stop_gradient:
            raise RuntimeError("cannot call backward() on a tensor with stop_gradient=True")
        if g is None:
            if t.size != 1:
                raise RuntimeError(
                    "grad must be provided for non-scalar backward roots "
                    f"(shape {t.shape})")
            g_arr = _ones_like_meta((tuple(t.shape), t.dtype))
        else:
            g_arr = g.value() if isinstance(g, Tensor) and not create_graph \
                else (g if isinstance(g, Tensor) else jnp.asarray(g))
        if create_graph and not isinstance(g_arr, Tensor):
            g_arr = Tensor(g_arr)
        node = t._grad_node
        if node is None:
            # backward on a leaf: grad goes straight to .grad
            if _may_acc(t):
                _leaf_add(t, g_arr)
            continue
        buf = buffers.setdefault(node, [None] * len(node.out_metas))
        _acc(buf, t._out_index, g_arr)
        if node not in roots:
            roots.append(node)

    if not roots:
        for t, g in leaf_acc.values():
            t._accumulate_grad(t._apply_grad_hooks(g))
        return

    indeg = _build_indegree(roots)
    # Roots that also appear as producers of other roots keep their counted in-degree;
    # ready = in-degree 0 among accumulated-root nodes.
    ready = collections.deque(n for n in roots if indeg.get(n, 0) == 0)
    pending = {n: d for n, d in indeg.items()}
    visited = set()

    while ready:
        node = ready.popleft()
        if node in visited:
            continue
        visited.add(node)
        buf = buffers.pop(node, [None] * len(node.out_metas))
        # the node's output cotangents are now FULLY accumulated (every
        # consumer ran): fire the output tensors' hooks here — once, on the
        # total — and satisfy retain_grad with the post-hook value
        out_refs = getattr(node, "_out_refs", None)
        cts = []
        for i, (b, m) in enumerate(zip(buf, node.out_metas)):
            ct = b if b is not None else _zero_ct(m)
            t_out = (out_refs[i]() if out_refs and i < len(out_refs)
                     and out_refs[i] is not None else None)
            if t_out is not None and b is not None:
                ct = t_out._apply_grad_hooks(ct)
                if t_out._retain_grad_flag and not t_out.stop_gradient \
                        and _may_acc(t_out):
                    t_out._accumulate_grad(ct)
            cts.append(ct)
        cotangents = tuple(cts)
        for t, g in node.run(cotangents, create_graph=create_graph):
            if g is None:
                continue
            p = t._grad_node
            if p is None:
                if not t.stop_gradient and _may_acc(t):
                    _leaf_add(t, g)
            else:
                pbuf = buffers.setdefault(p, [None] * len(p.out_metas))
                _acc(pbuf, t._out_index, g)
        if not retain_graph:
            node.release()
        for t in node.input_tensors:
            p = t._grad_node
            if p is None or p in visited:
                continue
            pending[p] -= 1
            if pending[p] == 0:
                ready.append(p)

    # flush leaves: hooks see the accumulated total exactly once
    for t, g in leaf_acc.values():
        t._accumulate_grad(t._apply_grad_hooks(g))


def grad(outputs, inputs, grad_outputs=None, retain_graph=None, create_graph=False,
         only_inputs=True, allow_unused=False):
    """paddle.grad analog (reference: GeneralGrad in eager/backward.cc).

    Computes d(outputs)/d(inputs) without touching .grad of other leaves.
    create_graph=True records the backward on the tape (recorded-vjp ops), so
    the returned grads are differentiable — double/higher-order grad.
    """
    from .tensor import Tensor

    outputs = outputs if isinstance(outputs, (list, tuple)) else [outputs]
    inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
    if retain_graph is None:
        retain_graph = create_graph  # paddle semantics: create implies retain

    # Snapshot and clear target grads, run backward, collect, restore.
    saved = [(t, t._grad, t._retain_grad_flag) for t in inputs]
    for t in inputs:
        t._grad = None
        t._retain_grad_flag = True
    try:
        run_backward(outputs, grad_outputs, retain_graph=retain_graph,
                     create_graph=create_graph,
                     accumulate_into={id(t) for t in inputs})
        results = []
        for t in inputs:
            if t._grad is None:
                if not allow_unused:
                    raise RuntimeError(
                        "one of the inputs has no gradient path from outputs "
                        "(pass allow_unused=True to get None)")
                results.append(None)
            elif isinstance(t._grad, Tensor):
                # create_graph path: the grad carries its own GradNode
                results.append(t._grad)
            else:
                results.append(Tensor(t._grad, stop_gradient=True))
        return results
    finally:
        for t, g, flag in saved:
            t._grad = g
            t._retain_grad_flag = flag
