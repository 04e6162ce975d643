"""Deferred-eager execution: batch the eager op stream into fused XLA executables.

SURVEY.md §7 hard part (a) — per-op "eager" dispatch on an AOT-compiled device pays
one executable launch per op, a fixed host cost regardless of compute. The
reference hides per-op latency with a C++ async
dispatch queue (fluid/eager + phi kernels are microseconds on CUDA); the TPU-native
equivalent is *deferral*: record ops into a graph, materialize on observation, and
compile the whole pending region into ONE cached executable (the torch/XLA
"LazyTensor" design, rebuilt on jax primitives).

How it works:
  - `record(key, fn, args)` appends a node (a pure jax-traceable `fn` over flat
    array args) and returns `LazyArray` placeholders whose shapes/dtypes come from
    a cached `jax.eval_shape` — no device work at op time.
  - Any observation (`Tensor.value()`, `.numpy()`, `float()`, jit entry, …) calls
    `LazyArray.force()`, which flushes the WHOLE pending graph: all still-alive
    LazyArrays become outputs of one `jax.jit`-compiled replay function, cached by
    the graph's structural signature. A steady-state training loop hits the cache
    and runs fwd+bwd as a single executable per step — intermediates whose
    GradNodes were released during backward are dead by flush time, so XLA DCEs
    and fuses them exactly like a compiled train step.
  - Python scalars become device constants through `scalar_const` (cached): a
    bare `jnp.asarray(2.0)` is a host→device transfer of its own (milliseconds
    on a TPU host).

Enabled when FLAGS_eager_fusion is set, FLAGS_check_nan_inf is off, and no
to_static trace is active. Multi-device processes keep explicit per-op
placement semantics via PER-PLACEMENT graphs: ops are recorded into the lazy
graph matching their arguments' device set (committed single-device arrays
and mesh-sharded global arrays alike), a value crossing placements flushes
its source graph (flush-on-placement-change), and an op whose own arguments
span two placements executes eagerly so jax raises the same error it would
without fusion. Single-device processes skip the placement bookkeeping
entirely. Everything else (autograd tape, hooks, version counters) is
unchanged — laziness lives strictly below the Tensor layer.
"""
from __future__ import annotations

import threading
import weakref
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from .flags import flag

_tls = threading.local()

# (key, input avals) -> (out_treedef, [ShapeDtypeStruct]) — eval_shape is ~0.3 ms,
# far too slow to run per op; steady-state loops hit this cache.
_SHAPE_CACHE: Dict[Tuple, Tuple] = {}

# graph structural signature -> compiled replay executable
_EXEC_CACHE: Dict[Tuple, Any] = {}

# python scalar -> device constant (dedups the per-op host→device transfer)
_CONST_CACHE: Dict[Tuple, jax.Array] = {}

_MULTI: Optional[bool] = None

# sharding object -> canonical device-set key (placement routing, multi-device)
_PKEY_CACHE: Dict[Any, Optional[Tuple]] = {}

_MAX_NODES = 8192  # safety valve: unobserved streams flush periodically


def enabled() -> bool:
    if not flag("FLAGS_eager_fusion") or flag("FLAGS_check_nan_inf"):
        return False
    global _MULTI
    if _MULTI is None:
        _MULTI = jax.device_count() > 1
    return True


def _placement_key(a) -> Optional[Tuple]:
    """Canonical key for the device set a committed array is pinned to; None
    for uncommitted arrays (they follow whatever computation uses them)."""
    if not getattr(a, "_committed", True):
        return None
    sh = getattr(a, "sharding", None)
    if sh is None:
        return None
    try:
        k = _PKEY_CACHE.get(sh, _placement_key)  # sentinel: self
    except TypeError:
        return None  # unhashable sharding: treat as unconstrained
    if k is _placement_key:
        try:
            k = tuple(sorted(d.id for d in sh.device_set))
        except Exception:
            k = None
        if len(_PKEY_CACHE) > 4096:
            _PKEY_CACHE.clear()
        _PKEY_CACHE[sh] = k
    return k


def scalar_const(v) -> jax.Array:
    """Device constant for a python/numpy scalar, transferred once per value."""
    import jax.numpy as jnp
    key = (type(v).__name__, v)
    c = _CONST_CACHE.get(key)
    if c is None:
        if len(_CONST_CACHE) > 65536:
            _CONST_CACHE.clear()
        c = _CONST_CACHE[key] = jnp.asarray(v)
    return c


class _Node:
    __slots__ = ("key", "fn", "args", "out_refs", "sig")

    def __init__(self, key, fn, args, n_out):
        self.key = key
        self.fn = fn          # pure traceable: fn(*flat_arrays) -> pytree
        self.args = args      # [('l', leaf_idx) | ('n', node_idx, out_pos)]
        self.out_refs: List = [None] * n_out
        self.sig = (key, tuple(args))


class LazyGraph:
    __slots__ = ("nodes", "leaves", "leaf_ids", "flushed", "pkey")

    def __init__(self, pkey=None):
        self.nodes: List[_Node] = []
        self.leaves: List[jax.Array] = []
        self.leaf_ids: Dict[int, int] = {}
        self.flushed = False
        self.pkey = pkey  # placement routing key (multi-device only)

    def _leaf(self, arr) -> Tuple:
        i = self.leaf_ids.get(id(arr))
        if i is None:
            i = len(self.leaves)
            self.leaves.append(arr)
            self.leaf_ids[id(arr)] = i
        return ("l", i)

    def flush(self):
        if self.flushed:
            return
        self.flushed = True
        if _tls.__dict__.get("graph") is self:
            _tls.graph = None
        graphs = _tls.__dict__.get("graphs")
        if graphs is not None and graphs.get(self.pkey) is self:
            del graphs[self.pkey]
        if not self.nodes:
            return
        out_slots = []
        targets = []
        for ni, node in enumerate(self.nodes):
            for pos, ref in enumerate(node.out_refs):
                la = ref() if ref is not None else None
                if la is not None:
                    out_slots.append((ni, pos))
                    targets.append(la)
        leaf_avals = tuple(
            (a.shape, a.dtype, bool(getattr(a, "weak_type", False)))
            for a in self.leaves
        )
        sig = (tuple(n.sig for n in self.nodes), leaf_avals, tuple(out_slots))
        exe = _EXEC_CACHE.get(sig)
        if exe is None:
            exe = _EXEC_CACHE[sig] = jax.jit(_build_replay(self.nodes, out_slots))
        results = exe(self.leaves)
        for la, r in zip(targets, results):
            la._concrete = r
        # free the recorded graph (saved activations live on as jax Arrays only
        # where a LazyArray target still holds them)
        self.nodes = []
        self.leaves = []
        self.leaf_ids = {}


def _build_replay(nodes, out_slots):
    tree_leaves = jax.tree_util.tree_leaves

    def replay(leaves):
        env = []
        for node in nodes:
            args = [leaves[e[1]] if e[0] == "l" else env[e[1]][e[2]]
                    for e in node.args]
            env.append(tree_leaves(node.fn(*args)))
        return [env[i][p] for i, p in out_slots]

    return replay


class LazyArray:
    """Placeholder for a pending op output; quacks like a jax.Array for the
    Tensor layer (shape/dtype/astype), materializes on observation."""

    __slots__ = ("_graph", "_node", "_pos", "aval", "_concrete", "__weakref__")

    def __init__(self, graph, node, pos, aval):
        self._graph = graph
        self._node = node
        self._pos = pos
        self.aval = aval
        self._concrete = None

    # ---------------------------------------------------------------- metadata
    @property
    def shape(self):
        return self.aval.shape

    @property
    def dtype(self):
        return self.aval.dtype

    @property
    def ndim(self):
        return len(self.aval.shape)

    @property
    def size(self):
        n = 1
        for s in self.aval.shape:
            n *= s
        return n

    # ---------------------------------------------------------------- observe
    @property
    def weak_type(self):
        return getattr(self.aval, "weak_type", False)

    def force(self) -> jax.Array:
        if self._concrete is None:
            self._graph.flush()
            if self._concrete is None:
                raise RuntimeError(
                    "deferred-eager value lost: its graph was flushed earlier "
                    "without materializing it (a previous flush raised, or the "
                    "graph was flushed from another thread before this value "
                    "was recorded)")
        return self._concrete

    def block_until_ready(self):
        return self.force().block_until_ready()

    def devices(self):
        return self.force().devices()

    @property
    def sharding(self):
        # placement metadata is only final once materialized (a pending
        # value's sharding is whatever the flush executable assigns)
        return self.force().sharding

    def __jax_array__(self):
        return self.force()

    def __array__(self, dtype=None, copy=None):
        a = np.asarray(self.force())
        return a.astype(dtype) if dtype is not None else a

    def __float__(self):
        return float(np.asarray(self.force()))

    def __int__(self):
        return int(np.asarray(self.force()))

    def __bool__(self):
        return bool(np.asarray(self.force()))

    def __repr__(self):
        state = "pending" if self._concrete is None else "ready"
        return f"LazyArray({self.aval.shape}, {self.aval.dtype}, {state})"

    # ------------------------------------------------------------- lazy math
    # (the Tensor layer routes math through dispatch; these cover raw-array
    # touch points like gradient accumulation `a + b` in the autograd walk)
    def astype(self, dt):
        try:
            if self.dtype == np.dtype(dt):
                return self
        except TypeError:
            pass
        return record(("cast", str(dt)), lambda a: a.astype(dt), (self,))

    def reshape(self, *shape):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        return record(("lreshape", shape),
                      lambda a: a.reshape(shape), (self,))

    def _binop(self, name, fn, other, reverse=False):
        if isinstance(other, (int, float, bool)):
            other = scalar_const(other)
        elif not isinstance(other, (jax.Array, LazyArray)):
            return NotImplemented
        args = (other, self) if reverse else (self, other)
        return record((name, reverse), fn, args)

    def __add__(self, o):
        import jax.numpy as jnp
        return self._binop("ladd", jnp.add, o)

    def __radd__(self, o):
        import jax.numpy as jnp
        return self._binop("ladd", jnp.add, o, reverse=True)

    def __mul__(self, o):
        import jax.numpy as jnp
        return self._binop("lmul", jnp.multiply, o)

    def __rmul__(self, o):
        import jax.numpy as jnp
        return self._binop("lmul", jnp.multiply, o, reverse=True)

    def __sub__(self, o):
        import jax.numpy as jnp
        return self._binop("lsub", jnp.subtract, o)

    def __rsub__(self, o):
        import jax.numpy as jnp
        return self._binop("lsub", jnp.subtract, o, reverse=True)

    def __truediv__(self, o):
        import jax.numpy as jnp
        return self._binop("ldiv", jnp.divide, o)

    def __neg__(self):
        import jax.numpy as jnp
        return record(("lneg",), jnp.negative, (self,))


def concrete(x):
    """Materialize if lazy; pass anything else through."""
    return x.force() if type(x) is LazyArray else x


def _current_graph(pkey=None) -> LazyGraph:
    if not _MULTI:
        g = _tls.__dict__.get("graph")
        if g is None or g.flushed:
            # g.flushed: another thread forced this graph (flush() clears only
            # the OWNER's thread-local); recording into a flushed graph would
            # strand the new nodes — they'd never execute
            g = _tls.graph = LazyGraph()
        return g
    graphs = _tls.__dict__.setdefault("graphs", {})
    g = graphs.get(pkey)
    if g is None or g.flushed:
        g = graphs[pkey] = LazyGraph(pkey)
    return g


def flush_all():
    """Materialize every pending op on this thread (profiling/debug aid)."""
    g = _tls.__dict__.get("graph")
    if g is not None:
        g.flush()
    graphs = _tls.__dict__.get("graphs")
    if graphs:
        for g in list(graphs.values()):
            g.flush()


def record(key, fn: Callable, args: Sequence):
    """Record fn(*args) as a lazy node; returns fn's output pytree with
    LazyArray leaves. `key` must capture fn's behavior completely (it is the
    unit of the executable cache signature). `args` are jax Arrays, LazyArrays,
    or numpy arrays (anything np/python is promoted to a leaf)."""
    import jax.numpy as jnp

    pkey = None
    if _MULTI:
        pkeys = set()
        for a in args:
            if type(a) is LazyArray:
                if a._concrete is None:
                    pkeys.add(a._graph.pkey)
                else:
                    # a READY lazy value's placement is its concrete array's
                    # (a flushed jit output is committed) — missing this
                    # would route it as a leaf into a foreign-placement
                    # graph and poison that graph's flush
                    pkeys.add(_placement_key(a._concrete))
            elif isinstance(a, jax.Array):
                pkeys.add(_placement_key(a))
        pkeys.discard(None)  # uncommitted values follow; no constraint
        if len(pkeys) > 1:
            return _cross_placement(key, fn, args)
        pkey = next(iter(pkeys)) if pkeys else None

    g = _current_graph(pkey)
    if len(g.nodes) >= _MAX_NODES:
        g.flush()
        g = _current_graph(pkey)

    encoded = []
    avals = []
    for a in args:
        if type(a) is LazyArray:
            if a._concrete is not None or a._graph is not g:
                arr = a.force()
                encoded.append(g._leaf(arr))
                avals.append((arr.shape, arr.dtype, arr.weak_type))
            else:
                encoded.append(("n", a._node, a._pos))
                avals.append((a.aval.shape, a.aval.dtype, False))
        else:
            if not isinstance(a, jax.Array):
                a = jnp.asarray(a)
            encoded.append(g._leaf(a))
            # weak_type matters: jnp.asarray(2.0) is weak f32, and
            # bf16 * weak-f32 stays bf16 — dropping weakness here would make
            # the recorded dtype diverge from the flushed value
            avals.append((a.shape, a.dtype, getattr(a, "weak_type", False)))

    shape_key = (key, tuple(avals))
    cached = _SHAPE_CACHE.get(shape_key)
    if cached is None:
        structs = [jax.core.ShapedArray(s, d, weak_type=w) for s, d, w in avals]
        out_struct = jax.eval_shape(fn, *structs)
        leaves, treedef = jax.tree_util.tree_flatten(out_struct)
        cached = _SHAPE_CACHE[shape_key] = (treedef, tuple(leaves))
    treedef, out_avals = cached

    node_idx = len(g.nodes)
    node = _Node(key, fn, tuple(encoded), len(out_avals))
    g.nodes.append(node)
    las = []
    for pos, aval in enumerate(out_avals):
        la = LazyArray(g, node_idx, pos, aval)
        node.out_refs[pos] = weakref.ref(la)
        las.append(la)
    return jax.tree_util.tree_unflatten(treedef, las)


def _cross_placement(key, fn, args):
    """An op whose arguments span two committed placements. Unfused eager
    would never have committed SCALAR intermediates (python-scalar math
    stays uncommitted), but a flushed graph's outputs are committed — so
    replicate stray scalar operands onto the placement owning the bulk of
    the data and retry the lazy record. If real tensors genuinely span
    placements, execute eagerly so jax raises the same error it would
    without fusion.

    Deliberate deviation: a USER-committed 1-element array gets the same
    silent transfer (we cannot tell it apart from a flushed intermediate).
    Unfused jax would raise there; following the bulk data is both harmless
    numerically and what the reference framework does with scalar
    operands."""
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    conc = [concrete(a) for a in args]
    sizes: Dict[Tuple, int] = {}
    rep: Dict[Tuple, jax.Array] = {}
    for a in conc:
        if isinstance(a, jax.Array):
            k = _placement_key(a)
            if k is not None:
                sizes[k] = sizes.get(k, 0) + a.size
                rep.setdefault(k, a)
    target = max(sizes, key=sizes.get)
    sh = rep[target].sharding
    if isinstance(sh, NamedSharding):
        repl = NamedSharding(sh.mesh, PartitionSpec())
    elif isinstance(sh, SingleDeviceSharding):
        repl = sh
    else:
        return fn(*conc)
    moved, ok = [], True
    for a in conc:
        if isinstance(a, jax.Array):
            k = _placement_key(a)
            if k is not None and k != target:
                if a.size <= 1:
                    a = jax.device_put(a, repl)
                else:
                    ok = False
        moved.append(a)
    if not ok:
        return fn(*moved)  # genuine cross-placement: surface jax's error
    return record(key, fn, moved)


def lazy_device_put(g, sh):
    """device_put that stays lazy when it can: a pending LazyArray whose
    graph's device set matches the target sharding's records the re-placement
    INTO the graph (device_put is jit-traceable), so per-parameter grad
    sharding doesn't flush the backward once per param. Anything else
    concretizes and places eagerly."""
    if type(g) is LazyArray and g._concrete is None:
        try:
            tk = tuple(sorted(d.id for d in sh.device_set))
        except Exception:
            tk = None
        if tk is not None and g._graph.pkey in (None, tk):
            # with_sharding_constraint, NOT device_put: inside the flush jit
            # GSPMD ignores device_put's placement for outputs (measured:
            # the flushed grad came back replicated), while a constraint
            # pins the output sharding
            return record(
                ("dput", sh),
                lambda a: jax.lax.with_sharding_constraint(a, sh), (g,))
    return jax.device_put(concrete(g), sh)


def cache_stats():
    return {"shape_cache": len(_SHAPE_CACHE), "exec_cache": len(_EXEC_CACHE),
            "const_cache": len(_CONST_CACHE)}


def clear_caches():
    _SHAPE_CACHE.clear()
    _EXEC_CACHE.clear()
    _CONST_CACHE.clear()
