"""Eager collective API.

Reference analog: python/paddle/distributed/communication/{all_reduce,all_gather,...}.py
lowering to ProcessGroupNCCL (process_group_nccl.cc) calls on comm streams.

TPU-native semantics — the "rank-stack" view: where the reference's rank r holds a
local tensor T_r, here there is ONE global array whose leading axis indexes ranks
(shape [n, ...], dim 0 sharded over the group's mesh axes). Collectives are ordinary
jnp ops with sharding constraints; under jit XLA lowers them to ICI collective HLOs
(all-reduce / all-gather / collective-permute) — the compiled equivalent of the
reference's eager NCCL calls. Every function also accepts an unsharded array and
places it onto the group first, so user scripts run unchanged on 1..N devices.
"""
from __future__ import annotations

import functools
from typing import List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.tensor import Tensor
from .group import Group, get_group


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


_REDUCERS = {
    ReduceOp.SUM: jnp.sum,
    ReduceOp.MAX: jnp.max,
    ReduceOp.MIN: jnp.min,
    ReduceOp.PROD: jnp.prod,
}


def _red_np(op):
    import numpy as np
    return {ReduceOp.SUM: np.sum, ReduceOp.MAX: np.max, ReduceOp.MIN: np.min,
            ReduceOp.PROD: np.prod, ReduceOp.AVG: np.sum}[op]


def _group_or_default(group) -> Group:
    return group if group is not None else get_group(0)


# --------------------------------------------------------- multi-process mode
#
# Under a launcher-spawned job (jax.distributed initialized, process_count>1)
# every rank is its OWN process holding a LOCAL tensor — the reference
# semantics (python/paddle/distributed/communication/all_reduce.py). The
# rank-stack dialect below remains the single-controller simulation; this
# backend handles the real per-process calls: collectives ride
# jax.experimental.multihost_utils (process_allgather + reduce for the
# reductions — O(world x bytes) moved per call, fine for eager/debug use;
# the compiled TrainStep path is the bandwidth-optimal psum), p2p rides the
# native C++ message bus with endpoints exchanged once at backend init.

def _mp_world() -> int:
    try:
        return jax.process_count()
    except Exception:
        return 1


def _mp_mode(group: Optional[Group]) -> bool:
    if _mp_world() <= 1:
        return False
    if group is not None and group.nranks != _mp_world():
        raise NotImplementedError(
            "multi-process eager collectives currently support the WORLD "
            "group; build sub-groups with compiled collectives (mesh axes)")
    return True


class _MPBackend:
    """Per-process backend: multihost collectives + bus p2p.

    The bus (endpoint exchange + TCP links) initializes EAGERLY at backend
    construction — i.e. on every rank's FIRST mp-collective call — so the
    endpoint all-gather is always the first global collective on every rank
    and can never pair with a different rank's data collective (a lazy
    exchange inside send/recv could).
    """

    _instance = None

    def __init__(self):
        self.rank = jax.process_index()
        self.world = jax.process_count()
        self._bus = None
        self._pending = {}          # src rank -> parked out-of-order arrays
        self._ensure_bus()

    @classmethod
    def get(cls) -> "_MPBackend":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    # ------------------------------------------------------- collectives

    def allgather_np(self, arr):
        """[world, ...] numpy across processes (same local shape on all)."""
        from jax.experimental import multihost_utils
        import numpy as np
        return np.asarray(multihost_utils.process_allgather(
            np.asarray(arr), tiled=False))

    # -------------------------------------------------- device fast path
    #
    # When every process addresses exactly one device (launcher CPU ranks;
    # one-chip-per-host TPU), the ranks form a 1-D global mesh and eager
    # all_reduce/all_gather can run as a jitted shard_map collective ON
    # DEVICE (XLA cross-process runtime) instead of the host
    # process_allgather round-trip — the reference's NCCL eager path analog.

    def _mesh(self):
        if not hasattr(self, "_mesh_cache"):
            self._mesh_cache = None
            try:
                import numpy as np
                from jax.sharding import Mesh
                devs = sorted(jax.devices(), key=lambda d: d.process_index)
                if (len(devs) == self.world
                        and len(jax.local_devices()) == 1):
                    self._mesh_cache = Mesh(np.array(devs), ("r",))
            except Exception:
                self._mesh_cache = None
        return self._mesh_cache

    def _dev_path_agreed(self):
        """Decide ONCE, collectively, whether the device fast path is usable.
        Each rank probes a tiny device all-reduce locally, then the ranks
        all-gather the success flags over the host path and enable the device
        path only if EVERY rank succeeded — a per-rank sticky fallback would
        let ranks diverge (some jitted-collective, some host-allgather) and
        deadlock the job with no diagnostic."""
        agreed = self.__dict__.get("_dev_agreed")
        if agreed is not None:
            return agreed
        import os
        import numpy as np
        # Two-round agreement, every round a HOST-path collective so the
        # global collective order is identical on all ranks regardless of
        # per-rank env/config drift:
        #   round 1: vote "willing to probe" (env var unset AND 1-D global
        #            mesh constructible — both are rank-local conditions).
        #            Only if EVERY rank is willing does anyone run the probe;
        #            a conditional probe would strand willing ranks inside
        #            the probe psum while a disabled rank skips past it.
        #   round 2: run the probe (a cross-process device psum) on all
        #            ranks, vote on its success.
        willing = (not os.environ.get("PADDLE_DISABLE_DEV_COLLECTIVE")
                   and self._mesh() is not None)
        flags = self.allgather_np(np.array([1 if willing else 0], np.int32))
        if flags.min() != 1:
            self._dev_agreed = False
            return False
        ok = False
        try:
            # Hazard note: if one rank dies between the willing vote and
            # joining the probe psum while peers are already inside it, the
            # job blocks on the backend's collective timeout — the probe is
            # one [1]-f32 psum to shrink that window. An all-ranks failure
            # (runtime without cross-process device collectives) raises on
            # every rank symmetrically and falls through to round 2.
            probe = self._dev_run(("probe",), np.zeros((1,), np.float32),
                                  lambda x: jax.lax.psum(x[0], "r")[None])
            ok = probe is not None
        except Exception:
            ok = False
        flags = self.allgather_np(np.array([1 if ok else 0], np.int32))
        self._dev_agreed = bool(flags.min() == 1)
        return self._dev_agreed

    def _dev_collective(self, kind, local, body):
        """Shared device-collective machinery: assemble the global [world,...]
        array from the local shard, run the cached jitted shard_map `body`,
        return this rank's output shard. Returns None when the collectively
        agreed decision (see _dev_path_agreed) is that the path is
        unavailable. A failure AFTER agreement raises loudly — silently
        falling back on one rank while others run the device collective
        would deadlock the job."""
        if not self._dev_path_agreed():
            return None
        try:
            return self._dev_run(kind, local, body)
        except Exception as e:
            raise RuntimeError(
                "device-collective fast path failed after all ranks agreed "
                f"to use it (rank {self.rank}, kind={kind!r}): {e!r}. "
                "Set PADDLE_DISABLE_DEV_COLLECTIVE=1 to force the host path "
                "on ALL ranks.") from e

    def _dev_run(self, kind, local, body):
        mesh = self._mesh()
        if mesh is None:
            return None
        import jax.numpy as _jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        local = _jnp.asarray(local)
        sh = NamedSharding(mesh, P("r"))
        garr = jax.make_array_from_single_device_arrays(
            (self.world,) + tuple(local.shape), sh,
            [jax.device_put(local[None], jax.local_devices()[0])])
        key = (kind, tuple(local.shape), str(local.dtype))
        fns = self.__dict__.setdefault("_dev_fns", {})
        fn = fns.get(key)
        if fn is None:
            fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("r"),
                                       out_specs=P("r")))
            fns[key] = fn
        out = fn(garr)
        return out.addressable_shards[0].data[0]

    def allreduce_dev(self, local, op):
        """Device-side all-reduce of each rank's local array; returns the
        reduced jax array, or None when the fast path is unavailable."""
        if op == ReduceOp.PROD:
            return None
        red = {ReduceOp.SUM: jax.lax.psum, ReduceOp.AVG: jax.lax.pmean,
               ReduceOp.MAX: jax.lax.pmax, ReduceOp.MIN: jax.lax.pmin}[op]
        return self._dev_collective(("ar", op), local,
                                    lambda x: red(x[0], "r")[None])

    def allgather_dev(self, local):
        """Device-side all-gather; [world, ...] jax array or None."""
        return self._dev_collective(
            "ag", local, lambda x: jax.lax.all_gather(x[0], "r")[None])

    def barrier(self):
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices("paddle_tpu_barrier")

    # --------------------------------------------------------------- p2p

    @staticmethod
    def _my_ip() -> str:
        """The address peers can reach: PADDLE_BIND_IP when set (must match
        the bus listener), else the interface that routes toward the jax
        coordinator (gethostbyname(hostname) maps to 127.0.1.1 on many
        distros — useless to remote ranks)."""
        import os
        import socket as _socket
        bind_ip = os.environ.get("PADDLE_BIND_IP")
        if bind_ip:
            return bind_ip
        master = os.environ.get("PADDLE_MASTER", "")
        if ":" in master:
            host, port = master.rsplit(":", 1)
            try:
                with _socket.socket(_socket.AF_INET,
                                    _socket.SOCK_DGRAM) as s:
                    s.connect((host, int(port)))  # no traffic; routing only
                    return s.getsockname()[0]
            except OSError:
                pass
        return _socket.gethostbyname(_socket.gethostname())

    def _ensure_bus(self):
        if self._bus is not None:
            return self._bus
        import numpy as np

        from .fleet_executor.bus import MessageBus
        bus = MessageBus(self.rank)
        port = bus.listen(0)
        ep = f"{self._my_ip()}:{port}".encode()
        assert len(ep) < 64
        padded = np.zeros(64, np.uint8)
        padded[:len(ep)] = np.frombuffer(ep, np.uint8)
        eps = self.allgather_np(padded)        # [world, 64]
        bus.open_mailbox(self.rank + 1)
        for r in range(self.world):
            raw = bytes(eps[r].tobytes()).rstrip(b"\x00").decode()
            host, p = raw.rsplit(":", 1)
            bus.route(r + 1, r)
            if r != self.rank:
                bus.connect(r, host, int(p))
        self._bus = bus
        return bus

    def send(self, arr, dst: int):
        import pickle

        import numpy as np
        bus = self._ensure_bus()
        a = np.asarray(arr)
        bus.send(self.rank + 1, dst + 1, 64,
                 pickle.dumps((a.dtype.str, a.shape, a.tobytes())))

    def recv(self, src: int):
        import pickle

        import numpy as np
        q = self._pending.get(src)
        if q:
            return q.pop(0)
        bus = self._ensure_bus()
        while True:
            msg = bus.recv(self.rank + 1, timeout_ms=300_000)
            if msg is None:
                raise TimeoutError(f"recv from rank {src} timed out")
            sender_actor, _typ, payload = msg
            dt, shape, raw = pickle.loads(payload)
            arr = np.frombuffer(raw, np.dtype(dt)).reshape(shape).copy()
            s = sender_actor - 1
            if s == src:
                return arr
            # reference recv(src) matches by source; park other senders
            self._pending.setdefault(s, []).append(arr)


def _stack_spec(group: Group, ndim: int) -> P:
    axes = group.axis_names
    ax0 = axes[0] if axes and len(axes) == 1 else (tuple(axes) if axes else None)
    return P(ax0, *([None] * (ndim - 1)))


def _place_on_group(arr: jax.Array, group: Group) -> jax.Array:
    """Shard dim 0 over the group axes (no-op if already so placed)."""
    mesh = group.mesh
    if mesh is None or group.nranks == 1:
        return arr
    target = NamedSharding(mesh, _stack_spec(group, arr.ndim))
    sh = getattr(arr, "sharding", None)
    if sh == target:
        return arr
    return jax.device_put(arr, target)


def _unwrap(x):
    return x.value() if isinstance(x, Tensor) else jnp.asarray(x)


@functools.lru_cache(maxsize=None)
def _jitted(op_key, mesh, axes, op=ReduceOp.SUM, nranks=None):
    spec_in = lambda nd: NamedSharding(mesh, P(axes[0] if len(axes) == 1
                                               else tuple(axes),
                                               *([None] * (nd - 1))))
    if op_key == "shard_reduce":
        # global array sharded over the group axes on dim 0: reduce shards
        def fn(x):
            y = x.reshape((nranks, x.shape[0] // nranks) + x.shape[1:])
            red = _REDUCERS.get(op, jnp.sum)(y, axis=0)
            if op == ReduceOp.AVG:
                red = jnp.sum(y, axis=0) / nranks
            return jax.lax.with_sharding_constraint(
                red, NamedSharding(mesh, P(*([None] * (x.ndim)))))
    elif op_key == "all_reduce":
        def fn(x):
            red = _REDUCERS.get(op, jnp.sum)
            y = red(x, axis=0, keepdims=True)
            if op == ReduceOp.AVG:
                y = jnp.sum(x, axis=0, keepdims=True) / x.shape[0]
            y = jnp.broadcast_to(y, x.shape)
            return jax.lax.with_sharding_constraint(y, spec_in(x.ndim))
    elif op_key == "reduce_scatter":
        def fn(x):
            red = _REDUCERS.get(op, jnp.sum)
            y = red(x, axis=0)
            if op == ReduceOp.AVG:
                y = jnp.sum(x, axis=0) / x.shape[0]
            return jax.lax.with_sharding_constraint(y, spec_in(x.ndim - 1))
    elif op_key == "all_gather":
        def fn(x):
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(*([None] * x.ndim))))
    elif op_key == "alltoall":
        def fn(x):
            y = jnp.swapaxes(x, 0, 1)
            return jax.lax.with_sharding_constraint(y, spec_in(x.ndim))
    else:
        raise KeyError(op_key)
    return jax.jit(fn)


def all_reduce(tensor, op: str = ReduceOp.SUM, group: Optional[Group] = None,
               sync_op: bool = True):
    """Multi-process mode (launcher jobs): every rank passes its LOCAL tensor
    and gets the cross-process reduction back — the reference per-process
    semantics. Single-controller mode: the rank-stack view, where every
    slice of dim 0 becomes the reduction of all slices."""
    if _mp_mode(group):
        be = _MPBackend.get()
        fast = be.allreduce_dev(_unwrap(tensor), op)
        if fast is not None:      # device collective (see _MPBackend fast path)
            if isinstance(tensor, Tensor):
                tensor._data = fast
                return tensor
            return Tensor(fast)
        stacked = be.allgather_np(_unwrap(tensor))
        red = _red_np(op)(stacked, axis=0)
        if op == ReduceOp.AVG:
            red = red / be.world
        if isinstance(tensor, Tensor):
            tensor._data = jnp.asarray(red)
            return tensor
        return Tensor(red)
    g = _group_or_default(group)
    x = _unwrap(tensor)
    if g.nranks <= 1:
        return tensor
    if x.shape[0] != g.nranks:
        # second accepted form: a GLOBAL array whose dim 0 is sharded EXACTLY
        # by the group's axes (group-axis order) — each rank's shard is its
        # "local tensor", and all_reduce reduces the shards elementwise (what
        # a ported per-process script means). Any other/mixed dim-0 sharding
        # would reshape into the wrong rank blocks, so it is rejected.
        spec = getattr(getattr(x, "sharding", None), "spec", None)
        d0 = None
        if spec is not None and len(tuple(spec)) >= 1:
            d0 = tuple(spec)[0]
        d0_t = tuple(d0) if isinstance(d0, tuple) else (d0,)
        # compare only non-singleton axes (size-1 axes don't partition), in
        # group-major order — a mismatch would reshape wrong rank blocks
        def nontrivial(axes):
            return tuple(a for a in axes
                         if a is not None and g.mesh.shape.get(a, 1) > 1)
        group_t = nontrivial(g.axis_names)
        ok = (nontrivial(d0_t) == group_t
              and all(a in g.axis_names for a in d0_t if a is not None))
        if ok and x.shape[0] % g.nranks == 0:
            out = _jitted("shard_reduce", g.mesh, g.axis_names, op,
                          nranks=g.nranks)(x)
        else:
            raise ValueError(
                f"all_reduce expects the rank-stack layout "
                f"[nranks={g.nranks}, ...] or a global array whose dim 0 is "
                f"sharded exactly by the group axes {group_t}; got shape "
                f"{tuple(x.shape)} with sharding {spec}. For sharded-model "
                f"gradients use the compiled path (shardings on the train "
                f"step).")
    else:
        x = _place_on_group(x, g)
        out = _jitted("all_reduce", g.mesh, g.axis_names, op)(x)
    if isinstance(tensor, Tensor):
        tensor._data = out
        return tensor
    return Tensor(out)


def reduce(tensor, dst: int = 0, op: str = ReduceOp.SUM,
           group: Optional[Group] = None, sync_op: bool = True):
    """Multi-process: rank dst gets the reduction of all LOCAL tensors,
    others keep theirs. Single-controller: only the dst slice gets the
    reduced value."""
    if _mp_mode(group):
        be = _MPBackend.get()
        stacked = be.allgather_np(_unwrap(tensor))
        if be.rank != dst:
            return tensor
        red = _red_np(op)(stacked, axis=0)
        if op == ReduceOp.AVG:
            red = red / be.world
        if isinstance(tensor, Tensor):
            tensor._data = jnp.asarray(red)
            return tensor
        return Tensor(red)
    g = _group_or_default(group)
    x = _unwrap(tensor)
    if g.nranks <= 1:
        return tensor
    x = _place_on_group(x, g)
    red = _jitted("all_reduce", g.mesh, g.axis_names, op)(x)
    out = x.at[dst].set(red[dst])
    if isinstance(tensor, Tensor):
        tensor._data = out
        return tensor
    return Tensor(out)


def all_gather(tensor_list: Optional[List] = None, tensor=None,
               group: Optional[Group] = None, sync_op: bool = True):
    """Gather every rank's slice; returns the full (replicated) stack.

    Call styles (reference parity): all_gather(tensor_list, tensor) appends each
    rank's tensor to tensor_list; all_gather(tensor=t) returns the stacked Tensor.
    """
    if tensor is None and tensor_list is not None and not isinstance(tensor_list, list):
        tensor, tensor_list = tensor_list, None
    if _mp_mode(group):
        be = _MPBackend.get()
        gathered = be.allgather_dev(_unwrap(tensor))
        if gathered is None:
            gathered = be.allgather_np(_unwrap(tensor))
        if tensor_list is not None:
            for i in range(gathered.shape[0]):
                tensor_list.append(Tensor(gathered[i]))
        return Tensor(gathered)
    g = _group_or_default(group)
    x = _unwrap(tensor)
    if g.nranks > 1:
        x = _place_on_group(x, g)
        x = _jitted("all_gather", g.mesh, g.axis_names)(x)
    stacked = Tensor(x)
    if tensor_list is not None:
        for i in range(x.shape[0]):
            tensor_list.append(Tensor(x[i]))
    return stacked


def all_gather_object(object_list: List, obj, group: Optional[Group] = None):
    """Multi-process: pickles each rank's object and gathers the real
    per-rank values. Single-controller: every rank's object is the same
    python object."""
    if _mp_mode(group):
        import pickle

        import numpy as np
        be = _MPBackend.get()
        blob = np.frombuffer(pickle.dumps(obj), np.uint8)
        n = np.asarray([blob.size], np.int64)
        sizes_all = be.allgather_np(n)
        max_n = int(sizes_all.max())
        padded = np.zeros(max_n, np.uint8)
        padded[:blob.size] = blob
        sizes = sizes_all[:, 0]
        blobs = be.allgather_np(padded)
        for r in range(be.world):
            object_list.append(pickle.loads(blobs[r][:sizes[r]].tobytes()))
        return object_list
    g = _group_or_default(group)
    object_list.extend([obj] * g.nranks)
    return object_list


def broadcast(tensor, src: int = 0, group: Optional[Group] = None,
              sync_op: bool = True):
    """Multi-process: every rank's LOCAL tensor becomes rank src's value.
    Single-controller: every slice of dim 0 becomes the src slice."""
    if _mp_mode(group):
        from jax.experimental import multihost_utils
        import numpy as np
        be = _MPBackend.get()
        # one source moves the data once (vs a full allgather)
        val = multihost_utils.broadcast_one_to_all(
            np.asarray(_unwrap(tensor)),
            is_source=(be.rank == src))
        if isinstance(tensor, Tensor):
            tensor._data = jnp.asarray(val)
            return tensor
        return Tensor(np.asarray(val))
    g = _group_or_default(group)
    x = _unwrap(tensor)
    if g.nranks <= 1:
        return tensor
    x = _place_on_group(x, g)
    y = jnp.broadcast_to(x[src:src + 1], x.shape)
    y = jax.device_put(y, NamedSharding(g.mesh, _stack_spec(g, x.ndim)))
    if isinstance(tensor, Tensor):
        tensor._data = y
        return tensor
    return Tensor(y)


def reduce_scatter(tensor, tensor_or_tensor_list=None, op: str = ReduceOp.SUM,
                   group: Optional[Group] = None, sync_op: bool = True):
    """Multi-process: each rank passes n local chunks; rank k receives the
    cross-rank reduction of chunk k. Single-controller: input rank-stack
    [n, n, ...] (dim 0 = source rank, dim 1 = destination chunk); output
    [n, ...] where slice k = reduction over sources of chunk k."""
    if _mp_mode(group):
        import numpy as np
        be = _MPBackend.get()
        src_in = tensor_or_tensor_list if tensor_or_tensor_list is not None \
            else tensor
        if isinstance(src_in, (list, tuple)):
            x = np.stack([np.asarray(_unwrap(t)) for t in src_in], 0)
        else:
            x = np.asarray(_unwrap(src_in))
            x = x.reshape((be.world, x.shape[0] // be.world) + x.shape[1:])
        gathered = be.allgather_np(x)        # [world, world, chunk...]
        red = _red_np(op)(gathered[:, be.rank], axis=0)
        if op == ReduceOp.AVG:
            red = red / be.world
        if isinstance(tensor, Tensor):
            tensor._data = jnp.asarray(red)
            return tensor
        return Tensor(red)
    g = _group_or_default(group)
    src = tensor_or_tensor_list if tensor_or_tensor_list is not None else tensor
    if isinstance(src, (list, tuple)):
        x = jnp.stack([_unwrap(t) for t in src], axis=0)
        x = jnp.broadcast_to(x[None], (g.nranks,) + x.shape) \
            if x.ndim >= 1 and x.shape[0] != g.nranks else x
    else:
        x = _unwrap(src)
    if g.nranks <= 1:
        out = x if not isinstance(src, (list, tuple)) else x[0]
    else:
        x = _place_on_group(x, g)
        out = _jitted("reduce_scatter", g.mesh, g.axis_names, op)(x)
    if tensor_or_tensor_list is not None and isinstance(tensor, Tensor):
        tensor._data = out
        return tensor
    return Tensor(out)


def alltoall(in_tensor_list, out_tensor_list=None, group: Optional[Group] = None,
             sync_op: bool = True):
    """Multi-process: each rank passes its LOCAL list of n chunks and gets
    back chunk[rank] from every rank. Single-controller rank-stack
    [n, n, ...]: out[j, i] = in[i, j]. List form gathers/scatters python
    lists for reference parity."""
    if _mp_mode(group):
        import numpy as np
        be = _MPBackend.get()
        x = np.stack([np.asarray(_unwrap(t)) for t in in_tensor_list], 0)
        gathered = be.allgather_np(x)          # [world, world, ...]
        outs = [Tensor(gathered[r, be.rank]) for r in range(be.world)]
        if out_tensor_list is not None:
            out_tensor_list.extend(outs)
        return outs
    g = _group_or_default(group)
    if isinstance(in_tensor_list, (list, tuple)):
        x = jnp.stack([_unwrap(t) for t in in_tensor_list], axis=0)
        x = x[None].repeat(g.nranks, 0) if x.ndim == 1 else x
    else:
        x = _unwrap(in_tensor_list)
    if g.nranks > 1:
        x = _place_on_group(x, g)
        x = _jitted("alltoall", g.mesh, g.axis_names)(x)
    else:
        x = jnp.swapaxes(x, 0, 1) if x.ndim >= 2 else x
    result = Tensor(x)
    if isinstance(out_tensor_list, list):
        for i in range(x.shape[0]):
            out_tensor_list.append(Tensor(x[i]))
    return result


def scatter(tensor, tensor_list=None, src: int = 0,
            group: Optional[Group] = None, sync_op: bool = True):
    """Multi-process: rank src's tensor_list is distributed — rank k
    receives tensor_list[k]. Single-controller: slice k of the result is
    tensor_list[k]."""
    if _mp_mode(group):
        from jax.experimental import multihost_utils
        import numpy as np
        be = _MPBackend.get()
        if be.rank == src:
            stacked = np.stack([np.asarray(_unwrap(t))
                                for t in tensor_list], 0)
        else:
            base = np.asarray(_unwrap(tensor))
            stacked = np.zeros((be.world,) + base.shape, base.dtype)
        full = multihost_utils.broadcast_one_to_all(
            stacked, is_source=(be.rank == src))
        val = np.asarray(full)[be.rank]
        if isinstance(tensor, Tensor):
            tensor._data = jnp.asarray(val)
            return tensor
        return Tensor(val)
    g = _group_or_default(group)
    if tensor_list is not None:
        x = jnp.stack([_unwrap(t) for t in tensor_list], axis=0)
    else:
        x = _unwrap(tensor)
    if g.nranks > 1:
        x = _place_on_group(x, g)
    if isinstance(tensor, Tensor):
        tensor._data = x
        return tensor
    return Tensor(x)


# --------------------------------------------------------------------- p2p
# Single-host eager p2p is an in-process mailbox (pipeline schedules use compiled
# ppermute over the pipe axis instead — fleet/meta_parallel/pp_utils).

_mailbox = {}


def send(tensor, dst: int = 0, group: Optional[Group] = None, sync_op: bool = True):
    """Multi-process: REAL point-to-point over the native message bus (TCP
    frames with the job's auth token — reference send over NCCL p2p).
    Single-controller: enqueue onto the group's FIFO mailbox; sender
    identity is not modeled, messages are delivered in send order. The
    compiled p2p path stays ppermute (fleet/meta_parallel/pp_utils)."""
    if _mp_mode(group):
        _MPBackend.get().send(_unwrap(tensor), dst)
        return
    g = _group_or_default(group)
    _mailbox.setdefault(g.id, []).append((dst, _unwrap(tensor)))


def recv(tensor, src: int = 0, group: Optional[Group] = None, sync_op: bool = True):
    """Multi-process: blocking matched-by-source receive over the bus.
    Single-controller: pop the oldest pending message (FIFO — see send)."""
    if _mp_mode(group):
        val = _MPBackend.get().recv(src)
        if isinstance(tensor, Tensor):
            tensor._data = jnp.asarray(val)
            return tensor
        return Tensor(val)
    g = _group_or_default(group)
    queue = _mailbox.get(g.id)
    if not queue:
        raise RuntimeError(f"recv: no message pending in group {g.id} "
                           f"(requested src={src})")
    _, val = queue.pop(0)
    if isinstance(tensor, Tensor):
        tensor._data = val
        return tensor
    return Tensor(val)


def barrier(group: Optional[Group] = None):
    """Multi-process: a real cross-process barrier; single-controller:
    device-level sync draining pending async work."""
    if _mp_mode(group):
        _MPBackend.get().barrier()
        return
    (jax.device_put(jnp.zeros(()), jax.devices()[0]) + 0).block_until_ready()


def wait(tensor, group: Optional[Group] = None, use_calc_stream: bool = True):
    x = _unwrap(tensor)
    if hasattr(x, "block_until_ready"):
        x.block_until_ready()
    return tensor


def split(x, size, operation="linear", axis=0, num_partitions=1, gather_out=True,
          weight_attr=None, bias_attr=None, name=None):
    """reference paddle.distributed.split: build a TP linear/embedding layer."""
    from .fleet.meta_parallel.mp_layers import (ColumnParallelLinear,
                                                RowParallelLinear,
                                                VocabParallelEmbedding)
    if operation == "embedding":
        layer = VocabParallelEmbedding(size[0], size[1], weight_attr=weight_attr)
        return layer(x)
    if axis == 0:
        layer = RowParallelLinear(size[0], size[1], weight_attr=weight_attr,
                                  has_bias=bias_attr is not False,
                                  input_is_parallel=False)
    else:
        layer = ColumnParallelLinear(size[0], size[1], weight_attr=weight_attr,
                                     has_bias=bias_attr is not False,
                                     gather_output=gather_out)
    return layer(x)
