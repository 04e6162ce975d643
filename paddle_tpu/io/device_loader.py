"""DeviceLoader — double-buffered host→device batch pipeline.

Reference analog: tf.data's ``prefetch_to_device`` and torch_xla's
``MpDeviceLoader`` — an accelerator that idles between steps waiting for the
next batch's collate + H2D transfer is pure lost MFU. The DataLoader already
hides decode/collate behind worker threads/processes; this layer hides the
*transfer*: a background thread pulls collated batches and ``jax.device_put``s
them ahead of consumption with a bounded prefetch depth, so step N+1's
transfer overlaps step N's device compute.

Sharding-aware: under a DP/TP mesh pass ``sharding=`` (a
``jax.sharding.Sharding`` applied to every array leaf, or a callable
``leaf_array -> Sharding`` for per-leaf placement — see ``batch_sharding``)
and the loader materializes correctly-placed global arrays off the critical
path, exactly the placement ``jit``/``TrainStep`` would otherwise have to
force at dispatch time.

Attribution: the loader makes one ``monitor.trace`` span call per phase —
``loader/wait`` (consumer stall: feed time that was NOT hidden),
``loader/fetch`` and ``loader/h2d`` (producer-side work that WAS hidden).
They lie in any profile being taken (``paddle/loader/*``), in the span ring,
and as ``stage`` events in a recording ``paddle.profiler.Profiler``, so
host-feed vs device-compute overlap is directly observable.
"""
from __future__ import annotations

import queue
import threading
import weakref
from typing import Callable, Optional, Union

import jax
import numpy as np

from .. import monitor as _monitor
from ..monitor import trace as _trace
from ..core.tensor import Tensor

__all__ = ["DeviceLoader", "batch_sharding", "stack_microbatches"]


def stack_microbatches(batches):
    """Stack K collated batches leaf-wise along a NEW leading axis.

    The result is the input format of ``jit.TrainStep(accumulate_steps=K)``:
    every array leaf gains a leading axis of length K. Host leaves (ndarray)
    stack on host — the cheap place, before the H2D transfer; device leaves
    (Tensor / jax.Array) stack on device to avoid a D2H round-trip."""
    b0 = batches[0]
    if isinstance(b0, tuple) and hasattr(b0, "_fields"):
        return type(b0)(*(stack_microbatches([b[i] for b in batches])
                          for i in range(len(b0))))
    if isinstance(b0, (list, tuple)):
        return type(b0)(stack_microbatches([b[i] for b in batches])
                        for i in range(len(b0)))
    if isinstance(b0, dict):
        return {k: stack_microbatches([b[k] for b in batches]) for k in b0}
    if isinstance(b0, Tensor):
        import jax.numpy as jnp
        return Tensor(jnp.stack([t.value() for t in batches]))
    if isinstance(b0, jax.Array):
        import jax.numpy as jnp
        return jnp.stack(list(batches))
    return np.stack([np.asarray(b) for b in batches])


def _stacked_iter(inner, k: int):
    """Group the inner iterator into stacks of K microbatches (one TrainStep
    call each). A trailing group of fewer than K batches is dropped —
    ``drop_last`` semantics, the accumulation window needs exactly K."""
    try:
        while True:
            group = []
            for _ in range(k):
                try:
                    group.append(next(inner))
                except StopIteration:
                    return
            yield stack_microbatches(group)
    finally:
        close = getattr(inner, "close", None)
        if close is not None:
            try:
                close()
            except Exception:
                pass


def batch_sharding(mesh, axis_name=None):
    """Per-leaf sharding callable: shard the leading (batch) axis over
    ``axis_name``, replicate the rest — the standard DP input placement.

    ``axis_name=None`` (default) picks every data-like mesh axis with
    degree > 1 out of ("data", "sharding"): a ZeRO sharding group IS a
    data-parallel group, so its inputs shard over the "sharding" axis too,
    composed with plain DP when both are present. Pass an explicit name (or
    tuple of names) to override."""
    from jax.sharding import NamedSharding, PartitionSpec

    if axis_name is None:
        axes = tuple(a for a in ("data", "sharding")
                     if mesh.shape.get(a, 1) > 1)
        # a single axis stays a plain name (spec prints/compares as before)
        axis_name = axes[0] if len(axes) == 1 else (axes if axes else "data")

    def leaf_sharding(arr):
        spec = [None] * max(int(getattr(arr, "ndim", 0)), 0)
        if spec:
            spec[0] = axis_name
        return NamedSharding(mesh, PartitionSpec(*spec))

    return leaf_sharding


_END = object()


def _produce(inner, put_fn, q, stop, state):
    """Producer thread body. MODULE-LEVEL on purpose: a running thread is a
    GC root, so a bound-method target would pin the iterator object forever
    and its __del__ (the abandonment teardown) could never fire. The thread
    only holds the pieces it needs; the iterator stays collectable."""
    try:
        while not stop.is_set():
            # producer-side work, as spans the NEXT step trace adopts: the
            # waterfall shows fetch/H2D that ran (hidden or not) ahead of
            # that step's dispatch
            with _trace.span("loader/fetch", "step"):
                try:
                    batch = next(inner)
                except StopIteration:
                    break
            with _trace.span("loader/h2d", "step"):
                on_device = put_fn(batch)
            # bounded put that notices abandonment (same pattern as
            # DataLoader._PrefetchIterator): a consumer that stopped
            # iterating must not leave this thread blocked forever
            while not stop.is_set():
                try:
                    q.put(on_device, timeout=0.2)
                    break
                except queue.Full:
                    continue
    except BaseException as e:  # propagate to the consumer
        state["err"] = e
    finally:
        close = getattr(inner, "close", None)
        if close is not None:
            try:
                close()
            except Exception:
                pass
        # stop-aware END delivery: a single bounded put could time out while
        # the consumer is busy on a full queue, leaving it blocked on get()
        # forever once it drains the queue
        while not stop.is_set():
            try:
                q.put(_END, timeout=0.2)
                break
            except queue.Full:
                continue


class _DeviceIterator:
    """One pass over the inner loader: background transfer thread + bounded
    queue. ``close()`` is idempotent and joins the thread; dropping the last
    reference (abandoned iteration) tears the thread down via __del__."""

    def __init__(self, inner_iter, put_fn: Callable, depth: int,
                 owner=None):
        self._q = queue.Queue(maxsize=max(int(depth), 1))
        self._stop = threading.Event()
        self._state = {"err": None}
        self._done = False
        # keep the owning DeviceLoader alive for the duration of the
        # iteration: the loader only holds US weakly, so without this ref a
        # temporary like `iter(DeviceLoader(...))` can be collected mid-epoch
        # and its __del__ would tear down this live iteration
        self._owner = owner
        self._thread = threading.Thread(
            target=_produce, args=(inner_iter, put_fn, self._q, self._stop,
                                   self._state),
            daemon=True, name="DeviceLoader-prefetch")
        self._thread.start()

    def __iter__(self):
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        # consumer stall ahead of the next step: adopted by that step's
        # trace, so "slow step" splits into waited-on-feed vs dispatch
        with _trace.span("loader/wait", "step") as wait:
            item = self._q.get()
            qsize = self._q.qsize()
            wait.set(qsize=qsize)
        if item is _END:
            self._done = True
            err = self._state["err"]
            if err is not None:
                self._state["err"] = None
                raise err
            raise StopIteration
        mon = _monitor._active
        if mon is not None:
            # feed-health telemetry: queue depth gauge + stall counter (a
            # blocking get means the producer lost the race this step; the
            # terminal END wait above is epoch teardown, not a stall)
            mon.loader_wait(wait.dur_s, qsize, span=(wait.t0, wait.t1))
        return item

    def close(self):
        """Stop the producer and release its queue slots; safe to call from
        ``finally`` blocks and repeatedly."""
        self._stop.set()
        # drain so a producer blocked in put() observes the stop quickly
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=10.0)
        self._done = True

    def __del__(self):
        self._stop.set()


class DeviceLoader:
    """Wrap a :class:`DataLoader` (or any iterable of batches) so batches
    arrive already resident on device.

    Args:
        loader: the inner batch source. Each batch may be a Tensor, an
            ndarray, or a (possibly nested) list/tuple/dict of them.
        prefetch_depth: how many device-resident batches to hold ahead of the
            consumer (the double-buffer depth; 2 hides one full transfer).
        sharding: ``None`` (default device placement), a
            ``jax.sharding.Sharding`` applied to every leaf, or a callable
            ``leaf_array -> Sharding`` (see :func:`batch_sharding`).
        device: optional ``jax.Device`` target when ``sharding`` is None.
        stack_batches: K > 1 stacks every K consecutive collated batches
            leaf-wise along a new leading axis *before* the H2D transfer —
            one prefetch slot then carries a full
            ``jit.TrainStep(accumulate_steps=K)`` accumulation window. A
            trailing partial group is dropped (``drop_last`` semantics).
    """

    def __init__(self, loader, prefetch_depth: int = 2,
                 sharding: Union[None, Callable, "jax.sharding.Sharding"] = None,
                 device=None, stack_batches: int = 1):
        if sharding is not None and device is not None:
            raise ValueError("pass either sharding or device, not both")
        self.loader = loader
        self.prefetch_depth = max(int(prefetch_depth), 1)
        self._sharding = sharding
        self._device = device
        self.stack_batches = max(int(stack_batches), 1)
        # weakref: abandoning an iteration (break/exception without close())
        # must let the iterator be collected, so its __del__ stops the
        # producer thread and frees the prefetched device batches — a strong
        # ref here would pin them for the loader's whole lifetime
        self._live: Optional[weakref.ref] = None

    def __len__(self):
        return len(self.loader) // self.stack_batches

    # ------------------------------------------------------------- transfer

    def _placement_for(self, arr):
        s = self._sharding
        if s is None:
            return self._device
        if self.stack_batches > 1 and getattr(arr, "ndim", 0) > 0:
            # leaves arrive STACKED (leading microbatch axis K): the user's
            # sharding describes ONE collated batch — resolve it against a
            # microbatch view and replicate the stacking axis in front, so
            # batch_sharding still shards the BATCH axis, not the K axis
            sh = s(arr[0]) if callable(s) else s
            from jax.sharding import (NamedSharding, PartitionSpec,
                                      SingleDeviceSharding)
            if isinstance(sh, NamedSharding):
                return NamedSharding(sh.mesh, PartitionSpec(None, *sh.spec))
            if sh is None or isinstance(sh, SingleDeviceSharding):
                return sh  # no axis semantics to shift
            raise ValueError(
                f"stack_batches={self.stack_batches} needs a NamedSharding "
                f"(its axis spec shifts past the new stacking axis); got "
                f"{type(sh).__name__}, whose placement would land on the "
                f"microbatch axis instead of the batch axis — use "
                f"batch_sharding(mesh) or an explicit NamedSharding")
        return s(arr) if callable(s) else s

    def _put_leaf(self, leaf):
        if isinstance(leaf, Tensor):
            v = leaf.value()
            return Tensor(jax.device_put(v, self._placement_for(v)))
        if isinstance(leaf, (np.ndarray, jax.Array)):
            return jax.device_put(leaf, self._placement_for(leaf))
        return leaf

    def _put_batch(self, batch):
        if isinstance(batch, tuple) and hasattr(batch, "_fields"):
            # namedtuple: positional fields, not a single iterable
            return type(batch)(*(self._put_batch(b) for b in batch))
        if isinstance(batch, (list, tuple)):
            return type(batch)(self._put_batch(b) for b in batch)
        if isinstance(batch, dict):
            return {k: self._put_batch(v) for k, v in batch.items()}
        return self._put_leaf(batch)

    # ------------------------------------------------------------ iteration

    def __iter__(self):
        self.close()
        inner = iter(self.loader)
        if self.stack_batches > 1:
            inner = _stacked_iter(inner, self.stack_batches)
        it = _DeviceIterator(inner, self._put_batch,
                             self.prefetch_depth, owner=self)
        self._live = weakref.ref(it)
        return it

    def close(self):
        """Shut down the active iteration's prefetch thread (idempotent)."""
        it = self._live() if self._live is not None else None
        if it is not None:
            it.close()
        self._live = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
