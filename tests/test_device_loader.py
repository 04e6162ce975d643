"""DeviceLoader: prefetch depth, sharding placement, shutdown, errors.

The contract under test: batches come off the loader already device-resident
(and correctly placed under a mesh), the background thread never runs more
than `prefetch_depth` batches ahead, abandoning iteration tears the thread
down, and a worker exception surfaces in the consumer instead of hanging it.
"""
import threading
import time

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.io import (DataLoader, Dataset, DeviceLoader, batch_sharding,
                           default_collate_fn)


class _ArrayDataset(Dataset):
    def __init__(self, n=32, dim=4):
        self.x = np.arange(n * dim, dtype=np.float32).reshape(n, dim)

    def __len__(self):
        return len(self.x)

    def __getitem__(self, i):
        return self.x[i], np.int64(i % 3)


class _CountingSource:
    """Iterable batch source that records how far ahead it has been pulled."""

    def __init__(self, n_batches=16):
        self.n = n_batches
        self.pulled = 0

    def __iter__(self):
        for i in range(self.n):
            self.pulled += 1
            yield Tensor(np.full((2, 3), float(i), np.float32))

    def __len__(self):
        return self.n


def test_batches_are_device_resident_and_values_match():
    dl = DataLoader(_ArrayDataset(), batch_size=8)
    batches = list(DeviceLoader(dl, prefetch_depth=2))
    assert len(batches) == 4
    for b, (x, y) in enumerate(batches):
        assert isinstance(x, Tensor) and isinstance(y, Tensor)
        assert isinstance(x.value(), jax.Array)
        np.testing.assert_array_equal(
            x.numpy(), np.arange(b * 32, b * 32 + 32,
                                 dtype=np.float32).reshape(8, 4))


def test_prefetch_depth_bounds_readahead():
    src = _CountingSource(n_batches=16)
    depth = 2
    it = iter(DeviceLoader(src, prefetch_depth=depth))
    first = next(it)
    # let the producer run ahead as far as it can
    deadline = time.time() + 5.0
    while src.pulled < depth + 2 and time.time() < deadline:
        time.sleep(0.01)
    # queue(depth) + one batch held in the blocked put + the one consumed
    assert src.pulled <= depth + 2, src.pulled
    assert float(first.numpy()[0, 0]) == 0.0
    rest = list(it)
    assert len(rest) == 15
    assert src.pulled == 16


def test_len_passthrough():
    dl = DataLoader(_ArrayDataset(), batch_size=8)
    assert len(DeviceLoader(dl)) == len(dl) == 4


def test_sharding_placement_on_mesh():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    n = jax.device_count()
    mesh = Mesh(np.array(jax.devices()).reshape(n), ("data",))
    dl = DataLoader(_ArrayDataset(n * 4, dim=4), batch_size=n * 2)
    loader = DeviceLoader(dl, sharding=batch_sharding(mesh))
    for x, y in loader:
        assert x.value().sharding == NamedSharding(mesh, P("data", None))
        assert y.value().sharding == NamedSharding(mesh, P("data"))
        # global array, one shard per device
        assert len(x.value().addressable_shards) == n


def test_fixed_sharding_object_applies_to_every_leaf():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()), ("data",))
    sh = NamedSharding(mesh, P())  # fully replicated
    src = _CountingSource(4)
    for t in DeviceLoader(src, sharding=sh):
        assert t.value().sharding == sh


def test_clean_shutdown_on_abandoned_iteration():
    src = _CountingSource(n_batches=1000)
    loader = DeviceLoader(src, prefetch_depth=2)
    it = iter(loader)
    next(it)
    next(it)
    loader.close()
    assert not it._thread.is_alive()
    # close is idempotent and the iterator is terminated
    loader.close()
    with pytest.raises(StopIteration):
        next(it)
    # far fewer than the full stream was ever pulled
    assert src.pulled < 20


def test_context_manager_shuts_down():
    src = _CountingSource(n_batches=100)
    with DeviceLoader(src, prefetch_depth=1) as loader:
        it = iter(loader)
        next(it)
    assert not it._thread.is_alive()


class _ExplodingDataset(Dataset):
    def __len__(self):
        return 8

    def __getitem__(self, i):
        if i >= 4:
            raise RuntimeError("boom at idx 4")
        return np.ones((2,), np.float32)


def test_exception_from_loader_thread_propagates():
    dl = DataLoader(_ExplodingDataset(), batch_size=2)
    it = iter(DeviceLoader(dl, prefetch_depth=2))
    got = []
    with pytest.raises(RuntimeError, match="boom at idx 4"):
        for b in it:
            got.append(b)
    assert len(got) == 2  # the two good batches arrived first
    assert not it._thread.is_alive()


def test_nested_batch_structures_transfer():
    batches = [{"ids": Tensor(np.ones((2, 3), np.float32)),
                "aux": [np.zeros((2,), np.int64), 1.5]}]
    out = list(DeviceLoader(batches, prefetch_depth=1))
    assert isinstance(out[0]["ids"], Tensor)
    assert isinstance(out[0]["aux"][0], jax.Array)
    assert out[0]["aux"][1] == 1.5  # non-array leaves pass through


def test_profiler_attributes_feed_stages():
    import paddle_tpu.profiler as profiler
    dl = DataLoader(_ArrayDataset(), batch_size=8)
    with profiler.Profiler() as p:
        for _ in DeviceLoader(dl, prefetch_depth=2):
            pass
    kinds = {(e.kind, e.name) for e in p.events}
    assert ("stage", "loader/wait") in kinds
    assert ("stage", "loader/h2d") in kinds
    assert ("stage", "loader/fetch") in kinds


def test_namedtuple_batches_preserved():
    from collections import namedtuple
    Batch = namedtuple("Batch", ["x", "y"])
    src = [Batch(np.ones((2, 3), np.float32), Tensor(np.zeros((2,), np.int64)))]
    out = list(DeviceLoader(src, prefetch_depth=1))
    assert isinstance(out[0], Batch)
    assert isinstance(out[0].x, jax.Array)
    assert isinstance(out[0].y, Tensor)


def test_abandoned_iteration_reclaimed_by_gc_without_close():
    """break-without-close must not pin the prefetch thread + device batches:
    dropping the iterator reference is enough (weakref in the loader)."""
    import gc
    src = _CountingSource(n_batches=1000)
    loader = DeviceLoader(src, prefetch_depth=2)

    def partial_consume():
        it = iter(loader)
        next(it)
        next(it)
        return it._thread

    thread = partial_consume()
    gc.collect()
    deadline = time.time() + 5.0
    while thread.is_alive() and time.time() < deadline:
        time.sleep(0.05)
    assert not thread.is_alive()
    assert src.pulled < 20


def test_overlap_report_without_explicit_step_calls():
    """The plain `with Profiler()` usage (no p.step()) must still yield a
    usable wall_s from the event span."""
    import paddle_tpu.profiler as profiler
    dl = DataLoader(_ArrayDataset(), batch_size=8)
    with profiler.Profiler() as p:
        for _ in DeviceLoader(dl, prefetch_depth=2):
            pass
    rep = p.overlap_report()
    assert rep["wall_s"] > 0
    assert rep["feed_stall_s"] <= rep["wall_s"] + 1e-6
