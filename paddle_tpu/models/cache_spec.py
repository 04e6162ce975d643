"""What a causal LM tells the serving engine: its cached-forward backbone,
its head, and, PER LAYER, what that layer caches between calls.

  ``kv_layer(heads, width)``   keys and values of every past position: the
                               engine backs it with paged block pools
                               and hands the layer ``(pool_k, pool_v,
                               block_table)``.
                               Pools are ``[blocks, block, heads, width]``;
                               with ``merged_rows`` ``[blocks, block *
                               heads, width]``, row ``t * heads + h``: the
                               same bytes, as one matrix a block. A layer
                               with fewer KV heads than a sublane tile asks
                               for it, because XLA tiles the last TWO dims:
                               a [.., 2, 256] pool gets small tiles that
                               every scatter and every kernel call has to
                               re-lay, a whole-pool copy each
  ``state_layer(arrays)``      a fixed-size recurrent state per sequence,
                               ``arrays`` = ((shape, dtype), ...) for ONE
                               sequence: the engine keeps ``[max_slots, *shape]``
                               arrays and hands the layer the rows of the
                               slots in the call

A model gives a ``ModelSpec`` from its ``decode_spec()``; the engine and the
pager allocate and thread caches from ``spec.layers`` and ask nothing else
about the architecture. The backbone is called as ``backbone(ids,
kv_caches=[per-layer cache], start_pos=, write_end=)`` and returns (hidden,
[per-layer new cache]); positions at or past ``write_end`` are padding (or a
dead decode slot) and must change no cache.
"""
from __future__ import annotations

from collections import namedtuple

import numpy as np

CacheLayer = namedtuple("CacheLayer", ["kind", "n_kv_heads", "head_dim",
                                       "arrays", "merged_rows"])


def kv_layer(n_kv_heads: int, head_dim: int,
             merged_rows: bool = False) -> CacheLayer:
    return CacheLayer("kv", int(n_kv_heads), int(head_dim), (),
                      bool(merged_rows))


def state_layer(arrays) -> CacheLayer:
    return CacheLayer("state", 0, 0, tuple(
        (tuple(int(d) for d in shape), np.dtype(dtype).name)
        for shape, dtype in arrays), False)


class ModelSpec(namedtuple("ModelSpec", [
        "backbone", "layers", "max_pos", "head_weight", "head_transpose"])):
    __slots__ = ()

    @property
    def num_layers(self) -> int:
        return len(self.layers)

    @property
    def kv_layers(self) -> list:
        return [c for c in self.layers if c.kind == "kv"]

    @property
    def state_layers(self) -> list:
        return [c for c in self.layers if c.kind == "state"]

    def _kv_geometry(self):
        geo = {(c.n_kv_heads, c.head_dim) for c in self.kv_layers}
        if len(geo) != 1:
            raise NotImplementedError(
                f"the engine's KV pools share one geometry; this model's "
                f"kv layers give {sorted(geo)}")
        return geo.pop()

    @property
    def n_kv_heads(self) -> int:
        return self._kv_geometry()[0]

    @property
    def head_dim(self) -> int:
        return self._kv_geometry()[1]

    @property
    def state_bytes_per_slot(self) -> int:
        return sum(int(np.prod(shape)) * np.dtype(dtype).itemsize
                   for c in self.state_layers for shape, dtype in c.arrays)
