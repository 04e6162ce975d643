"""What a causal LM tells the serving engine: its cached-forward backbone,
its head, and, PER LAYER, what that layer caches between calls: one entry,
or a tuple of entries for a block that keeps several caches (a state-space
mixer beside an attention mixer: ``(kv_layer(..), state_layer(..))``; two
latent-attention sublayers: ``(latent_layer(..), latent_layer(..))``).

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
  ``latent_layer(rank, rope)`` ONE row of every past position, ``[normed
                               latent (rank) | rotated shared key (rope)]``,
                               which every query head reads (latent
                               attention, absorbed form: the first ``rank``
                               lanes are also the values). Blocks behind the
                               block table like K/V, so the prefix cache,
                               copy-on-write and preemption work on it by
                               mechanism; ONE pool an entry, ``[blocks,
                               block, lanes]``, the layer handed ``(pool,
                               block_table)``. ``lanes`` is ``rank + rope``
                               rounded up to whole 128-lane tiles (the chip
                               lays a row out in whole tiles whatever is
                               declared: 576 takes 640, the pad zero); what
                               is COUNTED a token stays ``rank + rope``
  ``window_latent_layer(rank, rope, window)``
                               a latent row of the last ``window``
                               positions only (a sliding-window layer): a
                               RING a slot, ``window / block_size`` pages
                               of a ``[1 + max_slots * pages, block,
                               lanes]`` pool behind a fixed table (slot
                               ``s`` owns pages ``1 + s * pages ..``; block
                               0 is trash, as in the paged pools), position
                               ``p`` at ring row ``p mod window``. The layer
                               is handed ``(pool, ring_table)`` like a
                               latent entry; the ring is not the pager's,
                               so a model with such an entry is served
                               without the prefix cache (a ring cannot be
                               rebuilt from shared blocks)
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
dead decode slot) and must change no cache. A layer of several entries is
handed, and returns, a tuple of caches in its entries' order
(``spec.map_entries`` keeps that structure for whoever walks the caches).
"""
from __future__ import annotations

from collections import namedtuple

import numpy as np

CacheLayer = namedtuple("CacheLayer", ["kind", "n_kv_heads", "head_dim",
                                       "arrays", "merged_rows", "window"],
                        defaults=(0,))


def kv_layer(n_kv_heads: int, head_dim: int,
             merged_rows: bool = False) -> CacheLayer:
    return CacheLayer("kv", int(n_kv_heads), int(head_dim), (),
                      bool(merged_rows))


def latent_layer(rank: int, rope: int) -> CacheLayer:
    """``head_dim`` is the row's counted width, ``rank + rope``."""
    return CacheLayer("latent", 1, int(rank) + int(rope), (), True)


def window_latent_layer(rank: int, rope: int, window: int) -> CacheLayer:
    """A latent row of the last ``window`` positions, in a ring a slot."""
    return CacheLayer("window", 1, int(rank) + int(rope), (), True,
                      int(window))


def pool_lanes(width: int) -> int:
    """Lanes of a latent pool's row: ``width`` in whole 128-lane tiles."""
    return -(-int(width) // 128) * 128


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
    def entries(self) -> list:
        """Every cache entry, layer by layer."""
        return [c for layer in self.layers
                for c in ((layer,) if isinstance(layer, CacheLayer)
                          else layer)]

    def map_entries(self, fn, *per_layer) -> list:
        """``fn(entry, *that entry's items)`` over every entry, the results
        in the layers' own structure: ``per_layer`` are lists shaped like
        ``layers`` (one item a single-entry layer, a tuple of items a layer
        of several)."""
        return [fn(layer, *items) if isinstance(layer, CacheLayer)
                else tuple(fn(c, *its) for c, *its in zip(layer, *items))
                for layer, *items in zip(self.layers, *per_layer)]

    @property
    def kv_layers(self) -> list:
        return [c for c in self.entries if c.kind == "kv"]

    @property
    def latent_layers(self) -> list:
        """Entries that cache latent rows: paged, or in a ring."""
        return [c for c in self.entries if c.kind in ("latent", "window")]

    @property
    def window_layers(self) -> list:
        return [c for c in self.entries if c.kind == "window"]

    @property
    def state_layers(self) -> list:
        return [c for c in self.entries if c.kind == "state"]

    def _kv_geometry(self):
        geo = {(c.n_kv_heads, c.head_dim) for c in self.kv_layers}
        if len(geo) != 1:
            kinds = sorted({c.kind for c in self.entries})
            raise NotImplementedError(
                f"the engine's KV pools share one geometry; this model's "
                f"\"kv\" entries give {sorted(geo)} (its entries are of "
                f"kind {kinds})")
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
