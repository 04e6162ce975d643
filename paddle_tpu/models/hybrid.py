"""What the raw-array decoders (``qwen3_next.py``, ``falcon_h1.py``,
``longcat_flash.py``, ``deepseek_v3.py``) share: a bag of raw parameters,
the float32 RMS norm, the dense SwiGLU, positions and validity of a cached
call, rotary (rotate-half or interleaved pairs, YaRN's frequencies), the
depthwise convolution that carries its last inputs between calls, where a
call's positions land in a paged pool, the walk of a prefill chunk's
queries over the key blocks its slot holds (``walk_keys``; GPT and LLaMA
take it too, and ``latent_attention.py``), and grouped-query attention over
merged-row paged pools (``cache_spec.kv_layer(merged_rows=True)``) with the
paged decode kernel where the call is a decode step. Raw-array math, no
``Tensor`` inside.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn


def _dot(x, w):
    return jnp.dot(x, w, precision="highest" if x.dtype == jnp.float32
                   else None)


def rms_norm(x, w, eps, centred=True):
    """``x / rms(x) * (1 + w)`` (``centred``) or ``* w``, in float32, back
    in ``x``'s dtype."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    wf = w.astype(jnp.float32)
    return (y * (1.0 + wf if centred else wf)).astype(x.dtype)


def _positions(pos, s):
    """[B or 1, S] absolute positions from a scalar or per-row start."""
    pos = jnp.asarray(pos, jnp.int32)
    return (pos[:, None] if pos.ndim else pos[None, None]) \
        + jnp.arange(s, dtype=jnp.int32)[None, :]


def _valid(pos, end, b, s):
    """[B, S] bool: positions before ``end`` (None: all)."""
    if end is None:
        return jnp.ones((b, s), bool)
    end = jnp.asarray(end, jnp.int32)
    return jnp.broadcast_to(
        _positions(pos, s) < (end[:, None] if end.ndim else end), (b, s))


def _fresh(pos, valid):
    """[B, 1] bool: the call starts its sequence (``pos == 0``) and has
    something valid to write. A dead decode slot sits at position 0 too:
    only a call that writes starts afresh."""
    fresh = (jnp.asarray(pos, jnp.int32) == 0)
    fresh = fresh[:, None] if fresh.ndim else fresh[None, None]
    return jnp.broadcast_to(fresh, (valid.shape[0], 1)) \
        & jnp.any(valid, axis=1, keepdims=True)


class _Weights(nn.Layer):
    """A bag of raw parameters made with one initializer; ``cfg`` gives
    ``dtype`` and ``initializer_range``."""

    def __init__(self, cfg):
        super().__init__()
        self._cfg = cfg
        self._normal = nn.initializer.Normal(0.0, cfg.initializer_range)

    def mat(self, *shape):
        return self.create_parameter(shape, dtype=self._cfg.dtype,
                                     default_initializer=self._normal)

    def const(self, value, *shape):
        return self.create_parameter(
            shape, dtype=self._cfg.dtype,
            default_initializer=nn.initializer.Constant(value))


class DenseFFN(_Weights):
    """SwiGLU ``W_d(silu(W_g v) * W_u v)`` of ``width``, the product of the
    two halves in float32."""

    def __init__(self, cfg, width: int):
        super().__init__(cfg)
        h = cfg.hidden_size
        self.gate_proj = self.mat(h, width)
        self.up_proj = self.mat(h, width)
        self.down_proj = self.mat(width, h)

    def apply(self, v):
        with jax.named_scope("dense_ffn"):
            hid = jax.nn.silu(_dot(v, self.gate_proj.value())
                              .astype(jnp.float32)) \
                * _dot(v, self.up_proj.value()).astype(jnp.float32)
            return _dot(hid.astype(v.dtype), self.down_proj.value())


def conv_with_tail(mixed, tail, weight, valid, bias=None):
    """Causal depthwise convolution over [tail | this call's inputs], then
    SiLU, in float32: ``mixed [B, S, C]``, ``tail [B, width - 1, C]`` the
    inputs before the call, ``weight [C, width]`` (``bias [C]``). Returns
    (activations [B, S, C] float32, the next call's tail: the last
    ``width - 1`` inputs before the first position that is not ``valid``)."""
    s, width = mixed.shape[1], weight.shape[1]
    f32 = jnp.float32
    full = jnp.concatenate([tail.astype(mixed.dtype), mixed], axis=1)
    w = weight.astype(f32)
    conv = sum(full[:, j:j + s].astype(f32) * w[:, j] for j in range(width))
    if bias is not None:
        conv = conv + bias.astype(f32)
    n_valid = jnp.sum(valid.astype(jnp.int32), axis=1)    # [B]
    new_tail = jax.vmap(lambda f, n: jax.lax.dynamic_slice_in_dim(
        f, n, width - 1, axis=0))(full, n_valid).astype(tail.dtype)
    return jax.nn.silu(conv), new_tail


def rope(t, positions, rot, theta, inv=None, interleave=False):
    """Rotary on the first ``rot`` dims; t [B, S, n, hd]. Pair ``i`` is
    dims ``(i, i + rot / 2)`` (rotate-half) or, with ``interleave``, dims
    ``(2i, 2i + 1)`` (DeepSeek's layout); its frequency ``theta ** (-2i /
    rot)``, or ``inv[i]`` where given (``rope_frequencies``)."""
    half = rot // 2
    if inv is None:
        inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rot)
    ang = positions.astype(jnp.float32)[..., None] * inv  # [B|1, S, half]
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    tf = t.astype(jnp.float32)
    rest = tf[..., rot:]
    if interleave:
        pairs = tf[..., :rot].reshape(tf.shape[:-1] + (half, 2))
        x1, x2 = pairs[..., 0], pairs[..., 1]
        turned = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).reshape(tf.shape[:-1] + (rot,))
        return jnp.concatenate([turned, rest], axis=-1).astype(t.dtype)
    x1, x2 = tf[..., :half], tf[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            rest], axis=-1).astype(t.dtype)


def _yarn_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_frequencies(rot, theta, scaling=None):
    """(inverse frequencies [rot / 2] float32, or None for ``rope``'s own;
    the factor on the softmax scale) of a configuration's
    ``rope_scaling``. YaRN (``type`` "yarn"): frequencies between the
    correction dims of ``beta_fast`` and ``beta_slow`` rotations over
    ``original_max_position_embeddings`` are ramped from ``theta``'s own
    (below) to ``1 / factor`` of them (above); the softmax scale takes
    ``mscale(mscale_all_dim) ** 2``, ``mscale(m) = 0.1 m ln(factor) + 1``.
    YaRN's factor ``mscale(mscale) / mscale(mscale_all_dim)`` on cos and
    sin is 1 in every configuration served (DeepSeek-V3: both 1), and
    another is refused."""
    if not scaling:
        return None, 1.0
    kind = scaling.get("type", scaling.get("rope_type"))
    if kind != "yarn":
        raise NotImplementedError(f"rope_scaling of type {kind!r}")
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])
    theta = float(theta)

    def correction_dim(rotations):
        return rot * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(scaling.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction_dim(scaling.get("beta_slow", 1))),
               rot - 1)
    ramp = np.clip((np.arange(rot // 2) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    plain = theta ** (-np.arange(0, rot, 2) / rot)
    inv = plain / factor * ramp + plain * (1.0 - ramp)
    all_dims = scaling.get("mscale_all_dim", 0)
    if _yarn_mscale(factor, scaling.get("mscale", 1)) \
            != _yarn_mscale(factor, all_dims):
        raise NotImplementedError("YaRN with mscale apart from "
                                  "mscale_all_dim (cos and sin scaled)")
    scores = _yarn_mscale(factor, all_dims) ** 2 if all_dims else 1.0
    return inv.astype(np.float32), scores


def _block_of(table, wpos, end, bs_blk):
    """[B, S] physical block of each position ``wpos`` through ``table``;
    positions at or past ``end`` or beyond the table go to trash block
    0."""
    b, mbs = wpos.shape[0], table.shape[1]
    lidx = wpos // bs_blk
    phys = jnp.take_along_axis(
        jnp.broadcast_to(table, (b, mbs)),
        jnp.minimum(lidx, mbs - 1), axis=1)
    return jnp.where((wpos < end) & (lidx < mbs), phys, 0)


def _write_end(end):
    end = jnp.asarray(end, jnp.int32)
    return end[:, None] if end.ndim else end


def kernel_write(pools, rows, table, start, end, sharded=False):
    """The pools after ``kernels/pallas/pool_write.py`` wrote ``rows`` (one
    array a pool) at positions ``start + i`` before ``end``, or None where
    the kernel does not run (off a TPU, ``sharded`` pools, a layout it does
    not fit): the caller scatters. Notes which it was
    (``util.note_pool_write``)."""
    from ..kernels.pallas import pool_write
    from ..kernels.pallas.util import note_pool_write
    mode = None if sharded else pool_write.kernel_mode(pools[0], rows[0])
    if mode is None:
        note_pool_write("scatter")
        return None
    return pool_write.write_rows(pools, rows, table, start, end,
                                 interpret=mode == "interpret")


def write_rows(pool, table, rows, positions, end):
    """One row a position into ``pool [NB, BS, lanes]``: ``rows [B, S,
    lanes]`` land at ``(table[b, p // BS], p % BS)``; none at or past
    ``end`` or beyond the table (the scatter sends those to the trash
    block). Returns the pool after the write."""
    done = kernel_write((pool,), (rows,), table, positions[:, 0], end)
    if done is not None:
        return done[0]
    b, s = rows.shape[:2]
    wpos = jnp.broadcast_to(positions, (b, s))
    phys = _block_of(table, wpos, _write_end(end), pool.shape[1])
    return pool.at[phys, wpos % pool.shape[1]].set(rows.astype(pool.dtype))


# float32 score elements of one trip of ``walk_keys``: its key block is sized
# from them (as ``latent_attention.SCORE_BLOCK`` sizes a block of heads). A loop
# costs its layer some ten small launches a trip and the scheduler's overlap
# with the next layer's weights, so few score rows want a long trip and many
# a short one: 256 keys for 64 heads x 512 queries, 512 for 20 x 512, and
# for 16 x 256 the whole 2,048-wide row in one trip, which is no loop at
# all (the sweep and the cells on the chip: ``PERF.md`` section 6, PR 36)
WALK_SCORES = 1 << 23
_WALK_GEOMETRY: dict = {}


def walk_geometry() -> dict:
    """The geometry of the last traced walk: ``key_block``."""
    return dict(_WALK_GEOMETRY)


def key_block_for(score_rows, block_size, width):
    """Key positions a trip of ``walk_keys`` takes for ``score_rows`` (heads
    x queries) rows of scores: the power-of-two number of blocks that makes
    ``WALK_SCORES`` score elements, within the table's ``width``
    positions."""
    blocks = max(1, WALK_SCORES // (score_rows * block_size))
    blocks = 1 << (blocks.bit_length() - 1)
    return block_size * min(blocks, -(-width // block_size))


def walk_keys(table, positions, end, block_size, score_rows, fetch, score,
              value):
    """Causal softmax attention of ONE slot's chunk over the keys its
    table row holds before ``end``: the row is walked in trips of
    ``key_block`` positions (``key_block_for`` the call's ``score_rows``,
    heads x queries), ``ceil(end / key_block)`` of them and no more (a
    traced count: one program whatever ``end``), with the running maximum,
    sum and unnormalised context of an online softmax in float32. Nothing
    past the last trip is fetched, scored or normalised. Where one trip
    holds the row there is no loop.

    ``table [B, mbs]`` the block table's rows, ``positions [1, S]`` the
    queries' (one scalar cursor), ``end`` the scalar end of what the slot
    holds. What a geometry fetches and how it scores is the caller's:

    * ``fetch(entries [B, key_block // block_size])`` -> what a trip holds
      of the pools (position ``i`` of the trip at index ``i``);
    * ``score(held)`` -> float32 scores ``[..., S, key_block]``, scaled;
    * ``value(probs, held)`` -> float32 ``[..., S, D]``: ``probs [..., S,
      key_block]`` (float32, not yet normalised) times the trip's values.

    A key is seen by the queries at or after it (``-1e30`` elsewhere, so a
    trip wholly in a query's future changes nothing for it). Returns the
    context, float32 ``[..., S, D]``."""
    from ..kernels.pallas.util import note_attention_kernel
    if positions.shape[0] != 1:
        raise ValueError("walk_keys serves one cursor a call; per-row "
                         f"cursors came with positions {positions.shape}")
    note_attention_kernel("key_walk")
    key_block = key_block_for(score_rows, block_size,
                              table.shape[1] * block_size)
    _WALK_GEOMETRY.update(key_block=key_block)
    per = key_block // block_size
    # a width that is no multiple of a trip: the last trip's tail reads
    # trash block 0, past every position a live query can see
    table = jnp.pad(table, ((0, 0), (0, -table.shape[1] % per)))
    trips = jnp.clip(-(-jnp.asarray(end, jnp.int32) // key_block), 1,
                     table.shape[1] // per)
    q_pos = positions[0][:, None]

    def trip(t):
        held = fetch(jax.lax.dynamic_slice_in_dim(table, t * per, per, 1))
        key_pos = t * key_block + jnp.arange(key_block, dtype=jnp.int32)
        return held, jnp.where(key_pos[None, :] <= q_pos, score(held), -1e30)

    def step(t, carry):
        top, total, ctx = carry
        held, sc = trip(t)
        new_top = jnp.maximum(top, jnp.max(sc, axis=-1))
        probs = jnp.exp(sc - new_top[..., None])
        keep = jnp.exp(top - new_top)
        return (new_top, total * keep + jnp.sum(probs, axis=-1),
                ctx * keep[..., None] + value(probs, held))

    def shapes():
        held, sc = trip(jnp.int32(0))
        return sc[..., 0], value(sc, held)

    rows, ctx = jax.eval_shape(shapes)
    f32 = jnp.float32
    start = (jnp.full(rows.shape, -1e30, f32), jnp.zeros(rows.shape, f32),
             jnp.zeros(ctx.shape, f32))
    if table.shape[1] == per:       # a trip holds the row: straight-line code
        _, total, ctx = step(jnp.int32(0), start)
    else:
        _, total, ctx = jax.lax.fori_loop(0, trips, step, start)
    return ctx / total[..., None]


def walk_grouped(q, pools, table, positions, end, nkv, precision=None):
    """``walk_keys`` for grouped-query K/V pools: ``q [B, S, nh, hd]`` over
    ``pools`` = (K, V) whose block holds ``BS`` positions of ``nkv`` heads,
    as ``[NB, BS, nkv, hd]`` or as merged rows ``[NB, BS * nkv, hd]``.
    Float32 operands at ``precision``, scale ``hd ** -0.5``. Returns the
    context [B, S, nh, hd] in ``q``'s dtype."""
    b, s, nh, hd = q.shape
    f32 = jnp.float32
    bs_blk = math.prod(pools[0].shape[1:]) // (nkv * hd)
    qh = q.reshape(b, s, nkv, nh // nkv, hd).astype(f32)

    def fetch(entries):
        with jax.named_scope("kv_gather"):
            return tuple(jnp.take(p, entries, axis=0, mode="clip").reshape(
                b, -1, nkv, hd).astype(f32) for p in pools)

    def score(held):
        return jnp.einsum("bqkgd,bmkd->bkgqm", qh, held[0],
                          precision=precision) / math.sqrt(hd)

    def value(probs, held):
        return jnp.einsum("bkgqm,bmkd->bkgqd", probs, held[1],
                          precision=precision)

    ctx = walk_keys(table, positions, end, bs_blk, nh * s, fetch, score,
                    value)
    return jnp.moveaxis(ctx, 3, 1).reshape(b, s, nh, hd).astype(q.dtype)


def _write_merged(cache, k, v, positions, end):
    """``gpt._paged_kv_write`` for pools whose block is one matrix of
    (position, KV head) rows: position ``p`` of head ``h`` lands at
    ``(table[b, p // BS], (p % BS) * n_kv + h)``; positions at or past
    ``end`` or beyond the table are not written (the scatter sends them to
    trash block 0)."""
    pool_k, pool_v, table = cache
    b, s, nkv = k.shape[:3]
    bs_blk = pool_k.shape[1] // nkv
    wpos = jnp.broadcast_to(positions, (b, s))
    with jax.named_scope("kv_write"):
        done = kernel_write((pool_k, pool_v), (k, v), table, positions[:, 0],
                            end)
        if done is not None:
            return tuple(done)
        phys = _block_of(table, wpos, _write_end(end), bs_blk)
        row = (wpos % bs_blk)[..., None] * nkv \
            + jnp.arange(nkv, dtype=jnp.int32)
        at = (phys[..., None], row)
        return (pool_k.at[at].set(k.astype(pool_k.dtype)),
                pool_v.at[at].set(v.astype(pool_v.dtype)))


def _attend_merged(table, pos, q, pools, nkv):
    """The decode step's attention through the paged kernel (one query
    position a slot, per-slot cursors, a TPU or its test seam), or None
    for the gathered view."""
    from ..kernels.pallas import paged_decode
    if q.shape[1] != 1 or jnp.ndim(pos) != 1:
        return None
    mode = paged_decode.kernel_mode(q, pools[0], n_kv=nkv)
    if mode is None:
        return None
    with jax.named_scope("paged_decode"):
        return paged_decode.paged_decode_attention(
            q, pools[0], pools[1], table, pos + 1, n_kv=nkv,
            interpret=mode == "interpret")


def grouped_attention(q, k, v, cache, pos, positions, end):
    """Causal softmax attention of ``q [B, S, nh, hd]`` (rotated) over
    ``k v [B, S, nkv, hd]``, ``nh / nkv`` query heads a KV head, scale
    ``hd ** -0.5``. With ``cache`` = merged-row ``(pool_k, pool_v, table)``
    the call's K/V are written at their positions first (before
    ``end``) and the queries see everything the table holds up to their
    own position: a decode step through the paged kernel where it runs, one
    slot's chunk (a scalar cursor) by ``walk_keys`` over the blocks before
    ``end``, the rest (the decode step off the chip) over the gathered
    view. Returns (context [B, S, nh * hd] in ``q``'s dtype, the pools
    after the write or None)."""
    b, s, nh, hd = q.shape
    nkv = k.shape[2]
    new_cache = ctx = None
    if cache is None:
        k_buf, v_buf = k, v
    else:                           # paged: [NB, BS * n_kv, hd] pools
        we = end if end is not None else jnp.asarray(pos, jnp.int32) + s
        new_cache = _write_merged(cache, k, v, positions, we)
        ctx = _attend_merged(cache[2], pos, q, new_cache, nkv)
        if ctx is None and jnp.ndim(pos) == 0:      # one slot's chunk
            ctx = walk_grouped(q, new_cache, cache[2], positions, we, nkv,
                               precision="highest")
        if ctx is None:
            with jax.named_scope("kv_gather"):
                k_buf, v_buf = (jnp.take(p, cache[2], axis=0).reshape(
                    b, -1, nkv, hd) for p in new_cache)
    if ctx is None:
        m = k_buf.shape[1]
        qh = q.reshape(b, s, nkv, nh // nkv, hd).astype(jnp.float32)
        scores = jnp.einsum("bqkgd,bmkd->bkgqm", qh,
                            k_buf.astype(jnp.float32),
                            precision="highest") / math.sqrt(hd)
        key_pos = jnp.arange(m)[None, None, None, None, :]
        q_pos = positions[:, None, None, :, None]
        scores = jnp.where(key_pos <= q_pos, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bkgqm,bmkd->bqkgd", probs,
                         v_buf.astype(jnp.float32),
                         precision="highest").astype(q.dtype)
    return ctx.reshape(b, s, nh * hd), new_cache
