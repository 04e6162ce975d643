"""Multi-head latent attention (MLA), the one class the latent-attention
decoders share (``longcat_flash.py``: two sublayers a block;
``deepseek_v3.py``: one). What differs between them is read from the
configuration: the ``mla_scale_*`` factors (LongCat), ``rope_scaling``
(YaRN, DeepSeek-V3) and ``rope_interleave`` (rotary on interleaved pairs,
DeepSeek-V3; rotate-half otherwise).

Equations (``N*`` are RMSNorms ``w * x / rms(x)``)::

    cq = Nq(z Wqa) * q_scale            # q_scale = sqrt(hidden / q_lora_rank)
                                        #   with mla_scale_q_lora, else 1
    q  = cq Wqb                         # heads of [q_nope | q_rope]
    [ckv | k_rope] = z Wkva
    c  = Nkv(ckv) * kv_scale            # likewise with mla_scale_kv_lora
    [k_nope | v] = c Wkvb               # per head
    rotary on q_rope and on the ONE k_rope all heads share
    causal softmax of (q_nope . k_nope + q_rope . k_rope) * scale
    context Wo

GROUPED DIFFERENTIAL latent attention (Motif-3's GDLA) is the same class,
read from four more keys: ``num_key_value_heads`` ``G`` below the heads
(``Wkvb`` expands the latent to ``G`` groups of ``[k_nope | v]``, heads
``per * g .. per * g + per - 1`` read group ``g``), ``num_noise_heads``
(the last head of each group is its NOISE head: a signal head's context
is ``ctx_s - lam_s ctx_noise``, ``lam = sigmoid(z W_lam)`` a token and
signal head, the Differential Transformer's map difference written after
the values, which it is linear in), ``elementwise_attn_output_gate`` (the
signal heads' context times ``sigmoid(gamma)``, ``gamma`` read from extra
columns of ``Wqb``), and a per-layer ``window`` (a sliding-window layer:
keys ``t - window < j <= t``, plain rotary at ``swa_rope_theta``, its rows
in a ring a slot, ``cache_spec.window_latent_layer``). The absorbed decode
step takes the difference in latent space, before ``Wkvb_v``: the noise
heads are never up-projected. None of these keys is read for DeepSeek-V3
or LongCat, whose programs stay what they were.

``scale`` is ``(nope + rope) ** -0.5``, times ``mscale(mscale_all_dim) **
2`` under YaRN (``hybrid.rope_frequencies``, which also gives the rotary's
frequencies); the scale factors ride in the norms' float32 weights (one
rounding).

What is cached a token is ONE row ``[c | rotated k_rope]``
(``cache_spec.latent_layer``), not per-head keys and values. A cached call
writes its rows at their positions first (before ``write_end``); then

* a decode step (one position a slot, per-slot cursors) runs the ABSORBED
  form: ``q_lat = q_nope Wkvb_k^T`` per head, scores of ``[q_lat | q_rope]``
  against the cached rows, the context over the rows' first ``rank`` lanes,
  then ``Wkvb_v`` and ``Wo``: on a TPU (or under the test seam) in the
  Pallas kernel ``mla_decode`` (``kernels/pallas/paged_decode.py``, the
  scale through its ``scale=``), else over the gathered view;
* a prefill chunk (and the full forward) runs the EXPANDED form: per-head
  ``k_nope`` and ``v`` from the cached rows (cheaper than the absorbed form
  once many queries share the expansion). A chunk walks the rows its slot
  holds before ``write_end`` in key blocks, all heads a trip
  (``hybrid.walk_keys``), and never the rest of the table row; the full
  forward, which has no table, takes its own rows whole, groups of heads
  in blocks.

Both up-projections are read as they lie on the device. ``q = cq Wqb`` is
held row-major and its heads split off the product's output;
``Wkvb`` is held column-major by a serving engine (``serving_layouts``,
laid out once where the engine is built), groups outermost, as the
absorbed products, batched over the groups, read it; the expansion
(``_expand``) contracts its ``rank`` lanes in either layout. Left to
itself the compiler re-lays a whole weight of either kind every call.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from .cache_spec import latent_layer, window_latent_layer
from .hybrid import (_dot, _positions, _Weights, rms_norm, rope,
                     rope_frequencies, walk_keys, write_rows)

__all__ = ["LatentAttention"]

SCORE_BLOCK = 1 << 25         # score elements of one block of heads (f32)


def _row_major(t):
    """``t`` held row-major (its last axis minor), as a product's output
    comes out of a weight that is."""
    return with_layout_constraint(
        t, Layout(major_to_minor=tuple(range(t.ndim))))


class LatentAttention(_Weights):
    """``window``: the layer attends over its last ``window`` positions
    (rotary at ``swa_rope_theta``, unscaled) and caches them in a ring;
    0, over every position before it."""

    def __init__(self, cfg, window: int = 0):
        super().__init__(cfg)
        h, self.nh = cfg.hidden_size, cfg.num_attention_heads
        self.window = int(window)
        self.groups = int(getattr(cfg, "num_key_value_heads", 0) or self.nh)
        self.per = self.nh // self.groups
        self.noise = int(getattr(cfg, "num_noise_heads", 0) or 0)
        if self.nh % self.groups or self.noise not in (0, self.groups):
            raise ValueError(
                f"{self.nh} heads in {self.groups} latent groups with "
                f"{self.noise} noise heads: the heads split evenly and a "
                f"group has one noise head or none")
        self.signal = self.nh - self.noise
        self.gated = bool(getattr(cfg, "elementwise_attn_output_gate",
                                  False))
        # the heads' axes in the products: one key a head, or a group's
        # ``per`` heads (axis j) on one key (axis g)
        self._h, self._k = ("h", "h") if self.per == 1 else ("gj", "g")
        self.rank, self.q_rank = cfg.kv_lora_rank, cfg.q_lora_rank
        self.nope, self.rot = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        self.vd = cfg.v_head_dim
        self.theta, self.eps = cfg.rope_theta, cfg.rms_norm_eps
        self.q_scale = math.sqrt(h / self.q_rank) \
            if getattr(cfg, "mla_scale_q_lora", False) else 1.0
        self.kv_scale = math.sqrt(h / self.rank) \
            if getattr(cfg, "mla_scale_kv_lora", False) else 1.0
        self.interleave = bool(getattr(cfg, "rope_interleave", False))
        if self.window:
            self.theta = getattr(cfg, "swa_rope_theta", self.theta)
        self.inv, factor = rope_frequencies(
            self.rot, self.theta,
            None if self.window else getattr(cfg, "rope_scaling", None))
        self.scale = factor / math.sqrt(self.nope + self.rot)
        self.q_a_proj = self.mat(h, self.q_rank)
        self.q_a_layernorm = self.const(1.0, self.q_rank)
        self.q_b_proj = self.mat(self.q_rank, self.nh * (self.nope + self.rot)
                                 + self.gated * self.signal * self.vd)
        self.kv_a_proj = self.mat(h, self.rank + self.rot)
        self.kv_a_layernorm = self.const(1.0, self.rank)
        self.kv_b_proj = self.mat(self.rank,
                                  self.groups * (self.nope + self.vd))
        if self.noise:
            self.lambda_proj = self.mat(h, self.signal)
        self.o_proj = self.mat(self.signal * self.vd, h)

    def cache_entry(self):
        if self.window:
            return window_latent_layer(self.rank, self.rot, self.window)
        return latent_layer(self.rank, self.rot)

    def serving_layouts(self):
        """The weights a serving engine holds in another layout than it is
        handed, by parameter (major-to-minor axis order); the engine lays
        each out once, where it is built. ``Wkvb`` is held column-major,
        a group's ``[k_nope | v]`` columns of ``rank`` lanes each, groups
        outermost: the absorbed decode products are batched over the
        groups and read it as it lies (held row-major, every call re-lays
        all of it). The expansion contracts its ``rank`` lanes, which it
        reads in either layout."""
        return {self.kv_b_proj: (1, 0)}

    def _kv_b(self):
        """``Wkvb`` as [rank, groups (= heads without grouping), nope |
        v]."""
        return self.kv_b_proj.value().reshape(self.rank, self.groups,
                                              self.nope + self.vd)

    def _expand(self, c, dt):
        """Each group's ``[k_nope | v]`` of latent rows ``c [B, M, rank]``
        in ``dt`` (float32 accumulation): [B, M, groups, nope + vd]. One
        product against ``Wkvb`` whole, which contracts its ``rank``
        lanes in whatever layout it is held."""
        kv = jnp.einsum("bmr,rn->bmn", c, self.kv_b_proj.value(),
                        precision="highest" if dt == jnp.float32 else None,
                        preferred_element_type=jnp.float32).astype(dt)
        return kv.reshape(kv.shape[:2] + (self.groups, self.nope + self.vd))

    def _by_group(self, t):
        """[B, S, nh, d] -> [B, S, G, per, d] where heads share a group's
        key (unchanged where each head has its own)."""
        if self.per == 1:
            return t
        return t.reshape(t.shape[:2] + (self.groups, self.per)
                         + t.shape[3:])

    def _combine(self, ctx, lam):
        """The signal heads' context ``ctx_s - lam_s ctx_noise`` (float32)
        of ``ctx [B, S, nh, d]`` (no noise heads: ``ctx`` as it is).
        Returns [B, S, signal, d]."""
        if not self.noise:
            return ctx
        g = self._by_group(ctx.astype(jnp.float32))
        lam = lam.reshape(lam.shape[:2] + (self.groups, self.per - 1, 1))
        return (g[..., :-1, :] - lam * g[..., -1:, :]).reshape(
            ctx.shape[:2] + (self.signal, ctx.shape[-1]))

    def _rope(self, t, positions):
        return rope(t, positions, self.rot, self.theta, self.inv,
                    self.interleave)

    def _project(self, z, positions):
        """(q_nope [B,S,nh,nope], rotated q_rope [B,S,nh,rot], the rows to
        cache [B,S,rank+rot] = [c | rotated k_rope])."""
        b, s, _ = z.shape
        f32 = jnp.float32
        cq = rms_norm(_dot(z, self.q_a_proj.value()),
                      self.q_a_layernorm.value().astype(f32) * self.q_scale,
                      self.eps, centred=False)
        # the heads are split from the product's rows as they come out:
        # laid out heads first for the head-batched products downstream,
        # the product would re-lay all of ``Wqb`` a call to make them so
        q = _row_major(_dot(cq, self.q_b_proj.value()))
        gate = None
        if self.gated:
            at = self.nh * (self.nope + self.rot)
            q, gate = q[..., :at], q[..., at:]
        q = q.reshape(b, s, self.nh, self.nope + self.rot)
        kv = _dot(z, self.kv_a_proj.value())
        c = rms_norm(kv[..., :self.rank],
                     self.kv_a_layernorm.value().astype(f32) * self.kv_scale,
                     self.eps, centred=False)
        q_rope = self._rope(q[..., self.nope:], positions)
        k_rope = self._rope(kv[..., None, self.rank:], positions)[:, :, 0]
        rows = jnp.concatenate([c, k_rope], -1)
        if gate is None and not self.noise:
            return q[..., :self.nope], q_rope, rows
        return q[..., :self.nope], q_rope, rows, gate

    def _expanded(self, q_nope, q_rope, rows, positions, keys=None):
        """Causal attention of the call's queries over ``rows [B, M,
        >= rank+rot]`` (position ``m`` at index ``m``, or ``keys [M]``;
        one below 0 is no key), each group's keys and values expanded from
        the rows' latent, the groups' heads in blocks; a window layer's
        band. Returns the context [B, S, nh * vd]."""
        b, s, nh = q_nope.shape[:3]
        m, dt = rows.shape[1], q_nope.dtype
        prec = "highest" if dt == jnp.float32 else None
        f32 = jnp.float32
        kpos = jnp.arange(m)[None, None, None, :] if keys is None \
            else keys[None, None, None, :]
        qpos = positions[:, None, :, None]
        live = kpos <= qpos                                 # [B|1,1,S,M]
        if keys is not None:
            live &= kpos >= 0
        if self.window:
            live &= kpos > qpos - self.window
        if self.per > 1:                            # [B|1,1,1,S,M]
            live = live[:, None]
        g = self.groups
        gb = max(1, min(g, SCORE_BLOCK // max(s * m * self.per, 1)))
        while g % gb:
            gb -= 1
        h, k = self._h, self._k
        k_rope = rows[..., self.rank:self.rank + self.rot]

        def block(at):
            qn, qr, kv = at     # [B,S,gb(,per),nope] [..,rot] [B,M,gb,n+v]
            sc = jnp.einsum(f"bq{h}d,bm{k}d->b{h}qm", qn, kv[..., :self.nope],
                            precision=prec, preferred_element_type=f32) \
                + jnp.einsum(f"bq{h}d,bmd->b{h}qm", qr, k_rope,
                             precision=prec, preferred_element_type=f32)
            probs = jax.nn.softmax(jnp.where(live, sc * self.scale, -1e30),
                                   axis=-1).astype(dt)
            return jnp.einsum(f"b{h}qm,bm{k}d->bq{h}d", probs,
                              kv[..., self.nope:], precision=prec,
                              preferred_element_type=f32).astype(dt)

        at = (self._by_group(q_nope), self._by_group(q_rope),
              self._expand(rows[..., :self.rank], dt))
        if gb == g:
            ctx = block(at)
        else:
            def split(t):       # the group axis as (blocks, gb), blocks first
                return jnp.moveaxis(t.reshape(
                    t.shape[:2] + (g // gb, gb) + t.shape[3:]), 2, 0)

            ctx = jnp.moveaxis(jax.lax.map(block, tuple(map(split, at))),
                               0, 2)                # [B,S,n,gb(,per),vd]
        return ctx.reshape(b, s, nh * self.vd)

    def _walked(self, q_nope, q_rope, pool, table, positions, end):
        """One slot's chunk: the EXPANDED form block by block
        (``hybrid.walk_keys``): a trip takes its rows of ``pool`` through
        ``table``, expands ``k_nope | v`` for them from their latent, all
        heads at once, and scores as ``_expanded`` does (operands in the
        rows' dtype, float32 accumulation and softmax). Returns the
        context [B, S, nh * vd]."""
        b, s, nh = q_nope.shape[:3]
        dt = q_nope.dtype
        prec = "highest" if dt == jnp.float32 else None
        f32 = jnp.float32
        h, k = self._h, self._k
        q_nope, q_rope = self._by_group(q_nope), self._by_group(q_rope)

        def fetch(entries):
            with jax.named_scope("kv_gather"):
                rows = jnp.take(pool, entries, axis=0, mode="clip").reshape(
                    b, -1, pool.shape[2])
            return (self._expand(rows[..., :self.rank], dt),
                    rows[..., self.rank:self.rank + self.rot])

        def score(held):
            kv, k_rope = held
            return (jnp.einsum(f"bq{h}d,bm{k}d->b{h}qm", q_nope,
                               kv[..., :self.nope], precision=prec,
                               preferred_element_type=f32)
                    + jnp.einsum(f"bq{h}d,bmd->b{h}qm", q_rope, k_rope,
                                 precision=prec, preferred_element_type=f32)
                    ) * self.scale

        def value(probs, held):
            return jnp.einsum(f"b{h}qm,bm{k}d->b{h}qd", probs.astype(dt),
                              held[0][..., self.nope:], precision=prec,
                              preferred_element_type=f32)

        ctx = walk_keys(table, positions, end, pool.shape[1], nh * s, fetch,
                        score, value)                       # [B,nh,S,vd]
        # rounded where the walk leaves it, so that moving the heads behind
        # the queries copies the context in dt (left to itself, the
        # compiler moves the walk's float32 context: twice the bytes)
        ctx = _row_major(ctx.reshape(b, nh, s, self.vd).astype(dt))
        return jnp.moveaxis(ctx, 1, 2).reshape(b, s, nh * self.vd)

    def _absorbed(self, q_nope, q_rope, pool, table, last, lam=None):
        """The decode step: ``[q_nope Wkvb_k^T | q_rope]`` against the
        cached rows (a slot's rows ``0 .. last`` through ``table``),
        context over their latent lanes, the noise heads' taken off in
        latent space (``lam``), then ``Wkvb_v``. Through the Pallas kernel
        where it runs, else over the gathered view. Returns the context
        [B, 1, signal * vd]."""
        from ..kernels.pallas import paged_decode
        b, dt = q_nope.shape[0], q_nope.dtype
        prec = "highest" if dt == jnp.float32 else None
        w = self._kv_b()
        h, k = self._h, self._k
        q_lat = jnp.einsum(f"bq{h}d,r{k}d->bq{h}r", self._by_group(q_nope),
                           w[..., :self.nope], precision=prec,
                           preferred_element_type=jnp.float32).astype(dt)
        q_lat = q_lat.reshape(q_rope.shape[:3] + (self.rank,))
        lanes = pool.shape[-1]
        q_all = jnp.concatenate(
            [q_lat, q_rope, jnp.zeros(q_lat.shape[:3] + (
                lanes - self.rank - self.rot,), dt)], axis=-1)
        mode = paged_decode.latent_mode(q_all, pool, self.rank)
        if mode is not None:
            ctx_lat = paged_decode.latent_decode_attention(
                q_all, pool, table, last + 1, rank=self.rank,
                scale=self.scale, interpret=mode == "interpret")
        else:
            with jax.named_scope("kv_gather"):
                rows = jnp.take(pool, table, axis=0).reshape(b, -1, lanes)
            sc = jnp.einsum("bqhl,bml->bhqm", q_all, rows, precision=prec,
                            preferred_element_type=jnp.float32) * self.scale
            live = jnp.arange(rows.shape[1])[None, None, None, :] \
                <= last[:, None, None, None]
            probs = jax.nn.softmax(jnp.where(live, sc, -1e30), axis=-1)
            ctx_lat = jnp.einsum(
                "bhqm,bmr->bqhr", probs,
                rows[..., :self.rank].astype(jnp.float32),
                precision="highest").astype(dt)
        if self.noise:
            with jax.named_scope("gdla_combine"):
                ctx_lat = self._combine(ctx_lat, lam).astype(dt)
        if self.per > 1:        # the signal heads of a group on its values
            ctx_lat = ctx_lat.reshape(b, 1, self.groups, -1, self.rank)
        ctx = jnp.einsum(f"bq{h}r,r{k}d->bq{h}d", ctx_lat,
                         w[..., self.nope:], precision=prec,
                         preferred_element_type=jnp.float32).astype(dt)
        return ctx.reshape(b, 1, self.signal * self.vd)

    def _ring_write(self, pool, ring, rows, positions, we):
        """A window layer's rows into its rings: position ``p`` at ring row
        ``p mod window``, the last ``window`` valid positions of the call
        only (a longer chunk would land several on one row in one
        write). A run that passes the ring's end is two writes, its tail
        first, then what wraps to the ring's start: one write through the
        table written twice over would stage the same block twice."""
        b, s = rows.shape[:2]
        w = self.window
        start = positions[:, 0]
        n = jnp.clip(jnp.asarray(we, jnp.int32) - start, 0, s)
        if s > w:           # one cursor: keep the last `window` valid rows
            off = jnp.maximum(n - w, 0)
            rows = jax.lax.dynamic_slice_in_dim(rows, off[0], w, axis=1)
            start, n, s = start + off, n - off, w
        at = start % w
        steps = jnp.arange(s, dtype=jnp.int32)[None, :]
        pool = write_rows(pool, ring, rows, at[:, None] + steps, at + n)
        if s == 1:          # a decode row never passes the ring's end
            return pool
        wrapped = jax.lax.dynamic_slice_in_dim(
            jnp.concatenate([rows, jnp.zeros_like(rows)], axis=1),
            w - at[0], s, axis=1)
        return write_rows(pool, ring, wrapped, steps, at + n - w)

    def _window_chunk(self, q_nope, q_rope, rows, pool, ring, positions):
        """A window layer's chunk: the expanded form over the ring as it
        was before the call (ring row ``r`` holds the last position before
        the chunk that is ``r`` mod window; one below 0 is none) and the
        call's own rows, under the band."""
        b, s = rows.shape[:2]
        w = self.window
        with jax.named_scope("kv_gather"):
            held = jnp.take(pool, ring, axis=0).reshape(
                b, w, pool.shape[2])[..., :rows.shape[-1]]
        p0 = positions[0, 0]
        r = jnp.arange(w, dtype=jnp.int32)
        ring_pos = p0 - 1 - jnp.mod(p0 - 1 - r, w)
        return self._expanded(
            q_nope, q_rope, jnp.concatenate([held.astype(rows.dtype), rows],
                                            axis=1), positions,
            jnp.concatenate([ring_pos, positions[0]]))

    def _out(self, ctx, z, gate, lam):
        """The heads' context [B, S, (nh or signal) * vd] -> output [B, S,
        H]: the differential combine where the context still has its
        noise heads, the gate, ``Wo``."""
        b, s = z.shape[:2]
        if self.noise or self.gated:
            with jax.named_scope("gdla_combine"):
                if ctx.shape[-1] != self.signal * self.vd:
                    ctx = self._combine(
                        ctx.reshape(b, s, self.nh, self.vd), lam)
                if self.gated:
                    ctx = ctx.astype(jnp.float32) * jax.nn.sigmoid(
                        gate.astype(jnp.float32)).reshape(ctx.shape)
                ctx = ctx.astype(z.dtype)
        return _dot(ctx.reshape(b, s, self.signal * self.vd),
                    self.o_proj.value())

    def apply(self, z, cache, pos, end):
        """``z [B, S, H]`` (normed); ``cache`` = (pool, table) or None (a
        window layer: its rings and their table). Returns (output [B, S,
        H], the pool after the write or None)."""
        b, s, _ = z.shape
        positions = _positions(pos, s)
        grouped = self.noise or self.gated or self.per > 1
        with jax.named_scope("gdla_project" if grouped else "mla_project"):
            q_nope, q_rope, rows, *rest = self._project(z, positions)
            gate = rest[0] if rest else None
            lam = jax.nn.sigmoid(_dot(z, self.lambda_proj.value()).astype(
                jnp.float32)) if self.noise else None
        if cache is None:
            with jax.named_scope("mla_prefill"):
                ctx = self._expanded(q_nope, q_rope, rows, positions)
            return self._out(ctx, z, gate, lam), None
        pool, table = cache
        we = end if end is not None else jnp.asarray(pos, jnp.int32) + s
        lanes = pool.shape[-1]
        padded = jnp.concatenate([rows, jnp.zeros(
            (b, s, lanes - rows.shape[-1]), rows.dtype)], axis=-1)
        if self.window:
            if s == 1 and jnp.ndim(pos) == 1:
                with jax.named_scope("window_write"):
                    new_pool = self._ring_write(pool, table, padded,
                                                positions, we)
                with jax.named_scope("mla_decode"):
                    ctx = self._absorbed(
                        q_nope, q_rope, new_pool, table,
                        jnp.minimum(pos, self.window - 1), lam)
            else:
                with jax.named_scope("mla_prefill"):
                    ctx = self._window_chunk(q_nope, q_rope, rows, pool,
                                             table, positions)
                with jax.named_scope("window_write"):
                    new_pool = self._ring_write(pool, table, padded,
                                                positions, we)
            return self._out(ctx, z, gate, lam), (new_pool,)
        with jax.named_scope("latent_write"):
            new_pool = write_rows(pool, table, padded, positions, we)
        if s == 1 and jnp.ndim(pos) == 1:
            with jax.named_scope("mla_decode"):
                ctx = self._absorbed(q_nope, q_rope, new_pool, table, pos,
                                     lam)
        else:
            with jax.named_scope("mla_prefill"):
                ctx = self._walked(q_nope, q_rope, new_pool, table,
                                   positions, we)
        return self._out(ctx, z, gate, lam), (new_pool,)
