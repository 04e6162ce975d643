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
  forward, which has no table, takes its own rows whole, heads in blocks.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .cache_spec import latent_layer
from .hybrid import (_dot, _positions, _Weights, rms_norm, rope,
                     rope_frequencies, walk_keys, write_rows)

__all__ = ["LatentAttention"]

SCORE_BLOCK = 1 << 25         # score elements of one block of heads (f32)


class LatentAttention(_Weights):
    def __init__(self, cfg):
        super().__init__(cfg)
        h, self.nh = cfg.hidden_size, cfg.num_attention_heads
        self.rank, self.q_rank = cfg.kv_lora_rank, cfg.q_lora_rank
        self.nope, self.rot = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        self.vd = cfg.v_head_dim
        self.theta, self.eps = cfg.rope_theta, cfg.rms_norm_eps
        self.q_scale = math.sqrt(h / self.q_rank) \
            if getattr(cfg, "mla_scale_q_lora", False) else 1.0
        self.kv_scale = math.sqrt(h / self.rank) \
            if getattr(cfg, "mla_scale_kv_lora", False) else 1.0
        self.interleave = bool(getattr(cfg, "rope_interleave", False))
        self.inv, factor = rope_frequencies(
            self.rot, self.theta, getattr(cfg, "rope_scaling", None))
        self.scale = factor / math.sqrt(self.nope + self.rot)
        self.q_a_proj = self.mat(h, self.q_rank)
        self.q_a_layernorm = self.const(1.0, self.q_rank)
        self.q_b_proj = self.mat(self.q_rank, self.nh * (self.nope + self.rot))
        self.kv_a_proj = self.mat(h, self.rank + self.rot)
        self.kv_a_layernorm = self.const(1.0, self.rank)
        self.kv_b_proj = self.mat(self.rank, self.nh * (self.nope + self.vd))
        self.o_proj = self.mat(self.nh * self.vd, h)

    def cache_entry(self):
        return latent_layer(self.rank, self.rot)

    def _kv_b(self):
        """``Wkvb`` as [rank, heads, nope | v]."""
        return self.kv_b_proj.value().reshape(self.rank, self.nh,
                                              self.nope + self.vd)

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
        q = _dot(cq, self.q_b_proj.value()).reshape(
            b, s, self.nh, self.nope + self.rot)
        kv = _dot(z, self.kv_a_proj.value())
        c = rms_norm(kv[..., :self.rank],
                     self.kv_a_layernorm.value().astype(f32) * self.kv_scale,
                     self.eps, centred=False)
        q_rope = self._rope(q[..., self.nope:], positions)
        k_rope = self._rope(kv[..., None, self.rank:], positions)[:, :, 0]
        return q[..., :self.nope], q_rope, jnp.concatenate([c, k_rope], -1)

    def _expanded(self, q_nope, q_rope, rows, positions):
        """Causal attention of the call's queries over ``rows [B, M,
        >= rank+rot]`` (position ``m`` at index ``m``), per-head keys and
        values expanded from the rows' latent, heads in blocks. Returns
        the context [B, S, nh * vd]."""
        b, s, nh = q_nope.shape[:3]
        m, dt = rows.shape[1], q_nope.dtype
        prec = "highest" if dt == jnp.float32 else None
        c, k_rope = rows[..., :self.rank], \
            rows[..., self.rank:self.rank + self.rot]
        live = jnp.arange(m)[None, None, None, :] \
            <= positions[:, None, :, None]                  # [B|1,1,S,M]
        hb = max(1, min(nh, SCORE_BLOCK // max(s * m, 1)))
        while nh % hb:
            hb -= 1

        def block(at):
            qn, qr, w = at          # [B,S,hb,nope] [B,S,hb,rot] [rank,hb,n+v]
            kv = jnp.einsum("bmr,rhd->bmhd", c, w, precision=prec,
                            preferred_element_type=jnp.float32).astype(dt)
            sc = jnp.einsum("bqhd,bmhd->bhqm", qn, kv[..., :self.nope],
                            precision=prec,
                            preferred_element_type=jnp.float32) \
                + jnp.einsum("bqhd,bmd->bhqm", qr, k_rope, precision=prec,
                             preferred_element_type=jnp.float32)
            probs = jax.nn.softmax(jnp.where(live, sc * self.scale, -1e30),
                                   axis=-1).astype(dt)
            return jnp.einsum("bhqm,bmhd->bqhd", probs, kv[..., self.nope:],
                              precision=prec,
                              preferred_element_type=jnp.float32).astype(dt)

        w = self._kv_b()
        if hb == nh:
            ctx = block((q_nope, q_rope, w))
        else:
            def split(t, ax):   # the head axis as (blocks, hb), blocks first
                return jnp.moveaxis(t.reshape(
                    t.shape[:ax] + (nh // hb, hb) + t.shape[ax + 1:]), ax, 0)

            ctx = jax.lax.map(block, (split(q_nope, 2), split(q_rope, 2),
                                      split(w, 1)))       # [n,B,S,hb,vd]
            ctx = jnp.moveaxis(ctx, 0, 2)
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
        w = self._kv_b()

        def fetch(entries):
            with jax.named_scope("kv_gather"):
                rows = jnp.take(pool, entries, axis=0, mode="clip").reshape(
                    b, -1, pool.shape[2])
            kv = jnp.einsum("bmr,rhd->bmhd", rows[..., :self.rank], w,
                            precision=prec,
                            preferred_element_type=f32).astype(dt)
            return kv, rows[..., self.rank:self.rank + self.rot]

        def score(held):
            kv, k_rope = held
            return (jnp.einsum("bqhd,bmhd->bhqm", q_nope,
                               kv[..., :self.nope], precision=prec,
                               preferred_element_type=f32)
                    + jnp.einsum("bqhd,bmd->bhqm", q_rope, k_rope,
                                 precision=prec, preferred_element_type=f32)
                    ) * self.scale

        def value(probs, held):
            return jnp.einsum("bhqm,bmhd->bhqd", probs.astype(dt),
                              held[0][..., self.nope:], precision=prec,
                              preferred_element_type=f32)

        ctx = walk_keys(table, positions, end, pool.shape[1], nh * s, fetch,
                        score, value)                       # [B,nh,S,vd]
        return jnp.moveaxis(ctx, 1, 2).astype(dt).reshape(b, s,
                                                          nh * self.vd)

    def _absorbed(self, q_nope, q_rope, pool, table, pos):
        """The decode step: ``[q_nope Wkvb_k^T | q_rope]`` against the
        cached rows, context over their latent lanes, then ``Wkvb_v``.
        Through the Pallas kernel where it runs, else over the gathered
        view. Returns the context [B, 1, nh * vd]."""
        from ..kernels.pallas import paged_decode
        b, dt = q_nope.shape[0], q_nope.dtype
        prec = "highest" if dt == jnp.float32 else None
        w = self._kv_b()
        q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope, w[..., :self.nope],
                           precision=prec,
                           preferred_element_type=jnp.float32).astype(dt)
        lanes = pool.shape[-1]
        q_all = jnp.concatenate(
            [q_lat, q_rope, jnp.zeros(q_lat.shape[:3] + (
                lanes - self.rank - self.rot,), dt)], axis=-1)
        mode = paged_decode.latent_mode(q_all, pool, self.rank)
        if mode is not None:
            ctx_lat = paged_decode.latent_decode_attention(
                q_all, pool, table, pos + 1, rank=self.rank,
                scale=self.scale, interpret=mode == "interpret")
        else:
            with jax.named_scope("kv_gather"):
                rows = jnp.take(pool, table, axis=0).reshape(b, -1, lanes)
            sc = jnp.einsum("bqhl,bml->bhqm", q_all, rows, precision=prec,
                            preferred_element_type=jnp.float32) * self.scale
            live = jnp.arange(rows.shape[1])[None, None, None, :] \
                <= pos[:, None, None, None]
            probs = jax.nn.softmax(jnp.where(live, sc, -1e30), axis=-1)
            ctx_lat = jnp.einsum(
                "bhqm,bmr->bqhr", probs,
                rows[..., :self.rank].astype(jnp.float32),
                precision="highest").astype(dt)
        ctx = jnp.einsum("bqhr,rhd->bqhd", ctx_lat, w[..., self.nope:],
                         precision=prec,
                         preferred_element_type=jnp.float32).astype(dt)
        return ctx.reshape(b, 1, self.nh * self.vd)

    def apply(self, z, cache, pos, end):
        """``z [B, S, H]`` (normed); ``cache`` = (pool, table) or None.
        Returns (output [B, S, H], the pool after the write or None)."""
        b, s, _ = z.shape
        positions = _positions(pos, s)
        with jax.named_scope("mla_project"):
            q_nope, q_rope, rows = self._project(z, positions)
        new_pool = None
        if cache is None:
            with jax.named_scope("mla_prefill"):
                ctx = self._expanded(q_nope, q_rope, rows, positions)
        else:
            pool, table = cache
            lanes = pool.shape[-1]
            we = end if end is not None else jnp.asarray(pos, jnp.int32) + s
            with jax.named_scope("latent_write"):
                padded = jnp.concatenate([rows, jnp.zeros(
                    (b, s, lanes - rows.shape[-1]), rows.dtype)], axis=-1)
                new_pool = write_rows(pool, table, padded, positions, we)
            if s == 1 and jnp.ndim(pos) == 1:
                with jax.named_scope("mla_decode"):
                    ctx = self._absorbed(q_nope, q_rope, new_pool, table,
                                         pos)
            else:
                with jax.named_scope("mla_prefill"):
                    ctx = self._walked(q_nope, q_rope, new_pool, table,
                                       positions, we)
        return _dot(ctx, self.o_proj.value()), \
            (None if new_pool is None else (new_pool,))
