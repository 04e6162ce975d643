"""Decoder of the LongCat-Flash family (the language model of
LongCat-Flash-Omni): every block holds TWO latent-attention sublayers, two
dense feed-forwards and ONE routed expert layer whose output joins the
stream a sublayer later (the shortcut), its router wider than its real
experts by a set of zero-compute (identity) experts.

Layer equations (LongCat-Flash Technical Report; ``N*`` are RMSNorms ``w *
x / rms(x)``; there is no shared expert)::

    a1 = x  + MLA_1(N1(x))
    u  = N2(a1)
    m  = MoE(u)                        # the shortcut: computed here, added last
    h1 = a1 + FFN_1(u)                 # SwiGLU
    a2 = h1 + MLA_2(N3(h1))
    y  = a2 + FFN_2(N4(a2)) + m

* ``MLA(z)`` (multi-head latent attention): ``cq = Nq(z Wqa) * sqrt(hidden
  / q_lora_rank)``; ``q = cq Wqb`` -> heads of ``[q_nope | q_rope]``;
  ``[ckv | k_rope] = z Wkva``; ``c = Nkv(ckv) * sqrt(hidden /
  kv_lora_rank)``; ``[k_nope | v] = c Wkvb`` per head; rotate-half rotary on
  ``q_rope`` and on the ONE ``k_rope`` all heads share; causal softmax of
  ``(q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope)``; context
  ``Wo``. The two ``mla_scale_*`` factors ride in the norms' float32
  weights (one rounding).
* ``MoE(u)``: ``incubate/.../moe/held.py::HeldExpertsMoE`` with ``n_zero``
  zero experts, a choice bias, ``routed_scaling_factor`` and no
  renormalisation: float32 softmax over ``router_experts +
  zero_expert_num`` outputs, top-k of ``softmax + bias``, a real expert adds
  ``w * SwiGLU_e(u)``, a zero expert ``w * u``. This chip computes the
  ``num_experts`` real experts it HOLDS (ids ``expert_offset ..``) and
  the zero experts' term whole.

What is cached a token a sublayer is ONE row ``[c | rotated k_rope]``
(``cache_spec.latent_layer``), not per-head keys and values: a block of the
paged pool is a ``[block, lanes]`` matrix, and a layer owns two such pools
(``decode_spec()`` gives ``(latent, latent)`` a layer). A cached call
writes its rows at their positions first (before ``write_end``); then

* a decode step (one position a slot, per-slot cursors) runs the ABSORBED
  form: ``q_lat = q_nope Wkvb_k^T`` per head, scores of ``[q_lat | q_rope]``
  against the cached rows, the context over the rows' first ``rank`` lanes,
  then ``Wkvb_v`` and ``Wo``: on a TPU (or under the test seam) in the
  Pallas kernel ``mla_decode`` (``kernels/pallas/paged_decode.py``), else
  over the gathered view;
* a prefill chunk (and the full forward) runs the EXPANDED form: per-head
  ``k_nope`` and ``v`` from the cached rows (cheaper than the absorbed form
  once many queries share the expansion). A chunk walks the rows its slot
  holds before ``write_end`` in key blocks, all heads a trip
  (``hybrid.walk_keys``), and never the rest of the table row; the full
  forward, which has no table, takes its own rows whole, heads in blocks.

Inference-only raw-array math (as ``qwen3_next.py``): serving through
``serving.DecodeEngine`` and a full forward. The Omni model's audio and
vision encoders and its codec decoder are not here.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..incubate.distributed.models.moe.held import HeldExpertsMoE
from .cache_spec import ModelSpec, latent_layer
from .hybrid import (_dot, _positions, _valid, _Weights, rms_norm, rope,
                     walk_keys, write_rows)

__all__ = ["LongCatFlashConfig", "LongCatFlashModel",
           "LongCatFlashForCausalLM", "longcat_flash_tiny"]

SCORE_BLOCK = 1 << 25         # score elements of one block of heads (f32)


@dataclass
class LongCatFlashConfig:
    vocab_size: int = 131072
    hidden_size: int = 6144
    num_hidden_layers: int = 28           # published `num_layers`
    # latent attention
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    rope_theta: float = 1e7
    # dense feed-forwards (two a layer)
    ffn_hidden_size: int = 12288
    # experts, named as `Qwen3NextConfig` names them: `num_experts` real
    # ones are HELD here, ids `expert_offset ..`, of the `router_experts`
    # the router scores (0 -> = num_experts: all of them; the published
    # `n_routed_experts`), and `zero_expert_num` identity experts behind
    # those
    num_experts: int = 512
    router_experts: int = 0
    expert_offset: int = 0
    zero_expert_num: int = 256
    moe_topk: int = 12
    expert_ffn_hidden_size: int = 2048
    routed_scaling_factor: float = 6.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    initializer_range: float = 0.02
    dtype: str = "float32"

    def __post_init__(self):
        if self.router_experts == 0:
            self.router_experts = self.num_experts


def longcat_flash_tiny(**overrides) -> LongCatFlashConfig:
    """Two layers at toy widths: 24 experts + 12 zero experts, top-4."""
    cfg = dict(vocab_size=512, hidden_size=64, num_hidden_layers=2,
               num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               rope_theta=1e4, ffn_hidden_size=96, num_experts=24,
               zero_expert_num=12, moe_topk=4, expert_ffn_hidden_size=32,
               routed_scaling_factor=2.5, max_position_embeddings=256)
    cfg.update(overrides)
    return LongCatFlashConfig(**cfg)


class LatentAttention(_Weights):
    def __init__(self, cfg: LongCatFlashConfig):
        super().__init__(cfg)
        h, self.nh = cfg.hidden_size, cfg.num_attention_heads
        self.rank, self.q_rank = cfg.kv_lora_rank, cfg.q_lora_rank
        self.nope, self.rot = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        self.vd = cfg.v_head_dim
        self.theta, self.eps = cfg.rope_theta, cfg.rms_norm_eps
        self.q_scale = math.sqrt(h / self.q_rank) if cfg.mla_scale_q_lora \
            else 1.0
        self.kv_scale = math.sqrt(h / self.rank) if cfg.mla_scale_kv_lora \
            else 1.0
        self.scale = 1.0 / math.sqrt(self.nope + self.rot)
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
        q_rope = rope(q[..., self.nope:], positions, self.rot, self.theta)
        k_rope = rope(kv[..., None, self.rank:], positions, self.rot,
                      self.theta)[:, :, 0]
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


class DenseFFN(_Weights):
    def __init__(self, cfg: LongCatFlashConfig):
        super().__init__(cfg)
        h, i = cfg.hidden_size, cfg.ffn_hidden_size
        self.gate_proj = self.mat(h, i)
        self.up_proj = self.mat(h, i)
        self.down_proj = self.mat(i, h)

    def apply(self, v):
        with jax.named_scope("dense_ffn"):
            hid = jax.nn.silu(_dot(v, self.gate_proj.value())
                              .astype(jnp.float32)) \
                * _dot(v, self.up_proj.value()).astype(jnp.float32)
            return _dot(hid.astype(v.dtype), self.down_proj.value())


class LongCatFlashBlock(_Weights):
    def __init__(self, cfg: LongCatFlashConfig):
        super().__init__(cfg)
        self.eps = cfg.rms_norm_eps
        h = cfg.hidden_size
        # N1 .. N4 of the equations
        self.attn_norm_1, self.ffn_norm_1 = self.const(1.0, h), \
            self.const(1.0, h)
        self.attn_norm_2, self.ffn_norm_2 = self.const(1.0, h), \
            self.const(1.0, h)
        self.self_attn = nn.LayerList([LatentAttention(cfg)
                                       for _ in range(2)])
        self.mlps = nn.LayerList([DenseFFN(cfg) for _ in range(2)])
        self.mlp = HeldExpertsMoE(
            h, cfg.expert_ffn_hidden_size, cfg.router_experts, cfg.moe_topk,
            offset=cfg.expert_offset, count=cfg.num_experts,
            norm_topk_prob=False, n_zero=cfg.zero_expert_num,
            choice_bias=True, scaling=cfg.routed_scaling_factor,
            std=cfg.initializer_range, dtype=cfg.dtype)

    def _norm(self, w, x):
        return rms_norm(x, w.value(), self.eps, centred=False)

    def apply(self, x, cache, pos, end):
        """``cache`` = the two sublayers' paged caches in order, or None;
        returns them in that order."""
        b, s, h = x.shape
        c1, c2 = cache if cache is not None else (None, None)
        a, n1 = self.self_attn[0].apply(
            self._norm(self.attn_norm_1, x), c1, pos, end)
        a1 = x + a
        u = self._norm(self.ffn_norm_1, a1)
        # the shortcut: the expert layer reads the first sublayer's output
        # and joins the stream after the second feed-forward
        m = self.mlp.apply(u.reshape(b * s, h),
                           _valid(pos, end, b, s).reshape(-1))
        h1 = a1 + self.mlps[0].apply(u)
        a, n2 = self.self_attn[1].apply(
            self._norm(self.attn_norm_2, h1), c2, pos, end)
        a2 = h1 + a
        y = a2 + self.mlps[1].apply(
            self._norm(self.ffn_norm_2, a2)) \
            + m.reshape(b, s, h)
        return y, (n1, n2)


class LongCatFlashModel(_Weights):
    def __init__(self, cfg: LongCatFlashConfig):
        super().__init__(cfg)
        self.config = cfg
        self.embed_tokens = self.mat(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList([LongCatFlashBlock(cfg)
                                    for _ in range(cfg.num_hidden_layers)])
        self.norm = self.const(1.0, cfg.hidden_size)

    def forward(self, input_ids, kv_caches=None, start_pos=None,
                write_end=None):
        ids = input_ids.value() if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        x = self.embed_tokens.value()[ids]
        pos = jnp.int32(0) if start_pos is None else start_pos
        caches = kv_caches if kv_caches is not None \
            else [None] * len(self.layers)
        new_caches = []
        for block, cache in zip(self.layers, caches):
            x, nc = block.apply(x, cache, pos, write_end)
            new_caches.append(nc)
        hidden = Tensor(rms_norm(x, self.norm.value(),
                                 self.config.rms_norm_eps, centred=False))
        return hidden if kv_caches is None else (hidden, new_caches)


class LongCatFlashForCausalLM(_Weights):
    def __init__(self, cfg: LongCatFlashConfig):
        super().__init__(cfg)
        self.config = cfg
        self.model = LongCatFlashModel(cfg)
        self.lm_head = self.mat(cfg.hidden_size, cfg.vocab_size)

    def forward(self, input_ids):
        """Full forward, no cache: logits [B, S, V]."""
        return Tensor(_dot(self.model(input_ids).value(),
                           self.lm_head.value()))

    def decode_spec(self) -> ModelSpec:
        cfg = self.config
        layers = [tuple(a.cache_entry() for a in block.self_attn)
                  for block in self.model.layers]
        return ModelSpec(self.model, layers, cfg.max_position_embeddings,
                         self.lm_head, False)

    def generate(self, input_ids, max_new_tokens: int = 32, **kw):
        """Through ``serving.DecodeEngine`` (the only cached path this
        family has)."""
        from ..serving import generate_via_engine
        kw.pop("use_engine", None)
        return generate_via_engine(self, input_ids,
                                   max_new_tokens=max_new_tokens, **kw)
