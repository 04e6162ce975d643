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

* ``MLA(z)``: multi-head latent attention, ``latent_attention.py::
  LatentAttention`` (shared with ``deepseek_v3.py``) with both
  ``mla_scale_*`` factors (``sqrt(hidden / q_lora_rank)`` on the normed
  query latent, ``sqrt(hidden / kv_lora_rank)`` on the normed key/value
  latent), rotate-half rotary, no rope scaling: the cache is one ``[c |
  rotated k_rope]`` row a token a sublayer, a decode step runs the absorbed
  form (the kernel ``mla_decode`` on a TPU), a prefill chunk the expanded
  form over the key blocks its slot holds.
* ``MoE(u)``: ``incubate/.../moe/held.py::HeldExpertsMoE`` with ``n_zero``
  zero experts, a choice bias, ``routed_scaling_factor`` and no
  renormalisation: float32 softmax over ``router_experts +
  zero_expert_num`` outputs, top-k of ``softmax + bias``, a real expert adds
  ``w * SwiGLU_e(u)``, a zero expert ``w * u``. This chip computes the
  ``num_experts`` real experts it HOLDS (ids ``expert_offset ..``) and
  the zero experts' term whole.

A layer owns two latent pools (``decode_spec()`` gives ``(latent, latent)``
a layer).

Inference-only raw-array math (as ``qwen3_next.py``): serving through
``serving.DecodeEngine`` and a full forward. The Omni model's audio and
vision encoders and its codec decoder are not here.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..incubate.distributed.models.moe.held import HeldExpertsMoE
from .cache_spec import ModelSpec
from .hybrid import _dot, _valid, _Weights, DenseFFN, rms_norm
from .latent_attention import LatentAttention

__all__ = ["LongCatFlashConfig", "LongCatFlashModel",
           "LongCatFlashForCausalLM", "longcat_flash_tiny"]

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
        self.mlps = nn.LayerList([DenseFFN(cfg, cfg.ffn_hidden_size)
                                  for _ in range(2)])
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
