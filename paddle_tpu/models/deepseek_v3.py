"""Decoder of DeepSeek-V3 (the weights DeepSeek-R1 is served from): a
pre-norm block of multi-head latent attention under YaRN, then a dense
SwiGLU in the leading ``first_k_dense_replace`` layers and a routed expert
layer after them, whose router scores by sigmoid and keeps a token's
choice inside its best groups of experts, beside one ungated shared
expert.

Layer equations (DeepSeek-V3 Technical Report; ``N*`` are RMSNorms ``w * x
/ rms(x)``, eps ``rms_norm_eps``)::

    a = x + MLA(N1(x))
    y = a + F(N2(a))        # F = DenseFFN (width intermediate_size) in the
                            #   leading layers, MoE after them

* ``MLA(z)``: ``latent_attention.py::LatentAttention`` (shared with
  ``longcat_flash.py``), no ``mla_scale_*`` factors; rotary on INTERLEAVED
  pairs ``(2i, 2i + 1)`` (``rope_interleave``, as DeepSeek's own code
  rotates) with YaRN's frequencies (``rope_scaling``), and the softmax
  scale ``(nope + rope) ** -0.5 * mscale(mscale_all_dim) ** 2``
  (``hybrid.rope_frequencies``). One cached row ``[c | rotated k_rope]`` a
  token a layer (``decode_spec()`` gives one ``latent_layer`` a layer); the
  decode step runs the absorbed form (the kernel ``mla_decode`` on a TPU),
  a prefill chunk the expanded form over the key blocks its slot holds.
* ``MoE(u)``: ``incubate/.../moe/held.py::HeldExpertsMoE``: ``s =
  sigmoid(u W_g)`` in float32 over ``router_experts`` outputs; ``s + b``
  (``b`` the choice bias, ``e_score_correction_bias``) steers the choice
  only; a group's score is the sum of its two largest ``s + b``, the
  ``topk_group`` best of ``n_group`` groups are kept and the other outputs
  masked; the ``num_experts_per_tok`` best of what is left are chosen,
  weights ``s / sum(s)`` over the chosen times ``routed_scaling_factor``;
  a chosen expert adds ``w * SwiGLU_e(u)``, and ``n_shared_experts``
  shared experts (one SwiGLU of that many times ``moe_intermediate_size``)
  add ``SwiGLU_shared(u)`` ungated. This chip computes the ``num_experts``
  experts it HOLDS (ids ``expert_offset ..``; an assignment elsewhere is
  its owner's) and the shared expert whole.

Inference-only raw-array math (as ``qwen3_next.py``): serving through
``serving.DecodeEngine`` and a full forward. The multi-token-prediction
layer (``num_nextn_predict_layers``) is a draft head for speculative
decoding and is not built.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..incubate.distributed.models.moe.held import HeldExpertsMoE
from .cache_spec import ModelSpec
from .hybrid import _dot, _valid, _Weights, DenseFFN, rms_norm
from .latent_attention import LatentAttention

__all__ = ["DeepseekV3Config", "DeepseekV3Model", "DeepseekV3ForCausalLM",
           "deepseek_v3_tiny"]

YARN = {"type": "yarn", "factor": 40,
        "original_max_position_embeddings": 4096, "beta_fast": 32,
        "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}


@dataclass
class DeepseekV3Config:
    vocab_size: int = 129280
    hidden_size: int = 7168
    intermediate_size: int = 18432        # the leading dense layers
    moe_intermediate_size: int = 2048     # an expert, and a shared expert
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    # latent attention
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    rope_scaling: dict = field(default_factory=lambda: dict(YARN))
    rope_interleave: bool = True
    # experts, named as `Qwen3NextConfig` names them: `num_experts` are
    # HELD here, ids `expert_offset ..`, of the `router_experts` the router
    # scores (0 -> = num_experts: all of them; the published
    # `n_routed_experts`)
    num_experts: int = 256
    router_experts: int = 0
    expert_offset: int = 0
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"         # the choice bias steers the top-k
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 163840
    initializer_range: float = 0.02
    dtype: str = "float32"

    def __post_init__(self):
        if self.router_experts == 0:
            self.router_experts = self.num_experts


def deepseek_v3_tiny(**overrides) -> DeepseekV3Config:
    """Three layers at toy widths, the first dense: 4 of 16 experts held (4
    groups of 4, top-4 within 2 groups), YaRN over 32 original positions."""
    cfg = dict(vocab_size=512, hidden_size=64, intermediate_size=96,
               moe_intermediate_size=32, num_hidden_layers=3,
               first_k_dense_replace=1, num_attention_heads=4,
               q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=16, rope_theta=1e4,
               rope_scaling=dict(YARN, factor=4,
                                 original_max_position_embeddings=32),
               num_experts=4, router_experts=16, n_group=4, topk_group=2,
               num_experts_per_tok=4, max_position_embeddings=256)
    cfg.update(overrides)
    return DeepseekV3Config(**cfg)


class DeepseekV3Block(_Weights):
    def __init__(self, cfg: DeepseekV3Config, dense: bool):
        super().__init__(cfg)
        h = cfg.hidden_size
        self.eps, self.dense = cfg.rms_norm_eps, dense
        self.input_layernorm = self.const(1.0, h)
        self.post_attention_layernorm = self.const(1.0, h)
        self.self_attn = LatentAttention(cfg)
        if dense:
            self.mlp = DenseFFN(cfg, cfg.intermediate_size)
        else:
            self.mlp = HeldExpertsMoE(
                h, cfg.moe_intermediate_size, cfg.router_experts,
                cfg.num_experts_per_tok, offset=cfg.expert_offset,
                count=cfg.num_experts, norm_topk_prob=cfg.norm_topk_prob,
                shared_width=cfg.n_shared_experts * cfg.moe_intermediate_size,
                choice_bias=cfg.topk_method == "noaux_tc",
                scaling=cfg.routed_scaling_factor, scoring=cfg.scoring_func,
                n_group=cfg.n_group, topk_group=cfg.topk_group,
                shared_gated=False, std=cfg.initializer_range,
                dtype=cfg.dtype)

    def apply(self, x, cache, pos, end):
        """``cache`` = the layer's paged latent cache or None; returns it
        after the call."""
        b, s, h = x.shape
        a, new = self.self_attn.apply(
            rms_norm(x, self.input_layernorm.value(), self.eps,
                     centred=False), cache, pos, end)
        x = x + a
        u = rms_norm(x, self.post_attention_layernorm.value(), self.eps,
                     centred=False)
        if self.dense:
            return x + self.mlp.apply(u), new
        m = self.mlp.apply(u.reshape(b * s, h),
                           _valid(pos, end, b, s).reshape(-1))
        return x + m.reshape(b, s, h), new


class DeepseekV3Model(_Weights):
    def __init__(self, cfg: DeepseekV3Config):
        super().__init__(cfg)
        self.config = cfg
        self.embed_tokens = self.mat(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList([
            DeepseekV3Block(cfg, i < cfg.first_k_dense_replace)
            for i in range(cfg.num_hidden_layers)])
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


class DeepseekV3ForCausalLM(_Weights):
    def __init__(self, cfg: DeepseekV3Config):
        super().__init__(cfg)
        self.config = cfg
        self.model = DeepseekV3Model(cfg)
        self.lm_head = self.mat(cfg.hidden_size, cfg.vocab_size)

    def forward(self, input_ids):
        """Full forward, no cache: logits [B, S, V]."""
        return Tensor(_dot(self.model(input_ids).value(),
                           self.lm_head.value()))

    def decode_spec(self) -> ModelSpec:
        layers = [block.self_attn.cache_entry()
                  for block in self.model.layers]
        return ModelSpec(self.model, layers,
                         self.config.max_position_embeddings, self.lm_head,
                         False)

    def generate(self, input_ids, max_new_tokens: int = 32, **kw):
        """Through ``serving.DecodeEngine`` (the only cached path this
        family has)."""
        from ..serving import generate_via_engine
        kw.pop("use_engine", None)
        return generate_via_engine(self, input_ids,
                                   max_new_tokens=max_new_tokens, **kw)
