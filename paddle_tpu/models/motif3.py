"""Decoder of Motif-3 (Motif Technologies' Motif-3-Beta): grouped
differential latent attention in every layer, sliding-window layers beside
full ones, four residual streams joined by manifold-constrained
hyper-connections, and PolyNorm feed-forwards (dense in the leading
layers, a sigmoid-routed expert layer with one ungated shared expert after
them).

Layer equations (``N*`` are RMSNorms ``w * x / rms(x)``, eps
``rms_norm_eps``; ``X [n, C]`` the token's ``n = mhc_expansion_rate``
streams, ``X0 = [e; ...; e]`` from its embedding)::

    each sublayer F (attention, then feed-forward):
        u = H_pre X                         # kernels/pallas/mhc.py: one
        X <- clamp(H_res X + H_post^T F(N(u)), +-hidden_clamp)
                                            #   call a boundary, the write
                                            #   of F joined to the next read
    output: N_f(sum of the streams) W_head

* attention: ``latent_attention.py::LatentAttention`` (shared with
  ``deepseek_v3.py`` and ``longcat_flash.py``) with ``num_key_value_heads``
  latent groups of ``num_attention_heads / groups`` heads, the last head of
  a group its noise head (``num_noise_heads``), the signal heads' context
  ``ctx_s - sigmoid(z W_lam)_s ctx_noise``, an elementwise output gate
  (``elementwise_attn_output_gate``, read from extra ``Wqb`` columns). A
  ``full_attention`` layer (``layer_types``) rotates under YaRN without its
  scale factors (``apply_yarn_scaling`` false) and caches a paged latent
  row a position; a ``sliding_attention`` layer sees its last
  ``sliding_window`` positions, rotates plainly at ``swa_rope_theta`` and
  keeps them in a ring a slot (``cache_spec.window_latent_layer``).
* feed-forward: ``W_d(PolyNorm(W_g z) * W_u z)`` (``hybrid.DenseFFN`` with
  ``moe_grouped.Poly``) of ``intermediate_size`` in the first
  ``n_dense_first_layers`` layers; after them ``HeldExpertsMoE``: sigmoid
  scores over ``router_experts`` outputs in float32, the top
  ``experts_top_k`` of score + choice bias, weights renormalised
  (``route_norm``) times ``route_scale``, PolyNorm experts of
  ``moe_intermediate_size`` and ``num_shared_experts`` ungated shared
  expert. This chip computes the ``num_experts`` experts it HOLDS (ids
  ``expert_offset ..``; an assignment elsewhere is its owner's).

Inference-only raw-array math: serving through ``serving.DecodeEngine`` and
a full forward. The multi-token-prediction layer
(``num_nextn_predict_layers``) is a draft head and is not built.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..incubate.distributed.models.moe.held import HeldExpertsMoE
from ..kernels.pallas import mhc
from ..kernels.pallas.moe_grouped import Poly
from .cache_spec import ModelSpec
from .hybrid import _dot, _valid, _Weights, DenseFFN, rms_norm
from .latent_attention import LatentAttention

__all__ = ["Motif3Config", "Motif3Model", "Motif3ForCausalLM", "motif3_tiny"]

YARN = {"rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_fast": 32,
        "beta_slow": 1, "mscale": 1, "rope_theta": 10000,
        "apply_yarn_scaling": False}
WINDOW, FULL = "sliding_attention", "full_attention"


@dataclass
class Motif3Config:
    vocab_size: int = 220160
    hidden_size: int = 4096
    intermediate_size: int = 12288        # the leading dense layers
    moe_intermediate_size: int = 1280     # an expert, and the shared one
    num_hidden_layers: int = 53
    n_dense_first_layers: int = 2
    # each layer's attention, full or sliding-window: given, never derived
    layer_types: list = None
    # grouped differential latent attention
    num_attention_heads: int = 80
    num_key_value_heads: int = 16
    num_noise_heads: int = 16
    elementwise_attn_output_gate: bool = True
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    rope_scaling: dict = field(default_factory=lambda: dict(YARN))
    rope_interleave: bool = True
    sliding_window: int = 128
    swa_rope_theta: float = 10000.0
    # hyper-connections
    mhc_expansion_rate: int = 4
    mhc_sinkhorn_iters: int = 20
    hidden_clamp: float = 1e6
    # feed-forward
    hidden_act: str = "poly_norm"
    polynorm_output_scale: float = 0.5
    polynorm_bias_clamp: float = 0.5
    # experts, named as `DeepseekV3Config` names them: `num_experts` HELD
    # here, ids `expert_offset ..`, of the `router_experts` the router
    # scores (0 -> num_experts)
    num_experts: int = 384
    router_experts: int = 0
    expert_offset: int = 0
    num_shared_experts: int = 1
    experts_top_k: int = 8
    route_norm: bool = True
    route_scale: float = 2.0
    score_func: str = "sigmoid"
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    initializer_range: float = 0.02
    dtype: str = "float32"

    def __post_init__(self):
        if self.router_experts == 0:
            self.router_experts = self.num_experts
        if self.layer_types is None \
                or len(self.layer_types) != self.num_hidden_layers \
                or not set(self.layer_types) <= {WINDOW, FULL}:
            raise ValueError(
                f"layer_types must name each of the {self.num_hidden_layers}"
                f" layers {WINDOW!r} or {FULL!r}: {self.layer_types}")
        if self.hidden_act != "poly_norm":
            raise NotImplementedError(f"hidden_act {self.hidden_act!r}")


def motif3_tiny(**overrides) -> Motif3Config:
    """Five layers ``[W, W, F, W, W]`` at toy widths, the first dense: a
    window of 8, 2 latent groups of 4 signal heads and 1 noise head, 4 of
    16 experts held (top-4), YaRN over 32 original positions."""
    cfg = dict(vocab_size=512, hidden_size=64, intermediate_size=96,
               moe_intermediate_size=32, num_hidden_layers=5,
               n_dense_first_layers=1,
               layer_types=[WINDOW, WINDOW, FULL, WINDOW, WINDOW],
               num_attention_heads=10, num_key_value_heads=2,
               num_noise_heads=2, q_lora_rank=32, kv_lora_rank=16,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               rope_scaling=dict(YARN, factor=4,
                                 original_max_position_embeddings=32),
               sliding_window=8, swa_rope_theta=500.0, num_experts=4,
               router_experts=16, experts_top_k=4,
               max_position_embeddings=256)
    cfg.update(overrides)
    return Motif3Config(**cfg)


class HyperConnection(_Weights):
    """One sublayer's mHC coefficients: the weighted norm over ``n * C``
    values, ``phi [2n + n n, n C]`` (a row an output), the three factors
    ``alpha`` and the ``2n + n n`` biases."""

    def __init__(self, cfg: Motif3Config):
        super().__init__(cfg)
        n, h = cfg.mhc_expansion_rate, cfg.hidden_size
        self.n, self.eps = n, cfg.rms_norm_eps
        self.iters, self.clamp = cfg.mhc_sinkhorn_iters, cfg.hidden_clamp
        self.norm = self.const(1.0, n * h)
        self.phi = self.mat(2 * n + n * n, n * h)
        self.alpha = self.const(1.0, 3)
        self.bias = self.const(0.0, 2 * n + n * n)

    def apply(self, x, carry):
        """``x [T, n, C]``; ``carry`` = (the previous sublayer's output,
        its H_post, its H_res) or None. Returns (streams, u [T, C], this
        sublayer's H_post, H_res)."""
        y, post, res = carry if carry is not None else (None, None, None)
        with jax.named_scope("mhc"):
            return mhc.mhc(x, y, post, res, self.norm.value(),
                           self.phi.value(), self.alpha.value(),
                           self.bias.value(), eps=self.eps,
                           clamp=self.clamp, iters=self.iters)


class Motif3Block(_Weights):
    def __init__(self, cfg: Motif3Config, dense: bool, window: bool):
        super().__init__(cfg)
        h = cfg.hidden_size
        self.eps, self.dense = cfg.rms_norm_eps, dense
        self.attn_hc = HyperConnection(cfg)
        self.mlp_hc = HyperConnection(cfg)
        self.input_layernorm = self.const(1.0, h)
        self.post_attention_layernorm = self.const(1.0, h)
        self.self_attn = LatentAttention(
            cfg, cfg.sliding_window if window else 0)
        poly = Poly(cfg.polynorm_output_scale, cfg.polynorm_bias_clamp,
                    cfg.rms_norm_eps)
        if dense:
            self.mlp = DenseFFN(cfg, cfg.intermediate_size, poly)
        else:
            self.mlp = HeldExpertsMoE(
                h, cfg.moe_intermediate_size, cfg.router_experts,
                cfg.experts_top_k, offset=cfg.expert_offset,
                count=cfg.num_experts, norm_topk_prob=cfg.route_norm,
                shared_width=cfg.num_shared_experts
                * cfg.moe_intermediate_size,
                choice_bias=True, scaling=cfg.route_scale,
                scoring=cfg.score_func, shared_gated=False,
                poly=poly,
                std=cfg.initializer_range, dtype=cfg.dtype)

    def apply(self, x, carry, cache, pos, end):
        """``x [B, S, n, H]`` the streams, ``carry`` what the previous
        sublayer writes into them (None before the first), ``cache`` the
        layer's latent cache or None. Returns (streams, carry, cache
        after the call)."""
        b, s, n, h = x.shape
        flat, u, post, res = self.attn_hc.apply(x.reshape(b * s, n, h),
                                                carry)
        a, new = self.self_attn.apply(
            rms_norm(u.reshape(b, s, h), self.input_layernorm.value(),
                     self.eps, centred=False), cache, pos, end)
        flat, u, post2, res2 = self.mlp_hc.apply(
            flat, (a.reshape(b * s, h), post, res))
        z = rms_norm(u, self.post_attention_layernorm.value(), self.eps,
                     centred=False)
        if self.dense:
            m = self.mlp.apply(z)
        else:
            m = self.mlp.apply(z, _valid(pos, end, b, s).reshape(-1))
        return flat.reshape(b, s, n, h), (m, post2, res2), new


class Motif3Model(_Weights):
    def __init__(self, cfg: Motif3Config):
        super().__init__(cfg)
        self.config = cfg
        self.embed_tokens = self.mat(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList([
            Motif3Block(cfg, i < cfg.n_dense_first_layers,
                        cfg.layer_types[i] == WINDOW)
            for i in range(cfg.num_hidden_layers)])
        self.norm = self.const(1.0, cfg.hidden_size)

    def forward(self, input_ids, kv_caches=None, start_pos=None,
                write_end=None):
        cfg = self.config
        ids = input_ids.value() if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        e = self.embed_tokens.value()[ids]
        b, s, h = e.shape
        x = jnp.broadcast_to(e[:, :, None, :],
                             (b, s, cfg.mhc_expansion_rate, h))
        pos = jnp.int32(0) if start_pos is None else start_pos
        caches = kv_caches if kv_caches is not None \
            else [None] * len(self.layers)
        new_caches, carry = [], None
        for block, cache in zip(self.layers, caches):
            x, carry, nc = block.apply(x, carry, cache, pos, write_end)
            new_caches.append(nc)
        y, post, res = carry
        with jax.named_scope("mhc"):       # the last sublayer's write
            f32 = jnp.float32
            streams = jnp.einsum(
                "tij,tjc->tic", res, x.reshape(b * s, -1, h).astype(f32),
                precision="highest") + post[..., None] \
                * y.astype(f32)[:, None, :]
            streams = jnp.clip(streams, -cfg.hidden_clamp,
                               cfg.hidden_clamp).astype(x.dtype)
            out = jnp.sum(streams.astype(f32), axis=1).astype(x.dtype)
        hidden = Tensor(rms_norm(out.reshape(b, s, h), self.norm.value(),
                                 cfg.rms_norm_eps, centred=False))
        return hidden if kv_caches is None else (hidden, new_caches)


class Motif3ForCausalLM(_Weights):
    def __init__(self, cfg: Motif3Config):
        super().__init__(cfg)
        self.config = cfg
        self.model = Motif3Model(cfg)
        self.lm_head = self.mat(cfg.hidden_size, cfg.vocab_size)

    def forward(self, input_ids):
        """Full forward, no cache: logits [B, S, V]."""
        return Tensor(_dot(self.model(input_ids).value(),
                           self.lm_head.value()))

    def decode_spec(self) -> ModelSpec:
        """A paged latent entry a full layer, a ring a window layer."""
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
