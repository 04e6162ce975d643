"""Hybrid decoder of the Qwen3-Next family: Gated DeltaNet linear-attention
layers with a gated softmax-attention layer every ``full_attention_interval``
layers, every layer followed by a routed expert block, zero-centred RMSNorm.

Layer equations (HF ``modeling_qwen3_next``), ``norm(x) = x / rms(x) * (1 +
w)``; every layer ``x <- x + mixer(norm(x)); x <- x + moe(norm(x))``; final
norm; untied head.

* Gated DeltaNet (``i % interval != interval - 1``): ``q|k|v|z = x W_qkvz``,
  ``b|a = x W_ba``; causal depthwise convolution of width 4, no bias, over
  ``q|k|v``, then SiLU; ``q, k`` L2-normalised per head, ``q`` scaled by
  ``dk ** -0.5``, each key head serving ``nv / nk`` value heads; ``beta =
  sigmoid(b)``, ``g = -exp(A_log) * softplus(a + dt_bias)``; the delta rule
  of ``kernels/pallas/gdn.py`` on a float32 state; output ``(w * o /
  rms(o)) * silu(z)`` per head, then ``W_o``.
* Gated attention: ``W_q`` gives query | gate per head; ``q, k`` normed per
  head (zero-centred weight); rotary on the first ``partial_rotary_factor``
  of the head's dims (rotate-half form); causal softmax attention, grouped
  queries; output times ``sigmoid(gate)``; ``W_o``. No biases.
* Experts: ``incubate/.../moe/held.py::HeldExpertsMoE``: the router keeps
  its published width and top-k, this chip computes the experts it holds
  (``expert_offset``, ``num_experts``) and the shared expert.

Departures from the checkpoint: the fused ``in_proj_qkvz`` / ``in_proj_ba``
columns are laid out flat (q | k | v | z, b | a), a column permutation of
the checkpoint's per-key-head grouping; the multi-token-prediction layer is
not built.

Inference-only raw-array math (as the cached paths of GPT and LLaMA):
serving through ``serving.DecodeEngine`` and a full forward. Training needs
the chunked scan's backward and the router's auxiliary loss (ROADMAP).

Caches, one per layer (``decode_spec()`` declares them): a full layer takes
paged ``(pool_k, pool_v, table)`` with merged-row pools (``cache_spec``); a
linear layer ``(state [B, nv, dk, dv] float32, conv_tail [B, width - 1,
channels])``, the rows of the sequences in the call. A call whose
``start_pos`` is 0 starts from a zero state whatever the row held (data, not
shape); positions at or past ``write_end`` change nothing.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..incubate.distributed.models.moe.held import HeldExpertsMoE
from ..kernels.pallas import gdn
from .cache_spec import ModelSpec, kv_layer, state_layer
from .hybrid import (_dot, _fresh, _positions, _valid, _Weights,
                     conv_with_tail, grouped_attention, rms_norm, rope)

__all__ = ["Qwen3NextConfig", "Qwen3NextModel", "Qwen3NextForCausalLM",
           "qwen3_next_tiny"]


@dataclass
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    # gated attention
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    # gated delta net
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    # experts: `num_experts` are HELD here, ids `expert_offset ..`, of the
    # `router_experts` the router scores (0 -> = num_experts: all of them)
    num_experts: int = 512
    router_experts: int = 0
    expert_offset: int = 0
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    initializer_range: float = 0.02
    dtype: str = "float32"

    def __post_init__(self):
        if self.router_experts == 0:
            self.router_experts = self.num_experts

    def is_linear(self, i: int) -> bool:
        return (i + 1) % self.full_attention_interval != 0


def qwen3_next_tiny(**overrides) -> Qwen3NextConfig:
    """One period at toy widths: 32 routed experts, top-4."""
    cfg = dict(vocab_size=512, hidden_size=64, num_hidden_layers=4,
               num_attention_heads=4, num_key_value_heads=2, head_dim=32,
               linear_num_key_heads=2, linear_num_value_heads=4,
               linear_key_head_dim=16, linear_value_head_dim=16,
               num_experts=32, num_experts_per_tok=4,
               moe_intermediate_size=32, shared_expert_intermediate_size=32,
               max_position_embeddings=256)
    cfg.update(overrides)
    return Qwen3NextConfig(**cfg)


class GatedDeltaNet(_Weights):
    def __init__(self, cfg: Qwen3NextConfig):
        super().__init__(cfg)
        self.nk, self.nv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        self.dk, self.dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        self.width = cfg.linear_conv_kernel_dim
        self.eps = cfg.rms_norm_eps
        self.key_dim, self.value_dim = self.nk * self.dk, self.nv * self.dv
        self.channels = 2 * self.key_dim + self.value_dim
        h = cfg.hidden_size
        self.in_proj_qkvz = self.mat(h, self.channels + self.value_dim)
        self.in_proj_ba = self.mat(h, 2 * self.nv)
        self.conv1d = self.mat(self.channels, self.width)
        self.dt_bias = self.const(1.0, self.nv)
        self.A_log = self.const(0.0, self.nv)
        self.norm = self.const(1.0, self.dv)
        self.out_proj = self.mat(self.value_dim, h)

    def cache_arrays(self, dtype):
        return (((self.nv, self.dk, self.dv), "float32"),
                ((self.width - 1, self.channels), dtype))

    def apply(self, x, cache, pos, end):
        """``x [B, S, H]``; ``cache`` = (state, conv_tail) rows or None."""
        b, s, _ = x.shape
        f32 = jnp.float32
        valid = _valid(pos, end, b, s)                        # [B, S]
        if cache is None:
            state = jnp.zeros((b, self.nv, self.dk, self.dv), f32)
            tail = jnp.zeros((b, self.width - 1, self.channels), x.dtype)
        else:
            state, tail = cache
            fresh = _fresh(pos, valid)
            tail = jnp.where(fresh[..., None], jnp.zeros_like(tail), tail)
        qkvz = _dot(x, self.in_proj_qkvz.value())
        mixed, z = qkvz[..., :self.channels], qkvz[..., self.channels:]
        ba = _dot(x, self.in_proj_ba.value()).astype(f32)
        conv, new_tail = conv_with_tail(mixed, tail, self.conv1d.value(),
                                        valid)
        q = conv[..., :self.key_dim].reshape(b, s, self.nk, self.dk)
        k = conv[..., self.key_dim:2 * self.key_dim].reshape(
            b, s, self.nk, self.dk)
        v = conv[..., 2 * self.key_dim:].reshape(b, s, self.nv, self.dv)

        def l2(t):
            return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

        rep = self.nv // self.nk
        q = jnp.repeat(l2(q) * self.dk ** -0.5, rep, axis=2)
        k = jnp.repeat(l2(k), rep, axis=2)
        beta = jax.nn.sigmoid(ba[..., :self.nv])
        g = -jnp.exp(self.A_log.value().astype(f32)) * jax.nn.softplus(
            ba[..., self.nv:] + self.dt_bias.value().astype(f32))
        live = valid[..., None]
        beta, g = jnp.where(live, beta, 0.0), jnp.where(live, g, 0.0)
        # a fresh sequence forgets the row's last tenant
        if s == 1 and cache is not None:
            # ... by a decay of exp(-inf) = 0: the zeroing rides in the
            # recurrence and no pass over every slot's state is spent on it
            o, new_state = gdn.gdn_decode_step(
                q[:, 0], k[:, 0], v[:, 0],
                jnp.where(fresh, -jnp.inf, g[:, 0]), beta[:, 0], state,
                valid[:, 0])
            o = o[:, None]
        else:
            if cache is not None:
                state = jnp.where(fresh[..., None, None], 0.0, state)
            o, new_state = gdn.gdn_chunked(q, k, v, g, beta, state)
        o = rms_norm(o, self.norm.value(), self.eps, centred=False)
        o = o * jax.nn.silu(z.astype(f32).reshape(b, s, self.nv, self.dv))
        out = _dot(o.astype(x.dtype).reshape(b, s, self.value_dim),
                   self.out_proj.value())
        return out, (new_state, new_tail)


class GatedAttention(_Weights):
    def __init__(self, cfg: Qwen3NextConfig):
        super().__init__(cfg)
        self.nh, self.nkv = cfg.num_attention_heads, cfg.num_key_value_heads
        self.hd = cfg.head_dim
        self.rot = int(self.hd * cfg.partial_rotary_factor)
        self.theta, self.eps = cfg.rope_theta, cfg.rms_norm_eps
        h = cfg.hidden_size
        self.q_proj = self.mat(h, self.nh * self.hd * 2)
        self.k_proj = self.mat(h, self.nkv * self.hd)
        self.v_proj = self.mat(h, self.nkv * self.hd)
        self.o_proj = self.mat(self.nh * self.hd, h)
        self.q_norm = self.const(0.0, self.hd)
        self.k_norm = self.const(0.0, self.hd)

    def apply(self, x, cache, pos, end):
        b, s, _ = x.shape
        nh, nkv, hd = self.nh, self.nkv, self.hd
        qg = _dot(x, self.q_proj.value()).reshape(b, s, nh, 2 * hd)
        q, gate = qg[..., :hd], qg[..., hd:].reshape(b, s, nh * hd)
        k = _dot(x, self.k_proj.value()).reshape(b, s, nkv, hd)
        v = _dot(x, self.v_proj.value()).reshape(b, s, nkv, hd)
        q = rms_norm(q, self.q_norm.value(), self.eps)
        k = rms_norm(k, self.k_norm.value(), self.eps)
        positions = _positions(pos, s)
        q = rope(q, positions, self.rot, self.theta)
        k = rope(k, positions, self.rot, self.theta)
        ctx, new_cache = grouped_attention(q, k, v, cache, pos, positions,
                                           end)
        ctx = (ctx.astype(jnp.float32)
               * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(x.dtype)
        return _dot(ctx, self.o_proj.value()), new_cache


class Qwen3NextBlock(_Weights):
    def __init__(self, cfg: Qwen3NextConfig, index: int):
        super().__init__(cfg)
        self.linear = cfg.is_linear(index)
        self.eps = cfg.rms_norm_eps
        self.input_layernorm = self.const(0.0, cfg.hidden_size)
        if self.linear:
            self.linear_attn = GatedDeltaNet(cfg)
        else:
            self.self_attn = GatedAttention(cfg)
        self.post_attention_layernorm = self.const(0.0, cfg.hidden_size)
        self.mlp = HeldExpertsMoE(
            cfg.hidden_size, cfg.moe_intermediate_size, cfg.router_experts,
            cfg.num_experts_per_tok, offset=cfg.expert_offset,
            count=cfg.num_experts, norm_topk_prob=cfg.norm_topk_prob,
            shared_width=cfg.shared_expert_intermediate_size,
            std=cfg.initializer_range, dtype=cfg.dtype)

    def apply(self, x, cache, pos, end):
        b, s, h = x.shape
        y = rms_norm(x, self.input_layernorm.value(), self.eps)
        if self.linear:
            with jax.named_scope("linear_attention"):
                a, new_cache = self.linear_attn.apply(y, cache, pos, end)
        else:
            with jax.named_scope("gated_attention"):
                a, new_cache = self.self_attn.apply(y, cache, pos, end)
        x = x + a
        y = rms_norm(x, self.post_attention_layernorm.value(), self.eps)
        moe = self.mlp.apply(y.reshape(b * s, h),
                             _valid(pos, end, b, s).reshape(-1))
        return x + moe.reshape(b, s, h), new_cache


class Qwen3NextModel(_Weights):
    def __init__(self, cfg: Qwen3NextConfig):
        super().__init__(cfg)
        self.config = cfg
        self.embed_tokens = self.mat(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList([Qwen3NextBlock(cfg, i)
                                    for i in range(cfg.num_hidden_layers)])
        self.norm = self.const(0.0, cfg.hidden_size)

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
                                 self.config.rms_norm_eps))
        return hidden if kv_caches is None else (hidden, new_caches)


class Qwen3NextForCausalLM(_Weights):
    def __init__(self, cfg: Qwen3NextConfig):
        super().__init__(cfg)
        self.config = cfg
        self.model = Qwen3NextModel(cfg)
        self.lm_head = self.mat(cfg.hidden_size, cfg.vocab_size)

    def forward(self, input_ids):
        """Full forward, no cache: logits [B, S, V]."""
        return Tensor(_dot(self.model(input_ids).value(),
                           self.lm_head.value()))

    def decode_spec(self) -> ModelSpec:
        cfg = self.config
        layers = []
        for block in self.model.layers:
            if block.linear:
                layers.append(state_layer(
                    block.linear_attn.cache_arrays(cfg.dtype)))
            else:
                # few KV heads: a block is kept as one [BS * n_kv, hd]
                # matrix (cache_spec.kv_layer says why)
                layers.append(kv_layer(cfg.num_key_value_heads,
                                       cfg.head_dim, merged_rows=True))
        return ModelSpec(self.model, layers, cfg.max_position_embeddings,
                         self.lm_head, False)

    def generate(self, input_ids, max_new_tokens: int = 32, **kw):
        """Through ``serving.DecodeEngine`` (the only cached path this
        family has)."""
        from ..serving import generate_via_engine
        kw.pop("use_engine", None)
        return generate_via_engine(self, input_ids,
                                   max_new_tokens=max_new_tokens, **kw)
