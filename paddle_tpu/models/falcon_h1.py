"""Hybrid decoder of the Falcon-H1 family: in EVERY block a Mamba-2
state-space mixer and a grouped-query softmax-attention mixer read the same
normed input side by side and are summed, then a gated feed-forward; muP
multipliers on the embedding, both mixers, the keys, the feed-forward and
the head.

Layer equations (HF ``modeling_falcon_h1``), ``norm(x) = w * x / rms(x)``::

    h0      = embed[ids] * embedding_multiplier
    u       = norm_in(x)
    x      <- x + ssm_out_multiplier * mamba(u)
                + attention_out_multiplier * attn(attention_in_multiplier * u)
    x      <- x + mlp(norm_ff(x))
    logits  = lm_head_multiplier * head(norm_f(x))          (untied)

* Mamba-2 mixer: ``p = in_proj(ssm_in_multiplier * u) . mup`` = z | x | B |
  C | dt, ``mup`` the five ``ssm_multipliers`` laid over those segments;
  causal depthwise convolution of width ``mamba_d_conv`` with bias over
  ``x | B | C``, then SiLU; ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``; the recurrence of ``kernels/pallas/ssd.py`` on a float32
  state; gate then norm (``mamba_norm_before_gate`` false): ``y * silu(z)``,
  RMS-normed within each group's ``d_ssm / n_groups`` channels, times a
  weight; ``out_proj``. No projection bias.
* Attention: ``k * key_multiplier``; rotate-half rotary on all of the
  head's dims; causal softmax, ``heads / kv_heads`` query heads a KV head,
  scale ``head_dim ** -0.5``; ``o_proj``. No bias, no q/k norm.
* Feed-forward: ``down(up(v) * silu(mlp_multipliers[0] * gate(v))) *
  mlp_multipliers[1]``.

Departure from the checkpoint: ``lm_head_multiplier`` is applied to the
final norm's output (in float32, before its one rounding to the model's
dtype) and not to the logits: the head is linear, and the serving engine
multiplies hidden states with ``lm_head`` itself.

Inference-only raw-array math (as ``qwen3_next.py``): serving through
``serving.DecodeEngine`` and a full forward. Training needs the chunked
scan's backward (ROADMAP).

Caches: EVERY block owns two (``decode_spec()`` declares ``(kv_layer,
state_layer)`` a block): paged ``(pool_k, pool_v, table)`` with merged-row
pools, and ``(state [B, heads, d_state, d_head] float32, conv_tail [B,
d_conv - 1, channels])``, the rows of the sequences in the call. A call
whose ``start_pos`` is 0 starts from a zero state whatever the row held
(data, not shape); positions at or past ``write_end`` change nothing.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.tensor import Tensor
from ..kernels.pallas import ssd
from .cache_spec import ModelSpec, kv_layer, state_layer
from .hybrid import (_dot, _fresh, _positions, _valid, _Weights,
                     conv_with_tail, grouped_attention, rms_norm, rope)

__all__ = ["FalconH1Config", "FalconH1Model", "FalconH1ForCausalLM",
           "falcon_h1_tiny"]


@dataclass
class FalconH1Config:
    vocab_size: int = 261120
    hidden_size: int = 5120
    num_hidden_layers: int = 72
    intermediate_size: int = 21504
    # attention mixer
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e11
    # state-space mixer
    mamba_d_ssm: int = 4096
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_d_state: int = 256
    mamba_n_groups: int = 2
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    mamba_conv_bias: bool = True
    mamba_rms_norm: bool = True
    mamba_norm_before_gate: bool = False
    # muP multipliers
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    ssm_multipliers: list = field(default_factory=lambda: [
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
        0.3535533905932738])
    mlp_multipliers: list = field(default_factory=lambda: [
        0.1767766952966369, 0.011160714285714284])
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    initializer_range: float = 0.02
    dtype: str = "float32"

    def __post_init__(self):
        self.rope_theta = float(self.rope_theta)    # 1e11 as an int overflows
        if self.mamba_d_ssm != self.mamba_n_heads * self.mamba_d_head:
            raise ValueError("mamba_d_ssm must be mamba_n_heads x "
                             "mamba_d_head")


def falcon_h1_tiny(**overrides) -> FalconH1Config:
    """Two blocks at toy widths, every multiplier away from 1."""
    cfg = dict(vocab_size=512, hidden_size=64, num_hidden_layers=2,
               intermediate_size=96, num_attention_heads=5,
               num_key_value_heads=1, head_dim=16, rope_theta=1e4,
               mamba_d_ssm=32, mamba_n_heads=4, mamba_d_head=8,
               mamba_d_state=16, mamba_n_groups=2, mamba_chunk_size=8,
               embedding_multiplier=1.7, lm_head_multiplier=0.6,
               attention_in_multiplier=0.8, attention_out_multiplier=0.7,
               key_multiplier=0.5, ssm_in_multiplier=0.9,
               ssm_out_multiplier=0.6,
               ssm_multipliers=[0.7, 1.3, 0.8, 1.2, 0.9],
               mlp_multipliers=[0.75, 0.65], max_position_embeddings=256)
    cfg.update(overrides)
    return FalconH1Config(**cfg)


class Mamba2Mixer(_Weights):
    def __init__(self, cfg: FalconH1Config):
        super().__init__(cfg)
        self.nh, self.hd = cfg.mamba_n_heads, cfg.mamba_d_head
        self.groups, self.n = cfg.mamba_n_groups, cfg.mamba_d_state
        self.d_ssm, self.width = cfg.mamba_d_ssm, cfg.mamba_d_conv
        self.chunk, self.eps = cfg.mamba_chunk_size, cfg.rms_norm_eps
        self.gate_first = not cfg.mamba_norm_before_gate
        self.in_mult = cfg.ssm_in_multiplier
        self.channels = self.d_ssm + 2 * self.groups * self.n
        # z | x | B | C | dt, each segment under its own multiplier
        widths = (self.d_ssm, self.d_ssm, self.groups * self.n,
                  self.groups * self.n, self.nh)
        self.mup = np.concatenate([
            np.full((w,), m, np.float32)
            for w, m in zip(widths, cfg.ssm_multipliers)])
        h = cfg.hidden_size
        self.in_proj = self.mat(h, sum(widths))
        self.conv1d = self.mat(self.channels, self.width)
        self.conv_bias = self.const(0.0, self.channels) \
            if cfg.mamba_conv_bias else None
        self.dt_bias = self.const(1.0, self.nh)
        self.A_log = self.const(0.0, self.nh)
        self.D = self.const(1.0, self.nh)
        self.norm = self.const(1.0, self.d_ssm) if cfg.mamba_rms_norm \
            else None
        self.out_proj = self.mat(self.d_ssm, h)

    def cache_arrays(self, dtype):
        return (((self.nh, self.n, self.hd), "float32"),
                ((self.width - 1, self.channels), dtype))

    def _gated_norm(self, y, z):
        """``y [B, S, d_ssm]`` float32 under its gate ``z``: the gate, then
        (``gate_first``) or after the RMS norm within each group's
        channels, times the weight."""
        gate = jax.nn.silu(z.astype(jnp.float32))
        if self.norm is None:
            return y * gate
        if self.gate_first:
            y = y * gate
        b, s, _ = y.shape
        yg = y.reshape(b, s, self.groups, -1)
        yg = yg * jax.lax.rsqrt(jnp.mean(jnp.square(yg), -1, keepdims=True)
                                + self.eps)
        y = yg.reshape(b, s, -1) * self.norm.value().astype(jnp.float32)
        return y if self.gate_first else y * gate

    def apply(self, u, cache, pos, end):
        """``u [B, S, H]`` (normed); ``cache`` = (state, conv_tail) rows or
        None."""
        b, s, _ = u.shape
        f32 = jnp.float32
        valid = _valid(pos, end, b, s)                        # [B, S]
        if cache is None:
            state = jnp.zeros((b, self.nh, self.n, self.hd), f32)
            tail = jnp.zeros((b, self.width - 1, self.channels), u.dtype)
            fresh = None
        else:
            state, tail = cache
            fresh = _fresh(pos, valid)
            tail = jnp.where(fresh[..., None], jnp.zeros_like(tail), tail)
        p = (_dot(u * jnp.asarray(self.in_mult, u.dtype),
                  self.in_proj.value()).astype(f32) * self.mup).astype(u.dtype)
        z = p[..., :self.d_ssm]
        mixed = p[..., self.d_ssm:self.d_ssm + self.channels]
        dt = p[..., self.d_ssm + self.channels:].astype(f32)
        conv, new_tail = conv_with_tail(
            mixed, tail, self.conv1d.value(), valid,
            None if self.conv_bias is None else self.conv_bias.value())
        gn = self.groups * self.n
        x = conv[..., :self.d_ssm].reshape(b, s, self.nh, self.hd)
        bm = conv[..., self.d_ssm:self.d_ssm + gn].reshape(
            b, s, self.groups, self.n)
        cm = conv[..., self.d_ssm + gn:].reshape(b, s, self.groups, self.n)
        # a padded position (or a dead decode slot) has dt = 0: decay 1 and
        # no input, so it changes nothing
        dt = jnp.where(valid[..., None], jax.nn.softplus(
            dt + self.dt_bias.value().astype(f32)), 0.0)
        a = -jnp.exp(self.A_log.value().astype(f32))
        d = self.D.value()
        if s == 1 and cache is not None:
            y, new_state = ssd.ssd_decode_step(
                x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], d, state,
                valid[:, 0], forget=fresh[:, 0])
            y = y[:, None]
        else:
            if cache is not None:
                state = jnp.where(fresh[..., None, None], 0.0, state)
            y, new_state = ssd.ssd_chunked(x, dt, a, bm, cm, d, state,
                                           chunk=self.chunk)
        y = self._gated_norm(y.reshape(b, s, self.d_ssm), z)
        return _dot(y.astype(u.dtype), self.out_proj.value()), \
            (new_state, new_tail)


class GroupedAttention(_Weights):
    def __init__(self, cfg: FalconH1Config):
        super().__init__(cfg)
        self.nh, self.nkv = cfg.num_attention_heads, cfg.num_key_value_heads
        self.hd, self.theta = cfg.head_dim, cfg.rope_theta
        self.in_mult, self.key_mult = cfg.attention_in_multiplier, \
            cfg.key_multiplier
        h = cfg.hidden_size
        self.q_proj = self.mat(h, self.nh * self.hd)
        self.k_proj = self.mat(h, self.nkv * self.hd)
        self.v_proj = self.mat(h, self.nkv * self.hd)
        self.o_proj = self.mat(self.nh * self.hd, h)

    def apply(self, u, cache, pos, end):
        b, s, _ = u.shape
        u = u * jnp.asarray(self.in_mult, u.dtype)
        q = _dot(u, self.q_proj.value()).reshape(b, s, self.nh, self.hd)
        k = _dot(u, self.k_proj.value()).reshape(b, s, self.nkv, self.hd)
        v = _dot(u, self.v_proj.value()).reshape(b, s, self.nkv, self.hd)
        k = k * jnp.asarray(self.key_mult, k.dtype)
        positions = _positions(pos, s)
        q = rope(q, positions, self.hd, self.theta)
        k = rope(k, positions, self.hd, self.theta)
        ctx, new_cache = grouped_attention(q, k, v, cache, pos, positions,
                                           end)
        return _dot(ctx, self.o_proj.value()), new_cache


class GatedMLP(_Weights):
    def __init__(self, cfg: FalconH1Config):
        super().__init__(cfg)
        self.gate_mult, self.down_mult = cfg.mlp_multipliers
        h, i = cfg.hidden_size, cfg.intermediate_size
        self.gate_proj = self.mat(h, i)
        self.up_proj = self.mat(h, i)
        self.down_proj = self.mat(i, h)

    def apply(self, v):
        gate = _dot(v, self.gate_proj.value()).astype(jnp.float32)
        hid = _dot(v, self.up_proj.value()).astype(jnp.float32) \
            * jax.nn.silu(gate * self.gate_mult)
        out = _dot(hid.astype(v.dtype), self.down_proj.value())
        return (out.astype(jnp.float32) * self.down_mult).astype(v.dtype)


class FalconH1Block(_Weights):
    def __init__(self, cfg: FalconH1Config):
        super().__init__(cfg)
        self.eps = cfg.rms_norm_eps
        self.ssm_out, self.attn_out = cfg.ssm_out_multiplier, \
            cfg.attention_out_multiplier
        self.input_layernorm = self.const(1.0, cfg.hidden_size)
        self.mamba = Mamba2Mixer(cfg)
        self.self_attn = GroupedAttention(cfg)
        self.pre_ff_layernorm = self.const(1.0, cfg.hidden_size)
        self.feed_forward = GatedMLP(cfg)

    def apply(self, x, cache, pos, end):
        """``cache`` = (the attention's paged cache, the mixer's state
        rows), or None; returns them in that order."""
        kv, state = cache if cache is not None else (None, None)
        u = rms_norm(x, self.input_layernorm.value(), self.eps,
                     centred=False)
        with jax.named_scope("ssm_mixer"):
            m, new_state = self.mamba.apply(u, state, pos, end)
        with jax.named_scope("attention_mixer"):
            a, new_kv = self.self_attn.apply(u, kv, pos, end)
        x = (x.astype(jnp.float32) + m.astype(jnp.float32) * self.ssm_out
             + a.astype(jnp.float32) * self.attn_out).astype(x.dtype)
        with jax.named_scope("mlp"):
            x = x + self.feed_forward.apply(rms_norm(
                x, self.pre_ff_layernorm.value(), self.eps, centred=False))
        return x, (new_kv, new_state)


class FalconH1Model(_Weights):
    def __init__(self, cfg: FalconH1Config):
        super().__init__(cfg)
        self.config = cfg
        self.embed_tokens = self.mat(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList([FalconH1Block(cfg)
                                    for _ in range(cfg.num_hidden_layers)])
        self.final_layernorm = self.const(1.0, cfg.hidden_size)

    def forward(self, input_ids, kv_caches=None, start_pos=None,
                write_end=None):
        cfg = self.config
        ids = input_ids.value() if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        x = self.embed_tokens.value()[ids]
        x = (x.astype(jnp.float32) * cfg.embedding_multiplier).astype(x.dtype)
        pos = jnp.int32(0) if start_pos is None else start_pos
        caches = kv_caches if kv_caches is not None \
            else [None] * len(self.layers)
        new_caches = []
        for block, cache in zip(self.layers, caches):
            x, nc = block.apply(x, cache, pos, write_end)
            new_caches.append(nc)
        # the head's multiplier rides here (see the module's docstring)
        w = self.final_layernorm.value().astype(jnp.float32) \
            * cfg.lm_head_multiplier
        hidden = Tensor(rms_norm(x, w, cfg.rms_norm_eps, centred=False))
        return hidden if kv_caches is None else (hidden, new_caches)


class FalconH1ForCausalLM(_Weights):
    def __init__(self, cfg: FalconH1Config):
        super().__init__(cfg)
        self.config = cfg
        self.model = FalconH1Model(cfg)
        self.lm_head = self.mat(cfg.hidden_size, cfg.vocab_size)

    def forward(self, input_ids):
        """Full forward, no cache: logits [B, S, V]."""
        return Tensor(_dot(self.model(input_ids).value(),
                           self.lm_head.value()))

    def decode_spec(self) -> ModelSpec:
        cfg = self.config
        # 4 KV heads of bf16 under a 16-row tile: a block is kept as one
        # [BS * n_kv, hd] matrix (cache_spec.kv_layer says why)
        layers = [(kv_layer(cfg.num_key_value_heads, cfg.head_dim,
                            merged_rows=True),
                   state_layer(block.mamba.cache_arrays(cfg.dtype)))
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
