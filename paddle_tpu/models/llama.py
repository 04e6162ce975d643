"""LLaMA decoder family — RoPE + RMSNorm + SwiGLU + grouped-query attention.

SURVEY.md §6 stretch target (LLaMA-7B TP+PP). Built on the same substrate as
GPT: paddle_tpu.nn layers for eager/tape, the Pallas flash kernel where
eligible, the fused lm_head_ce loss, and TP via NamedSharding re-placement of
the q/k/v/o and gate/up/down projections (shard_llama_tp below).

Reference analogs for the building blocks: nn.RMSNorm surface
(python/paddle/nn — added post-snapshot upstream; here a first-class layer),
fused rotary embedding (incubate fused ops family).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from ..nn import functional as F
from .. import ops
from ..core.remat import (ATTN_CONTEXT, ATTN_OUT, ATTN_QKV, MLP_HIDDEN,
                          normalize_granularity, tag_activation)
from ..ops._helpers import _op

__all__ = ["LlamaConfig", "LlamaModel", "LlamaForCausalLM", "llama_tiny",
           "llama_7b", "shard_llama_tp"]


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 0          # 0 -> = num_heads (MHA); < heads -> GQA
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    use_flash_attention: bool = True
    initializer_range: float = 0.02
    # activation recompute ("none" | "selective" | "dots" | "full");
    # interval=N checkpoints every Nth block — see fleet/recompute.py
    recompute_granularity: str = "none"
    recompute_interval: int = 1

    def __post_init__(self):
        if self.num_kv_heads == 0:
            self.num_kv_heads = self.num_heads
        self.recompute_granularity, self.recompute_interval = \
            normalize_granularity(self.recompute_granularity,
                                  self.recompute_interval)


def llama_7b(**overrides) -> LlamaConfig:
    cfg = dict(vocab_size=32000, hidden_size=4096, intermediate_size=11008,
               num_layers=32, num_heads=32)
    cfg.update(overrides)
    return LlamaConfig(**cfg)


def llama_tiny(**overrides) -> LlamaConfig:
    cfg = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
               num_layers=2, num_heads=4, num_kv_heads=2,
               max_position_embeddings=128)
    cfg.update(overrides)
    return LlamaConfig(**cfg)


def _rope_fwd(q, k, *rest, theta=10000.0, has_pos=False):
    """Rotary embedding applied to q,k [B,S,H,D] (interleaved-pair form).
    Optional trailing position offset (KV-cache decoding: the chunk starts
    at an absolute position, not 0) — a scalar (lockstep batch) or a [B]
    vector (serving slots, each row at its own depth)."""
    B, S, H, D = q.shape
    p0 = rest[0].astype(jnp.float32) if has_pos else jnp.float32(0.0)
    # [S] for a scalar offset, [B, S] for per-row offsets
    pos = jnp.asarray(p0)[..., None] + jnp.arange(S, dtype=jnp.float32)
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = pos[..., None] * inv                 # [S, D/2] or [B, S, D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if ang.ndim == 2:
        cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    else:
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]

    def rot(x):
        x1, x2 = x[..., ::2], x[..., 1::2]
        xr1 = x1 * cos - x2 * sin
        xr2 = x1 * sin + x2 * cos
        return jnp.stack([xr1, xr2], axis=-1).reshape(x.shape)

    return rot(q.astype(jnp.float32)).astype(q.dtype), \
        rot(k.astype(jnp.float32)).astype(k.dtype)


from ..core.dispatch import register_op  # noqa: E402

register_op("rope", _rope_fwd)


class LlamaAttention(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        H = config.hidden_size
        self.num_heads = config.num_heads
        self.num_kv = config.num_kv_heads
        self.head_dim = H // config.num_heads
        self.theta = config.rope_theta
        self.use_flash = config.use_flash_attention
        self.q_proj = nn.Linear(H, H, bias_attr=False)
        self.k_proj = nn.Linear(H, self.num_kv * self.head_dim,
                                bias_attr=False)
        self.v_proj = nn.Linear(H, self.num_kv * self.head_dim,
                                bias_attr=False)
        self.o_proj = nn.Linear(H, H, bias_attr=False)

    def forward(self, x, kv_cache=None):
        if kv_cache is not None:
            return self._forward_cached(x, kv_cache)
        b, s, h = x.shape
        q = self.q_proj(x).reshape([b, s, self.num_heads, self.head_dim])
        k = self.k_proj(x).reshape([b, s, self.num_kv, self.head_dim])
        v = tag_activation(
            self.v_proj(x), ATTN_QKV).reshape([b, s, self.num_kv,
                                               self.head_dim])
        q, k = _op("rope", q, k, theta=self.theta)
        # selective recompute saves the POST-rope q/k (so backward replays
        # neither the projections nor the rotation) and raw v
        q = tag_activation(q, ATTN_QKV)
        k = tag_activation(k, ATTN_QKV)
        # GQA is handled below the functional API: the Pallas kernel folds q
        # heads onto their KV head in its index map (repeated K/V never
        # materializes in HBM); the XLA sdpa fallback expands heads itself
        from ..nn.functional.attention import flash_path_available
        if self.use_flash and flash_path_available(s, self.head_dim, x):
            out = F.flash_attention(q, k, v, causal=True,
                                    training=self.training)
        else:
            out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                                 training=self.training)
        # name the context like GPT does: selective remat then saves it (the
        # score/softmax region stays the part recomputed in backward) and
        # the health plane gets its per-layer context RMS
        out = tag_activation(out, ATTN_CONTEXT)
        return tag_activation(self.o_proj(out.reshape([b, s, h])), ATTN_OUT)

    def _forward_cached(self, x, kv_cache):
        """KV-cache attention with RoPE at absolute positions and GQA
        (queries fold onto their KV head). Inference-only raw-array math —
        mirrors GPTAttention._forward_cached, including the paged layout
        (``(pool_k, pool_v, table, pos, write_end)``: block-pooled K/V read
        through the table: gpt._paged_kv_write, then the decode kernel, a
        chunk's walk of its key blocks or gpt._paged_kv_gather's dense
        view)."""
        from ..core.tensor import Tensor
        from .gpt import (_paged_chunk_attend, _paged_decode_attend,
                          _paged_kv_gather, _paged_kv_write)

        b, s, h = x.shape
        nh, nkv, hd = self.num_heads, self.num_kv, self.head_dim
        pos = kv_cache[3] if len(kv_cache) == 5 else kv_cache[2]
        q = self.q_proj(x).reshape([b, s, nh, hd])
        k = self.k_proj(x).reshape([b, s, nkv, hd])
        v = self.v_proj(x).reshape([b, s, nkv, hd])
        q, k = _op("rope", q, k, Tensor(jnp.asarray(pos)), theta=self.theta,
                   has_pos=True)
        qv, kv_, vv = q.value(), k.value(), v.value()
        if len(kv_cache) == 5:
            new_cache = _paged_kv_write(kv_cache, kv_, vv)
            ctx = _paged_decode_attend(kv_cache, qv, new_cache)
            if ctx is None:
                ctx = _paged_chunk_attend(kv_cache, qv, new_cache)
            if ctx is not None:
                return self.o_proj(Tensor(ctx.reshape(b, s, h))), new_cache
            k_buf, v_buf = _paged_kv_gather(*new_cache, kv_cache[2])
        else:
            k_buf, v_buf, _ = kv_cache  # [B, M, n_kv, hd] + scalar cursor
            k_buf = jax.lax.dynamic_update_slice(
                k_buf, kv_.astype(k_buf.dtype), (0, pos, 0, 0))
            v_buf = jax.lax.dynamic_update_slice(
                v_buf, vv.astype(v_buf.dtype), (0, pos, 0, 0))
            new_cache = (k_buf, v_buf)
        if jnp.ndim(pos) == 1:
            q_pos = (pos[:, None] + jnp.arange(s))[:, None, None, :, None]
        else:
            q_pos = (pos + jnp.arange(s))[None, None, None, :, None]
        m = k_buf.shape[1]
        group = nh // nkv
        qg = qv.reshape(b, s, nkv, group, hd)
        scores = jnp.einsum("bqkgd,bmkd->bkgqm", qg.astype(jnp.float32),
                            k_buf.astype(jnp.float32)) / math.sqrt(hd)
        key_pos = jnp.arange(m)[None, None, None, None, :]
        scores = jnp.where(key_pos <= q_pos, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bkgqm,bmkd->bqkgd", probs,
                         v_buf.astype(jnp.float32)).astype(qv.dtype)
        out = self.o_proj(Tensor(ctx.reshape(b, s, h)))
        return out, new_cache


class LlamaMLP(nn.Layer):
    """SwiGLU: down(silu(gate(x)) * up(x))."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        H, I = config.hidden_size, config.intermediate_size
        self.gate_proj = nn.Linear(H, I, bias_attr=False)
        self.up_proj = nn.Linear(H, I, bias_attr=False)
        self.down_proj = nn.Linear(I, H, bias_attr=False)

    def forward(self, x):
        return self.down_proj(
            F.silu(tag_activation(self.gate_proj(x), MLP_HIDDEN))
            * tag_activation(self.up_proj(x), MLP_HIDDEN))


class LlamaBlock(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(config.hidden_size,
                                          epsilon=config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size,
                                                   epsilon=config.rms_norm_eps)
        self.mlp = LlamaMLP(config)

    def forward(self, x, kv_cache=None):
        if kv_cache is not None:
            a, nc = self.self_attn(self.input_layernorm(x), kv_cache=kv_cache)
            x = x + a
            return x + self.mlp(self.post_attention_layernorm(x)), nc
        x = x + self.self_attn(self.input_layernorm(x))
        return x + self.mlp(self.post_attention_layernorm(x))


class LlamaModel(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size, config.hidden_size)
        self.layers = nn.LayerList([LlamaBlock(config)
                                    for _ in range(config.num_layers)])
        self.norm = nn.RMSNorm(config.hidden_size,
                               epsilon=config.rms_norm_eps)
        self._init_weights(config)

    def _init_weights(self, config):
        std = config.initializer_range
        normal = nn.initializer.Normal(mean=0.0, std=std)
        resid = nn.initializer.Normal(
            mean=0.0, std=std / math.sqrt(2.0 * config.num_layers))
        for name, p in self.named_parameters():
            if p.ndim >= 2:
                init = (resid if name.endswith(("o_proj.weight",
                                                "down_proj.weight"))
                        else normal)
                p.set_value(init(tuple(p.shape), p.dtype))

    def forward(self, input_ids, kv_caches=None, start_pos=None,
                write_end=None, layer_subset=None):
        """``layer_subset`` (non-cached path only): run just the named
        block indices — the early-exit speculative drafter's shallow pass
        over the same weights (see GPTModel.forward)."""
        x = self.embed_tokens(input_ids)
        if kv_caches is not None:
            p0 = start_pos if start_pos is not None else jnp.int32(0)
            we = write_end if write_end is not None else p0 + \
                jnp.int32(input_ids.shape[1])
            new_caches = []
            for block, cache in zip(self.layers, kv_caches):
                if len(cache) == 3:    # paged: (pool_k, pool_v, block_table)
                    kc = (cache[0], cache[1], cache[2], p0, we)
                else:                  # contiguous: (k_buf, v_buf)
                    kc = (cache[0], cache[1], p0)
                x, nc = block(x, kv_cache=kc)
                new_caches.append(nc)
            return self.norm(x), new_caches
        gran = self.config.recompute_granularity
        interval = self.config.recompute_interval
        from ..core import dispatch
        use_rc = (gran != "none" and self.training
                  and (dispatch.in_trace() or dispatch.is_grad_enabled()))
        for i, block in enumerate(self.layers):
            if layer_subset is not None and i not in layer_subset:
                continue
            if use_rc and i % interval == 0:
                from ..distributed.fleet.recompute import recompute
                x = recompute(block, x, policy=gran)
            else:
                x = block(x)
        return self.norm(x)

    def enable_recompute(self, granularity="selective", interval: int = 1):
        """Activation recompute toggle — see GPTModel.enable_recompute."""
        self.config.recompute_granularity, self.config.recompute_interval = \
            normalize_granularity(granularity, interval)
        return self

    @property
    def _recompute_wanted(self) -> bool:
        return self.config.recompute_granularity != "none"


class LlamaForCausalLM(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.model = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                     bias_attr=False)
            # the head lives outside LlamaModel._init_weights' walk — apply
            # the same Normal(initializer_range) scheme here
            normal = nn.initializer.Normal(mean=0.0,
                                           std=config.initializer_range)
            self.lm_head.weight.set_value(
                normal(tuple(self.lm_head.weight.shape),
                       self.lm_head.weight.dtype))

    def enable_recompute(self, granularity="selective", interval: int = 1):
        """See LlamaModel.enable_recompute."""
        self.model.enable_recompute(granularity, interval)
        return self

    @property
    def _recompute_wanted(self) -> bool:
        return self.model._recompute_wanted

    def forward(self, input_ids, labels=None):
        hidden = self.model(input_ids)
        if labels is not None:
            tied = self.lm_head is None
            w = self.model.embed_tokens.weight if tied else self.lm_head.weight
            loss = _op("lm_head_ce", hidden[:, :-1, :], w, labels[:, 1:],
                       transpose_w=tied)
            return None, loss
        if self.lm_head is None:
            return ops.matmul(hidden, self.model.embed_tokens.weight,
                              transpose_y=True)
        return self.lm_head(hidden)

    def decode_spec(self):
        """What the serving engine drives (``models/cache_spec.py``): every
        layer caches K/V at the grouped KV-head count."""
        from .cache_spec import ModelSpec, kv_layer
        cfg = self.config
        tied = self.lm_head is None
        return ModelSpec(
            self.model, [kv_layer(cfg.num_kv_heads,
                                  cfg.hidden_size // cfg.num_heads)]
            * cfg.num_layers, cfg.max_position_embeddings,
            self.model.embed_tokens.weight if tied else self.lm_head.weight,
            tied)

    def generate(self, input_ids, max_new_tokens: int = 32,
                 temperature: float = 1.0, do_sample: bool = False,
                 top_k: int = 0, eos_token_id=None, seed=None,
                 max_length=None, use_engine: bool = False):
        """KV-cache incremental decoding — same compiled prefill+decode
        machinery as GPTForCausalLM.generate (RoPE positions offset by the
        cache cursor, GQA K/V buffers sized [B, M, n_kv, hd]); ``seed=None``
        derives sampling randomness from ``paddle.seed`` via
        ``core.random.host_generator()``. ``use_engine=True`` routes through
        the serving DecodeEngine (paged cache + slot scheduler)."""
        from .gpt import _generate_with_cache
        cfg = self.config
        if use_engine:
            from ..serving import generate_via_engine
            return generate_via_engine(
                self, input_ids, max_new_tokens=max_new_tokens,
                temperature=temperature, do_sample=do_sample, top_k=top_k,
                eos_token_id=eos_token_id, seed=seed, max_length=max_length)
        return _generate_with_cache(
            self, self.model, cfg.num_layers, cfg.num_kv_heads,
            cfg.hidden_size // cfg.num_heads,
            cfg.max_position_embeddings,
            head_weight=(self.model.embed_tokens.weight
                         if self.lm_head is None else self.lm_head.weight),
            head_transpose=self.lm_head is None,
            input_ids=input_ids, max_new_tokens=max_new_tokens,
            temperature=temperature, do_sample=do_sample, top_k=top_k,
            eos_token_id=eos_token_id, seed=seed, max_length=max_length)


def shard_llama_tp(model: LlamaForCausalLM, mesh=None, axis: str = "model"):
    """Tensor-parallel placement: column-shard q/k/v/gate/up, row-shard
    o/down, vocab-shard the embedding (the Fleet mp_layers recipe as
    NamedShardings — XLA inserts the TP collectives).

    Serving: a model sharded here makes ``serving.DecodeEngine`` mint SPMD
    executables with the paged KV pools head-sharded over ``axis``; when
    the GQA head count doesn't divide the TP degree (``num_kv_heads % tp
    != 0``) the engine falls back to sharding head_dim, so grouped-query
    models still scale past their KV-head count (gated at TP=4 with
    num_kv_heads=2 in tests/test_tp_serving.py)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..distributed.env import get_mesh
    mesh = mesh if mesh is not None else get_mesh()
    if mesh is None or mesh.shape.get(axis, 1) <= 1:
        return model
    col = NamedSharding(mesh, P(None, axis))
    row = NamedSharding(mesh, P(axis, None))
    for name, p in model.named_parameters():
        if name.endswith(("q_proj.weight", "k_proj.weight", "v_proj.weight",
                          "gate_proj.weight", "up_proj.weight")):
            p._data = jax.device_put(p.value(), col)
        elif name.endswith(("o_proj.weight", "down_proj.weight")):
            p._data = jax.device_put(p.value(), row)
        elif name.endswith("embed_tokens.weight"):
            p._data = jax.device_put(p.value(), row)
        elif name.endswith("lm_head.weight"):
            p._data = jax.device_put(p.value(), col)
    return model
