"""GPT decoder-only LM — the flagship / north-star model (BASELINE.json config 4).

Architecture follows the GPT-3 recipe (pre-LN transformer decoder, learned position
embeddings, GELU MLP with 4x width, tied LM head). Built on paddle_tpu.nn layers so the
same module runs eager, under @to_static, and under mesh sharding (the distributed
wrappers re-place parameter arrays with NamedShardings; see
paddle_tpu/distributed/fleet/meta_parallel).

Reference analogs: nn.TransformerDecoderLayer surface
(/root/reference/python/paddle/nn/layer/transformer.py) and the fused incubate stack
(/root/reference/python/paddle/incubate/nn/layer/fused_transformer.py:1021
FusedMultiTransformer) — here fusion is XLA's job, and attention uses
F.scaled_dot_product_attention (Pallas flash path on real TPUs).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from ..nn import functional as F
from .. import ops
from ..core.dispatch import register_op
from ..core.remat import (ATTN_CONTEXT, ATTN_OUT, ATTN_QKV, MLP_HIDDEN,
                          normalize_granularity, note_region, resolve_policy,
                          tag_activation, tag_array)
from ..core.tensor import Tensor
from ..ops._helpers import _op


def _lm_head_ce_fwd(hidden, weight, labels, *rest, transpose_w=True,
                    ignore_index=-100, has_bias=False):
    """Fused LM-head + next-token CE: hidden [B,S,H] (pre-shifted), weight
    [V,H] (tied embedding) or [H,V], labels [B,S] → scalar mean loss over
    non-ignored tokens. Optional trailing bias [V] (BERT's MLM decoder).

    One executable computes matmul → logsumexp → label-gather; the [B,S,V]
    logits never round-trip HBM in fp32 and no log-softmax tensor is formed
    (reference c_softmax_with_cross_entropy plays the same fusion role for the
    vocab-parallel case)."""
    dims = (((2,), (1,)), ((), ())) if transpose_w else (((2,), (0,)), ((), ()))
    logits = jax.lax.dot_general(hidden, weight, dims,
                                 preferred_element_type=jnp.float32)
    if has_bias:
        logits = logits + rest[0].astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    lbl = labels.astype(jnp.int32)
    valid = lbl != ignore_index
    gold = jnp.take_along_axis(
        logits, jnp.where(valid, lbl, 0)[..., None], axis=-1)[..., 0]
    per_tok = jnp.where(valid, lse - gold, 0.0)
    n = jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
    return jnp.sum(per_tok) / n


register_op("lm_head_ce", _lm_head_ce_fwd, nondiff_inputs=(2,))


def _gpt_scan_blocks_fwd(x, l1w, l1b, qw, qb, pw, pb, l2w, l2b, f1w, f1b, f2w,
                         f2b, *rest, num_heads, hidden_dropout=0.0,
                         attn_dropout=0.0, eps=1e-5, use_flash=False,
                         remat="none"):
    """All L transformer blocks as ONE `lax.scan` over stacked parameters.

    TPU-native replacement for the reference's fused_multi_transformer op
    (/root/reference/paddle/fluid/operators/fused/fused_multi_transformer_op.cu):
    there the answer to per-layer overhead is a hand-fused CUDA megakernel; here
    the L blocks become a single scan body that XLA compiles once (layers-fold
    keeps compile time O(1) in depth) with an optional rematerialization policy
    on the body. Stacked params carry a leading [L] dim.
    """
    b, s, h = x.shape
    hd = h // num_heads
    n_layers = l1w.shape[0]
    # no dropout -> no keys: a one-word placeholder whose [0] seeds the
    # rate-0 kernels (real keys are as wide as the PRNG impl makes them)
    keys = rest[0] if rest else jnp.zeros((n_layers, 1), jnp.uint32)

    def ln(z, w, bias):
        zf = z.astype(jnp.float32)
        mu = jnp.mean(zf, -1, keepdims=True)
        var = jnp.mean(jnp.square(zf - mu), -1, keepdims=True)
        return (((zf - mu) * jax.lax.rsqrt(var + eps)).astype(z.dtype) * w
                + bias)

    def drop(z, kd, salt):
        if hidden_dropout <= 0.0:
            return z
        k = jax.random.fold_in(jax.random.wrap_key_data(kd), salt)
        keep = jax.random.bernoulli(k, 1.0 - hidden_dropout, z.shape)
        return z * keep.astype(z.dtype) / (1.0 - hidden_dropout)

    def body(carry, per):
        (l1w_, l1b_, qw_, qb_, pw_, pb_, l2w_, l2b_, f1w_, f1b_, f2w_, f2b_,
         kd) = per
        with jax.named_scope("attention"):
            carry = carry + drop(attention(carry, l1w_, l1b_, qw_, qb_, pw_,
                                           pb_, kd), kd, 1)
        with jax.named_scope("mlp"):
            y = ln(carry, l2w_, l2b_)
            y = jax.nn.gelu(tag_array(y @ f1w_ + f1b_, MLP_HIDDEN),
                            approximate=True) @ f2w_ + f2b_
            return carry + drop(y, kd, 2), None

    def attention(carry, l1w_, l1b_, qw_, qb_, pw_, pb_, kd):
        y = ln(carry, l1w_, l1b_)
        qkv = tag_array(y @ qw_ + qb_, ATTN_QKV)     # [B,S,3H]
        from ..kernels.pallas.flash_attention import (
            flash_attention_blhd, packed_layout_supported)
        from ..kernels.pallas.flash_pair import pair_layout_supported
        from ..nn.functional.attention import packed_flash
        if use_flash and (pair_layout_supported(hd, num_heads, s)
                          or packed_layout_supported(hd)):
            # packed kernels straight off the fused projection: no head
            # split/merge inside the scan
            att = tag_array(packed_flash(qkv, num_heads, True, attn_dropout,
                                         kd[0]), ATTN_CONTEXT)
        elif use_flash:
            q, k, v = (t.reshape(b, s, num_heads, hd)
                       for t in jnp.split(qkv, 3, axis=-1))
            att = tag_array(flash_attention_blhd(q, k, v, causal=True,
                                                 dropout_rate=attn_dropout,
                                                 seed=kd[0].astype(jnp.int32)),
                            ATTN_CONTEXT)
        else:
            q, k, v = (t.reshape(b, s, num_heads, hd)
                       for t in jnp.split(qkv, 3, axis=-1))
            qt, kt, vt = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
            logits = (jnp.einsum("bhqd,bhkd->bhqk", qt, kt)
                      * (1.0 / math.sqrt(hd))).astype(jnp.float32)
            cm = jnp.tril(jnp.ones((s, s), bool))
            logits = jnp.where(cm, logits, jnp.finfo(jnp.float32).min)
            probs = jax.nn.softmax(logits, axis=-1).astype(qt.dtype)
            if attn_dropout > 0.0:
                k0 = jax.random.fold_in(jax.random.wrap_key_data(kd), 0)
                keep = jax.random.bernoulli(k0, 1.0 - attn_dropout, probs.shape)
                probs = probs * keep.astype(probs.dtype) / (1.0 - attn_dropout)
            att = tag_array(
                jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", probs, vt), 1, 2),
                ATTN_CONTEXT)
        return tag_array(att.reshape(b, s, h) @ pw_ + pb_, ATTN_OUT)

    if remat != "none":
        # "full" | "dots" | "selective" on the scan BODY: one jax.checkpoint
        # over the per-layer step, so the scan carries only what the policy
        # saves per layer (selective: the named linear residuals; the
        # unnamed [B,H,S,S] score/softmax region rematerializes in backward)
        note_region(remat)
        body = jax.checkpoint(body, policy=resolve_policy(remat))
    # health activation taps pause over the scan: the body's tag_array
    # values are scan-trace tracers that cannot escape to the step's
    # outputs (the discrete-block path gives per-layer RMS instead)
    from ..monitor.health import suspend_taps
    with suspend_taps():
        out, _ = jax.lax.scan(body, x, (l1w, l1b, qw, qb, pw, pb, l2w, l2b,
                                        f1w, f1b, f2w, f2b, keys))
    return out


register_op("gpt_scan_blocks", _gpt_scan_blocks_fwd, nondiff_inputs=(13,))


@dataclass
class GPTConfig:
    vocab_size: int = 50304            # 50257 padded to a multiple of 128 for the MXU
    hidden_size: int = 2048
    num_layers: int = 24
    num_heads: int = 16
    max_position_embeddings: int = 2048
    intermediate_size: int = 0         # 0 → 4*hidden
    hidden_dropout_prob: float = 0.1
    attention_dropout_prob: float = 0.1
    initializer_range: float = 0.02
    layer_norm_epsilon: float = 1e-5
    tie_word_embeddings: bool = True
    use_flash_attention: bool = True
    scan_layers: bool = False          # fold blocks into one lax.scan (fast compile)
    remat: str = "none"                # legacy alias of recompute_granularity
    # activation recompute (fleet/recompute.py policy layer):
    # "none" | "selective" | "dots" | "full"; interval=N checkpoints every
    # Nth block (discrete-block path; the scan path folds the policy into
    # its single body and ignores interval)
    recompute_granularity: str = "none"
    recompute_interval: int = 1

    def __post_init__(self):
        if self.intermediate_size == 0:
            self.intermediate_size = 4 * self.hidden_size
        if self.recompute_granularity == "none" and self.remat != "none":
            self.recompute_granularity = self.remat   # legacy remat= spelling
        self.recompute_granularity, self.recompute_interval = \
            normalize_granularity(self.recompute_granularity,
                                  self.recompute_interval)


def gpt3_1p3b(**overrides) -> "GPTConfig":
    """GPT-3 XL, 1.3B params: 24 layers, d=2048, 16 heads (BASELINE north star)."""
    cfg = dict(vocab_size=50304, hidden_size=2048, num_layers=24, num_heads=16,
               max_position_embeddings=2048)
    cfg.update(overrides)
    return GPTConfig(**cfg)


def gpt_tiny(**overrides) -> "GPTConfig":
    """Tiny config for tests / dryruns."""
    cfg = dict(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
               max_position_embeddings=128)
    cfg.update(overrides)
    return GPTConfig(**cfg)


# Tensor-parallel serving context (serving/engine.py sets it around its
# executable traces): the NamedSharding the KV pools live under on the
# device mesh and, with ``constrain``, a pin of it mid-graph on the updated
# pools and on the gathered per-row views (same rank-4 axis order). The
# constraint keeps the block-axis scatter/gather SHARD-LOCAL on the head
# axis: block indices are replicated data, so each device scatters and
# gathers only its own n_kv shard and no resharding ever lands inside the
# decode step. The engine asks for it only under HEAD-axis sharding
# P(None, None, "model", None): per-head attention consumes that layout
# unchanged, while pinning an hd-sharded pool or view fights GQA attention's
# preferred layout and forces XLA into full rematerializations. Any
# sharding at all keeps the decode step off the single-chip Pallas kernel
# (``_paged_decode_attend``).
_PAGED_KV_SHARD = {"sharding": None, "constrain": True}


def set_paged_kv_sharding(sharding, constrain=True):
    """Install (or clear, with None) the paged-pool sharding context.
    Returns the previous (sharding, constrain) pair so callers can restore
    it (try/finally)."""
    prev = (_PAGED_KV_SHARD["sharding"], _PAGED_KV_SHARD["constrain"])
    _PAGED_KV_SHARD["sharding"] = sharding
    _PAGED_KV_SHARD["constrain"] = bool(constrain)
    return prev


def _paged_kv_pin():
    """The sharding to pin pools and views to mid-graph, or None."""
    return _PAGED_KV_SHARD["sharding"] if _PAGED_KV_SHARD["constrain"] \
        else None


def _paged_kv_write(kv_cache, k, v):
    """Paged-cache write, shared by GPT and LLaMA cached attention.

    ``kv_cache`` is ``(pool_k, pool_v, table, pos, write_end)``: per-layer
    [NB, BS, n_kv, hd] pools, a [B, mbs] int32 block table, the write
    cursor(s) and the exclusive end of VALID new positions. ``k``/``v`` are
    this call's fresh projections, [B, S, n_kv, hd].

    Each position lands at ``(table[b, p // BS], p % BS)``; positions >=
    write_end (padded chunk tails) or beyond the table width are not
    written, so padding can never corrupt a live or shared block. On one
    chip as block copies (``kernels/pallas/pool_write.py``: nothing is
    written into trash block 0 either); elsewhere an XLA scatter that
    redirects them to the trash block. Under a tensor-parallel mesh
    (``set_paged_kv_sharding``) the updated pools are constrained to the
    head-sharded placement, so the scatter stays shard-local on the head
    axis. Returns the updated pools.
    """
    from .hybrid import kernel_write
    pool_k, pool_v, table, pos, write_end = kv_cache
    with jax.named_scope("kv_write"):
        done = kernel_write((pool_k, pool_v), (k, v), table, pos, write_end,
                            sharded=_PAGED_KV_SHARD["sharding"] is not None)
    if done is not None:
        return tuple(done)
    b, s = k.shape[:2]
    bs_blk = pool_k.shape[1]
    mbs = table.shape[1]
    if jnp.ndim(pos) == 1:             # per-slot cursors: decode, S == 1
        wpos = pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        end = write_end[:, None]
    else:                              # scalar cursor: one slot's chunk
        wpos = (pos + jnp.arange(s, dtype=jnp.int32))[None, :]
        wpos = jnp.broadcast_to(wpos, (b, s))
        end = jnp.broadcast_to(jnp.asarray(write_end)[None, None], (b, 1))
    shard = _paged_kv_pin()
    with jax.named_scope("kv_write"):
        lidx = wpos // bs_blk                                 # [B, S]
        phys = jnp.take_along_axis(table, jnp.minimum(lidx, mbs - 1), axis=1)
        phys = jnp.where((wpos < end) & (lidx < mbs), phys, 0)  # -> trash
        off = wpos % bs_blk
        pool_k = pool_k.at[phys, off].set(k.astype(pool_k.dtype))
        pool_v = pool_v.at[phys, off].set(v.astype(pool_v.dtype))
        if shard is not None:
            pool_k = jax.lax.with_sharding_constraint(pool_k, shard)
            pool_v = jax.lax.with_sharding_constraint(pool_v, shard)
    return pool_k, pool_v


def _paged_kv_gather(pool_k, pool_v, table):
    """Paged-cache read as a dense view: every row's blocks gathered back
    into contiguous [B, mbs*BS, n_kv, hd] buffers with ``jnp.take`` on the
    block axis — the caller's causal mask (key position <= query position)
    hides the stale tail exactly as it does for the contiguous layout.
    Who still attends over it: tensor-parallel serving (chunks, verify and
    the decode step; the views are constrained like the pools there, so
    the gather stays shard-local on the head axis) and the decode step
    where the paged kernel does not run (the CPU). One chip's chunks and
    speculative verify walk the slot's key blocks instead
    (``_paged_chunk_attend``), GPT's and LLaMA's alike."""
    shard = _paged_kv_pin()
    with jax.named_scope("kv_gather"):
        b, mbs = table.shape
        bs_blk, nkv, hd = pool_k.shape[1:]
        k_view = jnp.take(pool_k, table, axis=0).reshape(
            b, mbs * bs_blk, nkv, hd)
        v_view = jnp.take(pool_v, table, axis=0).reshape(
            b, mbs * bs_blk, nkv, hd)
        if shard is not None:
            k_view = jax.lax.with_sharding_constraint(k_view, shard)
            v_view = jax.lax.with_sharding_constraint(v_view, shard)
    return k_view, v_view


def _paged_decode_attend(kv_cache, q, pools):
    """The paged DECODE step's attention, where the input says decode: one
    query position a slot (``S == 1``), per-slot cursors, no pool sharding
    installed, and arrays the Pallas kernel can run on
    (``kernels/pallas/paged_decode.py``: a TPU, or its test seam). The
    kernel reads each slot's live blocks straight from the just-written
    pools; returns the context [B, 1, nh, hd], or None for every other
    caller: prefill chunks and speculative verify (``_paged_chunk_attend``),
    TP serving and the CPU's decode step (``_paged_kv_gather``'s dense
    view)."""
    table, pos = kv_cache[2], kv_cache[3]
    if (q.shape[1] != 1 or jnp.ndim(pos) != 1
            or _PAGED_KV_SHARD["sharding"] is not None):
        return None
    from ..kernels.pallas import paged_decode
    mode = paged_decode.kernel_mode(q, pools[0])
    if mode is None:
        return None
    with jax.named_scope("paged_decode"):
        return paged_decode.paged_decode_attention(
            q, pools[0], pools[1], table, pos + 1,
            interpret=mode == "interpret")


def _paged_chunk_attend(kv_cache, q, pools):
    """One slot's chunk over the paged cache (a scalar cursor: prefill
    chunks, speculative verify), no pool sharding installed: the queries
    walk the key blocks the slot holds before ``write_end``
    (``models/hybrid.py::walk_keys``), float32 at the default precision as
    over the view. Returns the context [B, S, nh, hd], or None for the
    callers that keep ``_paged_kv_gather``'s dense view: TP serving and the
    decode step off the chip (per-slot cursors)."""
    table, pos, write_end = kv_cache[2:]
    if jnp.ndim(pos) != 0 or _PAGED_KV_SHARD["sharding"] is not None:
        return None
    from .hybrid import _positions, walk_grouped
    return walk_grouped(q, pools, table, _positions(pos, q.shape[1]),
                        write_end, pools[0].shape[2])


class GPTAttention(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.num_heads = config.num_heads
        self.head_dim = config.hidden_size // config.num_heads
        self.qkv_proj = nn.Linear(config.hidden_size, 3 * config.hidden_size)
        self.out_proj = nn.Linear(config.hidden_size, config.hidden_size)
        self.dropout_p = config.attention_dropout_prob
        self.use_flash = config.use_flash_attention

    def forward(self, x, attn_mask=None, kv_cache=None):
        if kv_cache is not None:
            return self._forward_cached(x, kv_cache)
        b, s, h = x.shape
        drop = self.dropout_p if self.training else 0.0
        from ..kernels.pallas.flash_attention import packed_layout_supported
        from ..kernels.pallas.flash_pair import pair_layout_supported
        from ..nn.functional.attention import flash_path_available
        if (self.use_flash and attn_mask is None
                and (packed_layout_supported(self.head_dim)
                     or pair_layout_supported(self.head_dim, self.num_heads, s))
                and flash_path_available(s, self.head_dim, x)):
            # packed path: the fused projection feeds the kernel directly and
            # the context comes back [b, s, h] — no head split/merge relayout
            qkv = tag_activation(self.qkv_proj(x), ATTN_QKV)
            out = F.flash_attention_qkv_packed(qkv, self.num_heads,
                                               dropout=drop, causal=True,
                                               training=self.training)
            return tag_activation(self.out_proj(out), ATTN_OUT)
        qkv = tag_activation(self.qkv_proj(x), ATTN_QKV) \
            .reshape([b, s, 3, self.num_heads, self.head_dim])
        q, k, v = qkv.unbind(2)          # each [b, s, heads, head_dim]
        if self.use_flash and attn_mask is None:
            # Pallas flash kernel on real TPUs (auto-detected, in-kernel
            # dropout); XLA sdpa otherwise
            out = F.flash_attention(q, k, v, dropout=drop, causal=True,
                                    training=self.training)
        else:
            # always causal; attn_mask (e.g. additive padding mask) combines with it
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=attn_mask, dropout_p=drop, training=self.training,
                is_causal=True)
        out = out.reshape([b, s, h])
        return tag_activation(self.out_proj(out), ATTN_OUT)

    def _forward_cached(self, x, kv_cache):
        """KV-cache attention (serving): write this chunk's K/V at `pos` and
        attend the queries over every cached position <= their own
        (reference: the cache tensors fused_multi_transformer threads
        through generation). Inference-only math on raw arrays — no tape,
        runs inside the jitted generate loop with static shapes throughout.

        Two cache layouts:
          * contiguous — ``(k_buf, v_buf, pos)`` with [B, M, nh, hd]
            buffers, each batch row owning one row, and ONE scalar cursor
            (generate()'s lockstep batch);
          * paged — ``(pool_k, pool_v, table, pos, write_end)`` with
            [NB, BS, nh, hd] pools shared by all slots and a [B, mbs] int32
            block table. K/V lands at physical ``(table[b, p//BS], p%BS)``;
            the read side is the decode kernel, a chunk's walk of the
            slot's key blocks, or each row's blocks gathered back into a
            contiguous [B, mbs*BS, nh, hd] view via ``jnp.take`` on the
            block axis (``_paged_kv_gather`` says who takes which).
            Writes past ``write_end`` (padded chunk tails) or past the
            table redirect to trash block 0 so a shared or out-of-range
            block can never be corrupted by padding.

        The paged layout's `pos` is a scalar (one slot's prefill chunk) or
        a [B] vector (per-row cursors: the serving engine's decode step,
        each batch row a request at its own depth).
        """
        b, s, h = x.shape
        nh, hd = self.num_heads, self.head_dim
        qkv = self.qkv_proj(x).reshape([b, s, 3, nh, hd]).value()
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        if len(kv_cache) == 5:
            pos = kv_cache[3]
            new_cache = _paged_kv_write(kv_cache, k, v)
            ctx = _paged_decode_attend(kv_cache, q, new_cache)
            if ctx is None:
                ctx = _paged_chunk_attend(kv_cache, q, new_cache)
            if ctx is not None:
                return self.out_proj(Tensor(ctx.reshape(b, s, h))), new_cache
            k_buf, v_buf = _paged_kv_gather(*new_cache, kv_cache[2])
        else:
            k_buf, v_buf, pos = kv_cache   # jnp arrays + int32 scalar
            k_buf = jax.lax.dynamic_update_slice(
                k_buf, k.astype(k_buf.dtype), (0, pos, 0, 0))
            v_buf = jax.lax.dynamic_update_slice(
                v_buf, v.astype(v_buf.dtype), (0, pos, 0, 0))
            new_cache = (k_buf, v_buf)
        if jnp.ndim(pos) == 1:
            q_pos = (pos[:, None] + jnp.arange(s))[:, None, :, None]
        else:
            q_pos = (pos + jnp.arange(s))[None, None, :, None]
        m = k_buf.shape[1]
        scores = jnp.einsum("bqnd,bknd->bnqk", q.astype(jnp.float32),
                            k_buf.astype(jnp.float32)) / math.sqrt(hd)
        key_pos = jnp.arange(m)[None, None, None, :]
        scores = jnp.where(key_pos <= q_pos, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bnqk,bknd->bqnd", probs,
                         v_buf.astype(jnp.float32)).astype(q.dtype)
        out = self.out_proj(Tensor(ctx.reshape(b, s, h)))
        return out, new_cache


class GPTMLP(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.fc_in = nn.Linear(config.hidden_size, config.intermediate_size)
        self.fc_out = nn.Linear(config.intermediate_size, config.hidden_size)

    def forward(self, x):
        return self.fc_out(F.gelu(tag_activation(self.fc_in(x), MLP_HIDDEN),
                                  approximate=True))


class GPTBlock(nn.Layer):
    """Pre-LN decoder block."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.ln_1 = nn.LayerNorm(config.hidden_size, epsilon=config.layer_norm_epsilon)
        self.attn = GPTAttention(config)
        self.ln_2 = nn.LayerNorm(config.hidden_size, epsilon=config.layer_norm_epsilon)
        self.mlp = GPTMLP(config)
        self.dropout = nn.Dropout(config.hidden_dropout_prob)

    def forward(self, x, attn_mask=None, kv_cache=None):
        # named scopes are metadata on the ops (the profile an operator
        # opens shows them); the programs stay the same programs
        if kv_cache is not None:
            with jax.named_scope("attention"):
                a, new_cache = self.attn(self.ln_1(x), kv_cache=kv_cache)
                x = x + a
            with jax.named_scope("mlp"):
                x = x + self.mlp(self.ln_2(x))
            return x, new_cache
        with jax.named_scope("attention"):
            x = x + self.dropout(self.attn(self.ln_1(x), attn_mask))
        with jax.named_scope("mlp"):
            x = x + self.dropout(self.mlp(self.ln_2(x)))
        return x


class GPTScannedBlocks(nn.Layer):
    """The full block stack as stacked [L, ...] parameters + one scan op.

    Self-initializing (GPT-3 recipe baked in at creation); GPTModel._init_weights
    skips these params so the stacked LN weights keep their ones/zeros init.
    """

    def __init__(self, config: GPTConfig):
        super().__init__()
        L, H, I = config.num_layers, config.hidden_size, config.intermediate_size
        self.num_heads = config.num_heads
        self.head_dim = H // config.num_heads
        self.hidden_dropout = config.hidden_dropout_prob
        self.attn_dropout = config.attention_dropout_prob
        self.eps = config.layer_norm_epsilon
        self.use_flash = config.use_flash_attention
        self.remat = config.recompute_granularity
        std = config.initializer_range
        normal = nn.initializer.Normal(mean=0.0, std=std)
        resid = nn.initializer.Normal(mean=0.0, std=std / math.sqrt(2.0 * L))
        ones = nn.initializer.Constant(1.0)
        mk = self.create_parameter
        self.ln1_weight = mk([L, H], default_initializer=ones)
        self.ln1_bias = mk([L, H], is_bias=True)
        self.qkv_weight = mk([L, H, 3 * H], default_initializer=normal)
        self.qkv_bias = mk([L, 3 * H], is_bias=True)
        self.proj_weight = mk([L, H, H], default_initializer=resid)
        self.proj_bias = mk([L, H], is_bias=True)
        self.ln2_weight = mk([L, H], default_initializer=ones)
        self.ln2_bias = mk([L, H], is_bias=True)
        self.fc1_weight = mk([L, H, I], default_initializer=normal)
        self.fc1_bias = mk([L, I], is_bias=True)
        self.fc2_weight = mk([L, I, H], default_initializer=resid)
        self.fc2_bias = mk([L, H], is_bias=True)

    def forward(self, x, attn_mask=None):
        if attn_mask is not None:
            raise ValueError("scan_layers path supports causal masking only "
                             "(attn_mask must be None)")
        b, s, _ = x.shape
        training = self.training
        drop = self.hidden_dropout if training else 0.0
        adrop = self.attn_dropout if training else 0.0
        from ..nn.functional.attention import flash_path_available
        use_flash = (self.use_flash
                     and flash_path_available(s, self.head_dim, x))
        args = [x, self.ln1_weight, self.ln1_bias, self.qkv_weight,
                self.qkv_bias, self.proj_weight, self.proj_bias,
                self.ln2_weight, self.ln2_bias, self.fc1_weight, self.fc1_bias,
                self.fc2_weight, self.fc2_bias]
        if drop > 0.0 or adrop > 0.0:
            from ..core import random as rng
            base = rng.split_key()
            L = int(self.ln1_weight.shape[0])
            from ..core.tensor import Tensor as _T
            args.append(_T(jax.random.key_data(jax.random.split(base, L))))
        return _op("gpt_scan_blocks", *args, num_heads=self.num_heads,
                   hidden_dropout=drop, attn_dropout=adrop, eps=self.eps,
                   use_flash=use_flash, remat=self.remat)


class GPTModel(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.wte = nn.Embedding(config.vocab_size, config.hidden_size)
        self.wpe = nn.Embedding(config.max_position_embeddings, config.hidden_size)
        self.drop = nn.Dropout(config.hidden_dropout_prob)
        if config.scan_layers:
            self.h = GPTScannedBlocks(config)
        else:
            self.h = nn.LayerList([GPTBlock(config)
                                   for _ in range(config.num_layers)])
        self.ln_f = nn.LayerNorm(config.hidden_size, epsilon=config.layer_norm_epsilon)
        self._init_weights(config)

    def _init_weights(self, config):
        std = config.initializer_range
        normal = nn.initializer.Normal(mean=0.0, std=std)
        resid_scale = nn.initializer.Normal(
            mean=0.0, std=std / math.sqrt(2.0 * config.num_layers))
        for name, p in self.named_parameters():
            if config.scan_layers and name.startswith("h."):
                continue  # GPTScannedBlocks self-initializes its stacked params
            if p.ndim >= 2:
                # GPT-2/3 init: residual-out projections scaled by 1/sqrt(2L)
                init = (resid_scale if name.endswith(("out_proj.weight",
                                                      "fc_out.weight")) else normal)
                p.set_value(init(tuple(p.shape), p.dtype))

    def forward(self, input_ids, attn_mask=None, kv_caches=None,
                start_pos=None, write_end=None, layer_subset=None):
        """``layer_subset`` (non-cached path only): run just the named
        block indices — the early-exit speculative drafter's shallow pass
        over the same weights (the ``recompute_interval`` layer-selection
        idiom, applied to inference depth instead of checkpoint spacing)."""
        b, s = input_ids.shape
        if kv_caches is not None:
            if isinstance(self.h, GPTScannedBlocks):
                raise NotImplementedError(
                    "KV-cache generation requires scan_layers=False")
            p0 = start_pos if start_pos is not None else jnp.int32(0)
            if jnp.ndim(p0) == 1:
                # per-slot cursors: each batch row reads its own positions
                raw = p0[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
            else:
                raw = (p0 + jnp.arange(s, dtype=jnp.int32))[None, :]
            # clamp for the LEARNED table: a padded chunk tail can step past
            # it; valid positions are engine-validated < max_pos, so the
            # clamp only ever touches garbage lanes
            pos_ids = Tensor(jnp.minimum(
                raw, self.config.max_position_embeddings - 1))
            we = write_end if write_end is not None else p0 + s
            x = self.wte(input_ids) + self.wpe(pos_ids)
            new_caches = []
            for block, cache in zip(self.h, kv_caches):
                if len(cache) == 3:    # paged: (pool_k, pool_v, block_table)
                    kc = (cache[0], cache[1], cache[2], p0, we)
                else:                  # contiguous: (k_buf, v_buf)
                    kc = (cache[0], cache[1], p0)
                x, nc = block(x, kv_cache=kc)
                new_caches.append(nc)
            return self.ln_f(x), new_caches
        pos = ops.arange(0, s, dtype="int32").unsqueeze(0)
        x = self.wte(input_ids) + self.wpe(pos)
        x = self.drop(x)
        if isinstance(self.h, GPTScannedBlocks):
            if layer_subset is not None:
                raise NotImplementedError(
                    "layer_subset requires scan_layers=False (the scanned "
                    "stack has no per-block seam to skip at)")
            x = self.h(x, attn_mask)
        else:
            gran = self.config.recompute_granularity
            interval = self.config.recompute_interval
            from ..core import dispatch
            use_rc = (gran != "none" and self.training
                      and (dispatch.in_trace()
                           or dispatch.is_grad_enabled()))
            for i, block in enumerate(self.h):
                if layer_subset is not None and i not in layer_subset:
                    continue
                if use_rc and i % interval == 0:
                    # block forward under the recompute policy: the compiled
                    # path drops this block's residuals per `gran` and
                    # rematerializes them in backward
                    from ..distributed.fleet.recompute import recompute
                    x = recompute(block, x, attn_mask, policy=gran)
                else:
                    x = block(x, attn_mask)
        return self.ln_f(x)

    def enable_recompute(self, granularity="selective", interval: int = 1):
        """Turn activation recompute on/off after construction.

        granularity: "none" | "selective" | "dots" | "full" (True maps to
        "full", False/None to "none"); interval=N checkpoints every Nth
        block. The scan_layers path folds the policy into its single scan
        body (interval does not apply there)."""
        self.config.recompute_granularity, self.config.recompute_interval = \
            normalize_granularity(granularity, interval)
        granularity = self.config.recompute_granularity
        if isinstance(self.h, GPTScannedBlocks):
            self.h.remat = granularity
        return self

    @property
    def _recompute_wanted(self) -> bool:
        """Observability hook (jit.TrainStep emits remat/* gauges when the
        model it compiles declares recompute)."""
        return self.config.recompute_granularity != "none"


class GPTForCausalLM(nn.Layer):
    """LM head on GPTModel; loss = shifted next-token cross-entropy."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.gpt = GPTModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None  # reuse wte
        else:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                     bias_attr=False)

    def enable_recompute(self, granularity="selective", interval: int = 1):
        """See GPTModel.enable_recompute."""
        self.gpt.enable_recompute(granularity, interval)
        return self

    @property
    def _recompute_wanted(self) -> bool:
        return self.gpt._recompute_wanted

    def forward(self, input_ids, labels=None, attn_mask=None):
        hidden = self.gpt(input_ids, attn_mask)
        if labels is not None:
            # loss from the SHIFTED hidden states: the slice happens on [B,S,H]
            # (not [B,S,V]) and the head matmul + CE fuse into one executable
            tied = self.lm_head is None
            w = self.gpt.wte.weight if tied else self.lm_head.weight
            with jax.named_scope("lm_head_loss"):
                loss = _op("lm_head_ce", hidden[:, :-1, :], w, labels[:, 1:],
                           transpose_w=tied)
            # the logits are NOT materialized on the loss path — in eager that
            # second [B,S,V] projection would really execute each step. Output
            # structure is mode-independent: labels => (None, loss), always.
            return None, loss
        if self.lm_head is None:
            return ops.matmul(hidden, self.gpt.wte.weight, transpose_y=True)
        return self.lm_head(hidden)

    # ------------------------------------------------------------ generation

    def decode_spec(self):
        """What the serving engine drives (``models/cache_spec.py``): every
        layer caches K/V, one head a query head."""
        from .cache_spec import ModelSpec, kv_layer
        cfg = self.config
        if getattr(cfg, "scan_layers", False):
            raise NotImplementedError(
                "DecodeEngine requires scan_layers=False (the KV cache "
                "threads through discrete blocks)")
        tied = self.lm_head is None
        return ModelSpec(
            self.gpt, [kv_layer(cfg.num_heads,
                                cfg.hidden_size // cfg.num_heads)]
            * cfg.num_layers, cfg.max_position_embeddings,
            self.gpt.wte.weight if tied else self.lm_head.weight, tied)

    def generate(self, input_ids, max_new_tokens: int = 32,
                 temperature: float = 1.0, do_sample: bool = False,
                 top_k: int = 0, eos_token_id=None, seed=None,
                 max_length=None, use_engine: bool = False):
        """KV-cache incremental decoding, the WHOLE loop in one executable.

        Reference analog: generation over fused_multi_transformer's CacheKV
        tensors (incubate/nn/layer/fused_transformer.py:1021). TPU-native:
        prefill writes the prompt's K/V into static [B, M, nh, hd] buffers,
        then a lax.while_loop of single-token steps decodes up to
        max_new_tokens (stopping the loop early once EVERY row has emitted
        EOS) — one compiled program per (prompt_shape, max_new_tokens,
        sampling config), no per-token Python or recompiles. Greedy by
        default; do_sample=True draws from softmax(logits/temperature) with
        optional top-k; ``seed=None`` draws the sampling seed from
        ``core.random.host_generator()`` so ``paddle.seed`` makes generation
        reproducible. After an EOS a row keeps emitting EOS. Requires
        scan_layers=False (the cache threads through discrete blocks).

        ``use_engine=True`` routes through ``paddle_tpu.serving.DecodeEngine``
        (paged KV cache + slot scheduler) — same greedy tokens, and the
        engine's executables are shared with any concurrent serving traffic.
        """
        cfg = self.config
        if cfg.scan_layers:
            raise NotImplementedError(
                "generate() requires scan_layers=False")
        if max_length and max_length > cfg.max_position_embeddings:
            # GPT-specific: the LEARNED position table clamps past its end
            raise ValueError(
                f"max_length {max_length} exceeds the learned position "
                f"table ({cfg.max_position_embeddings}); positions past it "
                f"would silently clamp")
        if use_engine:
            from ..serving import generate_via_engine
            return generate_via_engine(
                self, input_ids, max_new_tokens=max_new_tokens,
                temperature=temperature, do_sample=do_sample, top_k=top_k,
                eos_token_id=eos_token_id, seed=seed, max_length=max_length)
        return _generate_with_cache(
            self, self.gpt, cfg.num_layers, cfg.num_heads,
            cfg.hidden_size // cfg.num_heads, cfg.max_position_embeddings,
            head_weight=(self.gpt.wte.weight if self.lm_head is None
                         else self.lm_head.weight),
            head_transpose=self.lm_head is None,
            input_ids=input_ids, max_new_tokens=max_new_tokens,
            temperature=temperature, do_sample=do_sample, top_k=top_k,
            eos_token_id=eos_token_id, seed=seed, max_length=max_length)



def shard_gpt_tp(model: "GPTForCausalLM", mesh=None, axis: str = "model"):
    """Tensor-parallel placement for GPT (the Fleet mp_layers recipe as
    NamedShardings, mirroring ``shard_llama_tp``): column-shard qkv_proj
    and fc_in (weights ``P(None, axis)``, biases ``P(axis)``), row-shard
    out_proj and fc_out (``P(axis, None)``, replicated bias — their output
    is the mp_allreduce psum), vocab-shard the token embedding (the tied
    LM head reads the same array). LayerNorms and the position table stay
    replicated. XLA's SPMD partitioner inserts the collectives; a dim not
    divisible by the axis degree is left replicated rather than refused, so
    odd geometries degrade instead of erroring."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..distributed.env import get_mesh
    mesh = mesh if mesh is not None else get_mesh()
    if mesh is None or mesh.shape.get(axis, 1) <= 1:
        return model
    tp = mesh.shape[axis]

    def put(p, spec, dim_sizes):
        if p is None or any(d % tp for d in dim_sizes):
            return
        p._data = jax.device_put(p.value(), NamedSharding(mesh, spec))

    for name, p in model.named_parameters():
        if name.endswith(("qkv_proj.weight", "fc_in.weight")):
            put(p, P(None, axis), (p.shape[1],))
        elif name.endswith(("qkv_proj.bias", "fc_in.bias")):
            put(p, P(axis), (p.shape[0],))
        elif name.endswith(("out_proj.weight", "fc_out.weight")):
            put(p, P(axis, None), (p.shape[0],))
        elif name.endswith("wte.weight"):
            put(p, P(axis, None), (p.shape[0],))
        elif name.endswith("lm_head.weight"):
            put(p, P(None, axis), (p.shape[1],))
    return model


def _lm_head_logits(hidden_last, head_weight, transpose: bool):
    """fp32 LM-head matmul over last hidden states. Shared by the eager
    compiled loop AND serving.DecodeEngine — one definition so the two
    decode paths cannot numerically drift apart (parity tests depend on
    greedy tokens matching exactly)."""
    w = head_weight.value().astype(jnp.float32)
    return hidden_last.astype(jnp.float32) @ (w.T if transpose else w)


def _pick_token(logits, key, do_sample: bool, temperature, top_k: int):
    """Greedy argmax or temperature + top-k categorical draw over [B, V]
    logits. Shared by the eager loop and the serving engine (see
    _lm_head_logits)."""
    if do_sample:
        lg = logits / jnp.maximum(temperature, 1e-6)
        if top_k and top_k > 0:
            kth = jnp.sort(lg, axis=-1)[:, -top_k][:, None]
            lg = jnp.where(lg < kth, -1e30, lg)
        return jax.random.categorical(key, lg, axis=-1)
    return jnp.argmax(logits, axis=-1)


def _resolve_decode_horizon(s0: int, max_new_tokens: int, max_length,
                            max_pos: int, seed, do_sample: bool):
    """Shared generate() front door (eager loop AND serving's
    generate_via_engine — one definition so the two entry points cannot
    drift): validate the token budget, size the KV horizon to the DECODE
    (not the model's position table — tight M more than doubles tok/s, see
    _generate_with_cache), and derive the sampling seed. Un-seeded sampling
    draws from host_generator() so paddle.seed reproduces it; greedy never
    reads the key and must not consume the shared stream."""
    if max_new_tokens < 0:
        raise ValueError(f"max_new_tokens must be >= 0, got {max_new_tokens}")
    m = int(max_length or min(s0 + max_new_tokens, max_pos))
    if s0 + max_new_tokens > m:
        raise ValueError(f"prompt {s0} + max_new_tokens {max_new_tokens} "
                         f"exceeds max_length {m}")
    if seed is None:
        if do_sample:
            from ..core.random import host_generator
            seed = int(host_generator().integers(0, 2**31 - 1))
        else:
            seed = 0
    return m, int(seed)


def _generate_with_cache(lm, backbone, num_layers: int, n_kv_heads: int,
                         head_dim: int, max_pos: int, head_weight,
                         head_transpose: bool, input_ids, max_new_tokens,
                         temperature, do_sample, top_k, eos_token_id, seed,
                         max_length):
    """Shared compiled prefill+decode loop (GPT and LLaMA): see
    GPTForCausalLM.generate for the contract. `backbone(ids, kv_caches=...,
    start_pos=...)` must return (hidden, new_caches)."""
    from ..core import dispatch

    ids_arr = input_ids.value() if isinstance(input_ids, Tensor) \
        else jnp.asarray(input_ids)
    b, s0 = ids_arr.shape
    # cache buffers sized to the DECODE, not the model's position table:
    # every step streams the whole [B, M, nh, hd] K/V pair per layer — at
    # GPT-medium M=1024 that is 0.54 GB read per step whatever the decode
    # length (its share of the step on the chip: not measured on this stack)
    m, seed = _resolve_decode_horizon(s0, max_new_tokens, max_length,
                                      max_pos, seed, do_sample)
    if max_new_tokens == 0:
        return Tensor(ids_arr.astype(jnp.int32))   # same dtype as n>0 paths
    # params AND buffers: an int8-quantized model (quantize_for_serving)
    # carries its weights as Int8Linear BUFFERS — rebinding them keeps the
    # executable weight-update-safe instead of baking them in as constants
    params = [p for _, p in lm.named_parameters()] \
        + [bf for _, bf in lm.named_buffers()]
    dtype = params[0].value().dtype
    eos = -1 if eos_token_id is None else int(eos_token_id)

    def head(hidden_last):
        return _lm_head_logits(hidden_last, head_weight, head_transpose)

    def pick(logits, key):
        return _pick_token(logits, key, do_sample, temperature, top_k)

    def gen_fn(param_arrays, ids, key0):
        ctx = dispatch.TraceContext()
        saved = [p._data for p in params]
        dispatch.push_trace(ctx)
        try:
            for p, a in zip(params, param_arrays):
                p._data = a
            caches = [(jnp.zeros((b, m, n_kv_heads, head_dim), dtype),
                       jnp.zeros((b, m, n_kv_heads, head_dim), dtype))
                      for _ in range(num_layers)]
            hidden, caches = backbone(Tensor(ids), kv_caches=caches,
                                      start_pos=jnp.int32(0))
            tok0 = pick(head(hidden.value()[:, -1]), key0).astype(jnp.int32)
            done0 = tok0 == eos

            # while_loop (not scan): once EVERY row has emitted EOS the loop
            # exits — a batch that finishes in 3 tokens pays 3 steps, not
            # max_new_tokens. Unvisited columns keep the EOS fill, which is
            # exactly what finished rows would have emitted.
            out0 = jnp.full((b, max_new_tokens), max(eos, 0), jnp.int32)
            out0 = jax.lax.dynamic_update_slice(out0, tok0[:, None], (0, 0))

            def cond(carry):
                _, _, done, _, i, _ = carry
                return (i < max_new_tokens) & ~jnp.all(done)

            def step(carry):
                caches, tok, done, key, i, out = carry
                key, sub = jax.random.split(key)
                hidden, caches = backbone(
                    Tensor(tok[:, None]), kv_caches=caches,
                    start_pos=jnp.int32(s0 - 1) + i)
                nxt = pick(head(hidden.value()[:, -1]), sub).astype(jnp.int32)
                nxt = jnp.where(done, eos, nxt)      # finished rows: EOS
                done = done | (nxt == eos)
                out = jax.lax.dynamic_update_slice(out, nxt[:, None],
                                                   (jnp.int32(0), i))
                return (caches, nxt, done, key, i + jnp.int32(1), out)

            carry = jax.lax.while_loop(
                cond, step, (caches, tok0, done0, key0, jnp.int32(1), out0))
            return carry[5]
        finally:
            dispatch.pop_trace()
            ctx.restore()
            for p, d in zip(params, saved):
                p._data = d

    # per-INSTANCE executable cache (dies with the model; bounded so shape
    # churn cannot grow it without limit)
    if not hasattr(lm, "_gen_cache"):
        lm._gen_cache = {}
    # the leaf fingerprint invalidates stale closures when the model's
    # parameter/buffer STRUCTURE changes underneath us (e.g. an in-place
    # int8 swap after a generate() call): the cached gen_fn closes over the
    # old leaf list and would rebind the new arrays to the wrong tensors
    leaf_sig = tuple((tuple(p.shape), str(p.value().dtype)) for p in params)
    cache_key = (b, s0, max_new_tokens, m, do_sample, top_k,
                 float(temperature), eos, leaf_sig)
    jitted = lm._gen_cache.get(cache_key)
    if jitted is None:
        if len(lm._gen_cache) >= 8:
            lm._gen_cache.pop(next(iter(lm._gen_cache)))
        jitted = jax.jit(gen_fn)
        lm._gen_cache[cache_key] = jitted
    new_tokens = jitted(tuple(p.value() for p in params),
                        ids_arr.astype(jnp.int32), jax.random.PRNGKey(seed))
    return Tensor(jnp.concatenate(
        [ids_arr.astype(jnp.int32), new_tokens.astype(jnp.int32)], axis=1))
