"""Attention functionals.

Reference analogs: `phi/kernels/flash_attn_kernel.h` (dynload'd FlashAttention lib) and
`incubate/nn/memory_efficient_attention.py`. On TPU the fused kernel is a Pallas flash
attention (paddle_tpu.kernels.pallas.flash_attention); the default path is plain XLA,
which already fuses the softmax chain well.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...core import random as rng
from ...core.dispatch import program_mesh, register_op
from ...core.remat import ATTN_CONTEXT, tag_array
from ...core.tensor import Tensor
from ...ops._helpers import _op

__all__ = ["scaled_dot_product_attention", "flash_attention",
           "flash_attention_qkv_packed"]


def _sdpa_fwd(q, k, v, *rest, causal=False, scale=None, has_mask=False,
              has_dropkey=False, dropout_p=0.0):
    # q,k,v: [B, L, H, D] (paddle flash_attn layout); rest = [attn_mask][prng_key]
    if k.shape[2] != q.shape[2]:
        # GQA: expand KV heads (the Pallas path folds them in its index map;
        # the XLA fallback materializes — same public semantics either way)
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qt = jnp.swapaxes(q, 1, 2)  # [B,H,L,D]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * s
    if has_mask:
        mask = rest[0]
        logits = logits + mask.astype(logits.dtype)
    if causal:
        ql, kl = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((ql, kl), bool), k=kl - ql)
        logits = jnp.where(cm, logits, jnp.finfo(logits.dtype).min)
    probs = jax.nn.softmax(logits, axis=-1)
    if has_dropkey:
        # dropout mask drawn inside the op from the key input — fused by XLA, fresh
        # per execution under to_static (key is threaded program state)
        key = rest[1] if has_mask else rest[0]
        keep = jax.random.bernoulli(jax.random.wrap_key_data(key),
                                    1.0 - dropout_p, probs.shape)
        probs = probs * keep.astype(probs.dtype) / (1.0 - dropout_p)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vt)
    # checkpoint name on the CONTEXT only: under the "selective" recompute
    # policy the context survives while every [B,H,S,S] intermediate above
    # (logits, probs, dropout mask) stays unnamed and is rematerialized in
    # backward — the Megatron selective-recompute memory/FLOPs trade
    return tag_array(jnp.swapaxes(out, 1, 2), ATTN_CONTEXT)  # [B,L,H,D]


register_op("sdpa", _sdpa_fwd, nondiff_inputs=(3, 4))


def _flash_attn_pallas_fwd(q, k, v, *rest, causal=False, dropout_rate=0.0):
    from ...kernels.pallas.flash_attention import flash_attention_blhd
    seed = rest[0] if rest else 0
    return tag_array(flash_attention_blhd(q, k, v, causal=causal,
                                          dropout_rate=dropout_rate,
                                          seed=seed), ATTN_CONTEXT)


# Pallas flash attention as a dispatch op: flows through the autograd tape; its
# custom_vjp supplies the gradient under the generic jit(vjp) backward. The
# dropout seed (input 3, when present) is a nondiff program-state input.
register_op("flash_attn_pallas", _flash_attn_pallas_fwd, nondiff_inputs=(3,))


def packed_flash(qkv, num_heads, causal, dropout_rate, seed):
    """The packed-qkv flash kernel for this head geometry, [B, L, 3*H*D] ->
    [B, L, H*D], callable from inside a multi-device program.

    The SPMD partitioner cannot split a Mosaic kernel ("Mosaic kernels
    cannot be automatically partitioned. Please wrap the call in a
    shard_map" — what the first ZeRO train step on four real chips died
    of). So when the tracer says the program is laid over a mesh
    (``dispatch.program_mesh()``), the call is mapped over it: the batch
    splits over the data-like axes that divide it (``io.batch_sharding``'s
    convention), everything else is replicated, and each shard runs the
    kernel unchanged."""
    from ...kernels.pallas.flash_attention import flash_attention_qkv_packed
    from ...kernels.pallas.flash_pair import (flash_pair_packed,
                                              pair_layout_supported)
    d = qkv.shape[-1] // (3 * num_heads)
    if pair_layout_supported(d, num_heads, qkv.shape[1]):
        # single-tile fast path (head-blocks fill the 128-lane quantum;
        # fused single-pass dqkv backward) — kernels/pallas/flash_pair.py
        def kernel(x, s):
            return flash_pair_packed(x, num_heads, causal,
                                     dropout_rate=dropout_rate, seed=s)
    else:
        def kernel(x, s):
            return flash_attention_qkv_packed(x, num_heads, causal=causal,
                                              dropout_rate=dropout_rate,
                                              seed=s)
    seed = jnp.asarray(seed, jnp.int32)
    mesh = program_mesh()
    if mesh is None:
        return kernel(qkv, seed)
    axes = tuple(a for a in ("data", "sharding") if mesh.shape.get(a, 1) > 1)
    if not axes or qkv.shape[0] % math.prod(mesh.shape[a] for a in axes):
        batch = P()      # nothing divides the batch: every device runs it all
    elif dropout_rate > 0.0:
        # the keep mask is seeded per (LOCAL batch row, head, tile): every
        # shard of a split batch would draw the same masks
        raise NotImplementedError(
            "in-kernel attention dropout is not supported when the batch is "
            "sharded over devices (the hardware-PRNG mask is seeded by the "
            "local batch index); use attention_dropout_prob=0.0 on a "
            "multi-chip mesh")
    else:
        batch = P(axes)
    return jax.shard_map(kernel, mesh=mesh, in_specs=(batch, P()),
                         out_specs=batch, check_vma=False)(qkv, seed)


def _flash_attn_packed_fwd(qkv, *rest, num_heads, causal=True,
                           dropout_rate=0.0):
    return tag_array(packed_flash(qkv, num_heads, causal, dropout_rate,
                                  rest[0] if rest else 0), ATTN_CONTEXT)


register_op("flash_attn_qkv_packed", _flash_attn_packed_fwd,
            nondiff_inputs=(1,))


def _flash_attn_lens_fwd(q, k, v, lens, *rest, causal=False, dropout_rate=0.0):
    from ...kernels.pallas.flash_attention import flash_attention_blhd
    seed = rest[0] if rest else 0
    return tag_array(flash_attention_blhd(q, k, v, causal=causal,
                                          dropout_rate=dropout_rate,
                                          seed=seed, kv_lens=lens),
                     ATTN_CONTEXT)


# encoder padding-mask flash: per-sequence kv lengths as a nondiff input
register_op("flash_attn_pallas_lens", _flash_attn_lens_fwd,
            nondiff_inputs=(3, 4))


def _flash_attn_segs_fwd(q, k, v, sq, sk, *rest, causal=False,
                         dropout_rate=0.0):
    from ...kernels.pallas.flash_attention import flash_attention_blhd
    seed = rest[0] if rest else 0
    return tag_array(flash_attention_blhd(q, k, v, causal=causal,
                                          dropout_rate=dropout_rate,
                                          seed=seed, q_segments=sq,
                                          kv_segments=sk), ATTN_CONTEXT)


# packed-sequence flash: segment ids gate attention (same-segment only)
register_op("flash_attn_pallas_segs", _flash_attn_segs_fwd,
            nondiff_inputs=(3, 4, 5))


def _flash_attn_segs_lens_fwd(q, k, v, lens, sq, sk, *rest, causal=False,
                              dropout_rate=0.0):
    from ...kernels.pallas.flash_attention import flash_attention_blhd
    seed = rest[0] if rest else 0
    return tag_array(flash_attention_blhd(q, k, v, causal=causal,
                                          dropout_rate=dropout_rate,
                                          seed=seed, kv_lens=lens,
                                          q_segments=sq, kv_segments=sk),
                     ATTN_CONTEXT)


# padding lengths AND packed segments together (the kernel masks with both)
register_op("flash_attn_pallas_segs_lens", _flash_attn_segs_lens_fwd,
            nondiff_inputs=(3, 4, 5, 6))


def flash_attention_qkv_packed(qkv, num_heads, dropout=0.0, causal=True,
                               training=True):
    """Flash attention on the fused projection output [B, L, 3*H*D] -> the
    pre-packed [B, L, H*D] context (zero layout copies; head_dim % 128 == 0).
    The hot path for MXU-aligned decoder blocks. Off-TPU (no Mosaic), falls
    back to splitting heads through scaled_dot_product_attention."""
    drop = float(dropout) if training else 0.0
    shape = qkv.shape
    d = shape[-1] // (3 * num_heads)
    from ...kernels.pallas.flash_attention import packed_layout_supported
    from ...kernels.pallas.flash_pair import pair_layout_supported
    if not (flash_path_available(shape[1], d, qkv)
            and (packed_layout_supported(d)
                 or pair_layout_supported(d, num_heads, shape[1]))):
        b, L = shape[0], shape[1]
        unwrap = qkv.value() if hasattr(qkv, "value") else qkv
        q, k, v = (Tensor(unwrap[:, :, i * num_heads * d:(i + 1) * num_heads * d]
                          .reshape(b, L, num_heads, d)) for i in range(3))
        out = scaled_dot_product_attention(q, k, v, dropout_p=drop,
                                           is_causal=causal, training=training)
        return out.reshape([b, L, num_heads * d])
    args = [qkv]
    if drop > 0.0:
        seed = rng.int32_seed()
        args.append(Tensor(seed))
    return _op("flash_attn_qkv_packed", *args, num_heads=int(num_heads),
               causal=bool(causal), dropout_rate=drop)


def scaled_dot_product_attention(query, key, value, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True, name=None,
                                 kv_lens=None, q_segments=None,
                                 kv_segments=None):
    """paddle.nn.functional.scaled_dot_product_attention parity: [B, L, H, D] layout.

    TPU-native extensions: kv_lens ([B] int) — per-sequence key counts
    (encoder padding mask, the structured form of attn_mask=[B,1,1,L]);
    q_segments/kv_segments ([B, L] int) — packed-sequence attention. With
    either of these, or with no attn_mask at all, the call routes to the
    Pallas flash kernel on TPU (reference: phi/kernels/flash_attn_kernel.h
    serves encoder and decoder attention alike); arbitrary additive attn_mask
    takes the XLA softmax chain.

    Attention dropout follows the eager-dropout recipe (functional/common.py): the keep
    mask is drawn host-side from the global RNG chain and passed as a nondiff input, so
    the op stays a pure function of its inputs (cacheable executable)."""
    drop = float(dropout_p) if training else 0.0
    if attn_mask is None and _pallas_usable(query):
        seed_args = []
        if drop > 0.0:
            seed = rng.int32_seed()
            seed_args = [Tensor(seed)]
        if q_segments is not None and kv_lens is not None:
            return _op("flash_attn_pallas_segs_lens", query, key, value,
                       kv_lens, q_segments, kv_segments, *seed_args,
                       causal=bool(is_causal), dropout_rate=drop)
        if q_segments is not None:
            return _op("flash_attn_pallas_segs", query, key, value,
                       q_segments, kv_segments, *seed_args,
                       causal=bool(is_causal), dropout_rate=drop)
        if kv_lens is not None:
            return _op("flash_attn_pallas_lens", query, key, value, kv_lens,
                       *seed_args, causal=bool(is_causal), dropout_rate=drop)
        return _op("flash_attn_pallas", query, key, value, *seed_args,
                   causal=bool(is_causal), dropout_rate=drop)
    if kv_lens is not None or q_segments is not None:
        # XLA fallback (or attn_mask given alongside the structured masks):
        # lower lens/segments to an additive mask and COMBINE with any user
        # mask — dropping either silently would attend padding keys
        structured = _structured_to_additive(query, key, kv_lens, q_segments,
                                             kv_segments)
        if attn_mask is None:
            attn_mask = structured
        else:
            am = attn_mask.value() if hasattr(attn_mask, "value") \
                else jnp.asarray(attn_mask)
            attn_mask = Tensor(structured.value() + am.astype(jnp.float32))
    args = [query, key, value]
    if attn_mask is not None:
        args.append(attn_mask)
    if drop > 0.0:
        args.append(Tensor(jax.random.key_data(rng.split_key())))
    return _op("sdpa", *args, causal=bool(is_causal), scale=None,
               has_mask=attn_mask is not None, has_dropkey=drop > 0.0,
               dropout_p=drop)


def _structured_to_additive(query, key, kv_lens, q_segments, kv_segments):
    """[B] lens / [B, L] segment ids -> additive [B, 1, Lq, Lk] mask."""
    lk = key.shape[1]
    lq = query.shape[1]
    unwrap = lambda t: t.value() if hasattr(t, "value") else jnp.asarray(t)
    valid = None
    if kv_lens is not None:
        cols = jnp.arange(lk)[None, :] < unwrap(kv_lens)[:, None]
        valid = jnp.broadcast_to(cols[:, None, :], (cols.shape[0], lq, lk))
    if q_segments is not None:
        sq = unwrap(q_segments)
        sk = unwrap(kv_segments)
        seg_ok = sq[:, :, None] == sk[:, None, :]
        valid = seg_ok if valid is None else (valid & seg_ok)
    add = jnp.where(valid, 0.0, jnp.float32(-1e30))[:, None, :, :]
    return Tensor(add)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None, rng_name="",
                    training=True, name=None, use_pallas=None):
    """paddle.nn.functional.flash_attention parity ([B,L,H,D]).

    On real TPU devices ≥ the pallas kernel's tile minimum, dispatches to the Pallas
    flash-attention kernel; otherwise falls back to the XLA softmax-chain (which XLA
    fuses into a flash-like schedule anyway for moderate L).
    """
    drop = float(dropout) if training else 0.0
    if use_pallas is None:
        use_pallas = _pallas_usable(query)
    if use_pallas:
        args = [query, key, value]
        if drop > 0.0:
            # in-kernel counter-based dropout; seed drawn from the global RNG
            # chain so to_static replays give fresh masks (threaded state)
            seed = rng.int32_seed()
            args.append(Tensor(seed))
        out = _op("flash_attn_pallas", *args, causal=bool(causal),
                  dropout_rate=drop)
    else:
        out = scaled_dot_product_attention(query, key, value, dropout_p=drop,
                                           is_causal=bool(causal),
                                           training=training)
    if return_softmax:
        return out, None
    return out


def flash_path_available(seq_len, head_dim, sample=None) -> bool:
    """The single gate for the Pallas flash kernel: tile minimums + TPU placement.

    Shared by every caller (functional API, scanned GPT stack) so shape
    constraints stay in one place. `sample` (Tensor or array) decides by actual
    placement when concrete; tracers fall back to the default backend, which is
    where the compiled program will run."""
    if seq_len < 128 or head_dim < 64:
        return False
    if sample is not None:
        arr = sample.value() if hasattr(sample, "value") else sample
        try:
            return any(d.platform == "tpu" for d in arr.devices())
        except Exception:
            pass
    return jax.default_backend() == "tpu"


def _pallas_usable(q):
    shape = q.shape
    return len(shape) == 4 and flash_path_available(shape[1], shape[3], q)
