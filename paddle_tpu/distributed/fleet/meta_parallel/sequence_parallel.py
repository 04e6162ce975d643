"""Sequence/context parallelism over the "sep" mesh axis.

The reference has NO sequence parallelism (SURVEY.md §2.4: repo-wide grep empty);
its long-context story stops at flash-attention kernels
(phi/kernels/flash_attn_kernel.h). This module fills that declared capability gap
the TPU-native way:

- `ring_attention(q, k, v)`: causal attention with the SEQUENCE dim sharded over
  "sep". Each device keeps its Q shard; K/V shards rotate around the ring via
  lax.ppermute (one hop per step, over ICI), and partial softmax results combine
  with the running log-sum-exp trick — flash attention's online softmax, applied
  across devices. Memory per device: O(S/sep * S/sep) per block instead of O(S²);
  activations elsewhere stay sharded [B, S/sep, H].
- `shard_sequence` / `gather_sequence`: place/unplace the activation sequence
  dim on the sep axis (SP region entry/exit).

Composability: the ring's shard_map specs are derived from the INPUT placements,
so batch sharded over "data" and heads sharded over "model" (TP) stay sharded
through the ring; only the sequence dim participates in the rotation.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...env import get_mesh

__all__ = ["ring_attention", "shard_sequence", "gather_sequence"]


def _ring_attn_local(q, k, v, sm_scale: float, S: int, axis: str,
                     vary: tuple = ()):
    """Per-device body: q,k,v [B, L, H, D] (L = local seq shard).

    Device r owns query block r and initially key block r. At ring step j it
    holds key block (r - j) mod S. Causal masking happens at BLOCK granularity:
    a key block strictly newer than the query block contributes nothing; the
    diagonal block applies the elementwise causal mask.
    """
    r = jax.lax.axis_index(axis)
    B, L, H, D = q.shape
    qt = jnp.swapaxes(q, 1, 2).astype(jnp.float32)  # [B,H,L,D]

    def step(carry, j):
        k_cur, v_cur, acc, lse = carry
        kb = (r - j) % S                             # key block id this step
        kt = jnp.swapaxes(k_cur, 1, 2).astype(jnp.float32)
        vt = jnp.swapaxes(v_cur, 1, 2).astype(jnp.float32)
        logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * sm_scale
        # block-causal mask: query global pos = r*L + i, key pos = kb*L + t
        qpos = r * L + jnp.arange(L)[:, None]
        kpos = kb * L + jnp.arange(L)[None, :]
        mask = qpos >= kpos
        logits = jnp.where(mask, logits, -jnp.inf)
        blk_lse = jax.scipy.special.logsumexp(logits, axis=-1)  # [B,H,L]
        # renormalize the running accumulator (flash online softmax across devices)
        new_lse = jnp.logaddexp(lse, blk_lse)
        probs = jnp.exp(logits - new_lse[..., None])
        probs = jnp.where(jnp.isfinite(new_lse)[..., None], probs, 0.0)
        scale_old = jnp.exp(lse - new_lse)
        scale_old = jnp.where(jnp.isfinite(new_lse), scale_old, 0.0)
        acc = acc * scale_old[..., None] + jnp.einsum("bhqk,bhkd->bhqd",
                                                      probs, vt)
        # rotate K/V one hop: device i's block moves to i+1
        perm = [(i, (i + 1) % S) for i in range(S)]
        k_nxt = jax.lax.ppermute(k_cur, axis, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis, perm)
        return (k_nxt, v_nxt, acc, new_lse), None

    # the carry varies over every axis the inputs are split on (sep + any
    # batch/head shardings that pass through), per typed-shard_map rules
    vary_all = tuple(dict.fromkeys((axis,) + tuple(vary)))
    acc0 = jax.lax.pcast(jnp.zeros((B, H, L, D), jnp.float32), vary_all,
                         to="varying")
    lse0 = jax.lax.pcast(jnp.full((B, H, L), -jnp.inf, jnp.float32),
                         vary_all, to="varying")
    (k_f, v_f, acc, lse), _ = jax.lax.scan(
        step, (k, v, acc0, lse0), jnp.arange(S))
    out = jnp.swapaxes(acc, 1, 2)                    # [B,L,H,D]
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh: Optional[Mesh] = None, axis: str = "sep",
                   sm_scale: Optional[float] = None):
    """Causal ring attention; q,k,v: [B, S_global, H, D] with the sequence dim
    sharded over `axis` (global arrays in, global arrays out)."""
    mesh = mesh if mesh is not None else get_mesh()
    S = mesh.shape[axis]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if S == 1:
        # degenerate: plain causal attention
        return _plain_causal(q, k, v, sm_scale)

    def spec_like(arr):
        # preserve the caller's batch ("data") and head ("model") shardings —
        # only the sequence dim (1) joins the ring
        base = [None, None, None, None]
        spec_t = getattr(getattr(arr, "sharding", None), "spec", None)
        if spec_t is not None:
            for i, s in enumerate(tuple(spec_t)[:4]):
                base[i] = s
        base[1] = axis
        return P(*base)

    sq, sk, sv = spec_like(q), spec_like(k), spec_like(v)
    vary = tuple({a for sp in (sq, sk, sv) for dim in tuple(sp)
                  for a in ((dim,) if isinstance(dim, str) else (dim or ()))
                  if a != axis})
    fn = jax.shard_map(partial(_ring_attn_local, sm_scale=sm_scale, S=S,
                               axis=axis, vary=vary),
                       mesh=mesh, in_specs=(sq, sk, sv), out_specs=sq)
    return fn(q, k, v)


def _plain_causal(q, k, v, sm_scale):
    qt, kt, vt = (jnp.swapaxes(t, 1, 2).astype(jnp.float32) for t in (q, k, v))
    logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * sm_scale
    L = logits.shape[-1]
    mask = jnp.tril(jnp.ones((L, L), bool))
    logits = jnp.where(mask, logits, -jnp.inf)
    out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(logits, -1), vt)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def shard_sequence(x, mesh: Optional[Mesh] = None, axis: str = "sep",
                   seq_dim: int = 1):
    """Place a [B, S, ...] array with S sharded over the sep axis."""
    mesh = mesh if mesh is not None else get_mesh()
    spec = [None] * x.ndim
    spec[seq_dim] = axis
    arr = x.value() if hasattr(x, "value") else x
    return jax.device_put(arr, NamedSharding(mesh, P(*spec)))


def gather_sequence(x, mesh: Optional[Mesh] = None, axis: str = "sep"):
    """Re-replicate a sequence-sharded array (the all-gather at SP exit)."""
    mesh = mesh if mesh is not None else get_mesh()
    arr = x.value() if hasattr(x, "value") else x
    return jax.device_put(arr, NamedSharding(mesh, P(*([None] * arr.ndim))))
