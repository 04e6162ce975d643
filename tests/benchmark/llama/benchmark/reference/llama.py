"""Plain reference of the LLaMA decoder (`paddle_tpu/models/llama.py`) for
the test fixture family: float32 `jax.numpy`, products at precision
"highest", no kernels, no cache. Pre-norm blocks with RMSNorm, rotary
positions in interleaved pairs on every head, grouped-query attention
(query head h reads KV head h // group), SwiGLU, an untied output head,
next-token cross-entropy averaged over every shifted position. Imports
nothing of the program.

Parameters are a flat dict of STACKED arrays (leading axis = layer):
  emb [V,H]  head [H,V]  norm_w [H]
  ln1_w ln2_w [L,H]   q_w o_w [L,H,H]   k_w v_w [L,H,KV*D]
  gate_w up_w [L,H,I]   down_w [L,I,H]
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import hi_dot

LAYER_KEYS = ("ln1_w", "q_w", "k_w", "v_w", "o_w", "ln2_w", "gate_w",
              "up_w", "down_w")
TOP_KEYS = ("emb", "head", "norm_w")


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def rope(x, theta):
    """x [B,S,N,D]: pairs (2i, 2i+1) turned by position x theta^(-2i/D)."""
    s, d = x.shape[1], x.shape[3]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def block(x, p, model, dot):
    b, s, h = x.shape
    n, kv = model["num_heads"], model["num_kv_heads"]
    hd, eps, theta = h // n, model["rms_norm_eps"], model["rope_theta"]
    y = rms_norm(x, p["ln1_w"], eps)
    q = rope(dot("bsh,hk->bsk", y, p["q_w"]).reshape(b, s, n, hd), theta)
    k = rope(dot("bsh,hk->bsk", y, p["k_w"]).reshape(b, s, kv, hd), theta)
    v = dot("bsh,hk->bsk", y, p["v_w"]).reshape(b, s, kv, hd)
    sc = dot("bqkgd,bmkd->bkgqm", q.reshape(b, s, kv, n // kv, hd), k) \
        / math.sqrt(hd)
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    ctx = dot("bkgqm,bmkd->bqkgd", jax.nn.softmax(sc, axis=-1), v)
    x = x + dot("bsh,hk->bsk", ctx.reshape(b, s, h), p["o_w"])
    y = rms_norm(x, p["ln2_w"], eps)
    y = jax.nn.silu(dot("bsh,hk->bsk", y, p["gate_w"])) \
        * dot("bsh,hk->bsk", y, p["up_w"])
    return x + dot("bsk,kh->bsh", y, p["down_w"])


def hidden_states(params, ids, model, dot=hi_dot):
    x = params["emb"].astype(jnp.float32)[ids]

    def body(x, layer):
        layer = {k: v.astype(jnp.float32) for k, v in layer.items()}
        return block(x, layer, model, dot), None

    x, _ = jax.lax.scan(body, x, {k: params[k] for k in LAYER_KEYS})
    return rms_norm(x, params["norm_w"].astype(jnp.float32),
                    model["rms_norm_eps"])


def logits(params, ids, model, dot=hi_dot):
    """Full forward: [B,S] -> [B,S,V] through the untied head."""
    return dot("bsh,hv->bsv", hidden_states(params, ids, model, dot),
               params["head"].astype(jnp.float32))


def loss_and_grads(params, ids, model, dot=hi_dot):
    """Mean next-token loss over ids [B,S] and its gradients."""
    def loss(params):
        lg = logits(params, ids, model, dot)[:, :-1]
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, ids[:, 1:, None], axis=-1)[..., 0]
        return jnp.mean(lse - gold)

    return jax.value_and_grad(loss)(params)
