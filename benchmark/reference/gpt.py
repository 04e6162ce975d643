"""Plain reference of the GPT-3 decoder block stack: float32 `jax.numpy`,
matmuls at precision "highest", no kernels, no cache, no batching tricks.

Follows Brown et al. 2020 (pre-LN decoder, learned positions, 4x GELU MLP,
tied output head) as `models/gpt.py` states it: LayerNorm eps 1e-5, tanh
GELU, qkv columns ordered (3, heads, head_dim), next-token cross-entropy
averaged over every shifted position. Imports nothing of the program.

Parameters are a flat dict of STACKED arrays (leading axis = layer):
  wte [V,H]  wpe [P,H]  lnf_w [H]  lnf_b [H]
  ln1_w ln1_b ln2_w ln2_b proj_b fc2_b [L,H]   qkv_w [L,H,3H]  qkv_b [L,3H]
  proj_w [L,H,H]  fc1_w [L,H,4H]  fc1_b [L,4H]  fc2_w [L,4H,H]

`model` is the configuration's whole `model` group; `dot` is the matmul
every product goes through (`common.hi_dot`), so the lower-precision
control (`common.fp8_dot`) is the same code with the products' operands
rounded.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import hi_dot

LAYER_KEYS = ("ln1_w", "ln1_b", "qkv_w", "qkv_b", "proj_w", "proj_b",
              "ln2_w", "ln2_b", "fc1_w", "fc1_b", "fc2_w", "fc2_b")
TOP_KEYS = ("wte", "wpe", "lnf_w", "lnf_b")


def layer_norm(x, w, b, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def block(x, p, n_heads, dot):
    """One pre-LN decoder block on x [B,S,H]; p holds ONE layer's arrays."""
    b, s, h = x.shape
    hd = h // n_heads
    y = layer_norm(x, p["ln1_w"], p["ln1_b"])
    qkv = dot("bsh,hk->bsk", y, p["qkv_w"]) + p["qkv_b"]
    q, k, v = (qkv[..., i * h:(i + 1) * h].reshape(b, s, n_heads, hd)
               for i in range(3))
    sc = dot("bqnd,bknd->bnqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(causal, sc, -jnp.inf)
    ctx = dot("bnqk,bknd->bqnd", jax.nn.softmax(sc, axis=-1), v)
    x = x + dot("bsh,hk->bsk", ctx.reshape(b, s, h), p["proj_w"]) \
        + p["proj_b"]
    y = layer_norm(x, p["ln2_w"], p["ln2_b"])
    y = jax.nn.gelu(dot("bsh,hk->bsk", y, p["fc1_w"]) + p["fc1_b"],
                    approximate=True)
    return x + dot("bsk,kh->bsh", y, p["fc2_w"]) + p["fc2_b"]


def hidden_states(params, ids, model, dot=hi_dot, remat=False):
    """ids [B,S] int32 -> final-LayerNorm hidden states [B,S,H] float32."""
    p = {k: v.astype(jnp.float32) for k, v in params.items()
         if k in TOP_KEYS}
    s, n_heads = ids.shape[1], model["num_heads"]
    x = p["wte"][ids] + p["wpe"][:s][None]

    def body(x, layer):
        layer = {k: v.astype(jnp.float32) for k, v in layer.items()}
        return block(x, layer, n_heads, dot), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, {k: params[k] for k in LAYER_KEYS})
    return layer_norm(x, p["lnf_w"], p["lnf_b"])


def logits(params, ids, model, dot=hi_dot):
    """Full forward: [B,S] -> [B,S,V] through the tied head."""
    hid = hidden_states(params, ids, model, dot)
    return dot("bsh,vh->bsv", hid, params["wte"].astype(jnp.float32))


def nll_sum(params, ids, model, dot=hi_dot):
    """Sum over the S-1 shifted positions of every row of -log p(next)."""
    hid = hidden_states(params, ids, model, dot, remat=True)[:, :-1]
    lg = dot("bsh,vh->bsv", hid, params["wte"].astype(jnp.float32))
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.sum(lse - gold)


def loss_and_grads(params, ids, model, dot=hi_dot, rows_per_block=1):
    """Mean next-token loss over ids [B,S] and its gradients, computed in
    blocks of rows so the [rows,heads,S,S] scores fit the device."""
    b, s = ids.shape
    n_tok = b * (s - 1)
    blocks = ids.reshape(b // rows_per_block, rows_per_block, s)

    def body(carry, rows):
        tot, acc = carry
        v, g = jax.value_and_grad(nll_sum)(params, rows, model, dot)
        return (tot + v, jax.tree_util.tree_map(jnp.add, acc, g)), None

    zero = jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, jnp.float32), params)
    (tot, acc), _ = jax.lax.scan(body, (jnp.float32(0.0), zero), blocks)
    return tot / n_tok, jax.tree_util.tree_map(lambda g: g / n_tok, acc)
