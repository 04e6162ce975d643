"""Plain reference of the `falcon_h1` family (Falcon-H1: a Mamba-2 mixer and
a grouped-query attention mixer side by side in every block, muP
multipliers): float32 `jax.numpy`, products through `dot` (`common.hi_dot`;
the control's `common.fp8_dot`), no kernels, no cache, no chunking. Imports
nothing of the program.

Follows HF `modeling_falcon_h1`. `norm(x) = w * x / rms(x)`, eps from the
configuration. `h0 = embed[ids] * embedding_multiplier`. Block: `u =
norm_in(x)`; `x <- x + ssm_out_multiplier * mamba(u) +
attention_out_multiplier * attn(attention_in_multiplier * u)` (both mixers
read the same normed input); `x <- x + mlp(norm_ff(x))`. `logits =
lm_head_multiplier * head(norm_f(x))`, untied.

* Mamba-2 mixer: `p = in_proj(ssm_in_multiplier * u) * mup`, split z | x |
  B | C | dt with `mup` the five `ssm_multipliers` over those segments;
  causal depthwise convolution with bias over x | B | C, then SiLU; `dt =
  softplus(dt + dt_bias)`, `A = -exp(A_log)`; per position and head `S <-
  exp(dt A) S + dt x (x) B_g`, `y = S C_g + D x` (a `lax.scan` over
  positions; the state is float32 as the configuration states it, so the
  recurrence's own sums do not pass through `dot`); `y * silu(z)`, then
  RMS-normed within each group's channels, times a weight
  (`mamba_norm_before_gate` false); `out_proj`.
* Attention: `k * key_multiplier`; rotate-half rotary on all of the head's
  dims; causal softmax, `heads / kv_heads` query heads a KV head, one KV
  head at a time; `o_proj`.
* Feed-forward: `down(up(v) * silu(mlp_multipliers[0] * gate(v))) *
  mlp_multipliers[1]`.

The share: `num_hidden_layers` layers and `vocab_size` rows of embedding
and head are what the configuration gives this chip; the reference is given
the same and treats them as the whole model.

Parameters are a flat dict with ONE ARRAY A LEAF (no stacks: `LAYER_KEYS`
is empty), layer i's under `l<i>.<name>`:
  embed [V,H]  head [H,V]  norm_f [H]
  l<i>.ln1 ln2 [H]
  l<i>.ssm_in [H, 2W+2GN+nh]  ssm_conv [W+2GN, 4]  ssm_conv_b [W+2GN]
  l<i>.ssm_dt ssm_alog ssm_d [nh]  ssm_norm [W]  ssm_out [W,H]
  l<i>.att_q [H, nq*hd]  att_k att_v [H, nkv*hd]  att_o [nq*hd, H]
  l<i>.mlp_gate mlp_up [H,I]  mlp_down [I,H]
Layers are upcast one at a time and the head's product is taken in blocks
of vocabulary rows, so one float32 layer and one block of the head beside
the stored arrays are all that is live.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import hi_dot

BLOCK_KEYS = ("ln1", "ln2", "ssm_in", "ssm_conv", "ssm_conv_b", "ssm_dt",
              "ssm_alog", "ssm_d", "ssm_norm", "ssm_out", "att_q", "att_k",
              "att_v", "att_o", "mlp_gate", "mlp_up", "mlp_down")
LAYER_KEYS = ()             # no stacked arrays: every leaf has its own key
TOP_KEYS = ("embed", "head", "norm_f")
HEAD_BLOCKS = 4             # the head's product, in this many column blocks


def key(i: int, name: str) -> str:
    return f"l{i}.{name}"


def norm(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                                 + eps)


def mamba(u, p, model, dot):
    """The Mamba-2 mixer on normed u [B,S,H]; p holds ONE layer's arrays."""
    b, s, _ = u.shape
    nh, hd = model["mamba_n_heads"], model["mamba_d_head"]
    g, n, width = model["mamba_n_groups"], model["mamba_d_state"], \
        model["mamba_d_conv"]
    w, gn = nh * hd, g * n
    mup = jnp.concatenate([jnp.full((k,), m, jnp.float32) for k, m in zip(
        (w, w, gn, gn, nh), model["ssm_multipliers"])])
    proj = dot("bsh,hk->bsk", u * model["ssm_in_multiplier"],
               p["ssm_in"]) * mup
    z, mixed, dt = proj[..., :w], proj[..., w:2 * w + 2 * gn], \
        proj[..., 2 * w + 2 * gn:]
    # causal depthwise convolution: position t sees t-3 .. t
    padded = jnp.pad(mixed, ((0, 0), (width - 1, 0), (0, 0)))
    conv = sum(padded[:, j:j + s] * p["ssm_conv"][:, j]
               for j in range(width))
    if model.get("mamba_conv_bias", True):
        conv = conv + p["ssm_conv_b"]
    conv = jax.nn.silu(conv)
    x = conv[..., :w].reshape(b, s, nh, hd)
    bm = jnp.repeat(conv[..., w:w + gn].reshape(b, s, g, n), nh // g, axis=2)
    cm = jnp.repeat(conv[..., w + gn:].reshape(b, s, g, n), nh // g, axis=2)
    dt = jax.nn.softplus(dt + p["ssm_dt"])                    # [B,S,nh]
    a = -jnp.exp(p["ssm_alog"])

    def step(state, at):                    # state [B,nh,hd,n]
        xt, bt, ct, dtt = at
        state = state * jnp.exp(dtt * a)[..., None, None] \
            + (dtt[..., None] * xt)[..., None] * bt[..., None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, ct,
                                 precision="highest") \
            + p["ssm_d"][:, None] * xt

    seq = tuple(jnp.moveaxis(t, 1, 0) for t in (x, bm, cm, dt))
    _, y = jax.lax.scan(step, jnp.zeros((b, nh, hd, n), jnp.float32), seq)
    y = jnp.moveaxis(y, 0, 1).reshape(b, s, w)
    gate = jax.nn.silu(z)
    if model.get("mamba_rms_norm", True):
        before = model.get("mamba_norm_before_gate", False)
        if not before:
            y = y * gate
        yg = y.reshape(b, s, g, w // g)
        yg = yg * jax.lax.rsqrt(jnp.mean(jnp.square(yg), -1, keepdims=True)
                                + model["rms_norm_eps"])
        y = yg.reshape(b, s, w) * p["ssm_norm"]
        if before:
            y = y * gate
    else:
        y = y * gate
    return dot("bsk,kh->bsh", y, p["ssm_out"])


def rotary(t, theta):
    """Rotate-half rotary on all dims; t [B,S,n,hd], positions 0 .. S-1."""
    hd = t.shape[-1]
    half = hd // 2
    inv = float(theta) ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / hd)
    ang = jnp.arange(t.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = t[..., :half], t[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def attention(u, p, model, dot):
    """Grouped-query softmax attention on normed u [B,S,H]."""
    b, s, _ = u.shape
    nh, nkv, hd = model["num_attention_heads"], \
        model["num_key_value_heads"], model["head_dim"]
    u = u * model["attention_in_multiplier"]
    q = dot("bsh,hk->bsk", u, p["att_q"]).reshape(b, s, nkv, nh // nkv, hd)
    k = dot("bsh,hk->bsk", u, p["att_k"]).reshape(b, s, nkv, hd) \
        * model["key_multiplier"]
    v = dot("bsh,hk->bsk", u, p["att_v"]).reshape(b, s, nkv, hd)
    q = rotary(q.reshape(b, s, nh, hd), model["rope_theta"]).reshape(q.shape)
    k = rotary(k, model["rope_theta"])
    causal = jnp.tril(jnp.ones((s, s), bool))
    ctx = []
    for j in range(nkv):                    # one KV head's scores at a time
        sc = dot("bqgd,bmd->bgqm", q[:, :, j], k[:, :, j]) / math.sqrt(hd)
        sc = jnp.where(causal, sc, -jnp.inf)
        ctx.append(dot("bgqm,bmd->bqgd", jax.nn.softmax(sc, axis=-1),
                       v[:, :, j]))
    ctx = jnp.stack(ctx, axis=2).reshape(b, s, nh * hd)
    return dot("bsk,kh->bsh", ctx, p["att_o"])


def mlp(v, p, model, dot):
    gate_mult, down_mult = model["mlp_multipliers"]
    hid = dot("bsh,hi->bsi", v, p["mlp_up"]) * jax.nn.silu(
        gate_mult * dot("bsh,hi->bsi", v, p["mlp_gate"]))
    return dot("bsi,ih->bsh", hid, p["mlp_down"]) * down_mult


def block(x, p, model, dot):
    eps = model["rms_norm_eps"]
    u = norm(x, p["ln1"], eps)
    x = x + model["ssm_out_multiplier"] * mamba(u, p, model, dot) \
        + model["attention_out_multiplier"] * attention(u, p, model, dot)
    return x + mlp(norm(x, p["ln2"], eps), p, model, dot)


def layer_arrays(params, i):
    """Layer i's arrays as float32 copies."""
    return {k: params[key(i, k)].astype(jnp.float32) for k in BLOCK_KEYS}


def hidden_states(params, ids, model, dot=hi_dot, remat=False):
    """ids [B,S] int32 -> final-norm hidden states [B,S,H] float32."""
    x = params["embed"].astype(jnp.float32)[ids] \
        * model["embedding_multiplier"]

    def one(x, p):
        return block(x, p, model, dot)

    for i in range(model["num_hidden_layers"]):
        x = (jax.checkpoint(one) if remat else one)(
            x, layer_arrays(params, i))
    return norm(x, params["norm_f"].astype(jnp.float32),
                model["rms_norm_eps"])


def _head(hid, head, model, dot):
    """`lm_head_multiplier * hid . head`, the head upcast and multiplied in
    blocks of vocabulary rows."""
    v = head.shape[1]
    step = -(-v // HEAD_BLOCKS)
    return model["lm_head_multiplier"] * jnp.concatenate([
        dot("bsh,hv->bsv", hid, head[:, at:at + step].astype(jnp.float32))
        for at in range(0, v, step)], axis=-1)


def logits(params, ids, model, dot=hi_dot):
    """Full forward: [B,S] -> [B,S,V] through the untied head."""
    return _head(hidden_states(params, ids, model, dot), params["head"],
                 model, dot)


def nll_sum(params, ids, model, dot=hi_dot):
    """Sum over the S-1 shifted positions of every row of -log p(next)."""
    hid = hidden_states(params, ids, model, dot, remat=True)[:, :-1]
    lg = _head(hid, params["head"], model, dot)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.sum(lse - gold)


def loss_and_grads(params, ids, model, dot=hi_dot):
    """Mean next-token loss over ids [B,S] and its gradients (`jax.grad` of
    the same forward)."""
    n_tok = ids.shape[0] * (ids.shape[1] - 1)
    tot, g = jax.value_and_grad(nll_sum)(params, ids, model, dot)
    return tot / n_tok, jax.tree_util.tree_map(lambda a: a / n_tok, g)
