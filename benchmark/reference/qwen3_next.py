"""Plain reference of the `qwen3_next` family (Qwen3-Next: Gated DeltaNet +
gated attention + routed experts): float32 `jax.numpy`, products through
`dot` (`common.hi_dot`; the control's `common.fp8_dot`), no kernels, no
cache, no batching tricks. Imports nothing of the program.

Follows HF `modeling_qwen3_next` (the equations are in the docstring of
`paddle_tpu/models/qwen3_next.py` and in ISSUE 28). `norm(x) = x / rms(x) *
(1 + w)`, eps from the configuration; every layer `x <- x + mixer(norm(x));
x <- x + moe(norm(x))`; final norm; untied head.

Departures from the checkpoint, the same as the program's and listed in the
configuration under `assumed`: the fused projections' columns are flat
(q | k | v | z and b | a); no multi-token-prediction layer. The recurrent
state is float32 as the configuration states it, so the delta rule's own
sums (a `lax.scan` over positions) are float32 sums and do not pass through
`dot`; every product with a weight, the attention scores and the context do.

The share: the configuration's `num_experts` experts, ids `expert_offset
..`, of the `router_experts` the router scores, are held here. The router
keeps its width and top-k; an assignment to an absent expert is left out
(its owner adds it on another chip), and the partial sum goes on. The MoE is
a dense loop over the held experts with a mask, one expert upcast at a time,
and layers are walked one at a time, so one float32 layer beside the stored
arrays is all that is live.

Parameters are a flat dict of STACKED arrays; the leading axis counts the
layers OF THAT KIND in order (all L; the linear ones; the full ones):
  embed [V,H]  head [H,V]  norm_f [H]
  ln1 ln2 [L,H]  router [L,H,R]  exp_gate exp_up [L,E,H,I]  exp_down [L,E,I,H]
  sh_gate sh_up [L,H,Is]  sh_down [L,Is,H]  sh_mix [L,H,1]
  lin_qkvz [Ll,H,2K+2W]  lin_ba [Ll,H,2nv]  lin_conv [Ll,2K+W,4]
  lin_dt lin_alog [Ll,nv]  lin_norm [Ll,dv]  lin_out [Ll,W,H]
  att_q [Lf,H,2*nh*hd]  att_k att_v [Lf,H,nkv*hd]  att_o [Lf,nh*hd,H]
  att_qn att_kn [Lf,hd]
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import hi_dot

ALL_KEYS = ("ln1", "ln2", "router", "exp_gate", "exp_up", "exp_down",
            "sh_gate", "sh_up", "sh_down", "sh_mix")
LINEAR_KEYS = ("lin_qkvz", "lin_ba", "lin_conv", "lin_dt", "lin_alog",
               "lin_norm", "lin_out")
FULL_KEYS = ("att_q", "att_k", "att_v", "att_o", "att_qn", "att_kn")
LAYER_KEYS = ALL_KEYS + LINEAR_KEYS + FULL_KEYS
TOP_KEYS = ("embed", "head", "norm_f")


def is_linear(model: dict, i: int) -> bool:
    return (i + 1) % model["full_attention_interval"] != 0


def norm(x, w, eps, centred=True):
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return y * (1.0 + w if centred else w)


def f32(p: dict) -> dict:
    return {k: v.astype(jnp.float32) for k, v in p.items()}


def delta_net(x, p, model, dot):
    """Gated DeltaNet on x [B,S,H]; p holds ONE linear layer's arrays."""
    b, s, _ = x.shape
    nk, nv = model["linear_num_key_heads"], model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    width = model["linear_conv_kernel_dim"]
    kd, vd = nk * dk, nv * dv
    qkvz = dot("bsh,hk->bsk", x, p["lin_qkvz"])
    mixed, z = qkvz[..., :2 * kd + vd], qkvz[..., 2 * kd + vd:]
    ba = dot("bsh,hk->bsk", x, p["lin_ba"])
    beta = jax.nn.sigmoid(ba[..., :nv])
    g = -jnp.exp(p["lin_alog"]) * jax.nn.softplus(ba[..., nv:]
                                                  + p["lin_dt"])
    # causal depthwise convolution: position t sees t-3 .. t
    padded = jnp.pad(mixed, ((0, 0), (width - 1, 0), (0, 0)))
    conv = sum(padded[:, j:j + s] * p["lin_conv"][:, j]
               for j in range(width))
    conv = jax.nn.silu(conv)
    q = conv[..., :kd].reshape(b, s, nk, dk)
    k = conv[..., kd:2 * kd].reshape(b, s, nk, dk)
    v = conv[..., 2 * kd:].reshape(b, s, nv, dv)

    def l2(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    q = jnp.repeat(l2(q) / math.sqrt(dk), nv // nk, axis=2)
    k = jnp.repeat(l2(k), nv // nk, axis=2)

    def step(state, at):                    # state [B,nv,dk,dv]
        qt, kt, vt, gt, bt = at
        state = state * jnp.exp(gt)[..., None, None]
        seen = jnp.einsum("bhkv,bhk->bhv", state, kt, precision="highest")
        state = state + jnp.einsum("bhk,bhv->bhkv", kt,
                                   (vt - seen) * bt[..., None],
                                   precision="highest")
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt,
                                 precision="highest")

    seq = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((b, nv, dk, dv), jnp.float32), seq)
    o = jnp.moveaxis(o, 0, 1)                                 # [B,S,nv,dv]
    o = norm(o, p["lin_norm"], model["rms_norm_eps"], centred=False)
    o = o * jax.nn.silu(z.reshape(b, s, nv, dv))
    return dot("bsk,kh->bsh", o.reshape(b, s, vd), p["lin_out"])


def rotary(t, model):
    """Rotate-half rotary on the first `partial_rotary_factor` of the head's
    dims; t [B,S,n,hd], positions 0 .. S-1."""
    hd = t.shape[-1]
    rot = int(hd * model["partial_rotary_factor"])
    half = rot // 2
    inv = model["rope_theta"] ** (-jnp.arange(half, dtype=jnp.float32) * 2.0
                                  / rot)
    ang = jnp.arange(t.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = t[..., :half], t[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            t[..., rot:]], axis=-1)


def attention(x, p, model, dot):
    """Gated softmax attention on x [B,S,H]; p: ONE full layer's arrays."""
    b, s, _ = x.shape
    nh, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    hd, eps = model["head_dim"], model["rms_norm_eps"]
    qg = dot("bsh,hk->bsk", x, p["att_q"]).reshape(b, s, nh, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:].reshape(b, s, nh * hd)
    k = dot("bsh,hk->bsk", x, p["att_k"]).reshape(b, s, nkv, hd)
    v = dot("bsh,hk->bsk", x, p["att_v"]).reshape(b, s, nkv, hd)
    q = rotary(norm(q, p["att_qn"], eps), model)
    k = rotary(norm(k, p["att_kn"], eps), model)
    q = q.reshape(b, s, nkv, nh // nkv, hd)
    sc = dot("bqkgd,bmkd->bkgqm", q, k) / math.sqrt(hd)
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    ctx = dot("bkgqm,bmkd->bqkgd", jax.nn.softmax(sc, axis=-1), v)
    ctx = ctx.reshape(b, s, nh * hd) * jax.nn.sigmoid(gate)
    return dot("bsk,kh->bsh", ctx, p["att_o"])


def swiglu(x, gate, up, down, dot):
    hid = jax.nn.silu(dot("bsh,hi->bsi", x, gate)) \
        * dot("bsh,hi->bsi", x, up)
    return dot("bsi,ih->bsh", hid, down)


def moe(x, p, model, dot):
    """Router over all `router_experts`, top-k, the HELD experts' part (a
    dense loop with a mask) + the shared expert. p: ONE layer's arrays;
    the experts' come as stored, [E, ...] or (whole stack [L, E, ...],
    layer), and are read and upcast ONE EXPERT AT A TIME inside the loop,
    so no layer's experts are ever copied out of the stack."""
    first = model.get("expert_offset", 0)

    def expert(key, e):
        a = p[key]
        if isinstance(a, tuple):
            stack, layer = a
            a, e = stack, (layer, e)
        else:
            e = (e,)
        lead = len(e)
        at = jax.lax.dynamic_slice(
            a, e + (0,) * (a.ndim - lead), (1,) * lead + a.shape[lead:])
        return at.reshape(a.shape[lead:]).astype(jnp.float32)
    probs = jax.nn.softmax(dot("bsh,he->bse", x, p["router"]), axis=-1)
    weights, ids = jax.lax.top_k(probs, model["num_experts_per_tok"])
    if model["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, -1, keepdims=True)

    def one(acc, e):
        w = jnp.sum(jnp.where(ids == first + e, weights, 0.0), axis=-1)
        y = swiglu(x, expert("exp_gate", e), expert("exp_up", e),
                   expert("exp_down", e), dot)
        return acc + y * w[..., None], None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(x),
                             jnp.arange(model["num_experts"]))
    shared = swiglu(x, p["sh_gate"], p["sh_up"], p["sh_down"], dot)
    mix = jax.nn.sigmoid(dot("bsh,ho->bso", x, p["sh_mix"]))
    return routed + shared * mix


def layer_arrays(params, model, i):
    """Layer i's arrays as float32 copies; its experts as (stack, i)."""
    lin = is_linear(model, i)
    j = sum(is_linear(model, n) == lin for n in range(i))
    big = ("exp_gate", "exp_up", "exp_down")
    p = {k: params[k][i].astype(jnp.float32) for k in ALL_KEYS
         if k not in big}
    p.update({k: (params[k], i) for k in big})
    p.update({k: params[k][j].astype(jnp.float32)
              for k in (LINEAR_KEYS if lin else FULL_KEYS)})
    return p, lin


def hidden_states(params, ids, model, dot=hi_dot, remat=False):
    """ids [B,S] int32 -> final-norm hidden states [B,S,H] float32."""
    eps = model["rms_norm_eps"]
    x = params["embed"].astype(jnp.float32)[ids]

    def block(x, p, lin):
        mixer = delta_net if lin else attention
        x = x + mixer(norm(x, p["ln1"], eps), p, model, dot)
        return x + moe(norm(x, p["ln2"], eps), p, model, dot)

    for i in range(model["num_hidden_layers"]):
        p, lin = layer_arrays(params, model, i)
        fn = jax.checkpoint(block, static_argnums=(2,)) if remat else block
        x = fn(x, p, lin)
    return norm(x, params["norm_f"].astype(jnp.float32), eps)


def logits(params, ids, model, dot=hi_dot):
    """Full forward: [B,S] -> [B,S,V] through the untied head."""
    hid = hidden_states(params, ids, model, dot)
    return dot("bsh,hv->bsv", hid, params["head"].astype(jnp.float32))


def nll_sum(params, ids, model, dot=hi_dot):
    """Sum over the S-1 shifted positions of every row of -log p(next)."""
    hid = hidden_states(params, ids, model, dot, remat=True)[:, :-1]
    lg = dot("bsh,hv->bsv", hid, params["head"].astype(jnp.float32))
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.sum(lse - gold)


def loss_and_grads(params, ids, model, dot=hi_dot):
    """Mean next-token loss over ids [B,S] and its gradients (`jax.grad` of
    the same forward; the router's choice is a constant of the gradient,
    as in the published model without its auxiliary loss)."""
    n_tok = ids.shape[0] * (ids.shape[1] - 1)
    tot, g = jax.value_and_grad(nll_sum)(params, ids, model, dot)
    return tot / n_tok, jax.tree_util.tree_map(lambda a: a / n_tok, g)
