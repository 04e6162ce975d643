"""Plain reference of the `longcat_flash` family (the language model of
LongCat-Flash-Omni: two latent-attention sublayers, two dense feed-forwards
and one shortcut-connected expert layer with zero-compute experts in every
block): float32 `jax.numpy`, products through `dot` (`common.hi_dot`; the
control's `common.fp8_dot`), no kernels, no cache, no absorbed form.
Imports nothing of the program.

Block (`N*` are RMSNorms `w * x / rms(x)`, eps from the configuration; no
shared expert):

    a1 = x  + MLA_1(N1(x))
    u  = N2(a1)
    m  = MoE(u)                     # the shortcut: computed here, added last
    h1 = a1 + FFN_1(u)              # SwiGLU
    a2 = h1 + MLA_2(N3(h1))
    y  = a2 + FFN_2(N4(a2)) + m

* `MLA(z)`, the EXPANDED form (every head's own keys and values, made from
  the normed latent; the program's decode step runs the absorbed form over
  cached latent rows, which this never does): `cq = Nq(z Wqa) * sqrt(hidden
  / q_lora_rank)`; `q = cq Wqb` -> heads of `[q_nope | q_rope]`; `[ckv |
  k_rope] = z Wkva`; `c = Nkv(ckv) * sqrt(hidden / kv_lora_rank)`; `[k_nope
  | v] = c Wkvb` per head; rotate-half rotary on `q_rope` and on the one
  `k_rope` all heads share; causal softmax of `(q_nope . k_nope + q_rope .
  k_rope) / sqrt(nope + rope)`; context `Wo`. Heads are walked in blocks of
  `HEAD_BLOCK`, so a 4096-position request's scores are 0.5 GB at a time.
* `MoE(u)`: float32 logits over `router_experts + zero_expert_num` outputs,
  `s = softmax`; the `moe_topk` are the top of `s + b` (`b` steers the
  choice only), weights `routed_scaling_factor * s` of the chosen, no
  renormalisation; a real expert adds `w * SwiGLU_e(u)`, a zero expert `w *
  u`.

The share: the configuration's `num_experts` real experts, ids
`expert_offset ..`, of the `router_experts` the router scores, are held
here; an assignment to an absent expert is left out (its owner adds it on
another chip). The zero experts hold no weights and their term is added
whole. `num_hidden_layers` layers and `vocab_size` rows of embedding and head are
what the configuration gives this chip, treated as the whole model.

Parameters are a flat dict with ONE ARRAY A LEAF (no stacks over layers:
`LAYER_KEYS` is empty), layer i's under `l<i>.<name>`; `<a>` is 1 or 2:
  embed [V,H]  head [H,V]  norm_f [H]
  l<i>.n1 n2 n3 n4 [H]
  l<i>.a<a>_qa [H,Rq]  a<a>_qn [Rq]  a<a>_qb [Rq, nh*(nope+rope)]
  l<i>.a<a>_kva [H, R+rope]  a<a>_kvn [R]  a<a>_kvb [R, nh*(nope+v)]
  l<i>.a<a>_o [nh*v, H]
  l<i>.f<a>_gate f<a>_up [H,I]  f<a>_down [I,H]
  l<i>.router [H, E_r+Z]  router_b [E_r+Z]
  l<i>.exp_gate exp_up [E,H,Ie]  exp_down [E,Ie,H]
Every array is upcast where it is used, the held experts ONE AT A TIME out
of their stack, and the head's product is taken in blocks of vocabulary
rows, so a few float32 matrices beside the stored arrays are all that is
live.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import hi_dot

_MLA = ("qa", "qn", "qb", "kva", "kvn", "kvb", "o")
_FFN = ("gate", "up", "down")
BLOCK_KEYS = ("n1", "n2", "n3", "n4") \
    + tuple(f"a{a}_{k}" for a in (1, 2) for k in _MLA) \
    + tuple(f"f{a}_{k}" for a in (1, 2) for k in _FFN) \
    + ("router", "router_b", "exp_gate", "exp_up", "exp_down")
LAYER_KEYS = ()             # no stacked arrays: every leaf has its own key
TOP_KEYS = ("embed", "head", "norm_f")
HEAD_BLOCKS = 4             # the head's product, in this many column blocks
HEAD_BLOCK = 8              # attention heads whose scores are live at once


def key(i: int, name: str) -> str:
    return f"l{i}.{name}"


def norm(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                                 + eps)


def rotary(t, theta):
    """Rotate-half rotary on all of t's last dim; t [B,S,n,rot], positions
    0 .. S-1."""
    rot = t.shape[-1]
    half = rot // 2
    inv = float(theta) ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rot)
    ang = jnp.arange(t.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = t[..., :half], t[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def mla(z, w, a, model, dot):
    """Latent attention, expanded form, on normed z [B,S,H]; `w(name)`
    gives one of the layer's arrays in float32, `a` the sublayer (1, 2)."""
    b, s, h = z.shape
    nh, rank, rq = model["num_attention_heads"], model["kv_lora_rank"], \
        model["q_lora_rank"]
    nope, rot, vd = model["qk_nope_head_dim"], model["qk_rope_head_dim"], \
        model["v_head_dim"]
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    q_scale = math.sqrt(h / rq) if model.get("mla_scale_q_lora", True) \
        else 1.0
    kv_scale = math.sqrt(h / rank) if model.get("mla_scale_kv_lora", True) \
        else 1.0
    cq = norm(dot("bsh,hr->bsr", z, w(f"a{a}_qa")), w(f"a{a}_qn"), eps) \
        * q_scale
    q = dot("bsr,rk->bsk", cq, w(f"a{a}_qb")).reshape(b, s, nh, nope + rot)
    kv = dot("bsh,hr->bsr", z, w(f"a{a}_kva"))
    c = norm(kv[..., :rank], w(f"a{a}_kvn"), eps) * kv_scale
    k_rope = rotary(kv[..., None, rank:], theta)[:, :, 0]     # [B,S,rot]
    q_nope, q_rope = q[..., :nope], rotary(q[..., nope:], theta)
    causal = jnp.tril(jnp.ones((s, s), bool))
    hb = min(HEAD_BLOCK, nh)
    while nh % hb:
        hb -= 1
    wkvb = w(f"a{a}_kvb").reshape(rank, nh // hb, hb, nope + vd)

    def heads(at):                  # one block of heads at a time
        qn, qr, wb = at             # [B,S,hb,nope] [B,S,hb,rot] [R,hb,n+v]
        kvh = dot("bmr,rhd->bmhd", c, wb)
        sc = (dot("bqhd,bmhd->bhqm", qn, kvh[..., :nope])
              + dot("bqhd,bmd->bhqm", qr, k_rope)) / math.sqrt(nope + rot)
        sc = jnp.where(causal, sc, -jnp.inf)
        return dot("bhqm,bmhd->bqhd", jax.nn.softmax(sc, axis=-1),
                   kvh[..., nope:])

    def blocks(t):                  # [B,S,nh,d] -> [nh/hb, B,S,hb,d]
        return jnp.moveaxis(t.reshape(b, s, nh // hb, hb, -1), 2, 0)

    ctx = jax.lax.map(heads, (blocks(q_nope), blocks(q_rope),
                              jnp.moveaxis(wkvb, 1, 0)))
    ctx = jnp.moveaxis(ctx, 0, 2).reshape(b, s, nh * vd)
    return dot("bsk,kh->bsh", ctx, w(f"a{a}_o"))


def ffn(x, w, a, dot):
    hid = jax.nn.silu(dot("bsh,hi->bsi", x, w(f"f{a}_gate"))) \
        * dot("bsh,hi->bsi", x, w(f"f{a}_up"))
    return dot("bsi,ih->bsh", hid, w(f"f{a}_down"))


def route(u, w, model, dot):
    """(ids [B,S,k] over the router's real + zero outputs, weights)."""
    probs = jax.nn.softmax(dot("bsh,he->bse", u, w("router")), axis=-1)
    _, ids = jax.lax.top_k(probs + w("router_b"), model["moe_topk"])
    weights = jnp.take_along_axis(probs, ids, axis=-1) \
        * model["routed_scaling_factor"]
    return ids, weights


def moe(u, w, stored, model, dot):
    """The held real experts' part (a dense loop with a mask, one expert
    read out of its stack and upcast at a time) + the zero experts' term.
    `stored(name)` gives a layer's array as stored."""
    first = model.get("expert_offset", 0)
    n_real = model.get("router_experts") or model["num_experts"]
    ids, weights = route(u, w, model, dot)

    def expert(name, e):
        a = stored(name)
        return jax.lax.dynamic_index_in_dim(a, e, 0, keepdims=False) \
            .astype(jnp.float32)

    def one(acc, e):
        we = jnp.sum(jnp.where(ids == first + e, weights, 0.0), axis=-1)
        hid = jax.nn.silu(dot("bsh,hi->bsi", u, expert("exp_gate", e))) \
            * dot("bsh,hi->bsi", u, expert("exp_up", e))
        return acc + dot("bsi,ih->bsh", hid, expert("exp_down", e)) \
            * we[..., None], None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(u),
                             jnp.arange(model["num_experts"]))
    w_zero = jnp.sum(jnp.where(ids >= n_real, weights, 0.0), axis=-1)
    return routed + u * w_zero[..., None]


def block(x, layer, model, dot):
    """`layer`: {name: the layer's array as stored}."""
    eps = model["rms_norm_eps"]

    def w(name):
        return layer[name].astype(jnp.float32)

    a1 = x + mla(norm(x, w("n1"), eps), w, 1, model, dot)
    u = norm(a1, w("n2"), eps)
    m = moe(u, w, layer.__getitem__, model, dot)
    h1 = a1 + ffn(u, w, 1, dot)
    a2 = h1 + mla(norm(h1, w("n3"), eps), w, 2, model, dot)
    return a2 + ffn(norm(a2, w("n4"), eps), w, 2, dot) + m


def hidden_states(params, ids, model, dot=hi_dot, remat=False):
    """ids [B,S] int32 -> final-norm hidden states [B,S,H] float32."""
    x = params["embed"].astype(jnp.float32)[ids]

    def one(x, layer):
        return block(x, layer, model, dot)

    for i in range(model["num_hidden_layers"]):
        x = (jax.checkpoint(one) if remat else one)(
            x, {k: params[key(i, k)] for k in BLOCK_KEYS})
    return norm(x, params["norm_f"].astype(jnp.float32),
                model["rms_norm_eps"])


def _head(hid, head, dot):
    """`hid . head`, the head upcast and multiplied in blocks of vocabulary
    rows."""
    v = head.shape[1]
    step = -(-v // HEAD_BLOCKS)
    return jnp.concatenate([
        dot("bsh,hv->bsv", hid, head[:, at:at + step].astype(jnp.float32))
        for at in range(0, v, step)], axis=-1)


def logits(params, ids, model, dot=hi_dot):
    """Full forward: [B,S] -> [B,S,V] through the untied head."""
    return _head(hidden_states(params, ids, model, dot), params["head"], dot)


def nll_sum(params, ids, model, dot=hi_dot):
    """Sum over the S-1 shifted positions of every row of -log p(next)."""
    hid = hidden_states(params, ids, model, dot, remat=True)[:, :-1]
    lg = _head(hid, params["head"], dot)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.sum(lse - gold)


def loss_and_grads(params, ids, model, dot=hi_dot):
    """Mean next-token loss over ids [B,S] and its gradients (`jax.grad` of
    the same forward; the router's choice is a constant of the gradient)."""
    n_tok = ids.shape[0] * (ids.shape[1] - 1)
    tot, g = jax.value_and_grad(nll_sum)(params, ids, model, dot)
    return tot / n_tok, jax.tree_util.tree_map(lambda a: a / n_tok, g)
