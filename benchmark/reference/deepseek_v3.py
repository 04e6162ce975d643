"""Plain reference of the `deepseek_v3` family (DeepSeek-V3's decoder:
multi-head latent attention under YaRN in every layer, a dense SwiGLU in
the leading layers and a sigmoid-routed, group-limited expert layer with
one ungated shared expert after them): float32 `jax.numpy`, products
through `dot` (`common.hi_dot`; the control's `common.fp8_dot`), no
kernels, no cache, no absorbed form. Imports nothing of the program.

Block (`N*` are RMSNorms `w * x / rms(x)`, eps from the configuration):

    a = x + MLA(N1(x))
    y = a + F(N2(a))      # F = FFN (SwiGLU, `intermediate_size`) in layers
                          #   below `first_k_dense_replace`, MoE after them

* `MLA(z)`, the EXPANDED form (every head's own keys and values, made from
  the normed latent; the program's decode step runs the absorbed form over
  cached latent rows, which this never does): `cq = Nq(z Wqa)`; `q = cq
  Wqb` -> heads of `[q_nope | q_rope]`; `[ckv | k_rope] = z Wkva`; `c =
  Nkv(ckv)`; `[k_nope | v] = c Wkvb` per head; rotary on INTERLEAVED pairs
  `(2i, 2i+1)` of `q_rope` and of the one `k_rope` all heads share, as
  complex numbers times `exp(i p f_i)`, the frequencies `f_i` YaRN's
  (`yarn`); causal softmax of `(q_nope . k_nope + q_rope . k_rope) *
  (nope + rope) ** -0.5 * mscale ** 2`; context `Wo`. Heads are walked in
  blocks of `HEAD_BLOCK`.
* `MoE(u)`: `s = sigmoid(u Wr)` in float32 over the router's
  `router_experts` outputs; `s + b` (`b` the choice bias) steers the
  choice only: the outputs fall in `n_group` equal groups, a group scores
  the sum of its two largest `s + b`, the `topk_group` best groups are
  kept and every other output is -inf; the `num_experts_per_tok` best of
  what is left are chosen, weights `s / sum(s)` over the chosen times
  `routed_scaling_factor`; a chosen expert adds `w * SwiGLU_e(u)`, the
  shared expert `SwiGLU_s(u)` with no gate.

The share: the configuration's `num_experts` experts, ids `expert_offset
..`, of the `router_experts` the router scores, are held here; an
assignment to an absent expert is left out (its owner adds it on another
chip). The shared expert is added whole. `num_hidden_layers` layers and
`vocab_size` rows of embedding and head are what the configuration gives
this chip, treated as the whole model.

Parameters are a flat dict with ONE ARRAY A LEAF (`LAYER_KEYS` is empty),
layer i's under `l<i>.<name>`:
  embed [V,H]  head [H,V]  norm_f [H]
  l<i>.n1 n2 [H]
  l<i>.a_qa [H,Rq]  a_qn [Rq]  a_qb [Rq, nh*(nope+rope)]
  l<i>.a_kva [H, R+rope]  a_kvn [R]  a_kvb [R, nh*(nope+v)]  a_o [nh*v, H]
  a dense layer:  l<i>.f_gate f_up [H,I]  f_down [I,H]
  a routed layer: l<i>.router [H,E_r]  router_b [E_r]
                  l<i>.exp_gate exp_up [E,H,Ie]  exp_down [E,Ie,H]
                  l<i>.sh_gate sh_up [H,Is]  sh_down [Is,H]
Every array is upcast where it is used, the held experts ONE AT A TIME out
of their stack, and the head's product is taken in blocks of vocabulary
rows.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .common import hi_dot

_MLA = ("a_qa", "a_qn", "a_qb", "a_kva", "a_kvn", "a_kvb", "a_o")
DENSE_KEYS = ("n1", "n2") + _MLA + ("f_gate", "f_up", "f_down")
ROUTED_KEYS = ("n1", "n2") + _MLA + (
    "router", "router_b", "exp_gate", "exp_up", "exp_down",
    "sh_gate", "sh_up", "sh_down")
LAYER_KEYS = ()             # no stacked arrays: every leaf has its own key
TOP_KEYS = ("embed", "head", "norm_f")
HEAD_BLOCKS = 4             # the head's product, in this many column blocks
HEAD_BLOCK = 8              # attention heads whose scores are live at once


def key(i: int, name: str) -> str:
    return f"l{i}.{name}"


def dense(model: dict, i: int) -> bool:
    return i < model["first_k_dense_replace"]


def layer_keys(model: dict, i: int) -> tuple:
    return DENSE_KEYS if dense(model, i) else ROUTED_KEYS


def yarn(model: dict):
    """(frequencies [rope / 2] float64, factor on the softmax scale) of
    DeepSeek-V3's YaRN (`rope_scaling`): the plain frequencies `theta **
    (-2i / rope)` kept below the correction dim of `beta_fast` rotations
    over the original context, divided by `factor` above that of
    `beta_slow`, a linear ramp between; the scale's factor `(0.1 *
    mscale_all_dim * ln(factor) + 1) ** 2`. No `rope_scaling`: the plain
    frequencies and 1."""
    rot, theta = model["qk_rope_head_dim"], float(model["rope_theta"])
    freqs = 1.0 / theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    sc = model.get("rope_scaling")
    if not sc:
        return freqs, 1.0
    factor, orig = float(sc["factor"]), sc["original_max_position_embeddings"]

    def dim_of(turns):          # the dim whose wave turns `turns` times
        return rot * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(dim_of(sc["beta_fast"])), 0)
    hi = min(math.ceil(dim_of(sc["beta_slow"])), rot - 1)
    ramp = np.clip((np.arange(rot // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    freqs = freqs * (1 - ramp) + freqs / factor * ramp

    def m(x):
        return 0.1 * x * math.log(factor) + 1.0 if factor > 1 else 1.0
    assert m(sc.get("mscale", 1)) == m(sc.get("mscale_all_dim", 0)), \
        "a rotary amplitude other than 1 is not written out here"
    return freqs, m(sc.get("mscale_all_dim", 0)) ** 2


def norm(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                                 + eps)


def rotary(t, freqs):
    """Pairs `(2i, 2i+1)` of t's last dim, read as `re + i im`, times
    `exp(i p freqs[i])` at position p = 0 .. S-1; t [B,S,n,rope]."""
    ang = jnp.arange(t.shape[1], dtype=jnp.float32)[:, None] \
        * jnp.asarray(freqs, jnp.float32)                     # [S, rope/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    re, im = t[..., 0::2], t[..., 1::2]
    return jnp.stack([re * cos - im * sin, re * sin + im * cos],
                     -1).reshape(t.shape)


def mla(z, w, model, dot):
    """Latent attention, expanded form, on normed z [B,S,H]; `w(name)`
    gives one of the layer's arrays in float32."""
    b, s, h = z.shape
    nh, rank = model["num_attention_heads"], model["kv_lora_rank"]
    nope, rot, vd = model["qk_nope_head_dim"], model["qk_rope_head_dim"], \
        model["v_head_dim"]
    eps = model["rms_norm_eps"]
    freqs, mscale2 = yarn(model)
    cq = norm(dot("bsh,hr->bsr", z, w("a_qa")), w("a_qn"), eps)
    q = dot("bsr,rk->bsk", cq, w("a_qb")).reshape(b, s, nh, nope + rot)
    kv = dot("bsh,hr->bsr", z, w("a_kva"))
    c = norm(kv[..., :rank], w("a_kvn"), eps)
    k_rope = rotary(kv[..., None, rank:], freqs)[:, :, 0]     # [B,S,rot]
    q_nope, q_rope = q[..., :nope], rotary(q[..., nope:], freqs)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scale = mscale2 / math.sqrt(nope + rot)
    hb = min(HEAD_BLOCK, nh)
    while nh % hb:
        hb -= 1
    wkvb = w("a_kvb").reshape(rank, nh // hb, hb, nope + vd)

    def heads(at):                  # one block of heads at a time
        qn, qr, wb = at             # [B,S,hb,nope] [B,S,hb,rot] [R,hb,n+v]
        kvh = dot("bmr,rhd->bmhd", c, wb)
        sc = (dot("bqhd,bmhd->bhqm", qn, kvh[..., :nope])
              + dot("bqhd,bmd->bhqm", qr, k_rope)) * scale
        sc = jnp.where(causal, sc, -jnp.inf)
        return dot("bhqm,bmhd->bqhd", jax.nn.softmax(sc, axis=-1),
                   kvh[..., nope:])

    def blocks(t):                  # [B,S,nh,d] -> [nh/hb, B,S,hb,d]
        return jnp.moveaxis(t.reshape(b, s, nh // hb, hb, -1), 2, 0)

    ctx = jax.lax.map(heads, (blocks(q_nope), blocks(q_rope),
                              jnp.moveaxis(wkvb, 1, 0)))
    ctx = jnp.moveaxis(ctx, 0, 2).reshape(b, s, nh * vd)
    return dot("bsk,kh->bsh", ctx, w("a_o"))


def swiglu(x, gate, up, down, dot):
    hid = jax.nn.silu(dot("bsh,hi->bsi", x, gate)) * dot("bsh,hi->bsi", x, up)
    return dot("bsi,ih->bsh", hid, down)


def route(u, w, model, dot):
    """(ids [B,S,k] over the router's outputs, weights [B,S,k])."""
    scores = jax.nn.sigmoid(dot("bsh,he->bse", u, w("router")))
    choice = scores + w("router_b")
    g = model["n_group"]
    grouped = choice.reshape(choice.shape[:-1] + (g, -1))
    best2 = jnp.sort(grouped, axis=-1)[..., -2:]
    group_score = jnp.sum(best2, axis=-1)                     # [B,S,G]
    _, top_groups = jax.lax.top_k(group_score, model["topk_group"])
    kept = jnp.sum(jax.nn.one_hot(top_groups, g), axis=-2) > 0
    choice = jnp.where(kept[..., None], grouped, -jnp.inf).reshape(
        choice.shape)
    _, ids = jax.lax.top_k(choice, model["num_experts_per_tok"])
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    if model.get("norm_topk_prob", True):
        chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return ids, chosen * model["routed_scaling_factor"]


def moe(u, w, stored, model, dot):
    """The held experts' part (a dense loop with a mask, one expert read
    out of its stack and upcast at a time) + the shared expert. `stored
    (name)` gives a layer's array as stored."""
    first = model.get("expert_offset", 0)
    ids, weights = route(u, w, model, dot)

    def expert(name, e):
        a = stored(name)
        return jax.lax.dynamic_index_in_dim(a, e, 0, keepdims=False) \
            .astype(jnp.float32)

    def one(acc, e):
        we = jnp.sum(jnp.where(ids == first + e, weights, 0.0), axis=-1)
        return acc + swiglu(u, expert("exp_gate", e), expert("exp_up", e),
                            expert("exp_down", e), dot) * we[..., None], None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(u),
                             jnp.arange(model["num_experts"]))
    return routed + swiglu(u, w("sh_gate"), w("sh_up"), w("sh_down"), dot)


def block(x, layer, model, dot, is_dense):
    """`layer`: {name: the layer's array as stored}."""
    eps = model["rms_norm_eps"]

    def w(name):
        return layer[name].astype(jnp.float32)

    a = x + mla(norm(x, w("n1"), eps), w, model, dot)
    u = norm(a, w("n2"), eps)
    if is_dense:
        return a + swiglu(u, w("f_gate"), w("f_up"), w("f_down"), dot)
    return a + moe(u, w, layer.__getitem__, model, dot)


def hidden_states(params, ids, model, dot=hi_dot, remat=False):
    """ids [B,S] int32 -> final-norm hidden states [B,S,H] float32."""
    x = params["embed"].astype(jnp.float32)[ids]
    for i in range(model["num_hidden_layers"]):
        def one(x, layer, is_dense=dense(model, i)):
            return block(x, layer, model, dot, is_dense)
        x = (jax.checkpoint(one) if remat else one)(
            x, {k: params[key(i, k)] for k in layer_keys(model, i)})
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
