"""Plain reference of the `motif3` family (Motif-3's decoder: grouped
differential latent attention, sliding-window layers beside full ones,
four residual streams joined by manifold-constrained hyper-connections,
PolyNorm feed-forwards, a sigmoid-routed expert layer with one ungated
shared expert): float32 `jax.numpy`, products through `dot`
(`common.hi_dot`; the control's `common.fp8_dot`), no kernels, no cache,
no absorbed form. Imports nothing of the program.

Streams: `X0 = [e; ...; e]` (n = `mhc_expansion_rate` copies of the
token's embedding). Each sublayer F of a layer (attention, then
feed-forward), with its own `hc_*` arrays (`N` an RMSNorm `w * x /
rms(x)`, eps `rms_norm_eps`):

    x^ = N_hc(vec X)                         # over n * C values
    p  = phi x^                              # phi [2n + n n, n C]
    H_pre  = sigmoid(alpha0 p[:n] + b[:n])
    H_post = 2 sigmoid(alpha1 p[n:2n] + b[n:2n])
    H_res  = Sinkhorn(exp(alpha2 p[2n:] + b[2n:]))   # 20 rounds: rows,
                                                      #   then columns
    X <- clamp(H_res X + H_post^T F(N_F(H_pre X)), +-hidden_clamp)

output: `N_f(sum of the streams) W_head`.

* attention (GDLA), the EXPANDED form: `cq = Nq(z Wqa)`; `[q | gamma] = cq
  Wqb` (80 heads of `[q_nope | q_rope]`, then the gate of each signal
  head); `[c | k_rope] = z Wkva`, `c = Nkv(c)`; `[k_nope_g | v_g] = c Wkvb`
  for each of the `num_key_value_heads` groups; head `h` reads group `h //
  per`, the last head of a group its noise head. Rotation on interleaved
  pairs, as complex numbers: YaRN's frequencies on a full layer (no factor
  anywhere: `apply_yarn_scaling` false), `swa_rope_theta`'s own on a
  window layer. Every head's map `A_h = softmax((q_nope . k_nope + q_rope .
  k_rope) / sqrt(nope + rope))` over the keys `j <= t` (and `t - window <
  j` on a window layer); a signal head's output `(A_s - lam_s A_noise)
  v_g`, `lam = sigmoid(z W_lam)`, times `sigmoid(gamma_s)`; then `Wo`.
  Walked a group at a time.
* feed-forward: `W_d(PolyNorm(W_g z) * W_u z)`, `PolyNorm(x) = s (a1
  N(x) + a2 N(x^2) + a3 N(x^3) + clamp(b, +-c))`, `N` unweighted over the
  whole intermediate row; dense of `intermediate_size` in the first
  `n_dense_first_layers` layers; after them `s = sigmoid(z Wr)` over the
  router's `router_experts` outputs, the `experts_top_k` best of `s + b`
  (the choice bias), weights `s / sum(s)` over the chosen times
  `route_scale`, each chosen held expert adding `w * FFN_e(z)`, and the
  shared expert `FFN_s(z)` with no gate.

The share: `num_experts` experts, ids `expert_offset ..`, of the router's
`router_experts` are held here; an assignment to an absent expert is left
out. `num_hidden_layers` layers and `vocab_size` rows of embedding and head
are what the configuration gives this chip, treated as the whole model.

Parameters are a flat dict, ONE ARRAY A LEAF, layer i's under
`l<i>.<name>`:
  embed [V,H]  head [H,V]  norm_f [H]
  l<i>.hc_a_norm hc_f_norm [nH]  hc_a_phi hc_f_phi [2n+nn, nH]
  l<i>.hc_a_alpha hc_f_alpha [3]  hc_a_bias hc_f_bias [2n+nn]
  l<i>.n1 n2 [H]
  l<i>.a_qa [H,Rq]  a_qn [Rq]  a_qb [Rq, nh*(nope+rope) + sig*v]
  l<i>.a_kva [H, R+rope]  a_kvn [R]  a_kvb [R, G*(nope+v)]
  l<i>.a_lam [H, sig]  a_o [sig*v, H]
  a dense layer:  l<i>.f_gate f_up [H,I]  f_down [I,H]  f_poly [4]
  a routed layer: l<i>.router [H,E_r]  router_b [E_r]
                  l<i>.exp_gate exp_up [E,H,Ie]  exp_down [E,Ie,H]
                  l<i>.exp_poly [E,4]
                  l<i>.sh_gate sh_up [H,Is]  sh_down [Is,H]  sh_poly [4]
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .common import hi_dot

_HC = tuple(f"hc_{s}_{k}" for s in "af" for k in ("norm", "phi", "alpha",
                                                  "bias"))
_ATT = ("a_qa", "a_qn", "a_qb", "a_kva", "a_kvn", "a_kvb", "a_lam", "a_o")
DENSE_KEYS = _HC + ("n1", "n2") + _ATT + ("f_gate", "f_up", "f_down",
                                          "f_poly")
ROUTED_KEYS = _HC + ("n1", "n2") + _ATT + (
    "router", "router_b", "exp_gate", "exp_up", "exp_down", "exp_poly",
    "sh_gate", "sh_up", "sh_down", "sh_poly")
LAYER_KEYS = ()             # no stacked arrays: every leaf has its own key
TOP_KEYS = ("embed", "head", "norm_f")
HEAD_BLOCKS = 4             # the head's product, in this many column blocks
WINDOW = "sliding_attention"


def key(i: int, name: str) -> str:
    return f"l{i}.{name}"


def dense(model: dict, i: int) -> bool:
    return i < model["n_dense_first_layers"]


def window(model: dict, i: int) -> bool:
    return model["layer_types"][i] == WINDOW


def layer_keys(model: dict, i: int) -> tuple:
    return DENSE_KEYS if dense(model, i) else ROUTED_KEYS


def heads(model: dict) -> dict:
    """The head split: `groups` latent groups of `per` heads, the last of
    each its noise head; `signal` heads in all."""
    nh, g = model["num_attention_heads"], model["num_key_value_heads"]
    noise = model["num_noise_heads"]
    assert nh % g == 0 and noise == g
    return dict(nh=nh, groups=g, per=nh // g, signal=nh - noise)


def frequencies(model: dict, is_window: bool):
    """Rotary frequencies [rope / 2] float64: `swa_rope_theta`'s own on a
    window layer; YaRN's on a full one (the plain ones kept below the
    correction dim of `beta_fast` turns over the original context, divided
    by `factor` above that of `beta_slow`, a linear ramp between). No
    amplitude or scale factor: `apply_yarn_scaling` is false."""
    rot = model["qk_rope_head_dim"]
    theta = float(model["swa_rope_theta"] if is_window
                  else model["rope_theta"])
    freqs = 1.0 / theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    sc = model.get("rope_scaling")
    if is_window or not sc:
        return freqs
    assert sc.get("apply_yarn_scaling") is False
    factor, orig = float(sc["factor"]), sc["original_max_position_embeddings"]

    def dim_of(turns):
        return rot * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(dim_of(sc["beta_fast"])), 0)
    hi = min(math.ceil(dim_of(sc["beta_slow"])), rot - 1)
    ramp = np.clip((np.arange(rot // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    return freqs * (1 - ramp) + freqs / factor * ramp


def norm(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                                 + eps)


def rotary(t, freqs):
    """Pairs `(2i, 2i+1)` of t's last dim as `re + i im`, times `exp(i p
    freqs[i])` at position p = 0 .. S-1; t [B,S,n,rope]."""
    ang = jnp.arange(t.shape[1], dtype=jnp.float32)[:, None] \
        * jnp.asarray(freqs, jnp.float32)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    re, im = t[..., 0::2], t[..., 1::2]
    return jnp.stack([re * cos - im * sin, re * sin + im * cos],
                     -1).reshape(t.shape)


def gdla(z, w, model, dot, is_window):
    """Grouped differential latent attention, expanded, on normed z
    [B,S,H]; `w(name)` one of the layer's arrays in float32."""
    b, s, _ = z.shape
    hd = heads(model)
    nh, g, per, sig = hd["nh"], hd["groups"], hd["per"], hd["signal"]
    rank = model["kv_lora_rank"]
    nope, rot, vd = model["qk_nope_head_dim"], model["qk_rope_head_dim"], \
        model["v_head_dim"]
    eps = model["rms_norm_eps"]
    freqs = frequencies(model, is_window)
    cq = norm(dot("bsh,hr->bsr", z, w("a_qa")), w("a_qn"), eps)
    qg = dot("bsr,rk->bsk", cq, w("a_qb"))
    q = qg[..., :nh * (nope + rot)].reshape(b, s, nh, nope + rot)
    gamma = qg[..., nh * (nope + rot):].reshape(b, s, sig, vd)
    lam = jax.nn.sigmoid(dot("bsh,hk->bsk", z, w("a_lam")))     # [B,S,sig]
    kv = dot("bsh,hr->bsr", z, w("a_kva"))
    c = norm(kv[..., :rank], w("a_kvn"), eps)
    k_rope = rotary(kv[..., None, rank:], freqs)[:, :, 0]
    q_nope, q_rope = q[..., :nope], rotary(q[..., nope:], freqs)
    t = jnp.arange(s)
    seen = t[None, :] <= t[:, None]
    if is_window:
        seen &= t[None, :] > t[:, None] - model["sliding_window"]
    scale = 1.0 / math.sqrt(nope + rot)
    wkvb = w("a_kvb").reshape(rank, g, nope + vd)

    def group(at):                  # one group's heads at a time
        qn, qr, wg, lg = at         # [B,S,per,nope] [B,S,per,rot] [R,n+v]
        kg = dot("bmr,rd->bmd", c, wg)                          # [B,S,n+v]
        sc = (dot("bqhd,bmd->bhqm", qn, kg[..., :nope])
              + dot("bqhd,bmd->bhqm", qr, k_rope)) * scale
        maps = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        diff = maps[:, :-1] - jnp.moveaxis(lg, 2, 1)[..., None] \
            * maps[:, -1:]                                      # signal
        return dot("bhqm,bmd->bqhd", diff, kg[..., nope:])

    def by_group(t_):               # [B,S,nh|sig,...] -> [G,B,S,per|..]
        return jnp.moveaxis(t_.reshape((b, s, g, -1) + t_.shape[3:]), 2, 0)

    out = jax.lax.map(group, (by_group(q_nope), by_group(q_rope),
                              jnp.moveaxis(wkvb, 1, 0), by_group(lam)))
    out = jnp.moveaxis(out, 0, 2).reshape(b, s, sig, vd)
    out = out * jax.nn.sigmoid(gamma)
    return dot("bsk,kh->bsh", out.reshape(b, s, sig * vd), w("a_o"))


def poly_ffn(x, gate, up, down, coef, model, dot):
    """`W_d(PolyNorm(W_g x) * W_u x)`; coef = (a1, a2, a3, b)."""
    eps = model["rms_norm_eps"]
    g = dot("bsh,hi->bsi", x, gate)

    def n(v):
        return v / jnp.sqrt(jnp.mean(v * v, -1, keepdims=True) + eps)
    c = model["polynorm_bias_clamp"]
    act = model["polynorm_output_scale"] * (
        coef[0] * n(g) + coef[1] * n(g ** 2) + coef[2] * n(g ** 3)
        + jnp.clip(coef[3], -c, c))
    return dot("bsi,ih->bsh", act * dot("bsh,hi->bsi", x, up), down)


def route(u, w, model, dot):
    """(ids [B,S,k] over the router's outputs, weights [B,S,k])."""
    scores = jax.nn.sigmoid(dot("bsh,he->bse", u, w("router")))
    _, ids = jax.lax.top_k(scores + w("router_b"), model["experts_top_k"])
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    if model["route_norm"]:
        chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return ids, chosen * model["route_scale"]


def moe(u, w, stored, model, dot):
    """The held experts' part (one expert read out of its stack and upcast
    at a time) + the shared expert."""
    first = model.get("expert_offset", 0)
    ids, weights = route(u, w, model, dot)

    def expert(name, e):
        return jax.lax.dynamic_index_in_dim(stored(name), e, 0,
                                            keepdims=False) \
            .astype(jnp.float32)

    def one(acc, e):
        we = jnp.sum(jnp.where(ids == first + e, weights, 0.0), axis=-1)
        y = poly_ffn(u, expert("exp_gate", e), expert("exp_up", e),
                     expert("exp_down", e), expert("exp_poly", e), model,
                     dot)
        return acc + y * we[..., None], None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(u),
                             jnp.arange(model["num_experts"]))
    return routed + poly_ffn(u, w("sh_gate"), w("sh_up"), w("sh_down"),
                             w("sh_poly"), model, dot)


def sinkhorn(m, rounds):
    for _ in range(rounds):
        m = m / jnp.sum(m, axis=-1, keepdims=True)     # rows
        m = m / jnp.sum(m, axis=-2, keepdims=True)     # columns
    return m


def hyper(x, w, side, model, dot):
    """(H_pre [B,S,n], H_post [B,S,n], H_res [B,S,n,n]) of streams x
    [B,S,n,H] for sublayer `side` ("a" or "f")."""
    b, s, n, h = x.shape
    flat = norm(x.reshape(b, s, n * h), w(f"hc_{side}_norm"),
                model["rms_norm_eps"])
    p = dot("bsk,jk->bsj", flat, w(f"hc_{side}_phi"))
    a, bias = w(f"hc_{side}_alpha"), w(f"hc_{side}_bias")
    pre = jax.nn.sigmoid(a[0] * p[..., :n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * p[..., n:2 * n] + bias[n:2 * n])
    res = sinkhorn(jnp.exp(a[2] * p[..., 2 * n:] + bias[2 * n:])
                   .reshape(b, s, n, n), model["mhc_sinkhorn_iters"])
    return pre, post, res


def sublayer(x, fn, w, side, model, dot):
    pre, post, res = hyper(x, w, side, model, dot)
    u = jnp.einsum("bsi,bsih->bsh", pre, x, precision="highest")
    y = fn(u)
    lim = model["hidden_clamp"]
    return jnp.clip(jnp.einsum("bsij,bsjh->bsih", res, x,
                               precision="highest")
                    + post[..., None] * y[:, :, None, :], -lim, lim)


def block(x, layer, model, dot, is_dense, is_window):
    """`x [B,S,n,H]` the streams; `layer`: {name: the array as stored}."""
    eps = model["rms_norm_eps"]

    def w(name):
        return layer[name].astype(jnp.float32)

    x = sublayer(x, lambda u: gdla(norm(u, w("n1"), eps), w, model, dot,
                                   is_window), w, "a", model, dot)

    def ffn(u):
        z = norm(u, w("n2"), eps)
        if is_dense:
            return poly_ffn(z, w("f_gate"), w("f_up"), w("f_down"),
                            w("f_poly"), model, dot)
        return moe(z, w, layer.__getitem__, model, dot)

    return sublayer(x, ffn, w, "f", model, dot)


def hidden_states(params, ids, model, dot=hi_dot, remat=False):
    """ids [B,S] int32 -> final-norm hidden states [B,S,H] float32."""
    e = params["embed"].astype(jnp.float32)[ids]
    n = model["mhc_expansion_rate"]
    x = jnp.broadcast_to(e[:, :, None], e.shape[:2] + (n, e.shape[-1]))
    for i in range(model["num_hidden_layers"]):
        def one(x, layer, is_dense=dense(model, i), is_w=window(model, i)):
            return block(x, layer, model, dot, is_dense, is_w)
        x = (jax.checkpoint(one) if remat else one)(
            x, {k: params[key(i, k)] for k in layer_keys(model, i)})
    return norm(jnp.sum(x, axis=2), params["norm_f"].astype(jnp.float32),
                model["rms_norm_eps"])


def _head(hid, head, dot):
    v = head.shape[1]
    step = -(-v // HEAD_BLOCKS)
    return jnp.concatenate([
        dot("bsh,hv->bsv", hid, head[:, at:at + step].astype(jnp.float32))
        for at in range(0, v, step)], axis=-1)


def logits(params, ids, model, dot=hi_dot):
    """Full forward: [B,S] -> [B,S,V] through the untied head."""
    return _head(hidden_states(params, ids, model, dot), params["head"], dot)


def nll_sum(params, ids, model, dot=hi_dot):
    hid = hidden_states(params, ids, model, dot, remat=True)[:, :-1]
    lg = _head(hid, params["head"], dot)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.sum(lse - gold)


def loss_and_grads(params, ids, model, dot=hi_dot):
    """Mean next-token loss over ids [B,S] and its gradients (the router's
    choice a constant of the gradient)."""
    n_tok = ids.shape[0] * (ids.shape[1] - 1)
    tot, g = jax.value_and_grad(nll_sum)(params, ids, model, dot)
    return tot / n_tok, jax.tree_util.tree_map(lambda a: a / n_tok, g)
