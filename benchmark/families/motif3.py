"""The `motif3` family (`paddle_tpu.models.motif3`; plain reference in
`reference/motif3.py`): what the harness asks of a model family, for
Motif-3's decoder (grouped differential latent attention in every layer,
128-position window layers beside full ones, four residual streams joined
by manifold-constrained hyper-connections, PolyNorm feed-forwards: dense in
the leading layers, a sigmoid-routed expert layer with one ungated shared
expert after them), of which THIS CHIP HOLDS A SHARE (a pipeline stage's
layers, `num_experts` of `router_experts` experts, a slice of the
vocabulary).

  weights   `shapes`, `n_params`, `make`: ONE ARRAY A PROGRAM LEAF
  program   `build`, `leaf_map`, `FUSED`
  counts    from shapes alone and for the share held: `matmul_params`,
            `forward_flops`, `kv_bytes_per_token`, `weight_bytes`,
            `decode_step_bytes`, the training counts the harness asks of
            every family (no cell trains this one), and for the expert,
            latent and hyper-connection readers `expert_bytes`,
            `moe_flops`, `experts_touched`, `mla_flops`, `mla_bytes`,
            `mhc_flops`, `mhc_bytes`. A window layer reads at most
            `sliding_window` rows of a sequence.
"""
from __future__ import annotations

import math

from .. import weights
from ..reference.motif3 import dense, heads, key, layer_keys, window

# ------------------------------------------------------------- weights


def _dims(m: dict) -> dict:
    hd = heads(m)
    return dict(h=m["hidden_size"], v=m["vocab_size"],
                n_l=m["num_hidden_layers"],
                n_dense=min(m["n_dense_first_layers"],
                            m["num_hidden_layers"]),
                n_win=sum(window(m, i) for i in range(m["num_hidden_layers"])),
                win=m["sliding_window"], nh=hd["nh"], g=hd["groups"],
                sig=hd["signal"], rq=m["q_lora_rank"], r=m["kv_lora_rank"],
                nope=m["qk_nope_head_dim"], rot=m["qk_rope_head_dim"],
                vd=m["v_head_dim"], i=m["intermediate_size"],
                ie=m["moe_intermediate_size"], held=m["num_experts"],
                routed=m.get("router_experts") or m["num_experts"],
                shared=m["num_shared_experts"], top_k=m["experts_top_k"],
                n=m["mhc_expansion_rate"])


def _block_shapes(d: dict, is_dense: bool) -> dict:
    h, nh, n = d["h"], d["nh"], d["n"]
    p = 2 * n + n * n
    out = {}
    for side in "af":
        out.update({f"hc_{side}_norm": (n * h,),
                    f"hc_{side}_phi": (p, n * h),
                    f"hc_{side}_alpha": (3,), f"hc_{side}_bias": (p,)})
    out.update({"n1": (h,), "n2": (h,),
                "a_qa": (h, d["rq"]), "a_qn": (d["rq"],),
                "a_qb": (d["rq"], nh * (d["nope"] + d["rot"])
                         + d["sig"] * d["vd"]),
                "a_kva": (h, d["r"] + d["rot"]), "a_kvn": (d["r"],),
                "a_kvb": (d["r"], d["g"] * (d["nope"] + d["vd"])),
                "a_lam": (h, d["sig"]), "a_o": (d["sig"] * d["vd"], h)})
    if is_dense:
        out.update({"f_gate": (h, d["i"]), "f_up": (h, d["i"]),
                    "f_down": (d["i"], h), "f_poly": (4,)})
        return out
    si = d["shared"] * d["ie"]
    out.update({"router": (h, d["routed"]), "router_b": (d["routed"],),
                "exp_gate": (d["held"], h, d["ie"]),
                "exp_up": (d["held"], h, d["ie"]),
                "exp_down": (d["held"], d["ie"], h),
                "exp_poly": (d["held"], 4),
                "sh_gate": (h, si), "sh_up": (h, si), "sh_down": (si, h),
                "sh_poly": (4,)})
    return out


def shapes(model: dict) -> dict:
    d = _dims(model)
    out = {"embed": (d["v"], d["h"]), "head": (d["h"], d["v"]),
           "norm_f": (d["h"],)}
    for i in range(d["n_l"]):
        block = _block_shapes(d, dense(model, i))
        assert tuple(sorted(block)) == tuple(sorted(layer_keys(model, i)))
        out.update({key(i, k): s for k, s in block.items()})
    return out


def n_params(model: dict) -> int:
    return sum(math.prod(s) for s in shapes(model).values())


ROUTER_SPREAD = 1.0     # of the router's logits (deepseek_v3's reading)
CHOICE_BIAS_STD = 1e-2  # beside the gaps between the best sigmoid scores
EXPERT_OUT = 0.6        # an expert's output rms about 0.3 (deepseek_v3's)
STREAM_SPREAD = 1.0     # of the mHC projection's outputs: sigmoid gates
#                         0.27-0.73 for most tokens, H_res's entries e^+-1
#                         apart before Sinkhorn, so it is neither the
#                         identity nor uniform
POLY = (1.0 / 3, 0.15)  # a1 a2 a3 b: the three terms of like size


def _recipe(model: dict) -> dict:
    """{array name within a block or at the top: (mean, std)}: matrices
    scaled so that every projection's output, the attention scores
    (spread about 1.5), lam (sigmoid of a spread about 1: 0.27-0.73),
    the gate, the hyper-connections' coefficients, the streams and the
    logits (spread about 1.5) stay of order 1; every norm weight and the
    mHC factors N(1, 0.1), so a dropped one shows."""
    d = _dims(model)
    rt = math.sqrt
    h, n = d["h"], d["n"]
    qk = rt(1.5)                    # rms of a query's and a key's entries
    norm = (1.0, 0.1)
    si = d["shared"] * d["ie"]
    return {"embed": (0.0, 1.0), "head": (0.0, 1.5 / rt(h)), "norm_f": norm,
            "hc_a_norm": norm, "hc_f_norm": norm,
            "hc_a_phi": (0.0, STREAM_SPREAD / rt(n * h)),
            "hc_f_phi": (0.0, STREAM_SPREAD / rt(n * h)),
            "hc_a_alpha": norm, "hc_f_alpha": norm,
            "hc_a_bias": (0.0, 0.5), "hc_f_bias": (0.0, 0.5),
            "n1": norm, "n2": norm,
            "a_qa": (0.0, 1.0 / rt(h)), "a_qn": norm,
            "a_qb": (0.0, qk / rt(d["rq"])),
            "a_kva": (0.0, 1.0 / rt(h)), "a_kvn": norm,
            "a_kvb": (0.0, qk / rt(d["r"])),
            "a_lam": (0.0, 1.0 / rt(h)),
            "a_o": (0.0, 0.6 / rt(d["sig"] * d["vd"])),
            "f_gate": (0.0, 1.0 / rt(h)), "f_up": (0.0, 1.0 / rt(h)),
            "f_down": (0.0, 0.5 / rt(d["i"])), "f_poly": POLY,
            "router": (0.0, ROUTER_SPREAD / rt(h)),
            "router_b": (0.0, CHOICE_BIAS_STD),
            "exp_gate": (0.0, 1.0 / rt(h)), "exp_up": (0.0, 1.0 / rt(h)),
            "exp_down": (0.0, EXPERT_OUT / rt(d["ie"])), "exp_poly": POLY,
            "sh_gate": (0.0, 1.0 / rt(h)), "sh_up": (0.0, 1.0 / rt(h)),
            "sh_down": (0.0, 0.5 / rt(si)), "sh_poly": POLY}


def make(model: dict, seed: int, dtype="bfloat16"):
    _program()      # a program without this family fails before GBs are drawn
    table = _recipe(model)
    return weights.draw(shapes(model), lambda name: table[name.split(".")[-1]],
                        seed, dtype)


# ------------------------------------------------------------- program

_HC = {f"hc_{s}_{k}": f"{side}.{k}" for s, side in (("a", "attn_hc"),
                                                     ("f", "mlp_hc"))
       for k in ("norm", "phi", "alpha", "bias")}
_ATT = dict(_HC, a_qa="self_attn.q_a_proj", a_qn="self_attn.q_a_layernorm",
            a_qb="self_attn.q_b_proj", a_kva="self_attn.kv_a_proj",
            a_kvn="self_attn.kv_a_layernorm", a_kvb="self_attn.kv_b_proj",
            a_lam="self_attn.lambda_proj", a_o="self_attn.o_proj",
            n1="input_layernorm", n2="post_attention_layernorm")
_DENSE = dict(_ATT, f_gate="mlp.gate_proj", f_up="mlp.up_proj",
              f_down="mlp.down_proj", f_poly="mlp.poly_coef")
_ROUTED = dict(_ATT, router="mlp.gate", router_b="mlp.gate_bias",
               exp_gate="mlp.experts_gate_proj", exp_up="mlp.experts_up_proj",
               exp_down="mlp.experts_down_proj", exp_poly="mlp.experts_poly",
               sh_gate="mlp.shared_gate_proj", sh_up="mlp.shared_up_proj",
               sh_down="mlp.shared_down_proj", sh_poly="mlp.shared_poly")
_TOP = {"embed": "model.embed_tokens", "head": "lm_head",
        "norm_f": "model.norm"}
FUSED = {}          # every leaf is judged whole


def leaf_map(model: dict) -> dict:
    """{program leaf name: (the array's key, None)}."""
    out = {name: (k, None) for k, name in _TOP.items()}
    for i in range(model["num_hidden_layers"]):
        for k, name in (_DENSE if dense(model, i) else _ROUTED).items():
            out[f"model.layers.{i}.{name}"] = (key(i, k), None)
    return out


def _program():
    """The program's classes for this family; a commit that lacks them
    fails here, naming the module."""
    from paddle_tpu.models.motif3 import Motif3Config, Motif3ForCausalLM
    return Motif3Config, Motif3ForCausalLM


def build(cfg: dict):
    """`Motif3ForCausalLM` at the configuration's sizes, its initial arrays
    made on the HOST (the benchmark's replace every one at once)."""
    import jax
    config, lm = _program()
    with jax.default_device(jax.devices("cpu")[0]):
        return lm(config(dtype=cfg["dtype"], **cfg["model"]))


# -------------------------------------------------------------- counts

def mla_params(model: dict) -> int:
    """One GDLA layer's parameters: matrices (the gate's columns in Wqb,
    Wlam) and its two norms."""
    d = _dims(model)
    return d["h"] * d["rq"] + d["rq"] \
        + d["rq"] * (d["nh"] * (d["nope"] + d["rot"]) + d["sig"] * d["vd"]) \
        + d["h"] * (d["r"] + d["rot"]) + d["r"] \
        + d["r"] * d["g"] * (d["nope"] + d["vd"]) + d["h"] * d["sig"] \
        + d["sig"] * d["vd"] * d["h"]


def mhc_params(model: dict) -> int:
    """One sublayer's hyper-connection: the norm over n C values, phi, the
    three factors and the biases."""
    d = _dims(model)
    p = 2 * d["n"] + d["n"] ** 2
    return d["n"] * d["h"] * (1 + p) + 3 + p


def expert_params(model: dict) -> int:
    """An expert's three matrices and its four PolyNorm numbers."""
    d = _dims(model)
    return 3 * d["h"] * d["ie"] + 4


def _dense_params(model: dict) -> int:
    """Matrices every token multiplies, the routed experts left out."""
    d = _dims(model)
    routed_layers = d["n_l"] - d["n_dense"]
    return d["n_l"] * (mla_params(model) + 2 * mhc_params(model)) \
        + d["n_dense"] * 3 * d["h"] * d["i"] \
        + routed_layers * (d["h"] * d["routed"]
                           + d["shared"] * expert_params(model)) \
        + d["h"] * d["v"]


def matmul_params(model: dict) -> float:
    """Parameters a token multiplies HERE: the dense ones and the held
    share of its top-k under uniform routing."""
    d = _dims(model)
    return _dense_params(model) + (d["n_l"] - d["n_dense"]) \
        * expert_params(model) * d["top_k"] * d["held"] / d["routed"]


def _pair_flops(model: dict) -> float:
    """Per (query, key) pair of one layer, expanded: every head's scores
    and values, 2 x heads x (nope + rope + v)."""
    d = _dims(model)
    return 2.0 * d["nh"] * (d["nope"] + d["rot"] + d["vd"])


def _rows(model: dict, context_tokens: float, sequences: float) -> float:
    """Rows all layers read for `sequences` holding `context_tokens` in
    all: every one on a full layer, at most `sliding_window` a sequence on
    a window layer."""
    d = _dims(model)
    return (d["n_l"] - d["n_win"]) * context_tokens \
        + d["n_win"] * min(context_tokens, d["win"] * sequences)


def forward_flops(model: dict, new_tokens: int, context_tokens: int) -> float:
    """2 per matmul parameter per token, the expanded form's pairs (a
    window layer's capped at `sliding_window` keys a query), the
    hyper-connections' own arithmetic."""
    return 2.0 * matmul_params(model) * new_tokens \
        + _pair_flops(model) * _rows(model, context_tokens, new_tokens) \
        + 2 * _dims(model)["n_l"] * mhc_flops(model, new_tokens)


def train_flops_per_token(model: dict, seq: int) -> float:
    d = _dims(model)
    keys = (d["n_l"] - d["n_win"]) * 0.5 * seq \
        + d["n_win"] * min(0.5 * seq, d["win"])
    return 3.0 * forward_flops(model, 1, 0) + 3.0 * _pair_flops(model) * keys


def attention_train_flops(model: dict, batch: int, seq: int) -> float:
    d = _dims(model)
    keys = (d["n_l"] - d["n_win"]) * 0.5 * seq \
        + d["n_win"] * min(0.5 * seq, d["win"])
    return 3.0 * _pair_flops(model) * keys * seq * batch


def attention_train_bytes(model: dict, batch: int, seq: int,
                          elem: int = 2) -> float:
    d = _dims(model)
    return 6.0 * d["nh"] * (d["nope"] + d["rot"] + d["vd"]) * batch * seq \
        * elem * d["n_l"]


def kv_bytes_per_token(model: dict, elem: int = 2, context=None) -> float:
    """One latent row `[c | k_rope]` a layer a position held; with
    `context`, a sequence of that many positions' mean per position, a
    window layer holding `min(context, sliding_window)` of them."""
    d = _dims(model)
    row = (d["r"] + d["rot"]) * elem
    if context is None:
        return d["n_l"] * row
    return row * _rows(model, context, 1) / context


def mla_flops(model: dict, tokens: float) -> float:
    """The absorbed decode attention of ONE layer over that many cached
    rows: every head's scores against a row's rank + rope lanes and its
    context over the rank lanes."""
    d = _dims(model)
    return 2.0 * tokens * d["nh"] * (d["r"] + d["rot"] + d["r"])


def mla_bytes(model: dict, tokens: float, elem: int = 2) -> float:
    """The latent rows ONE layer's decode attention reads, once."""
    d = _dims(model)
    return float(tokens) * (d["r"] + d["rot"]) * elem


def mhc_flops(model: dict, rows: float) -> float:
    """One boundary's arithmetic for that many rows: the 2n + n n wide
    projection of n C values, the norm, H_res X and H_post^T y, H_pre X,
    and 20 Sinkhorn rounds on n x n."""
    d = _dims(model)
    n, h = d["n"], d["h"]
    p = 2 * n + n * n
    return float(rows) * (2 * n * h * p + 3 * n * h + 2 * n * n * h
                          + 2 * n * h + 2 * n * h
                          + model["mhc_sinkhorn_iters"] * 4 * n * n)


def mhc_bytes(model: dict, rows: float, elem: int = 2) -> float:
    """What `rows` rows mixed at the boundaries of one executable move:
    a row's streams read and written, the sublayer's output read and the
    next one's input written, the coefficients in and out (float32); and
    every boundary's own parameters read once."""
    d = _dims(model)
    n, h = d["n"], d["h"]
    per_row = (2 * n * h + 2 * h) * elem + 2 * (n + n * n) * 4
    return float(rows) * per_row + 2 * d["n_l"] * mhc_params(model) * elem


def expert_bytes(model: dict, elem: int = 2) -> int:
    return expert_params(model) * elem


def moe_flops(model: dict, assignments: float) -> float:
    """The grouped matmul's operations for that many assignments."""
    d = _dims(model)
    return 2.0 * 3 * d["h"] * d["ie"] * assignments


def experts_touched(model: dict, live_slots: float) -> float:
    d = _dims(model)
    return d["held"] * (1.0 - (1.0 - d["top_k"] / d["routed"]) ** live_slots)


def weight_bytes(model: dict, elem: int = 2) -> int:
    """Every parameter read once but the embedding table."""
    d = _dims(model)
    return (n_params(model) - d["v"] * d["h"]) * elem


def decode_step_bytes(model: dict, live_context_tokens: int,
                      live_slots: int, elem: int = 2) -> float:
    """Least HBM traffic of one decode step: the weights outside the held
    experts once, the experts a step of `live_slots` tokens expects to
    touch, the latent rows every live slot's layers read (a window layer
    at most `sliding_window` a slot) and one position written a layer."""
    d = _dims(model)
    n_moe = d["n_l"] - d["n_dense"]
    row = (d["r"] + d["rot"]) * elem
    return weight_bytes(model, elem) \
        - n_moe * d["held"] * expert_bytes(model, elem) \
        + n_moe * experts_touched(model, live_slots) \
        * expert_bytes(model, elem) \
        + row * (_rows(model, live_context_tokens, live_slots)
                 + d["n_l"] * live_slots)
