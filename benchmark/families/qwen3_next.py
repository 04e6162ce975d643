"""The `qwen3_next` family (`paddle_tpu.models.qwen3_next`; plain reference
in `reference/qwen3_next.py`): what the harness asks of a model family, for
a hybrid of Gated DeltaNet and gated attention layers with routed experts,
of which THIS CHIP HOLDS A SHARE (`num_experts` of `router_experts`, a
slice of the vocabulary).

  weights   `shapes`, `n_params`, `make`: the stacked arrays, from the seed
  program   `build`, `leaf_map`, `FUSED`
  counts    from shapes alone and for the share held: `matmul_params`,
            `forward_flops`, `kv_bytes_per_token`, `weight_bytes`,
            `decode_step_bytes`, the training counts, and for this family's
            own readers `state_bytes_per_slot`, `gdn_state_bytes`,
            `gdn_flops`, `expert_bytes`, `moe_flops`, `experts_touched`
"""
from __future__ import annotations

import math

from .. import weights
from ..reference.qwen3_next import is_linear

# ------------------------------------------------------------- weights


def _dims(m: dict) -> dict:
    kd = m["linear_num_key_heads"] * m["linear_key_head_dim"]
    vd = m["linear_num_value_heads"] * m["linear_value_head_dim"]
    n_l = m["num_hidden_layers"]
    n_lin = sum(is_linear(m, i) for i in range(n_l))
    return dict(h=m["hidden_size"], v=m["vocab_size"], n_l=n_l, n_lin=n_lin,
                n_full=n_l - n_lin, kd=kd, vd=vd, nv=m["linear_num_value_heads"],
                dk=m["linear_key_head_dim"], dv=m["linear_value_head_dim"],
                width=m["linear_conv_kernel_dim"],
                nh=m["num_attention_heads"], nkv=m["num_key_value_heads"],
                hd=m["head_dim"], held=m["num_experts"],
                routed=m.get("router_experts") or m["num_experts"],
                top_k=m["num_experts_per_tok"],
                i=m["moe_intermediate_size"],
                i_sh=m["shared_expert_intermediate_size"])


def shapes(model: dict) -> dict:
    d = _dims(model)
    h, n_l, ll, lf = d["h"], d["n_l"], d["n_lin"], d["n_full"]
    e, i, i_sh = d["held"], d["i"], d["i_sh"]
    att = d["nh"] * d["hd"]
    return {
        "embed": (d["v"], h), "head": (h, d["v"]), "norm_f": (h,),
        "ln1": (n_l, h), "ln2": (n_l, h), "router": (n_l, h, d["routed"]),
        "exp_gate": (n_l, e, h, i), "exp_up": (n_l, e, h, i),
        "exp_down": (n_l, e, i, h), "sh_gate": (n_l, h, i_sh),
        "sh_up": (n_l, h, i_sh), "sh_down": (n_l, i_sh, h),
        "sh_mix": (n_l, h, 1),
        "lin_qkvz": (ll, h, 2 * d["kd"] + 2 * d["vd"]),
        "lin_ba": (ll, h, 2 * d["nv"]),
        "lin_conv": (ll, 2 * d["kd"] + d["vd"], d["width"]),
        "lin_dt": (ll, d["nv"]), "lin_alog": (ll, d["nv"]),
        "lin_norm": (ll, d["dv"]), "lin_out": (ll, d["vd"], h),
        "att_q": (lf, h, 2 * att), "att_k": (lf, h, d["nkv"] * d["hd"]),
        "att_v": (lf, h, d["nkv"] * d["hd"]), "att_o": (lf, att, h),
        "att_qn": (lf, d["hd"]), "att_kn": (lf, d["hd"]),
    }


def n_params(model: dict) -> int:
    return sum(math.prod(s) for s in shapes(model).values())


def make(model: dict, seed: int, dtype="bfloat16"):
    """N(0, 0.02) matrices and embeddings, residual-out projections scaled
    by 1/sqrt(2L). What the program initialises to a constant gets small
    random values, so a dropped term shows: zero-centred norm weights N(0,
    0.02), the DeltaNet output norm N(1, 0.02), `A_log` N(0, 0.3),
    `dt_bias` N(-2, 0.3) (a decay of about 0.88 a position), the depthwise
    convolution N(0, 0.3)."""
    _program()      # a program without this family fails before 7 GB are drawn
    std = 0.02
    resid = std / math.sqrt(2.0 * model["num_hidden_layers"])

    def recipe(name):
        if name in ("lin_out", "att_o", "exp_down", "sh_down"):
            return 0.0, resid
        return {"lin_norm": (1.0, std), "lin_alog": (0.0, 0.3),
                "lin_dt": (-2.0, 0.3), "lin_conv": (0.0, 0.3)}.get(
                    name, (0.0, std))

    return weights.draw(shapes(model), recipe, seed, dtype)


# ------------------------------------------------------------- program

_ALL = {
    "ln1": "input_layernorm", "ln2": "post_attention_layernorm",
    "router": "mlp.gate", "exp_gate": "mlp.experts_gate_proj",
    "exp_up": "mlp.experts_up_proj", "exp_down": "mlp.experts_down_proj",
    "sh_gate": "mlp.shared_gate_proj", "sh_up": "mlp.shared_up_proj",
    "sh_down": "mlp.shared_down_proj", "sh_mix": "mlp.shared_expert_gate",
}
_LINEAR = {
    "lin_qkvz": "linear_attn.in_proj_qkvz", "lin_ba": "linear_attn.in_proj_ba",
    "lin_conv": "linear_attn.conv1d", "lin_dt": "linear_attn.dt_bias",
    "lin_alog": "linear_attn.A_log", "lin_norm": "linear_attn.norm",
    "lin_out": "linear_attn.out_proj",
}
_FULL = {
    "att_q": "self_attn.q_proj", "att_k": "self_attn.k_proj",
    "att_v": "self_attn.v_proj", "att_o": "self_attn.o_proj",
    "att_qn": "self_attn.q_norm", "att_kn": "self_attn.k_norm",
}
_TOP = {"embed": "model.embed_tokens", "head": "lm_head",
        "norm_f": "model.norm"}
FUSED = {}          # every leaf is judged whole


def leaf_map(model: dict) -> dict:
    """{program leaf name: (stacked key, index in that key's stack)}."""
    out = {name: (key, None) for key, name in _TOP.items()}
    seen = {True: 0, False: 0}
    for i in range(model["num_hidden_layers"]):
        lin = is_linear(model, i)
        for key, name in _ALL.items():
            out[f"model.layers.{i}.{name}"] = (key, i)
        for key, name in (_LINEAR if lin else _FULL).items():
            out[f"model.layers.{i}.{name}"] = (key, seen[lin])
        seen[lin] += 1
    return out


def _program():
    """The program's classes for this family; a commit that lacks them
    fails here, naming the module."""
    from paddle_tpu.models.qwen3_next import (Qwen3NextConfig,
                                              Qwen3NextForCausalLM)
    return Qwen3NextConfig, Qwen3NextForCausalLM


def build(cfg: dict):
    """`Qwen3NextForCausalLM` at the configuration's sizes. Its initial
    arrays are made on the HOST (the benchmark's replace every one at once,
    and a second copy of the held experts does not fit beside them)."""
    import jax
    config, lm = _program()
    with jax.default_device(jax.devices("cpu")[0]):
        return lm(config(dtype=cfg["dtype"], **cfg["model"]))


# -------------------------------------------------------------- counts

def _mixer_params(d: dict) -> tuple:
    lin = d["h"] * (2 * d["kd"] + 2 * d["vd"] + 2 * d["nv"]) \
        + d["vd"] * d["h"]
    att = d["nh"] * d["hd"]
    full = d["h"] * (2 * att + 2 * d["nkv"] * d["hd"]) + att * d["h"]
    return lin, full


def expert_params(model: dict) -> int:
    d = _dims(model)
    return 3 * d["h"] * d["i"]


def _dense_params(model: dict) -> int:
    """Matrices every token multiplies, the routed experts left out: the
    mixers' projections, the router, the shared expert and its gate, the
    head."""
    d = _dims(model)
    lin, full = _mixer_params(d)
    per_layer = d["h"] * d["routed"] + 3 * d["h"] * d["i_sh"] + d["h"]
    return d["n_lin"] * lin + d["n_full"] * full + d["n_l"] * per_layer \
        + d["h"] * d["v"]


def matmul_params(model: dict) -> float:
    """Parameters a token multiplies HERE: the dense matrices and the held
    share of its top-k experts (top_k x held / routed of them a layer)."""
    d = _dims(model)
    return _dense_params(model) + d["n_l"] * expert_params(model) \
        * d["top_k"] * d["held"] / d["routed"]


def forward_flops(model: dict, new_tokens: int, context_tokens: int) -> float:
    """2 per matmul parameter per token; on the full layers 4 x heads x
    width per (query, key) pair; on the linear layers the delta rule's
    three [dk, dv] products per value head per token."""
    d = _dims(model)
    return 2.0 * matmul_params(model) * new_tokens \
        + 4.0 * d["n_full"] * d["nh"] * d["hd"] * context_tokens \
        + gdn_flops(model, new_tokens)


def train_flops_per_token(model: dict, seq: int) -> float:
    d = _dims(model)
    return 3.0 * forward_flops(model, 1, 0) \
        + 0.5 * 12.0 * d["n_full"] * d["nh"] * d["hd"] * seq


def attention_train_flops(model: dict, batch: int, seq: int) -> float:
    d = _dims(model)
    return 0.5 * 12.0 * d["n_full"] * d["nh"] * d["hd"] * seq * seq * batch


def attention_train_bytes(model: dict, batch: int, seq: int,
                          elem: int = 2) -> float:
    d = _dims(model)
    return (6.0 * d["nh"] + 6.0 * d["nkv"]) * d["hd"] * batch * seq * elem \
        * d["n_full"]


def kv_bytes_per_token(model: dict, elem: int = 2) -> int:
    """K and V of one position, the full layers only."""
    d = _dims(model)
    return 2 * d["n_full"] * d["nkv"] * d["hd"] * elem


def state_bytes_per_slot(model: dict, elem: int = 2) -> int:
    """One sequence's recurrent state, all linear layers: the float32
    [nv, dk, dv] matrix and the convolution's last width-1 inputs."""
    d = _dims(model)
    return d["n_lin"] * (d["nv"] * d["dk"] * d["dv"] * 4
                         + (d["width"] - 1) * (2 * d["kd"] + d["vd"]) * elem)


def gdn_state_bytes(model: dict) -> int:
    """One sequence's float32 state matrices, all linear layers: what the
    decode step's recurrence reads once and writes once."""
    d = _dims(model)
    return d["n_lin"] * d["nv"] * d["dk"] * d["dv"] * 4


def gdn_flops(model: dict, tokens: float) -> float:
    """The delta rule's three [dk, dv] products per value head, all linear
    layers, for that many tokens."""
    d = _dims(model)
    return 3.0 * 2.0 * d["dk"] * d["dv"] * d["nv"] * d["n_lin"] * tokens


def expert_bytes(model: dict, elem: int = 2) -> int:
    """One expert's three matrices."""
    return expert_params(model) * elem


def moe_flops(model: dict, assignments: float) -> float:
    """The grouped matmul's operations for that many (token, held expert)
    assignments."""
    return 2.0 * expert_params(model) * assignments


def experts_touched(model: dict, live_slots: float) -> float:
    """Held experts a layer expects to touch in a step of `live_slots`
    tokens under uniform routing."""
    d = _dims(model)
    return d["held"] * (1.0 - (1.0 - d["top_k"] / d["routed"]) ** live_slots)


def weight_bytes(model: dict, elem: int = 2) -> int:
    """Every parameter read once but the embedding table, of which a decode
    step reads one row per slot."""
    d = _dims(model)
    return (n_params(model) - d["v"] * d["h"]) * elem


def decode_step_bytes(model: dict, live_context_tokens: int,
                      live_slots: int, elem: int = 2) -> float:
    """Least HBM traffic of one decode step: the weights outside the routed
    experts once, the experts a step of `live_slots` tokens expects to
    touch, every live slot's state read and written, its context read and
    one position written."""
    d = _dims(model)
    dense = weight_bytes(model, elem) \
        - d["n_l"] * d["held"] * expert_bytes(model, elem)
    return dense \
        + d["n_l"] * experts_touched(model, live_slots) \
        * expert_bytes(model, elem) \
        + 2 * state_bytes_per_slot(model, elem) * live_slots \
        + kv_bytes_per_token(model, elem) * (live_context_tokens
                                             + live_slots)
