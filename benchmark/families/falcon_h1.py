"""The `falcon_h1` family (`paddle_tpu.models.falcon_h1`; plain reference in
`reference/falcon_h1.py`): what the harness asks of a model family, for a
decoder whose every block runs a Mamba-2 mixer and a grouped-query
attention mixer side by side, of which THIS CHIP HOLDS A SHARE (a pipeline
stage's layers, a slice of the vocabulary).

  weights   `shapes`, `n_params`, `make`: ONE ARRAY A PROGRAM LEAF (layer
            i's under `l<i>.<name>`; no stacks, so `system.assign` hands the
            program the arrays themselves and set-up holds the weights once)
  program   `build`, `leaf_map`, `FUSED`
  counts    from shapes alone and for the share held: `matmul_params`,
            `forward_flops`, `kv_bytes_per_token`, `weight_bytes`,
            `decode_step_bytes`, the training counts, and for this family's
            own readers `state_bytes_per_slot`, `ssd_state_bytes`,
            `ssd_flops`
"""
from __future__ import annotations

import math

from .. import weights
from ..reference.falcon_h1 import BLOCK_KEYS, key

# ------------------------------------------------------------- weights


def _dims(m: dict) -> dict:
    nh, hd = m["mamba_n_heads"], m["mamba_d_head"]
    gn = m["mamba_n_groups"] * m["mamba_d_state"]
    return dict(h=m["hidden_size"], v=m["vocab_size"],
                n_l=m["num_hidden_layers"], i=m["intermediate_size"],
                nh=nh, hd=hd, n=m["mamba_d_state"], w=nh * hd, gn=gn,
                channels=nh * hd + 2 * gn, width=m["mamba_d_conv"],
                nq=m["num_attention_heads"], nkv=m["num_key_value_heads"],
                ad=m["head_dim"])


def _block_shapes(d: dict) -> dict:
    h, w, att = d["h"], d["w"], d["nq"] * d["ad"]
    kv = d["nkv"] * d["ad"]
    return {
        "ln1": (h,), "ln2": (h,),
        "ssm_in": (h, w + d["channels"] + d["nh"]),
        "ssm_conv": (d["channels"], d["width"]),
        "ssm_conv_b": (d["channels"],), "ssm_dt": (d["nh"],),
        "ssm_alog": (d["nh"],), "ssm_d": (d["nh"],), "ssm_norm": (w,),
        "ssm_out": (w, h),
        "att_q": (h, att), "att_k": (h, kv), "att_v": (h, kv),
        "att_o": (att, h),
        "mlp_gate": (h, d["i"]), "mlp_up": (h, d["i"]),
        "mlp_down": (d["i"], h),
    }


def shapes(model: dict) -> dict:
    d = _dims(model)
    out = {"embed": (d["v"], d["h"]), "head": (d["h"], d["v"]),
           "norm_f": (d["h"],)}
    block = _block_shapes(d)
    assert tuple(block) == BLOCK_KEYS
    for i in range(d["n_l"]):
        out.update({key(i, k): s for k, s in block.items()})
    return out


def n_params(model: dict) -> int:
    return sum(math.prod(s) for s in shapes(model).values())


def _recipe(model: dict) -> dict:
    """{array name within a block or at the top: (mean, std)}: matrices
    scaled so that, WITH the configuration's multipliers in place, every
    projection's output, the hidden stream (each mixer and the feed-forward
    add about 0.3 a block) and the logits (spread about 1.5) stay of order
    1; what the program initialises to a constant gets small random values,
    so a dropped term shows."""
    d = _dims(model)
    rt = math.sqrt
    h = d["h"]
    mup = model["ssm_multipliers"]
    mup_mid = math.exp(sum(math.log(x) for x in mup) / len(mup))
    a_in = model["attention_in_multiplier"]
    gate_mult, down_mult = model["mlp_multipliers"]
    # scores spread about 1.5: h * std^2 * a_in^2 * key_multiplier
    qk = rt(1.5 / (h * a_in ** 2 * model["key_multiplier"]))
    return {
        "embed": (0.0, 1.0 / model["embedding_multiplier"]),
        "head": (0.0, 1.5 / (rt(h) * model["lm_head_multiplier"])),
        "norm_f": (1.0, 0.1), "ln1": (1.0, 0.1), "ln2": (1.0, 0.1),
        "ssm_in": (0.0, 1.0 / (model["ssm_in_multiplier"] * rt(h)
                               * mup_mid)),
        "ssm_conv": (0.0, 0.3), "ssm_conv_b": (0.0, 0.1),
        "ssm_dt": (-2.0, 0.3), "ssm_alog": (0.0, 0.3), "ssm_d": (1.0, 0.1),
        "ssm_norm": (1.0, 0.1),
        "ssm_out": (0.0, 0.3 / (rt(d["w"]) * model["ssm_out_multiplier"])),
        "att_q": (0.0, qk), "att_k": (0.0, qk),
        "att_v": (0.0, 1.0 / (rt(h) * a_in)),
        "att_o": (0.0, 0.6 / (rt(d["nq"] * d["ad"])
                              * model["attention_out_multiplier"])),
        "mlp_gate": (0.0, 1.0 / (rt(h) * gate_mult)),
        "mlp_up": (0.0, 1.0 / rt(h)),
        "mlp_down": (0.0, 0.75 / (rt(d["i"]) * down_mult)),
    }


def make(model: dict, seed: int, dtype="bfloat16"):
    _program()      # a program without this family fails before 6 GB are drawn
    table = _recipe(model)
    return weights.draw(shapes(model), lambda name: table[name.split(".")[-1]],
                        seed, dtype)


# ------------------------------------------------------------- program

_BLOCK = {
    "ln1": "input_layernorm", "ln2": "pre_ff_layernorm",
    "ssm_in": "mamba.in_proj", "ssm_conv": "mamba.conv1d",
    "ssm_conv_b": "mamba.conv_bias", "ssm_dt": "mamba.dt_bias",
    "ssm_alog": "mamba.A_log", "ssm_d": "mamba.D", "ssm_norm": "mamba.norm",
    "ssm_out": "mamba.out_proj",
    "att_q": "self_attn.q_proj", "att_k": "self_attn.k_proj",
    "att_v": "self_attn.v_proj", "att_o": "self_attn.o_proj",
    "mlp_gate": "feed_forward.gate_proj", "mlp_up": "feed_forward.up_proj",
    "mlp_down": "feed_forward.down_proj",
}
_TOP = {"embed": "model.embed_tokens", "head": "lm_head",
        "norm_f": "model.final_layernorm"}
FUSED = {}          # every leaf is judged whole


def leaf_map(model: dict) -> dict:
    """{program leaf name: (the array's key, None)}: no leaf is a slice of
    a stack."""
    out = {name: (k, None) for k, name in _TOP.items()}
    for i in range(model["num_hidden_layers"]):
        for k, name in _BLOCK.items():
            out[f"model.layers.{i}.{name}"] = (key(i, k), None)
    return out


def _program():
    """The program's classes for this family; a commit that lacks them
    fails here, naming the module."""
    from paddle_tpu.models.falcon_h1 import (FalconH1Config,
                                             FalconH1ForCausalLM)
    return FalconH1Config, FalconH1ForCausalLM


def build(cfg: dict):
    """`FalconH1ForCausalLM` at the configuration's sizes. Its initial
    arrays are made on the HOST (the benchmark's replace every one at once,
    and a second copy of the weights does not fit beside them)."""
    import jax
    config, lm = _program()
    with jax.default_device(jax.devices("cpu")[0]):
        return lm(config(dtype=cfg["dtype"], **cfg["model"]))


# -------------------------------------------------------------- counts

def _layer_matmul_params(d: dict) -> int:
    h, att, kv = d["h"], d["nq"] * d["ad"], d["nkv"] * d["ad"]
    return h * (d["w"] + d["channels"] + d["nh"]) + d["w"] * h \
        + h * (att + 2 * kv) + att * h + 3 * h * d["i"]


def matmul_params(model: dict) -> int:
    """Parameters a token multiplies: both mixers' projections and the
    feed-forward of every layer, and the head."""
    d = _dims(model)
    return d["n_l"] * _layer_matmul_params(d) + d["h"] * d["v"]


def forward_flops(model: dict, new_tokens: int, context_tokens: int) -> float:
    """2 per matmul parameter per token; 4 x heads x width per (query, key)
    pair a layer; the state-space recurrence's products per token."""
    d = _dims(model)
    return 2.0 * matmul_params(model) * new_tokens \
        + 4.0 * d["n_l"] * d["nq"] * d["ad"] * context_tokens \
        + ssd_flops(model, new_tokens)


def train_flops_per_token(model: dict, seq: int) -> float:
    d = _dims(model)
    return 3.0 * forward_flops(model, 1, 0) \
        + 0.5 * 12.0 * d["n_l"] * d["nq"] * d["ad"] * seq


def attention_train_flops(model: dict, batch: int, seq: int) -> float:
    d = _dims(model)
    return 0.5 * 12.0 * d["n_l"] * d["nq"] * d["ad"] * seq * seq * batch


def attention_train_bytes(model: dict, batch: int, seq: int,
                          elem: int = 2) -> float:
    d = _dims(model)
    return (6.0 * d["nq"] + 6.0 * d["nkv"]) * d["ad"] * batch * seq * elem \
        * d["n_l"]


def kv_bytes_per_token(model: dict, elem: int = 2) -> int:
    """K and V of one position, every layer."""
    d = _dims(model)
    return 2 * d["n_l"] * d["nkv"] * d["ad"] * elem


def state_bytes_per_slot(model: dict, elem: int = 2) -> int:
    """One sequence's recurrent state, all layers: the float32 [heads,
    d_state, d_head] matrices and the convolution's last width-1 inputs."""
    d = _dims(model)
    return ssd_state_bytes(model) \
        + d["n_l"] * (d["width"] - 1) * d["channels"] * elem


def ssd_state_bytes(model: dict) -> int:
    """One sequence's float32 state matrices, all layers: what the decode
    step's recurrence reads once and writes once."""
    d = _dims(model)
    return d["n_l"] * d["nh"] * d["hd"] * d["n"] * 4


def ssd_flops(model: dict, tokens: float) -> float:
    """The recurrence's products for that many tokens, all layers: per
    element of a head's [d_head, d_state] state the decay, the input's
    outer product and its sum, and the output's product and its sum."""
    d = _dims(model)
    return 6.0 * d["nh"] * d["hd"] * d["n"] * d["n_l"] * tokens


def weight_bytes(model: dict, elem: int = 2) -> int:
    """Every parameter read once but the embedding table, of which a decode
    step reads one row per slot."""
    d = _dims(model)
    return (n_params(model) - d["v"] * d["h"]) * elem


def decode_step_bytes(model: dict, live_context_tokens: int,
                      live_slots: int, elem: int = 2) -> float:
    """Least HBM traffic of one decode step: the weights once, every live
    slot's state read and written, its context read and one position
    written."""
    return weight_bytes(model, elem) \
        + 2 * state_bytes_per_slot(model, elem) * live_slots \
        + kv_bytes_per_token(model, elem) * (live_context_tokens
                                             + live_slots)
