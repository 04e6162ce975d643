"""The `longcat_flash` family (`paddle_tpu.models.longcat_flash`; plain
reference in `reference/longcat_flash.py`): what the harness asks of a model
family, for a decoder whose every block runs two latent-attention
sublayers, two dense feed-forwards and one shortcut-connected expert layer
with zero-compute experts, of which THIS CHIP HOLDS A SHARE (a pipeline
stage's layers, `num_experts` of `router_experts` real experts, a
slice of the vocabulary).

  weights   `shapes`, `n_params`, `make`: ONE ARRAY A PROGRAM LEAF (layer
            i's under `l<i>.<name>`; no stacks over layers, so
            `system.assign` hands the program the arrays themselves and
            set-up holds the weights once)
  program   `build`, `leaf_map`, `FUSED`
  counts    from shapes alone and for the share held: `matmul_params` (a
            zero expert counted 0), `forward_flops`, `kv_bytes_per_token`,
            `weight_bytes`, `decode_step_bytes`, the training counts, and
            for this family's own readers `expert_bytes`, `moe_flops`,
            `experts_touched`, `mla_flops`, `mla_bytes`
"""
from __future__ import annotations

import math

from .. import weights
from ..reference.longcat_flash import BLOCK_KEYS, key

# ------------------------------------------------------------- weights


def _dims(m: dict) -> dict:
    nope, rot, vd = m["qk_nope_head_dim"], m["qk_rope_head_dim"], \
        m["v_head_dim"]
    return dict(h=m["hidden_size"], v=m["vocab_size"],
                n_l=m["num_hidden_layers"], nh=m["num_attention_heads"],
                rq=m["q_lora_rank"],
                r=m["kv_lora_rank"], nope=nope, rot=rot, vd=vd,
                i=m["ffn_hidden_size"], ie=m["expert_ffn_hidden_size"],
                held=m["num_experts"],
                routed=m.get("router_experts") or m["num_experts"],
                zero=m["zero_expert_num"], top_k=m["moe_topk"])


def _block_shapes(d: dict) -> dict:
    h, nh = d["h"], d["nh"]
    out = {"n1": (h,), "n2": (h,), "n3": (h,), "n4": (h,)}
    for a in (1, 2):
        out.update({
            f"a{a}_qa": (h, d["rq"]), f"a{a}_qn": (d["rq"],),
            f"a{a}_qb": (d["rq"], nh * (d["nope"] + d["rot"])),
            f"a{a}_kva": (h, d["r"] + d["rot"]), f"a{a}_kvn": (d["r"],),
            f"a{a}_kvb": (d["r"], nh * (d["nope"] + d["vd"])),
            f"a{a}_o": (nh * d["vd"], h)})
    for a in (1, 2):
        out.update({f"f{a}_gate": (h, d["i"]), f"f{a}_up": (h, d["i"]),
                    f"f{a}_down": (d["i"], h)})
    outputs = d["routed"] + d["zero"]
    out.update({"router": (h, outputs), "router_b": (outputs,),
                "exp_gate": (d["held"], h, d["ie"]),
                "exp_up": (d["held"], h, d["ie"]),
                "exp_down": (d["held"], d["ie"], h)})
    return out


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


ROUTER_SPREAD = 1.5     # of the router's logits: top-12 of 768 then weigh
#                         about 1.5 in all WITH the factor 6
CHOICE_BIAS_STD = 1e-3  # beside a 12th-best probability of about 0.011:
#                         flips about 8 % of the choices


def _recipe(model: dict) -> dict:
    """{array name within a block or at the top: (mean, std)}: matrices
    scaled so that, WITH `routed_scaling_factor` and both `mla_scale_*`
    factors in place, every projection's output, the attention scores
    (spread about 1.5), the hidden stream (each attention and feed-forward
    add about 0.3 a sublayer, the expert layer about 0.6) and the logits
    (spread about 1.5) stay of order 1; every norm weight N(1, 0.1), so a
    dropped one shows."""
    d = _dims(model)
    rt = math.sqrt
    h = d["h"]
    q_scale = rt(h / d["rq"]) if model.get("mla_scale_q_lora", True) else 1.0
    kv_scale = rt(h / d["r"]) if model.get("mla_scale_kv_lora", True) else 1.0
    qk = rt(1.5)                # rms of a query's and of a key's entries
    norm = (1.0, 0.1)
    mla = {"qa": (0.0, 1.0 / rt(h)), "qn": norm,
           "qb": (0.0, qk / (q_scale * rt(d["rq"]))),
           "kva": (0.0, 1.0 / rt(h)), "kvn": norm,
           "kvb": (0.0, qk / (kv_scale * rt(d["r"]))),
           "o": (0.0, 0.6 / rt(d["nh"] * d["vd"]))}
    ffn = {"gate": (0.0, 1.0 / rt(h)), "up": (0.0, 1.0 / rt(h)),
           "down": (0.0, 0.5 / rt(d["i"]))}
    out = {"embed": (0.0, 1.0), "head": (0.0, 1.5 / rt(h)), "norm_f": norm,
           "n1": norm, "n2": norm, "n3": norm, "n4": norm,
           "router": (0.0, ROUTER_SPREAD / rt(h)),
           "router_b": (0.0, CHOICE_BIAS_STD),
           "exp_gate": (0.0, 1.0 / rt(h)), "exp_up": (0.0, 1.0 / rt(h)),
           "exp_down": (0.0, 1.67 / rt(d["ie"]))}
    for a in (1, 2):
        out.update({f"a{a}_{k}": v for k, v in mla.items()})
        out.update({f"f{a}_{k}": v for k, v in ffn.items()})
    return out


def make(model: dict, seed: int, dtype="bfloat16"):
    _program()      # a program without this family fails before 10 GB are drawn
    table = _recipe(model)
    return weights.draw(shapes(model), lambda name: table[name.split(".")[-1]],
                        seed, dtype)


# ------------------------------------------------------------- program

_MLA = {"qa": "q_a_proj", "qn": "q_a_layernorm", "qb": "q_b_proj",
        "kva": "kv_a_proj", "kvn": "kv_a_layernorm", "kvb": "kv_b_proj",
        "o": "o_proj"}
_FFN = {"gate": "gate_proj", "up": "up_proj", "down": "down_proj"}
_BLOCK = {"n1": "attn_norm_1", "n2": "ffn_norm_1", "n3": "attn_norm_2",
          "n4": "ffn_norm_2", "router": "mlp.gate",
          "router_b": "mlp.gate_bias", "exp_gate": "mlp.experts_gate_proj",
          "exp_up": "mlp.experts_up_proj",
          "exp_down": "mlp.experts_down_proj"}
for _a in (1, 2):
    _BLOCK.update({f"a{_a}_{k}": f"self_attn.{_a - 1}.{n}"
                   for k, n in _MLA.items()})
    _BLOCK.update({f"f{_a}_{k}": f"mlps.{_a - 1}.{n}"
                   for k, n in _FFN.items()})
_TOP = {"embed": "model.embed_tokens", "head": "lm_head",
        "norm_f": "model.norm"}
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
    from paddle_tpu.models.longcat_flash import (LongCatFlashConfig,
                                                 LongCatFlashForCausalLM)
    return LongCatFlashConfig, LongCatFlashForCausalLM


def build(cfg: dict):
    """`LongCatFlashForCausalLM` at the configuration's sizes. Its initial
    arrays are made on the HOST (the benchmark's replace every one at once,
    and a second copy of the weights does not fit beside them)."""
    import jax
    config, lm = _program()
    with jax.default_device(jax.devices("cpu")[0]):
        return lm(config(dtype=cfg["dtype"], **cfg["model"]))


# -------------------------------------------------------------- counts

def mla_params(model: dict) -> int:
    """One latent-attention sublayer's matrices."""
    d = _dims(model)
    nh = d["nh"]
    return d["h"] * d["rq"] + d["rq"] * nh * (d["nope"] + d["rot"]) \
        + d["h"] * (d["r"] + d["rot"]) \
        + d["r"] * nh * (d["nope"] + d["vd"]) + nh * d["vd"] * d["h"]


def expert_params(model: dict) -> int:
    d = _dims(model)
    return 3 * d["h"] * d["ie"]


def _dense_params(model: dict) -> int:
    """Matrices every token multiplies, the routed experts left out: both
    sublayers' projections, both feed-forwards, the router; the head."""
    d = _dims(model)
    per_layer = 2 * mla_params(model) + 2 * 3 * d["h"] * d["i"] \
        + d["h"] * (d["routed"] + d["zero"])
    return d["n_l"] * per_layer + d["h"] * d["v"]


def matmul_params(model: dict) -> float:
    """Parameters a token multiplies HERE: the dense matrices and the held
    share of its top-k choices under uniform routing over the router's
    outputs (top_k x held / (routed + zero) real experts a layer; a zero
    expert multiplies nothing and counts 0)."""
    d = _dims(model)
    return _dense_params(model) + d["n_l"] * expert_params(model) \
        * d["top_k"] * d["held"] / (d["routed"] + d["zero"])


def forward_flops(model: dict, new_tokens: int, context_tokens: int) -> float:
    """2 per matmul parameter per token; per (query, key) pair a sublayer
    the expanded form's 2 x heads x (nope + rope + v)."""
    d = _dims(model)
    return 2.0 * matmul_params(model) * new_tokens \
        + 2.0 * 2 * d["n_l"] * d["nh"] \
        * (d["nope"] + d["rot"] + d["vd"]) * context_tokens


def train_flops_per_token(model: dict, seq: int) -> float:
    d = _dims(model)
    return 3.0 * forward_flops(model, 1, 0) + 0.5 * 6.0 * 2 * d["n_l"] \
        * d["nh"] * (d["nope"] + d["rot"] + d["vd"]) * seq


def attention_train_flops(model: dict, batch: int, seq: int) -> float:
    d = _dims(model)
    return 0.5 * 6.0 * 2 * d["n_l"] * d["nh"] \
        * (d["nope"] + d["rot"] + d["vd"]) * seq * seq * batch


def attention_train_bytes(model: dict, batch: int, seq: int,
                          elem: int = 2) -> float:
    d = _dims(model)
    return 6.0 * d["nh"] * (d["nope"] + d["rot"] + d["vd"]) * batch * seq \
        * elem * 2 * d["n_l"]


def kv_bytes_per_token(model: dict, elem: int = 2) -> int:
    """One latent row `[c | k_rope]` a sublayer, two sublayers a layer."""
    d = _dims(model)
    return 2 * d["n_l"] * (d["r"] + d["rot"]) * elem


def mla_flops(model: dict, tokens: float) -> float:
    """The absorbed decode attention of ONE sublayer over that many cached
    tokens: every head's scores against a row's rank + rope lanes and its
    context over the rank lanes, one pass."""
    d = _dims(model)
    return 2.0 * tokens * d["nh"] * (d["r"] + d["rot"] + d["r"])


def mla_bytes(model: dict, tokens: float, elem: int = 2) -> float:
    """The latent rows ONE sublayer's decode attention reads for that many
    cached tokens, once."""
    d = _dims(model)
    return float(tokens) * (d["r"] + d["rot"]) * elem


def expert_bytes(model: dict, elem: int = 2) -> int:
    """One expert's three matrices."""
    return expert_params(model) * elem


def moe_flops(model: dict, assignments: float) -> float:
    """The grouped matmul's operations for that many (token, held expert)
    assignments."""
    return 2.0 * expert_params(model) * assignments


def experts_touched(model: dict, live_slots: float) -> float:
    """Held experts a layer expects to touch in a step of `live_slots`
    tokens under uniform routing over the router's outputs."""
    d = _dims(model)
    return d["held"] * (1.0 - (1.0 - d["top_k"] / (d["routed"] + d["zero"]))
                        ** live_slots)


def weight_bytes(model: dict, elem: int = 2) -> int:
    """Every parameter read once but the embedding table, of which a decode
    step reads one row per slot."""
    d = _dims(model)
    return (n_params(model) - d["v"] * d["h"]) * elem


def decode_step_bytes(model: dict, live_context_tokens: int,
                      live_slots: int, elem: int = 2) -> float:
    """Least HBM traffic of one decode step: the weights outside the routed
    experts once, the experts a step of `live_slots` tokens expects to
    touch, every live slot's latent rows read and one position written."""
    d = _dims(model)
    dense = weight_bytes(model, elem) \
        - d["n_l"] * d["held"] * expert_bytes(model, elem)
    return dense \
        + d["n_l"] * experts_touched(model, live_slots) \
        * expert_bytes(model, elem) \
        + kv_bytes_per_token(model, elem) * (live_context_tokens
                                             + live_slots)
