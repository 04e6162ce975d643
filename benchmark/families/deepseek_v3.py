"""The `deepseek_v3` family (`paddle_tpu.models.deepseek_v3`; plain
reference in `reference/deepseek_v3.py`): what the harness asks of a model
family, for DeepSeek-V3's decoder (latent attention under YaRN in every
layer; a dense SwiGLU in the leading layers, a sigmoid-routed,
group-limited expert layer with one ungated shared expert after them), of
which THIS CHIP HOLDS A SHARE (a pipeline stage's layers, `num_experts` of
`router_experts` experts, a slice of the vocabulary).

  weights   `shapes`, `n_params`, `make`: ONE ARRAY A PROGRAM LEAF (layer
            i's under `l<i>.<name>`; no stacks over layers, so set-up
            holds the weights once)
  program   `build`, `leaf_map`, `FUSED`
  counts    from shapes alone and for the share held: `matmul_params`,
            `forward_flops`, `kv_bytes_per_token`, `weight_bytes`,
            `decode_step_bytes`, the training counts the harness asks of
            every family (no cell trains this one), and for the expert and
            latent readers
            `expert_bytes`, `moe_flops`, `experts_touched`, `mla_flops`,
            `mla_bytes`
"""
from __future__ import annotations

import math

from .. import weights
from ..reference.deepseek_v3 import dense, key, layer_keys, yarn

# ------------------------------------------------------------- weights


def _dims(m: dict) -> dict:
    return dict(h=m["hidden_size"], v=m["vocab_size"],
                n_l=m["num_hidden_layers"],
                n_dense=min(m["first_k_dense_replace"],
                            m["num_hidden_layers"]),
                nh=m["num_attention_heads"], rq=m["q_lora_rank"],
                r=m["kv_lora_rank"], nope=m["qk_nope_head_dim"],
                rot=m["qk_rope_head_dim"], vd=m["v_head_dim"],
                i=m["intermediate_size"], ie=m["moe_intermediate_size"],
                held=m["num_experts"],
                routed=m.get("router_experts") or m["num_experts"],
                shared=m["n_shared_experts"], top_k=m["num_experts_per_tok"])


def _block_shapes(d: dict, is_dense: bool) -> dict:
    h, nh = d["h"], d["nh"]
    out = {"n1": (h,), "n2": (h,),
           "a_qa": (h, d["rq"]), "a_qn": (d["rq"],),
           "a_qb": (d["rq"], nh * (d["nope"] + d["rot"])),
           "a_kva": (h, d["r"] + d["rot"]), "a_kvn": (d["r"],),
           "a_kvb": (d["r"], nh * (d["nope"] + d["vd"])),
           "a_o": (nh * d["vd"], h)}
    if is_dense:
        out.update({"f_gate": (h, d["i"]), "f_up": (h, d["i"]),
                    "f_down": (d["i"], h)})
        return out
    si = d["shared"] * d["ie"]
    out.update({"router": (h, d["routed"]), "router_b": (d["routed"],),
                "exp_gate": (d["held"], h, d["ie"]),
                "exp_up": (d["held"], h, d["ie"]),
                "exp_down": (d["held"], d["ie"], h),
                "sh_gate": (h, si), "sh_up": (h, si), "sh_down": (si, h)})
    return out


def shapes(model: dict) -> dict:
    d = _dims(model)
    out = {"embed": (d["v"], d["h"]), "head": (d["h"], d["v"]),
           "norm_f": (d["h"],)}
    for i in range(d["n_l"]):
        block = _block_shapes(d, dense(model, i))
        assert tuple(block) == layer_keys(model, i)
        out.update({key(i, k): s for k, s in block.items()})
    return out


def n_params(model: dict) -> int:
    return sum(math.prod(s) for s in shapes(model).values())


ROUTER_SPREAD = 1.0     # of the router's logits: sigmoid scores 0.27-0.73
#                         for most outputs, the top-8 of 128 near 0.9
CHOICE_BIAS_STD = 1e-2  # beside the gaps between the best sigmoid scores:
#                         flips about 10 % of the choices
EXPERT_OUT = 0.6        # an expert's output rms about 0.36, so an assignment
#                         of weight 2.5 / 8 adds about 0.11 (at 1.67, 0.31 an
#                         assignment: bf16's near-tied choices among 256
#                         sigmoid outputs then moved a served logit by up to
#                         2.5, the size of a wrong token; PERF.md section 2)


def _recipe(model: dict) -> dict:
    """{array name within a block or at the top: (mean, std)}: matrices
    scaled so that, WITH YaRN's factor on the softmax scale and
    `routed_scaling_factor` in place, every projection's output, the
    attention scores (spread about 1.5), the hidden stream (attention
    adds about 0.5, dense feed-forward and shared expert about 0.3 each,
    a held expert about 0.11 an assignment) and the logits (spread about
    1.5)
    stay of order 1; every norm weight N(1, 0.1), so a dropped one
    shows."""
    d = _dims(model)
    rt = math.sqrt
    h = d["h"]
    qk = rt(1.5 / yarn(model)[1])   # rms of a query's and a key's entries
    norm = (1.0, 0.1)
    si = d["shared"] * d["ie"]
    return {"embed": (0.0, 1.0), "head": (0.0, 1.5 / rt(h)), "norm_f": norm,
            "n1": norm, "n2": norm,
            "a_qa": (0.0, 1.0 / rt(h)), "a_qn": norm,
            "a_qb": (0.0, qk / rt(d["rq"])),
            "a_kva": (0.0, 1.0 / rt(h)), "a_kvn": norm,
            "a_kvb": (0.0, qk / rt(d["r"])),
            "a_o": (0.0, 0.6 / rt(d["nh"] * d["vd"])),
            "f_gate": (0.0, 1.0 / rt(h)), "f_up": (0.0, 1.0 / rt(h)),
            "f_down": (0.0, 0.5 / rt(d["i"])),
            "router": (0.0, ROUTER_SPREAD / rt(h)),
            "router_b": (0.0, CHOICE_BIAS_STD),
            "exp_gate": (0.0, 1.0 / rt(h)), "exp_up": (0.0, 1.0 / rt(h)),
            "exp_down": (0.0, EXPERT_OUT / rt(d["ie"])),
            "sh_gate": (0.0, 1.0 / rt(h)), "sh_up": (0.0, 1.0 / rt(h)),
            "sh_down": (0.0, 0.5 / rt(si))}


def make(model: dict, seed: int, dtype="bfloat16"):
    _program()      # a program without this family fails before 9 GB are drawn
    table = _recipe(model)
    return weights.draw(shapes(model), lambda name: table[name.split(".")[-1]],
                        seed, dtype)


# ------------------------------------------------------------- program

_MLA = {"a_qa": "self_attn.q_a_proj", "a_qn": "self_attn.q_a_layernorm",
        "a_qb": "self_attn.q_b_proj", "a_kva": "self_attn.kv_a_proj",
        "a_kvn": "self_attn.kv_a_layernorm", "a_kvb": "self_attn.kv_b_proj",
        "a_o": "self_attn.o_proj", "n1": "input_layernorm",
        "n2": "post_attention_layernorm"}
_DENSE = dict(_MLA, f_gate="mlp.gate_proj", f_up="mlp.up_proj",
              f_down="mlp.down_proj")
_ROUTED = dict(_MLA, router="mlp.gate", router_b="mlp.gate_bias",
               exp_gate="mlp.experts_gate_proj", exp_up="mlp.experts_up_proj",
               exp_down="mlp.experts_down_proj",
               sh_gate="mlp.shared_gate_proj", sh_up="mlp.shared_up_proj",
               sh_down="mlp.shared_down_proj")
_TOP = {"embed": "model.embed_tokens", "head": "lm_head",
        "norm_f": "model.norm"}
FUSED = {}          # every leaf is judged whole


def leaf_map(model: dict) -> dict:
    """{program leaf name: (the array's key, None)}: no leaf is a slice of
    a stack."""
    out = {name: (k, None) for k, name in _TOP.items()}
    for i in range(model["num_hidden_layers"]):
        for k, name in (_DENSE if dense(model, i) else _ROUTED).items():
            out[f"model.layers.{i}.{name}"] = (key(i, k), None)
    return out


def _program():
    """The program's classes for this family; a commit that lacks them
    fails here, naming the module."""
    from paddle_tpu.models.deepseek_v3 import (DeepseekV3Config,
                                               DeepseekV3ForCausalLM)
    return DeepseekV3Config, DeepseekV3ForCausalLM


def build(cfg: dict):
    """`DeepseekV3ForCausalLM` at the configuration's sizes. Its initial
    arrays are made on the HOST (the benchmark's replace every one at once,
    and a second copy of the weights does not fit beside them)."""
    import jax
    config, lm = _program()
    with jax.default_device(jax.devices("cpu")[0]):
        return lm(config(dtype=cfg["dtype"], **cfg["model"]))


# -------------------------------------------------------------- counts

def mla_params(model: dict) -> int:
    """One latent-attention layer's matrices."""
    d = _dims(model)
    nh = d["nh"]
    return d["h"] * d["rq"] + d["rq"] * nh * (d["nope"] + d["rot"]) \
        + d["h"] * (d["r"] + d["rot"]) \
        + d["r"] * nh * (d["nope"] + d["vd"]) + nh * d["vd"] * d["h"]


def expert_params(model: dict) -> int:
    d = _dims(model)
    return 3 * d["h"] * d["ie"]


def _dense_params(model: dict) -> int:
    """Matrices every token multiplies, the routed experts left out: every
    layer's attention, the leading layers' feed-forward, the routed layers'
    router and shared expert; the head."""
    d = _dims(model)
    routed_layers = d["n_l"] - d["n_dense"]
    return d["n_l"] * mla_params(model) + d["n_dense"] * 3 * d["h"] * d["i"] \
        + routed_layers * (d["h"] * d["routed"]
                           + d["shared"] * expert_params(model)) \
        + d["h"] * d["v"]


def matmul_params(model: dict) -> float:
    """Parameters a token multiplies HERE: the dense matrices and the held
    share of its top-k choices under uniform routing (top_k x held /
    routed experts a routed layer; the group limit moves which experts,
    not how many in expectation)."""
    d = _dims(model)
    return _dense_params(model) + (d["n_l"] - d["n_dense"]) \
        * expert_params(model) * d["top_k"] * d["held"] / d["routed"]


def forward_flops(model: dict, new_tokens: int, context_tokens: int) -> float:
    """2 per matmul parameter per token; per (query, key) pair a layer the
    expanded form's 2 x heads x (nope + rope + v)."""
    d = _dims(model)
    return 2.0 * matmul_params(model) * new_tokens \
        + d["n_l"] * _pair_flops(model) * context_tokens


def _pair_flops(model: dict) -> float:
    """Per (query, key) pair of one layer: the expanded form's 2 x heads x
    (nope + rope + v)."""
    d = _dims(model)
    return 2.0 * d["nh"] * (d["nope"] + d["rot"] + d["vd"])


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward and backward (3x the forward's matmuls), causal attention
    over half of `seq` keys on average."""
    d = _dims(model)
    return 3.0 * forward_flops(model, 1, 0) \
        + 0.5 * 3.0 * d["n_l"] * _pair_flops(model) * seq


def attention_train_flops(model: dict, batch: int, seq: int) -> float:
    d = _dims(model)
    return 0.5 * 3.0 * d["n_l"] * _pair_flops(model) * seq * seq * batch


def attention_train_bytes(model: dict, batch: int, seq: int,
                          elem: int = 2) -> float:
    d = _dims(model)
    return 6.0 * d["nh"] * (d["nope"] + d["rot"] + d["vd"]) * batch * seq \
        * elem * d["n_l"]


def kv_bytes_per_token(model: dict, elem: int = 2) -> int:
    """One latent row `[c | k_rope]` a layer."""
    d = _dims(model)
    return d["n_l"] * (d["r"] + d["rot"]) * elem


def mla_flops(model: dict, tokens: float) -> float:
    """The absorbed decode attention of ONE layer over that many cached
    tokens: every head's scores against a row's rank + rope lanes and its
    context over the rank lanes, one pass."""
    d = _dims(model)
    return 2.0 * tokens * d["nh"] * (d["r"] + d["rot"] + d["r"])


def mla_bytes(model: dict, tokens: float, elem: int = 2) -> float:
    """The latent rows ONE layer's decode attention reads for that many
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
    """Held experts a routed layer expects to touch in a step of
    `live_slots` tokens under uniform routing (each expert taken by a token
    with chance top_k / routed)."""
    d = _dims(model)
    return d["held"] * (1.0 - (1.0 - d["top_k"] / d["routed"]) ** live_slots)


def weight_bytes(model: dict, elem: int = 2) -> int:
    """Every parameter read once but the embedding table, of which a decode
    step reads one row per slot."""
    d = _dims(model)
    return (n_params(model) - d["v"] * d["h"]) * elem


def decode_step_bytes(model: dict, live_context_tokens: int,
                      live_slots: int, elem: int = 2) -> float:
    """Least HBM traffic of one decode step: the weights outside the held
    experts once, the experts a step of `live_slots` tokens expects to
    touch, every live slot's latent rows read and one position written."""
    d = _dims(model)
    n_moe = d["n_l"] - d["n_dense"]
    return weight_bytes(model, elem) \
        - n_moe * d["held"] * expert_bytes(model, elem) \
        + n_moe * experts_touched(model, live_slots) \
        * expert_bytes(model, elem) \
        + kv_bytes_per_token(model, elem) * (live_context_tokens
                                             + live_slots)
