"""A second family made of files only: `paddle_tpu.models.llama` for the
tests (`test_bench_family.py` lays it over a copy of the tiny tree). It
gives what `benchmark/families/gpt.py` gives, for another decoder: no
stock file knows its leaves, its shapes or its arithmetic. Never a
benchmark configuration.
"""
from __future__ import annotations

import math

from .. import weights


def _dims(model: dict):
    h, n = model["hidden_size"], model["num_heads"]
    return h, model["num_layers"], model["num_kv_heads"] * (h // n), \
        model["intermediate_size"], model["vocab_size"]


def shapes(model: dict) -> dict:
    h, n_l, kvd, i, v = _dims(model)
    return {
        "emb": (v, h), "head": (h, v), "norm_w": (h,),
        "ln1_w": (n_l, h), "q_w": (n_l, h, h), "k_w": (n_l, h, kvd),
        "v_w": (n_l, h, kvd), "o_w": (n_l, h, h), "ln2_w": (n_l, h),
        "gate_w": (n_l, h, i), "up_w": (n_l, h, i), "down_w": (n_l, i, h),
    }


def n_params(model: dict) -> int:
    return sum(math.prod(s) for s in shapes(model).values())


def make(model: dict, seed: int, dtype="bfloat16"):
    std = 0.02
    resid = std / math.sqrt(2.0 * model["num_layers"])

    def recipe(name):
        if name in ("o_w", "down_w"):
            return 0.0, resid
        return (1.0, std) if name in ("ln1_w", "ln2_w", "norm_w") \
            else (0.0, std)

    return weights.draw(shapes(model), recipe, seed, dtype)


_BLOCK_LEAVES = {
    "ln1_w": "input_layernorm.weight", "q_w": "self_attn.q_proj.weight",
    "k_w": "self_attn.k_proj.weight", "v_w": "self_attn.v_proj.weight",
    "o_w": "self_attn.o_proj.weight",
    "ln2_w": "post_attention_layernorm.weight",
    "gate_w": "mlp.gate_proj.weight", "up_w": "mlp.up_proj.weight",
    "down_w": "mlp.down_proj.weight",
}
_TOP_LEAVES = {"emb": "model.embed_tokens.weight", "head": "lm_head.weight",
               "norm_w": "model.norm.weight"}
FUSED = {}          # every leaf is judged whole


def leaf_map(model: dict) -> dict:
    out = {name: (key, None) for key, name in _TOP_LEAVES.items()}
    for i in range(model["num_layers"]):
        for key, name in _BLOCK_LEAVES.items():
            out[f"model.layers.{i}.{name}"] = (key, i)
    return out


def build(cfg: dict):
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    return LlamaForCausalLM(LlamaConfig(**cfg["model"]))


# counts: grouped K/V make the K and V projections and the cache narrower
# than GPT's; the MLP has three matrices; the head is untied, so the
# embedding table is gathered from and never multiplied

def matmul_params(model: dict) -> int:
    h, n_l, kvd, i, v = _dims(model)
    return n_l * (2 * h * h + 2 * h * kvd + 3 * h * i) + h * v


def train_flops_per_token(model: dict, seq: int) -> float:
    h, n_l = model["hidden_size"], model["num_layers"]
    return 6.0 * matmul_params(model) + 0.5 * 12.0 * n_l * h * seq


def attention_train_flops(model: dict, batch: int, seq: int) -> float:
    h, n_l = model["hidden_size"], model["num_layers"]
    return 0.5 * 12.0 * n_l * h * seq * seq * batch


def attention_train_bytes(model: dict, batch: int, seq: int,
                          elem: int = 2) -> float:
    """q, o, do, dq at H a position; k, v, dk, dv at KV*D: forward reads
    q k v and writes o; backward reads q k v o do and writes dq dk dv."""
    h, n_l, kvd, _, _ = _dims(model)
    return (6.0 * h + 6.0 * kvd) * batch * seq * elem * n_l


def forward_flops(model: dict, new_tokens: int, context_tokens: int) -> float:
    h, n_l = model["hidden_size"], model["num_layers"]
    return 2.0 * matmul_params(model) * new_tokens \
        + 4.0 * n_l * h * context_tokens


def kv_bytes_per_token(model: dict, elem: int = 2) -> int:
    _, n_l, kvd, _, _ = _dims(model)
    return 2 * n_l * kvd * elem


def weight_bytes(model: dict, elem: int = 2) -> int:
    """Every parameter read once but the embedding table, of which a
    decode step reads one row per slot."""
    return (n_params(model)
            - model["vocab_size"] * model["hidden_size"]) * elem


def decode_step_bytes(model: dict, live_context_tokens: int,
                      live_slots: int, elem: int = 2) -> float:
    return weight_bytes(model, elem) \
        + kv_bytes_per_token(model, elem) * (live_context_tokens
                                             + live_slots)
