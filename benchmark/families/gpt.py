"""The `gpt` family (`paddle_tpu.models.gpt`; plain reference in
`reference/gpt.py`): what the harness asks of a model family, found by the
`family` a configuration names.

  weights   `shapes`, `n_params`, `make`: the stacked arrays, from the seed
  program   `build`: the program's model at the configuration's sizes;
            `leaf_map`: its leaves against the stacked arrays; `FUSED`:
            stacked leaves judged by their parts
  counts    `matmul_params`, `forward_flops`, `train_flops_per_token`,
            `attention_train_flops`, `attention_train_bytes`,
            `kv_bytes_per_token`, `weight_bytes`, `decode_step_bytes`: the
            operations and bytes the MODEL needs, from its shapes alone

`train_flops_per_token` is copied from
`paddle_tpu/monitor/goodput.py::analytic_train_flops_per_token` as
`bench.py` feeds it (matmul parameters only; see PERF.md, Open questions).
"""
from __future__ import annotations

import math

from .. import weights

# ------------------------------------------------------------- weights


def shapes(model: dict) -> dict:
    """{stacked key: shape} for a configuration's `model` group."""
    v, h, n_l = model["vocab_size"], model["hidden_size"], model["num_layers"]
    p, i = model["max_position_embeddings"], 4 * model["hidden_size"]
    return {
        "wte": (v, h), "wpe": (p, h), "lnf_w": (h,), "lnf_b": (h,),
        "ln1_w": (n_l, h), "ln1_b": (n_l, h), "qkv_w": (n_l, h, 3 * h),
        "qkv_b": (n_l, 3 * h), "proj_w": (n_l, h, h), "proj_b": (n_l, h),
        "ln2_w": (n_l, h), "ln2_b": (n_l, h), "fc1_w": (n_l, h, i),
        "fc1_b": (n_l, i), "fc2_w": (n_l, i, h), "fc2_b": (n_l, h),
    }


def n_params(model: dict) -> int:
    return sum(math.prod(s) for s in shapes(model).values())


def make(model: dict, seed: int, dtype="bfloat16"):
    """GPT-3 recipe: N(0, 0.02) matrices and embeddings, residual-out
    projections scaled by 1/sqrt(2L). Biases and LayerNorm parameters are
    given small random values too (the program initialises them to 0 and
    1): a check on all-zero biases would not see a bias that is dropped."""
    std = 0.02
    resid = std / math.sqrt(2.0 * model["num_layers"])

    def recipe(name):
        if name in ("proj_w", "fc2_w"):
            return 0.0, resid
        if name.endswith("_w") and name.startswith("ln"):
            return 1.0, std
        return 0.0, std

    return weights.draw(shapes(model), recipe, seed, dtype)


# ------------------------------------------------------------- program

# stacked reference key -> the program's leaf name inside one block
_BLOCK_LEAVES = {
    "ln1_w": "ln_1.weight", "ln1_b": "ln_1.bias",
    "qkv_w": "attn.qkv_proj.weight", "qkv_b": "attn.qkv_proj.bias",
    "proj_w": "attn.out_proj.weight", "proj_b": "attn.out_proj.bias",
    "ln2_w": "ln_2.weight", "ln2_b": "ln_2.bias",
    "fc1_w": "mlp.fc_in.weight", "fc1_b": "mlp.fc_in.bias",
    "fc2_w": "mlp.fc_out.weight", "fc2_b": "mlp.fc_out.bias",
}
_TOP_LEAVES = {"wte": "gpt.wte.weight", "wpe": "gpt.wpe.weight",
               "lnf_w": "gpt.ln_f.weight", "lnf_b": "gpt.ln_f.bias"}

# the fused qkv bias is compared by its q, k and v thirds: the key third
# has no gradient under softmax and must not hide in the other two
FUSED = {"qkv_b": ("q", "k", "v")}


def leaf_map(model: dict) -> dict:
    """{program leaf name: (stacked key, layer index or None)}."""
    out = {name: (key, None) for key, name in _TOP_LEAVES.items()}
    for i in range(model["num_layers"]):
        for key, name in _BLOCK_LEAVES.items():
            out[f"gpt.h.{i}.{name}"] = (key, i)
    return out


def build(cfg: dict):
    """`GPTForCausalLM` at the configuration's sizes."""
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    return GPTForCausalLM(GPTConfig(hidden_dropout_prob=0.0,
                                    attention_dropout_prob=0.0,
                                    **cfg["model"]))


# -------------------------------------------------------------- counts

def matmul_params(model: dict) -> int:
    """Parameters a token multiplies: 12*L*H^2 of the blocks (qkv 3H^2,
    out H^2, MLP 8H^2) and the tied head's V*H. Embedding gathers, biases
    and LayerNorms do no matmul work."""
    h, n_l = model["hidden_size"], model["num_layers"]
    return 12 * n_l * h * h + model["vocab_size"] * h


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward + backward FLOPs of one trained token: 6 per matmul
    parameter, plus causal attention's two S x S products, 12*L*H*S for
    the full square, halved because the mask makes half of it unneeded.
    Recomputed operations do not count."""
    h, n_l = model["hidden_size"], model["num_layers"]
    return 6.0 * matmul_params(model) + 0.5 * 12.0 * n_l * h * seq


def attention_train_flops(model: dict, batch: int, seq: int) -> float:
    """Causal attention's share of a step, forward and backward, all
    layers: (QK^T and PV) x 2 FLOPs x 3 (fwd + 2 bwd) x half the square."""
    h, n_l = model["hidden_size"], model["num_layers"]
    return 0.5 * 12.0 * n_l * h * seq * seq * batch


def attention_train_bytes(model: dict, batch: int, seq: int,
                          elem: int = 2) -> float:
    """Least HBM traffic of attention in a step: forward reads q, k, v and
    writes o; backward reads q, k, v, o, do and writes dq, dk, dv."""
    h, n_l = model["hidden_size"], model["num_layers"]
    return 12.0 * batch * seq * h * elem * n_l


def forward_flops(model: dict, new_tokens: int, context_tokens: int) -> float:
    """Forward FLOPs of processing `new_tokens` positions whose attention
    reads `context_tokens` cached positions IN TOTAL (summed over the new
    positions): 2 per matmul parameter per token, 4*H per (query, key)
    pair per layer."""
    h, n_l = model["hidden_size"], model["num_layers"]
    return 2.0 * matmul_params(model) * new_tokens \
        + 4.0 * n_l * h * context_tokens


def kv_bytes_per_token(model: dict, elem: int = 2) -> int:
    """K and V of one position, all layers."""
    return 2 * model["num_layers"] * model["hidden_size"] * elem


def weight_bytes(model: dict, elem: int = 2) -> int:
    """Every parameter read once. The position table is left out: a
    decode step reads one row of it per slot."""
    pos = model["max_position_embeddings"] * model["hidden_size"]
    return (n_params(model) - pos) * elem


def decode_step_bytes(model: dict, live_context_tokens: int,
                      live_slots: int, elem: int = 2) -> float:
    """Least HBM traffic of one decode step: the weights once, every live
    slot's context read once, one new position's K and V written per
    slot."""
    return weight_bytes(model, elem) \
        + kv_bytes_per_token(model, elem) * (live_context_tokens
                                             + live_slots)
