"""Operations and bytes the MODEL needs, from its shapes alone. These are
the benchmark's yardstick: they never depend on what implements a step, so
a kernel that replaces an XLA fusion leaves every share defined.

`train_flops_per_token` is copied from
`paddle_tpu/monitor/goodput.py::analytic_train_flops_per_token` as
`bench.py` feeds it (matmul parameters only; see PERF.md, Open questions).
"""
from __future__ import annotations


def matmul_params(model: dict) -> int:
    """Parameters that sit in a matmul: 12*L*H^2 of the blocks (qkv 3H^2,
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


def weight_bytes(model: dict, n_params: int, elem: int = 2) -> int:
    """Every parameter read once. The position table is left out: a
    decode step reads one row of it per slot."""
    pos = model["max_position_embeddings"] * model["hidden_size"]
    return (n_params - pos) * elem


def decode_step_bytes(model: dict, n_params: int, live_context_tokens: int,
                      live_slots: int, elem: int = 2) -> float:
    """Least HBM traffic of one decode step: the weights once, every live
    slot's context read once, one new position's K and V written per
    slot."""
    return weight_bytes(model, n_params, elem) \
        + kv_bytes_per_token(model, elem) * (live_context_tokens
                                             + live_slots)


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak_flops: float, peak_bytes: float):
    """(share in %, which bound applies) of the least time the chip could
    take over the time it took."""
    t_f, t_b = flops / peak_flops, nbytes / peak_bytes
    bound = "compute" if t_f >= t_b else "memory"
    return 100.0 * max(t_f, t_b) / seconds, bound
