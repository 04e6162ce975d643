"""Mixture-of-Experts with expert parallelism.

Reference analog: python/paddle/incubate/distributed/models/moe/moe_layer.py
(MoELayer over global_scatter/global_gather all-to-all ops,
fluid/operators/collective/global_scatter_op.*) and gate/*.py (naive, switch,
gshard).

TPU-native: the GShard einsum formulation. Token→expert dispatch and return are
dense einsums against a [tokens, experts, capacity] one-hot dispatch tensor;
expert FFN weights carry a leading [E] dim sharded over the expert mesh axis, and
a with_sharding_constraint on the [E, C, H] dispatched activations makes XLA emit
the all-to-all over ICI — the compiled equivalent of global_scatter/global_gather.
No per-rank bookkeeping, no capacity-overflow crashes: over-capacity tokens drop
(combine weight 0) exactly as GShard specifies.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .....core.dispatch import register_op
from .....core.tensor import Tensor
from .....nn import initializer
from .....nn.layer import Layer
from .....ops._helpers import _op

__all__ = ["MoELayer", "switch_gate", "gshard_gate", "naive_gate",
           "HeldExpertsMoE", "route_topk", "collect_counters"]


def _one_hot_dispatch(gates, capacity):
    """gates: [T, E] routing probs (already top-k masked). Returns
    dispatch [T, E, C] bool-ish, combine [T, E, C] weights, aux load info."""
    T, E = gates.shape
    # position of each token within its expert's queue (tokens in order)
    chosen = gates > 0.0
    pos = jnp.cumsum(chosen.astype(jnp.int32), axis=0) - 1        # [T, E]
    keep = chosen & (pos < capacity)
    pos_oh = jax.nn.one_hot(jnp.where(keep, pos, capacity), capacity + 1,
                            dtype=gates.dtype)[..., :capacity]     # [T, E, C]
    dispatch = pos_oh * keep[..., None].astype(gates.dtype)
    combine = dispatch * gates[..., None]
    return dispatch, combine


def _load_balance_loss(router_probs, expert_mask):
    """Switch-transformer aux loss: E * sum_e f_e * P_e."""
    E = router_probs.shape[-1]
    density = expert_mask.mean(axis=0)           # fraction routed per expert
    density_proxy = router_probs.mean(axis=0)    # mean router prob per expert
    return jnp.sum(density * density_proxy) * E


def _moe_ffn_fwd(x, gate_w, w1, b1, w2, b2, *, top_k=2, capacity_factor=1.25,
                 expert_axis="", jitter=0.0):
    """x: [B, S, H]; gate_w: [H, E]; w1: [E, H, I]; b1: [E, I]; w2: [E, I, H];
    b2: [E, H]. Returns (y [B,S,H], aux_loss scalar)."""
    B, S, H = x.shape
    E = gate_w.shape[-1]
    T = B * S
    xt = x.reshape(T, H)
    logits = (xt @ gate_w).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                        # [T, E]
    if top_k >= E:
        topk_probs = probs
    else:
        thresh = jnp.sort(probs, axis=-1)[:, -top_k][:, None]
        topk_probs = jnp.where(probs >= thresh, probs, 0.0)
    topk_probs = topk_probs / jnp.maximum(topk_probs.sum(-1, keepdims=True),
                                          1e-9)
    if capacity_factor <= 0:
        capacity = T                                               # no dropping
    else:
        capacity = max(1, int(math.ceil(capacity_factor * top_k * T / E)))
    dispatch, combine = _one_hot_dispatch(topk_probs, capacity)
    aux = _load_balance_loss(probs, (topk_probs > 0).astype(jnp.float32))

    expert_in = jnp.einsum("tec,th->ech", dispatch.astype(x.dtype), xt)
    ex_sharding = None
    if expert_axis:
        from jax.sharding import NamedSharding, PartitionSpec as P
        from .....distributed.env import get_mesh
        mesh = get_mesh()
        if mesh is not None and mesh.shape.get(expert_axis, 1) > 1:
            ex_sharding = NamedSharding(mesh, P(expert_axis, None, None))
    if ex_sharding is not None:
        # forces the all-to-all: tokens leave their data-parallel home and land
        # on the expert's devices (global_scatter analog, compiled)
        expert_in = jax.lax.with_sharding_constraint(expert_in, ex_sharding)
    h = jax.nn.gelu(jnp.einsum("ech,ehi->eci", expert_in, w1) + b1[:, None, :],
                    approximate=True)
    expert_out = jnp.einsum("eci,eih->ech", h, w2) + b2[:, None, :]
    if ex_sharding is not None:
        expert_out = jax.lax.with_sharding_constraint(expert_out, ex_sharding)
    y = jnp.einsum("ech,tec->th", expert_out, combine.astype(x.dtype))
    return y.reshape(B, S, H), aux.astype(jnp.float32)


register_op("moe_ffn", _moe_ffn_fwd)


def naive_gate(top_k=1):
    return {"top_k": top_k, "capacity_factor": 0.0}


def switch_gate(capacity_factor=1.25):
    """Switch transformer: top-1 routing."""
    return {"top_k": 1, "capacity_factor": capacity_factor}


def gshard_gate(capacity_factor=2.0):
    """GShard: top-2 routing."""
    return {"top_k": 2, "capacity_factor": capacity_factor}


class MoELayer(Layer):
    """Expert-parallel FFN block (reference MoELayer).

    gate: "naive" (no capacity, top-1), "switch" (top-1 + capacity),
    "gshard" (top-2 + capacity), or a dict from the gate factories above.
    expert_axis: mesh axis the experts shard over ("" = no expert parallelism).
    The aux (load-balance) loss from the last forward is `self.aux_loss` —
    add `layer.aux_loss * coeff` to the training loss.
    """

    def __init__(self, d_model: int, d_hidden: int, num_experts: int,
                 gate="switch", expert_axis: str = "", name=None):
        super().__init__()
        if isinstance(gate, str):
            gate = {"naive": naive_gate(), "switch": switch_gate(),
                    "gshard": gshard_gate()}[gate]
        self._gate_cfg = dict(gate)
        self.num_experts = num_experts
        self._expert_axis = expert_axis
        normal = initializer.Normal(std=0.02)
        self.gate_weight = self.create_parameter(
            [d_model, num_experts], default_initializer=normal)
        self.w1 = self.create_parameter([num_experts, d_model, d_hidden],
                                        default_initializer=normal)
        self.b1 = self.create_parameter([num_experts, d_hidden], is_bias=True)
        self.w2 = self.create_parameter([num_experts, d_hidden, d_model],
                                        default_initializer=normal)
        self.b2 = self.create_parameter([num_experts, d_model], is_bias=True)
        self.aux_loss: Optional[Tensor] = None
        if expert_axis:
            self._place_experts()

    def _place_experts(self):
        from .....distributed.env import get_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = get_mesh()
        if mesh is None or mesh.shape.get(self._expert_axis, 1) <= 1:
            return
        for p in (self.w1, self.b1, self.w2, self.b2):
            spec = P(self._expert_axis, *([None] * (p.ndim - 1)))
            p._data = jax.device_put(p.value(), NamedSharding(mesh, spec))

    def forward(self, x):
        y, aux = _op("moe_ffn", x, self.gate_weight, self.w1, self.b1,
                     self.w2, self.b2, expert_axis=self._expert_axis,
                     **self._gate_cfg)
        self.aux_loss = aux
        return y


from .held import HeldExpertsMoE, collect_counters, route_topk  # noqa: E402,F401
