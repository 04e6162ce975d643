"""Compiled pipeline schedule: shard_map + ppermute ring over the "pipe" axis.

Reference analog: PipelineParallel.forward_backward_pipeline (1F1B,
fleet/meta_parallel/pipeline_parallel.py:117) and PipelineParallelWithInterleave
(:461, virtual stages) with p2p_communication.py send/recv. There, a Python
scheduler issues per-microbatch sends/recvs between rank processes.

TPU-native: the ENTIRE schedule — fill, steady state, drain, and (with
num_virtual > 1) the interleaved/circular rotation — is one XLA executable:
a lax.scan over schedule ticks inside shard_map, with lax.ppermute moving
activations stage→stage over ICI. Every device computes every tick (bubbles are
masked), the backward pipeline falls out of jax.grad reversing the scan+permutes,
and XLA overlaps the permute DMA of tick t with compute of tick t+1 — the
overlap the reference hand-builds with batch_isend_irecv.

Constraints (same as any ring pipeline): stage_fn must be shape-preserving
([mb, ...] -> [mb, ...]) so activations can rotate; embedding/head live outside
the ring. Microbatch count M must be >= stage count S when num_virtual > 1
(wrap-around latency M-S+1 must be positive).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["pipeline_apply", "CompiledPipeline"]


def _ring_body(w_local, xs, stage_fn, S: int, M: int, V: int, axis: str):
    """Runs on ONE device (inside shard_map). w_local leaves: [1, V, ...]."""
    s = jax.lax.axis_index(axis)
    w_local = jax.tree_util.tree_map(lambda l: l[0], w_local)  # [V, ...]
    T = V * M + S - 1
    buf = jnp.zeros((M,) + xs.shape[1:], xs.dtype)      # per-microbatch inbox
    outputs = jnp.zeros((M,) + xs.shape[1:], xs.dtype)
    # the carry holds per-DEVICE state (each stage's inbox differs), so mark it
    # varying over the pipe axis for the typed shard_map carry check
    buf = jax.lax.pcast(buf, (axis,), to="varying")
    outputs = jax.lax.pcast(outputs, (axis,), to="varying")

    def tick(carry, t):
        buf, outputs = carry
        pos = t - s
        valid = (pos >= 0) & (pos < V * M)
        v = jnp.clip(pos // M, 0, V - 1)
        m = jnp.clip(pos % M, 0, M - 1)
        first_feed = (s == 0) & (v == 0)
        x_in = jnp.where(first_feed, xs[m], buf[m])
        w_v = jax.tree_util.tree_map(lambda l: l[v], w_local)
        y = stage_fn(w_v, x_in)
        y = jnp.where(valid, y, jnp.zeros_like(y))
        # final global stage (device S-1, virtual V-1) writes the output slot
        is_out = valid & (s == S - 1) & (v == V - 1)
        outputs = outputs.at[m].set(jnp.where(is_out, y, outputs[m]))
        # rotate: stage s -> s+1 (cyclic; the wrap edge feeds virtual stage v+1)
        y_recv = jax.lax.ppermute(y, axis, [(i, (i + 1) % S) for i in range(S)])
        prev = (s - 1) % S
        pos_in = t - prev
        v_in = pos_in // M
        m_in = jnp.clip(pos_in % M, 0, M - 1)
        valid_in = (pos_in >= 0) & (pos_in < V * M) & \
            ~((s == 0) & (v_in == V - 1))   # drop the ring's final outputs
        buf = buf.at[m_in].set(jnp.where(valid_in, y_recv, buf[m_in]))
        return (buf, outputs), None

    (buf, outputs), _ = jax.lax.scan(tick, (buf, outputs), jnp.arange(T))
    # only device S-1 holds real outputs (others wrote zeros) — psum replicates
    return jax.lax.psum(outputs, axis)


def pipeline_apply(stage_params: Any, xs: jnp.ndarray,
                   stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
                   mesh: Mesh, axis: str = "pipe", num_virtual: int = 1):
    """Apply S*num_virtual pipeline stages to M microbatches, compiled.

    stage_params: pytree with leading dims [S*num_virtual, ...] per leaf
    (global stage g = v*S + s runs as virtual stage v on device s).
    xs: [M, mb, ...] microbatched inputs (replicated).
    Returns [M, mb, ...] outputs, replicated.
    """
    S = mesh.shape[axis]
    M = int(xs.shape[0])
    V = int(num_virtual)
    if V > 1 and M < S:
        raise ValueError(f"interleaved pipeline needs micro-batches >= stages "
                         f"(got M={M} < S={S})")

    def split_vs(leaf):
        # [V*S, ...] -> [S, V, ...]: device s owns global stages s, S+s, ...
        lead = leaf.shape[0]
        if lead != V * S:
            raise ValueError(f"stage_params leading dim {lead} != "
                             f"num_virtual*stages {V * S}")
        return jnp.swapaxes(leaf.reshape((V, S) + leaf.shape[1:]), 0, 1)

    w = jax.tree_util.tree_map(split_vs, stage_params)
    w_specs = jax.tree_util.tree_map(
        lambda l: P(axis, *([None] * (l.ndim - 1))), w)
    fn = jax.shard_map(
        partial(_ring_body, stage_fn=stage_fn, S=S, M=M, V=V, axis=axis),
        mesh=mesh, in_specs=(w_specs, P(*([None] * xs.ndim))), out_specs=P())
    return fn(w, xs)


class CompiledPipeline:
    """Convenience wrapper: jit the ring once per (shapes, loss_fn) and expose
    forward(+loss) and grads — a compiled train-side replacement for the
    reference's interleaved 1F1B scheduler."""

    def __init__(self, stage_fn, mesh: Optional[Mesh] = None, axis: str = "pipe",
                 num_virtual: int = 1, loss_fn: Optional[Callable] = None):
        from ...env import get_mesh
        self._mesh = mesh if mesh is not None else get_mesh()
        self._axis = axis
        self._V = num_virtual
        self._stage_fn = stage_fn
        self._loss_fn = loss_fn
        self._fwd = jax.jit(self._forward)
        self._grad = jax.jit(jax.value_and_grad(self._loss)) \
            if loss_fn is not None else None

    def _forward(self, stage_params, xs):
        return pipeline_apply(stage_params, xs, self._stage_fn, self._mesh,
                              self._axis, self._V)

    def _loss(self, stage_params, xs, *labels):
        out = self._forward(stage_params, xs)
        return self._loss_fn(out, *labels)

    def forward(self, stage_params, xs):
        return self._fwd(stage_params, xs)

    def loss_and_grad(self, stage_params, xs, *labels):
        if self._grad is None:
            raise ValueError("CompiledPipeline built without loss_fn")
        return self._grad(stage_params, xs, *labels)
