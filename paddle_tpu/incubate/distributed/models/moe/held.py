"""An expert layer that is TOLD which experts it holds: one chip's share of
an expert-parallel deployment, without its exchange.

``HeldExpertsMoE`` is built with the router's full width (``n_routed``),
``top_k``, ``norm_topk_prob`` and the range of experts that live here
(``offset``, ``count``). A call scores every token against ALL routed
experts in float32, keeps the published top-k and their weights, and returns

    the shared expert's output  +  sum over assignments to HELD experts
                                +  the zero experts' term

A router may be WIDER than the real experts: ``n_zero`` zero-compute
(identity) experts follow them, ids ``n_routed .. n_routed + n_zero - 1``
(LongCat-Flash). An assignment to one adds ``weight * x`` and multiplies
nothing, so the compute a token costs varies with its choice. They hold no
weights: every chip computes their term alike from the replicated router,
and it is added WHOLE here (counted once when shares are summed, like the
shared expert). ``choice_bias`` gives the router a per-output bias that
only steers WHICH experts are kept (top-k of ``softmax + bias``), never
their weights; ``scaling`` multiplies the kept weights (the model's
``routed_scaling_factor``).

so an assignment to an absent expert is left out: its owner adds it, on
another chip, and the partial sum is what goes on to the next layer. Summed
over the shares of a deployment, the shared expert counted once, the parts
are the uncut layer (``tests/test_qwen3_next.py`` holds them to it). No
capacity and no dropped token: the held experts' groups are multiplied by
``kernels/pallas/moe_grouped.py``. Nothing here stands in for the other
chips or their traffic; the all-to-all over an ``"expert"`` mesh axis is
the next step (ROADMAP).

Inference-only raw-array math, like the cached attention paths: the router's
auxiliary loss and a backward through the grouped matmul are not here yet.

``scoring`` may be ``"sigmoid"`` (an independent score an output), and
``n_group`` / ``topk_group`` limit a token's choice to its ``topk_group``
best of ``n_group`` equal groups of experts (DeepSeek-V3: sigmoid, 8 groups
of 32, 4 kept, top-8 renormalised, x 2.5; a deployment maps a group onto a
node, so a chip that holds part of one group is reached only by the tokens
that kept that group). A shared expert is gated by ``sigmoid(x w_s)``
(Qwen3-Next) or, without ``shared_gated``, added whole (DeepSeek-V3).

The experts are SwiGLU, or with ``poly`` (``moe_grouped.Poly``: Motif-3's
``hidden_act`` ``poly_norm``) PolyNorm on the gate branch, each expert's
and the shared expert's four numbers ``a1 a2 a3 b`` parameters of their
own, ``experts_poly [count, 4]`` and ``shared_poly [4]``.

``collect_counters()`` is how a serving executable reads the step's routing:
inside it every call adds three traced integers (assignments of valid
tokens, those that fell on held experts, held experts with at least one),
a layer with zero experts a fourth (assignments to zero experts), and a
layer with groups a fifth (over the valid tokens, the distinct groups each
token's top-k spans, summed): ``counter_names`` says which.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np

from .....nn import initializer
from .....nn.layer import Layer
from .....kernels.pallas import moe_grouped

__all__ = ["HeldExpertsMoE", "route_topk", "collect_counters"]

_COUNTERS = []                # innermost collector last


class _Counters:
    def __init__(self):
        self.items = []

    def total(self):
        """int32 [3] (to [5]: ``HeldExpertsMoE.counter_names``) summed
        over the calls recorded, or None for none."""
        if not self.items:
            return None
        return jnp.sum(jnp.stack(self.items), axis=0).astype(jnp.int32)


@contextlib.contextmanager
def collect_counters():
    c = _Counters()
    _COUNTERS.append(c)
    try:
        yield c
    finally:
        _COUNTERS.pop()


def _limit_groups(choice, n_group: int, topk_group: int):
    """``choice [T, E]`` with every output outside each token's
    ``topk_group`` best of ``n_group`` equal groups set to -inf; a group's
    score is the sum of its two largest ``choice``."""
    t, e = choice.shape
    grouped = choice.reshape(t, n_group, e // n_group)
    score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)      # [T, G]
    _, keep = jax.lax.top_k(score, topk_group)                   # [T, kg]
    kept = jnp.any(keep[..., None] == jnp.arange(n_group), axis=1)
    return jnp.where(kept[..., None], grouped, -jnp.inf).reshape(t, e)


def route_topk(x, router_w, top_k: int, norm_topk_prob: bool,
               choice_bias=None, scaling: float = 1.0,
               scoring: str = "softmax", n_group: int = 1,
               topk_group: int = 1):
    """Float32 router: ``scoring`` (softmax or sigmoid) over ALL the
    router's outputs, top-k, weights renormalised to sum 1 when the model
    says so. ``x [T, H]``, ``router_w [H, outputs]``. With ``choice_bias
    [outputs]`` the k are the top of ``score + bias`` and their weights
    still the scores' own; with ``n_group > 1`` the k are taken from the
    ``topk_group`` best of ``n_group`` equal groups of outputs (a group's
    score the sum of its two largest ``score + bias``; DeepSeek-V3's
    group-limited choice). The weights are multiplied by ``scaling``.
    Returns (ids [T, k] int32, weights [T, k] float32)."""
    logits = jnp.einsum("th,he->te", x.astype(jnp.float32),
                        router_w.astype(jnp.float32), precision="highest",
                        preferred_element_type=jnp.float32)
    if scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
    elif scoring == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"router scoring {scoring!r}: softmax or sigmoid")
    if choice_bias is None and n_group == 1:
        weights, ids = jax.lax.top_k(probs, top_k)
    else:
        choice = probs if choice_bias is None \
            else probs + choice_bias.astype(jnp.float32)
        if n_group > 1:
            choice = _limit_groups(choice, n_group, topk_group)
        _, ids = jax.lax.top_k(choice, top_k)
        weights = jnp.take_along_axis(probs, ids, axis=-1)
    if norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if scaling != 1.0:
        weights = weights * scaling
    return ids.astype(jnp.int32), weights


def _swiglu(x, gate_w, up_w, down_w):
    prec = "highest" if x.dtype == jnp.float32 else None
    hid = jax.nn.silu(jnp.dot(x, gate_w, precision=prec)) \
        * jnp.dot(x, up_w, precision=prec)
    return jnp.dot(hid, down_w, precision=prec)


def _poly_ffn(x, gate_w, up_w, down_w, coef, poly):
    """``W_d(PolyNorm(W_g x) * W_u x)``, the hidden row in float32."""
    prec = "highest" if x.dtype == jnp.float32 else None
    f32 = jnp.float32
    hid = moe_grouped.hidden(
        jnp.dot(x, gate_w, precision=prec, preferred_element_type=f32),
        jnp.dot(x, up_w, precision=prec, preferred_element_type=f32),
        coef, poly)
    return jnp.dot(hid.astype(x.dtype), down_w, precision=prec)


class HeldExpertsMoE(Layer):
    """Router over ``n_routed`` experts (and ``n_zero`` zero-compute experts
    after them; ``scoring`` softmax or sigmoid; ``n_group`` groups of which
    a token's choice keeps ``topk_group``) + the ``count`` real ones held
    here (global ids ``offset .. offset + count - 1``) + an optional shared
    expert, gated by ``sigmoid(x w_s)`` or, without ``shared_gated``,
    added whole."""

    def __init__(self, hidden_size: int, expert_width: int, n_routed: int,
                 top_k: int, *, offset: int = 0, count: int = None,
                 norm_topk_prob: bool = True, shared_width: int = 0,
                 n_zero: int = 0, choice_bias: bool = False,
                 scaling: float = 1.0, scoring: str = "softmax",
                 n_group: int = 1, topk_group: int = 1,
                 shared_gated: bool = True, poly: "moe_grouped.Poly" = None,
                 std: float = 0.02, dtype=None):
        super().__init__()
        count = n_routed if count is None else count
        if not (0 <= offset and offset + count <= n_routed and count >= 1):
            raise ValueError(f"held experts [{offset}, {offset + count}) "
                             f"do not lie inside the router's {n_routed}")
        if n_group > 1 and (n_zero or n_routed % n_group
                            or not 1 <= topk_group <= n_group
                            or top_k > topk_group * (n_routed // n_group)):
            raise ValueError(
                f"{n_routed} experts (+ {n_zero} zero) in {n_group} groups, "
                f"top-{top_k} within {topk_group} of them")
        self.n_routed, self.top_k = int(n_routed), int(top_k)
        self.offset, self.count = int(offset), int(count)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.n_zero, self.scaling = int(n_zero), float(scaling)
        self.scoring = str(scoring)
        self.n_group, self.topk_group = int(n_group), int(topk_group)
        self.poly = poly
        normal = initializer.Normal(0.0, std)

        def mat(*shape):
            return self.create_parameter(shape, dtype=dtype,
                                         default_initializer=normal)

        h, i = hidden_size, expert_width
        self.gate = mat(h, n_routed + self.n_zero)      # the router
        self.gate_bias = self.create_parameter(
            (n_routed + self.n_zero,), dtype=dtype,
            default_initializer=initializer.Constant(0.0)) \
            if choice_bias else None
        self.experts_gate_proj = mat(count, h, i)
        self.experts_up_proj = mat(count, h, i)
        self.experts_down_proj = mat(count, i, h)

        def poly_coef(*lead):       # a1 = a2 = a3 = 1/3, b = 0
            start = np.broadcast_to(np.array([1, 1, 1, 0], np.float32) / 3,
                                    lead + (4,))
            return self.create_parameter(
                lead + (4,), dtype=dtype,
                default_initializer=initializer.Assign(start.copy()))
        if poly is not None:
            self.experts_poly = poly_coef(count)
        self.shared_width = int(shared_width)
        if shared_width:
            self.shared_gate_proj = mat(h, shared_width)
            self.shared_up_proj = mat(h, shared_width)
            self.shared_down_proj = mat(shared_width, h)
            if poly is not None:
                self.shared_poly = poly_coef()
            if shared_gated:
                self.shared_expert_gate = mat(h, 1)
        self.shared_gated = bool(shared_width and shared_gated)

    @property
    def counter_names(self) -> tuple:
        """What each entry of a call's counter vector counts."""
        return ("assignments", "local", "touched") \
            + (("zero",) if self.n_zero else ()) \
            + (("groups",) if self.n_group > 1 else ())

    def apply(self, x, valid=None):
        """``x [T, H]`` raw array in the model's dtype, ``valid [T]`` bool
        (default: all). Returns [T, H] in ``x``'s dtype."""
        t = x.shape[0]
        valid = jnp.ones((t,), bool) if valid is None else valid
        with jax.named_scope("moe_route"):
            ids, weights = route_topk(
                x, self.gate.value(), self.top_k, self.norm_topk_prob,
                None if self.gate_bias is None else self.gate_bias.value(),
                self.scaling, self.scoring, self.n_group, self.topk_group)
        with jax.named_scope("moe_experts"):
            poly = {} if self.poly is None else dict(
                coef=self.experts_poly.value(), poly=self.poly)
            out, counts = moe_grouped.moe_grouped(
                x, ids, weights, valid, self.experts_gate_proj.value(),
                self.experts_up_proj.value(), self.experts_down_proj.value(),
                self.offset, **poly)
        if self.n_zero:
            with jax.named_scope("zero_experts"):
                to_zero = ids >= self.n_routed
                w_zero = jnp.sum(jnp.where(to_zero, weights, 0.0), axis=-1)
                out = out + x.astype(jnp.float32) * w_zero[:, None]
        if _COUNTERS:
            counted = [jnp.sum(valid.astype(jnp.int32)) * self.top_k,
                       jnp.sum(counts),
                       jnp.sum((counts > 0).astype(jnp.int32))]
            if self.n_zero:
                counted.append(jnp.sum(
                    (to_zero & valid[:, None]).astype(jnp.int32)))
            if self.n_group > 1:
                group = ids // (self.n_routed // self.n_group)      # [T, k]
                spans = jnp.any(group[..., None] == jnp.arange(
                    self.n_group), axis=1)                          # [T, G]
                counted.append(jnp.sum(
                    (spans & valid[:, None]).astype(jnp.int32)))
            _COUNTERS[-1].items.append(jnp.stack(counted))
        if self.shared_width:
            with jax.named_scope("shared_expert"):
                prec = "highest" if x.dtype == jnp.float32 else None
                if self.poly is None:
                    sh = _swiglu(x, self.shared_gate_proj.value(),
                                 self.shared_up_proj.value(),
                                 self.shared_down_proj.value())
                else:
                    sh = _poly_ffn(x, self.shared_gate_proj.value(),
                                   self.shared_up_proj.value(),
                                   self.shared_down_proj.value(),
                                   self.shared_poly.value(), self.poly)
                if self.shared_gated:
                    g = jax.nn.sigmoid(jnp.dot(
                        x, self.shared_expert_gate.value(),
                        precision=prec).astype(jnp.float32))
                    out = out + sh.astype(jnp.float32) * g
                else:
                    out = out + sh.astype(jnp.float32)
        return out.astype(x.dtype)

    def forward(self, x):
        from .....core.tensor import Tensor
        a = x.value()
        return Tensor(self.apply(a.reshape(-1, a.shape[-1]))
                      .reshape(a.shape))
