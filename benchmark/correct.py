"""The comparison that decides `correct`: what the timed path produced
against the plain reference, each number beside its limit. The limits are
data (`limits/<cell>.json`), set from on-chip readings as PERF.md records.

Training: the first steps of the very TrainStep the window then drives.
Serving: a seeded sample of the requests the window finished, the longest
among them, each served token held against the reference's logits at its
position.
"""
from __future__ import annotations

import statistics

import jax
import jax.numpy as jnp
import numpy as np

from . import sketch as SK
from . import system
from .reference import common


# ------------------------------------------------------------- training

def follow_reference(cell, seed, rows, steps, **kw) -> dict:
    """`reference_training`, arranged by the program's leaves."""
    losses, g1, upd, sk = reference_training(cell, seed, rows, steps, **kw)
    fam, model = cell.family, cell.config["model"]
    split = system.compare_map(fam, model)
    return {"losses": losses, "grad": by_leaf(g1, split),
            "update": by_leaf(upd, split),
            "sketch": by_leaf(sk, fam.leaf_map(model))}


def reference_training(cell, seed: int, rows, steps: int,
                       dot=common.hi_dot, batch_rows=None):
    """Follow the first `steps` updates of the cell's job in float32,
    through its family's plain reference, from the same weights and rows.
    `batch_rows` (default: all) plants the half-batch fault: only those
    rows of each batch are used, the mean taken over them.
    Returns (losses, first-gradient leaf norms, update leaf norms,
    first-gradient sketches): norms as {stacked key: array [L] or []},
    sketches as {stacked key: [L, K] or [K]}."""
    fam, ref = cell.family, cell.reference
    model, job = cell.config["model"], cell.mix
    o = job["optimizer"]
    batch = job["batch"]
    use = list(range(batch)) if batch_rows is None else list(batch_rows)
    w0 = fam.make(model, seed, cell.config["dtype"])
    params = {k: v.astype(jnp.float32) for k, v in w0.items()}
    start = params
    m = {k: jnp.zeros_like(v) for k, v in params.items()}
    v2 = {k: jnp.zeros_like(v) for k, v in params.items()}

    def leaf_norms(tree):
        return common.leaf_norms(tree, ref.LAYER_KEYS, fam.FUSED)

    @jax.jit
    def one(params, m, v2, ids, t):
        loss, g = ref.loss_and_grads(params, ids, model, dot)
        p2, m, v2 = common.adamw_step(
            params, m, v2, g, t, o["lr"], o["beta1"], o["beta2"], o["eps"],
            o["weight_decay"])
        sk = {k: SK.sketch_layers(a) if k in ref.LAYER_KEYS
              else SK.sketch(a) for k, a in g.items()}
        return loss, (leaf_norms(g), sk), p2, m, v2

    losses, g1, sk1 = [], None, None
    for s in range(steps):
        ids = np.stack([rows(s * batch + r) for r in use])
        loss, gn, params, m, v2 = one(params, m, v2, jnp.asarray(ids),
                                      jnp.float32(s + 1))
        losses.append(float(loss))
        if s == 0:
            g1 = {k: np.asarray(a) for k, a in gn[0].items()}
            sk1 = {k: np.asarray(a) for k, a in gn[1].items()}
    delta = jax.jit(lambda a, b: leaf_norms(
        {k: a[k] - b[k] for k in a}))(params, start)
    return losses, g1, {k: np.asarray(a) for k, a in delta.items()}, sk1


def by_leaf(tree: dict, leaf_map: dict) -> dict:
    """{stacked key: [L, ...] or [...]} -> {comparison leaf name: that
    layer's entry}, scalars as floats."""
    out = {}
    for name, (key, layer) in leaf_map.items():
        a = tree[key] if layer is None else tree[key][layer]
        out[name] = float(a) if np.ndim(a) == 0 else a
    return out


def worst_leaf_gap(got: dict, want: dict, leaves=None):
    """Largest over leaves of |got - want| over the larger of |want| of
    that leaf and of the median leaf. With norms (scalars) that is the
    gap between two lengths; with sketches (vectors) the relative
    distance between two gradients. Returns (gap, leaf name)."""
    leaves = list(want) if leaves is None else leaves
    size = {n: float(np.linalg.norm(np.atleast_1d(want[n]))) for n in leaves}
    med = statistics.median(size.values())
    gap, who = 0.0, None
    for n in leaves:
        g = float(np.linalg.norm(np.atleast_1d(
            np.asarray(got[n]) - want[n]))) / max(size[n], med, 1e-30)
        if g >= gap:
            gap, who = g, n
    return gap, who


def moving_leaves(ref_grad: dict) -> list:
    """Leaves whose reference gradient is at least a thousandth of the
    median leaf's: the others (a key's bias under softmax) move under
    Adam by round-off alone and are left out of the update's comparison."""
    med = statistics.median(ref_grad.values())
    return [n for n, g in ref_grad.items() if g >= 1e-3 * med]


def compare_training(got: dict, want: dict) -> dict:
    """got / want: {"losses": [...], "grad": {leaf: norm}, "update":
    {leaf: norm}, "sketch": {leaf: [K]}}. Returns {number name: value}."""
    out = {"loss_gap": max(abs(a - b) / abs(b) for a, b in
                           zip(got["losses"], want["losses"]))}
    out["grad_norm_gap"], out["grad_norm_leaf"] = worst_leaf_gap(
        got["grad"], want["grad"])
    out["update_norm_gap"], out["update_norm_leaf"] = worst_leaf_gap(
        got["update"], want["update"], moving_leaves(want["grad"]))
    out["grad_sketch_gap"], out["grad_sketch_leaf"] = worst_leaf_gap(
        got["sketch"], want["sketch"])
    return out


# -------------------------------------------------------------- serving

def pick_sample(finished: list, k: int, seed: int) -> list:
    """k finished requests drawn from the seed, the longest always in."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: -(len(r["prompt"])
                                             + len(r["tokens"])))
    rest = order[1:]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, 77])
    pick = rng.permutation(len(rest))[:max(k - 1, 0)]
    return [order[0]] + [rest[i] for i in sorted(pick)]


def served_token_gaps(cell, seed: int, sample: list, pad_to: int,
                      control: bool = False):
    """For each sampled request run the family's reference ONCE over its
    prompt and served tokens (padded to `pad_to`, the longest a served
    request can be), and read, at every served position, how far the
    served token's logit lies below the reference's best. With `control`
    the token judged is the one the fp8 forward puts first instead.
    Returns (widest gap, mean gap over the tokens, tokens compared)."""
    ref, model = cell.reference, cell.config["model"]
    w = cell.family.make(model, seed, cell.config["dtype"])

    @jax.jit
    def gaps(w, ids, judged, first, count):
        lg = ref.logits(w, ids[None], model, common.hi_dot)[0]
        if control:
            low = ref.logits(w, ids[None], model, common.fp8_dot)[0]
            judged = jnp.argmax(low, axis=-1).astype(jnp.int32)
        got = jnp.take_along_axis(lg, judged[:, None], axis=-1)[:, 0]
        pos = jnp.arange(ids.shape[0])
        live = (pos >= first) & (pos < first + count)
        gap = jnp.where(live, jnp.max(lg, axis=-1) - got, 0.0)
        return jnp.max(gap), jnp.sum(gap)

    widest, total, n_tok = 0.0, 0.0, 0
    for r in sample:
        seq = list(r["prompt"]) + list(r["tokens"])
        n, m = len(r["prompt"]), len(r["tokens"])
        ids = np.zeros(pad_to, np.int32)
        ids[:n + m - 1] = seq[:-1]
        judged = np.zeros(pad_to, np.int32)
        judged[n - 1:n + m - 1] = r["tokens"]     # token i sits after i-1
        top, tot = gaps(w, jnp.asarray(ids), jnp.asarray(judged), n - 1, m)
        widest, total = max(widest, float(top)), total + float(tot)
        n_tok += m
    return widest, total / max(n_tok, 1), n_tok


def verdict(numbers: dict, limits: dict) -> tuple:
    """([(name, value, limit)], correct). Every limit named in the cell's
    file must have its number, and none may pass it."""
    rows, ok = [], bool(limits)
    for name, limit in limits.items():
        value = numbers.get(name)
        rows.append((name, value, limit))
        if value is None or not (value <= limit):
            ok = False
    return rows, ok
