"""The DeepSeek-V3 decoder `models/deepseek_v3.py` (latent attention under
YaRN on interleaved pairs in every layer, a dense SwiGLU in the leading
layer, a sigmoid-routed, group-limited expert layer with one ungated shared
expert after it) at a tiny size on the CPU, float32, seeded weights: the
model against the benchmark's independent reference
(`benchmark/reference/deepseek_v3.py`), every term shown to matter, the
YaRN tables against their closed form, the two rotary pairings apart, the
group-limited router against a plain loop, its fifth counter, and the
expert-parallel shares against the uncut layer. The serving engine's side
is `test_deepseek_v3_serving.py`, which shares this file's helpers."""
import dataclasses
import math
import os
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import paddle_tpu as paddle                                    # noqa: E402
from paddle_tpu.incubate.distributed.models.moe import held     # noqa: E402
from paddle_tpu.models import hybrid                           # noqa: E402
from paddle_tpu.models.deepseek_v3 import (                    # noqa: E402
    YARN, DeepseekV3Config, DeepseekV3ForCausalLM, deepseek_v3_tiny)

# the program's own tiny preset as the benchmark's `model` dict: three
# layers, the first dense; 4 heads of 16 + 8 over a latent of 16, YaRN over
# 32 original positions; 4 of 16 experts held, 4 groups of 4, top-4 within
# 2 groups, factor 2.5
MODEL = {k: v for k, v in dataclasses.asdict(deepseek_v3_tiny()).items()
         if k not in ("dtype", "initializer_range")}
CHUNK = 16                  # the engine tests' prefill chunk
SEED = 2**31 + 5
# float32 program against the float32 reference: what is left is the order
# of summation (4e-6 read); a bf16 rounding anywhere moves logits of order
# 1 by 1e-3 and more
TOL = 1e-5


def family():
    from benchmark.families import deepseek_v3 as fam
    from benchmark.reference import deepseek_v3 as ref
    return fam, ref


def program(seed=SEED, arrays=None, model=MODEL, **program_over):
    """(model object, the benchmark's arrays it was given, model dict).
    `program_over` changes the PROGRAM's configuration only: the arrays and
    the returned dict stay `model`'s, which is what the reference is
    given."""
    fam, _ = family()
    if arrays is None:
        arrays = fam.make(model, seed, "float32")
    prog = DeepseekV3ForCausalLM(DeepseekV3Config(
        dtype="float32", **dict(model, **program_over)))
    lm = fam.leaf_map(model)
    for name, p in prog.named_parameters():
        p._data = arrays[lm[name][0]]
    prog.eval()
    return prog, arrays, model


@pytest.fixture(scope="module")
def tiny():
    return program()


def ids_of(n, seed=0, batch=1):
    return np.random.default_rng(seed).integers(0, 512, (batch, n))


_REF = {}


def reference_logits(arrays, model, ids):
    """The reference's logits, one compiled forward a model dict."""
    _, ref = family()
    at = repr(sorted(model.items()))
    if at not in _REF:
        _REF[at] = jax.jit(lambda w, i: ref.logits(w, i, model))
    return np.asarray(_REF[at](arrays, jnp.asarray(ids)))


def forward(prog, ids):
    """The program's full forward, compiled (its weights as they are
    now)."""
    return np.asarray(jax.jit(lambda i: prog(paddle.Tensor(i)).value())(
        jnp.asarray(ids)))


def logits_gap(prog, arrays, model, ids):
    got = forward(prog, ids)
    want = reference_logits(arrays, model, ids)
    return got, want, float(np.abs(got - want).max())


# --------------------------------------------- against the plain reference

@pytest.mark.parametrize("n", [1, 40])
def test_full_forward_is_the_plain_reference(tiny, n):
    """40 positions pass the 32 of YaRN's original context."""
    prog, arrays, model = tiny
    got, want, gap = logits_gap(prog, arrays, model, ids_of(n, seed=n,
                                                            batch=2))
    assert got.shape == (2, n, 512)
    assert float(np.abs(want).max()) > 1.0          # logits of order 1
    assert gap < TOL


TERMS = {
    # a changed program against the unchanged reference: each must show
    "no_yarn": dict(rope_scaling=None),
    "rotate_half": dict(rope_interleave=False),
    "softmax_router": dict(scoring_func="softmax"),
    "no_group_limit": dict(n_group=1, topk_group=1),
    "not_renormalised": dict(norm_topk_prob=False),
    "other_factor": dict(routed_scaling_factor=1.0),
    "other_eps": dict(rms_norm_eps=1e-2),
}


@pytest.mark.parametrize("which", sorted(TERMS))
def test_every_term_of_the_block_matters(tiny, which):
    _, arrays, model = tiny
    prog, _, _ = program(arrays=arrays, **TERMS[which])
    *_, gap = logits_gap(prog, arrays, model, ids_of(40, seed=3))
    assert gap > 1e-3, which


LEAVES = ["post_attention_layernorm", "self_attn.kv_b_proj",
          "mlp.gate_bias", "mlp.shared_down_proj"]


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_leaf_of_a_routed_layer_reaches_the_logits(tiny, leaf):
    """Each array doubled (the choice bias times 40: it only flips a choice
    somewhere) moves the logits; none is dropped."""
    prog, _, _ = tiny
    ids = ids_of(40, seed=8, batch=2)
    base = forward(prog, ids)
    p = dict(prog.named_parameters())[f"model.layers.2.{leaf}"]
    kept = p._data
    try:
        p._data = kept * (40.0 if leaf == "mlp.gate_bias" else 2.0)
        moved = forward(prog, ids)
    finally:
        p._data = kept
    assert float(np.abs(moved - base).max()) > 1e-4, leaf


def test_the_leading_layer_is_dense_and_the_rest_routed(tiny):
    prog, _, _ = tiny
    kinds = [type(b.mlp).__name__ for b in prog.model.layers]
    assert kinds == ["DenseFFN", "HeldExpertsMoE", "HeldExpertsMoE"]
    moe = prog.model.layers[1].mlp
    assert (moe.scoring, moe.n_group, moe.topk_group, moe.shared_gated) == (
        "sigmoid", 4, 2, False)
    assert not hasattr(moe, "shared_expert_gate")
    assert moe.counter_names == ("assignments", "local", "touched",
                                 "groups")


# ------------------------------------------------------------- rotary

def test_the_yarn_tables_are_the_closed_form():
    """DeepSeek-V3's published `rope_scaling` at 64 rope dims: correction
    dims 10 (32 turns over 4096 positions) and 23 (one turn); below 10 the
    plain frequencies, above 23 a fortieth of them, linear between; cos
    and sin untouched, the softmax scale times (0.1 ln 40 + 1) ** 2."""
    inv, scores = hybrid.rope_frequencies(64, 10000, YARN)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)

    def dim_of(turns):
        return 64 * math.log(4096 / (turns * 2 * math.pi)) \
            / (2 * math.log(10000))
    assert (math.floor(dim_of(32)), math.ceil(dim_of(1))) == (10, 23)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    want = plain * (1 - ramp) + plain / 40 * ramp
    assert inv.dtype == np.float32 and inv.shape == (32,)
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)
    assert scores == pytest.approx((0.1 * math.log(40) + 1) ** 2)
    assert scores == pytest.approx(1.87385, abs=1e-5)
    assert hybrid.rope_frequencies(64, 10000, None) == (None, 1.0)
    # mscale apart from mscale_all_dim would scale cos and sin: refused
    with pytest.raises(NotImplementedError, match="mscale"):
        hybrid.rope_frequencies(64, 10000, dict(YARN, mscale=2))
    with pytest.raises(NotImplementedError, match="linear"):
        hybrid.rope_frequencies(64, 10000, {"type": "linear", "factor": 2})


def test_interleaved_pairs_are_not_rotate_half():
    """Both keep every pair's length and turn it by the same angles, but
    pair different dims: interleaved turns (2i, 2i+1) as a complex number,
    rotate-half (i, i + rot/2)."""
    rng = np.random.default_rng(0)
    t = jnp.asarray(rng.normal(size=(1, 5, 2, 12)), jnp.float32)
    pos = jnp.arange(5, dtype=jnp.int32)[None]
    inter = np.asarray(hybrid.rope(t, pos, 8, 1e4, interleave=True))
    half = np.asarray(hybrid.rope(t, pos, 8, 1e4))
    assert float(np.abs(inter - half)[:, 1:, :, :8].max()) > 0.1
    # the 4 dims past the rotary pass through both untouched
    np.testing.assert_array_equal(inter[..., 8:], np.asarray(t)[..., 8:])
    np.testing.assert_array_equal(half[..., 8:], np.asarray(t)[..., 8:])
    ang = np.arange(5)[:, None] * 1e4 ** (-np.arange(4) * 2.0 / 8)
    z = (np.asarray(t)[..., 0:8:2] + 1j * np.asarray(t)[..., 1:8:2]) \
        * np.exp(1j * ang)[None, :, None]
    np.testing.assert_allclose(inter[..., 0:8:2], z.real, atol=1e-5)
    np.testing.assert_allclose(inter[..., 1:8:2], z.imag, atol=1e-5)


# ---------------------------------------------- the group-limited router

def loop_router(x, w, bias, top_k, n_group, topk_group, scaling):
    """DeepSeek-V3's choice written out token by token in float64."""
    ids, weights = [], []
    for row in np.asarray(x, np.float64):
        s = 1.0 / (1.0 + np.exp(-(row @ np.asarray(w, np.float64))))
        b = s + np.asarray(bias, np.float64)
        size = len(s) // n_group
        group_score = [sorted(b[g * size:(g + 1) * size])[-2:]
                       for g in range(n_group)]
        keep = sorted(range(n_group), key=lambda g: -sum(group_score[g]))
        keep = set(keep[:topk_group])
        allowed = [e for e in range(len(s)) if e // size in keep]
        chosen = sorted(allowed, key=lambda e: -b[e])[:top_k]
        wts = s[chosen] / s[chosen].sum() * scaling
        ids.append(chosen)
        weights.append(wts)
    return np.asarray(ids), np.asarray(weights)


def test_the_group_limited_sigmoid_router_is_a_plain_loop():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(48, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, 24)) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.normal(size=24) * 0.05, jnp.float32)
    got_ids, got_w = held.route_topk(x, w, 3, True, bias, 2.5, "sigmoid",
                                     n_group=6, topk_group=2)
    want_ids, want_w = loop_router(x, w, bias, 3, 6, 2, 2.5)
    assert np.array_equal(np.asarray(got_ids), want_ids)
    np.testing.assert_allclose(np.asarray(got_w), want_w, rtol=1e-5)
    # the limit is what decides: the same scores with no groups choose
    # outside the two kept groups for some tokens
    free, _ = held.route_topk(x, w, 3, True, bias, 2.5, "sigmoid")
    assert not np.array_equal(np.sort(np.asarray(free), -1),
                              np.sort(want_ids, -1))
    spans = [len({e // 4 for e in row}) for row in want_ids]
    assert max(spans) <= 2 and max(len({e // 4 for e in row})
                                   for row in np.asarray(free)) == 3


def test_a_bad_grouping_is_refused():
    for kw in (dict(n_group=5), dict(n_group=4, topk_group=5),
               dict(n_group=4, n_zero=4), dict(n_group=8, topk_group=1)):
        with pytest.raises(ValueError, match="groups"):
            held.HeldExpertsMoE(16, 8, 24, 4, **kw)


def moe_layer(**over):
    kw = dict(n_routed=16, top_k=4, norm_topk_prob=True, shared_width=24,
              choice_bias=True, scaling=2.5, scoring="sigmoid", n_group=4,
              topk_group=2, shared_gated=False, std=0.3, dtype="float32")
    kw.update(over)
    paddle.seed(11)
    return held.HeldExpertsMoE(32, 16, kw.pop("n_routed"), kw.pop("top_k"),
                               **kw)


def test_the_groups_counter_sums_the_groups_each_valid_token_spans():
    layer = moe_layer(offset=4, count=4)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(40, 32)),
                    jnp.float32)
    valid = jnp.arange(40) % 5 != 0
    with held.collect_counters() as counted:
        layer.apply(x, valid)
    ids, _ = held.route_topk(x, layer.gate.value(), 4, True,
                             layer.gate_bias.value(), 2.5, "sigmoid", 4, 2)
    spans = np.asarray([len({e // 4 for e in row}) for row in
                        np.asarray(ids)])
    total = counted.total().tolist()
    # assignments, local, touched, groups (a layer with zero experts would
    # count them fourth and the groups fifth)
    assert len(total) == 4 and total[0] == 32 * 4
    assert total[3] == int(spans[np.asarray(valid)].sum())
    assert 32 < total[3] <= 64                      # 1 or 2 a token


def test_the_shared_expert_is_added_ungated():
    """Every routed expert silenced: what is left is the shared SwiGLU
    itself, no sigmoid gate."""
    layer = moe_layer()
    layer.experts_down_proj._data = jnp.zeros_like(
        layer.experts_down_proj.value())
    x = jnp.asarray(np.random.default_rng(3).normal(size=(8, 32)),
                    jnp.float32)
    want = held._swiglu(x, layer.shared_gate_proj.value(),
                        layer.shared_up_proj.value(),
                        layer.shared_down_proj.value())
    assert float(jnp.abs(want).max()) > 0.1
    assert float(jnp.abs(layer.apply(x) - want).max()) < 1e-6


def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer(tiny):
    """16 experts over 4 shares of 4 (a share is one of the router's 4
    groups): the four partial blocks, with attention and the shared
    expert counted ONCE, sum to the uncut block."""
    _, arrays, model = tiny
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, 11, 64)),
                    jnp.float32)

    def block_out(prog):
        return prog.model.layers[1].apply(x, None, jnp.int32(0), None)[0]

    whole = dict(model, num_experts=16, expert_offset=0)
    full_arrays = dict(arrays)
    rng = np.random.default_rng(7)
    for k, v in arrays.items():
        if ".exp_" in k:        # the other 12 experts, drawn alike
            extra = rng.normal(size=(12,) + v.shape[1:]) * float(v.std())
            full_arrays[k] = jnp.concatenate(
                [v, jnp.asarray(extra, jnp.float32)])
    full = block_out(program(arrays=full_arrays, model=whole)[0])
    parts = []
    for j in range(4):
        share = dict(model, num_experts=4, expert_offset=4 * j)
        cut = {k: (v[4 * j:4 * j + 4] if ".exp_" in k else v)
               for k, v in full_arrays.items()}
        parts.append(block_out(program(arrays=cut, model=share)[0]))
    silent = {k: (jnp.zeros_like(v) if k.endswith("exp_down") else v)
              for k, v in arrays.items()}
    common = block_out(program(arrays=silent)[0])
    assert float(jnp.abs(parts[1] - common).max()) > 0.05   # experts matter
    total = common + sum(p - common for p in parts)
    assert float(jnp.abs(total - full).max()) < 1e-5


# ----------------------------------------------------- latent attention

def test_absorbed_decode_is_the_expanded_attention(tiny):
    """The decode step's form under YaRN and interleaved pairs equals
    per-head keys and values, position by position, over a pool whose
    blocks are shuffled."""
    prog, _, _ = tiny
    attn = prog.model.layers[2].self_attn
    assert attn.scale == pytest.approx((0.1 * math.log(4) + 1) ** 2
                                       / math.sqrt(24))
    rng = np.random.default_rng(5)
    b, n, bs = 3, 45, 4
    z = jnp.asarray(rng.normal(size=(b, n, 64)), jnp.float32)
    positions = jnp.arange(n)[None]
    q_nope, q_rope, rows = attn._project(z, positions)
    want = attn._expanded(q_nope, q_rope, rows, positions)
    mbs = -(-n // bs)
    table = rng.permutation(b * mbs).reshape(b, mbs).astype(np.int32) + 1
    pool = np.zeros((b * mbs + 1, bs, 128), np.float32)
    padded = np.zeros((b, mbs * bs, 128), np.float32)
    padded[:, :n, :24] = np.asarray(rows)
    for i in range(b):
        pool[table[i]] = padded[i].reshape(mbs, bs, 128)
    for t in (0, 4, 33, n - 1):
        got = attn._absorbed(q_nope[:, t:t + 1], q_rope[:, t:t + 1],
                             jnp.asarray(pool), jnp.asarray(table),
                             jnp.full((b,), t, jnp.int32))
        assert float(jnp.abs(got[:, 0] - want[:, t]).max()) < 2e-6, t


def test_heads_in_blocks_are_the_heads_at_once(tiny, monkeypatch):
    from paddle_tpu.models import latent_attention
    prog, _, _ = tiny
    ids = ids_of(33, seed=2)
    whole = forward(prog, ids)
    monkeypatch.setattr(latent_attention, "SCORE_BLOCK", 2 * 33 * 33)
    blocks = forward(prog, ids)
    assert float(np.abs(whole - blocks).max()) < 1e-5


def test_what_a_layer_caches_is_one_latent_row(tiny):
    prog, _, model = tiny
    fam, _ = family()
    spec = prog.decode_spec()
    assert [c.kind for c in spec.layers] == ["latent"] * 3
    assert len(spec.latent_layers) == 3 and not spec.kv_layers
    assert {c.head_dim for c in spec.latent_layers} == {16 + 8}
    assert fam.kv_bytes_per_token(model, elem=4) == 3 * 24 * 4


def test_parameter_count_of_the_tiny_share(tiny):
    prog, arrays, model = tiny
    fam, _ = family()
    n = sum(int(np.prod(p.shape)) for _, p in prog.named_parameters())
    assert n == fam.n_params(model) == sum(
        int(np.prod(a.shape)) for a in arrays.values())
    assert {n for n, _ in prog.named_parameters()} == set(fam.leaf_map(model))


def test_the_reference_gives_a_loss_and_a_gradient_for_every_leaf(tiny):
    """`loss_and_grads`, which the harness asks of every family's
    reference, is `jax.grad` of the forward the serving comparison uses:
    its loss is the mean next-token loss of `logits`, every array has a
    finite gradient, and every leaf but the choice bias (which steers the
    choice and never a weight) has one that is not zero."""
    _, arrays, model = tiny
    fam, ref = family()
    ids = ids_of(12, seed=12, batch=2).astype("int32")
    loss, grads = jax.jit(lambda w, i: ref.loss_and_grads(w, i, model))(
        arrays, jnp.asarray(ids))
    lg = reference_logits(arrays, model, ids)[:, :-1]
    want = np.mean(jax.scipy.special.logsumexp(lg, -1) - np.take_along_axis(
        lg, ids[:, 1:, None], -1)[..., 0])
    assert abs(float(loss) - float(want)) < TOL * abs(float(want))
    assert sorted(grads) == sorted(fam.shapes(model))
    for k, g in grads.items():
        assert g.shape == arrays[k].shape and bool(jnp.all(jnp.isfinite(g)))
        moved = float(jnp.abs(g).max()) > 0
        assert moved == (not k.endswith(".router_b")), k
