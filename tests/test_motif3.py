"""The Motif-3 decoder `models/motif3.py` (grouped differential latent
attention, window layers beside a full one, four residual streams joined by
manifold-constrained hyper-connections, PolyNorm feed-forwards and experts)
at a tiny size on the CPU, float32, seeded weights: the model against the
benchmark's independent reference (`benchmark/reference/motif3.py`), every
mechanism shown to matter, the expert-parallel shares against the uncut
layer, and the two kernels this model brings (`mhc`, the PolyNorm grouped
expert matmul) through the interpreter against their plain forms. The
serving engine's side is `test_motif3_serving.py`, which shares this
file's helpers."""
import dataclasses
import hashlib
import os
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import paddle_tpu as paddle                                    # noqa: E402
from paddle_tpu.kernels.pallas import mhc, moe_grouped          # noqa: E402
from paddle_tpu.models import latent_attention                  # noqa: E402
from paddle_tpu.models.motif3 import (                          # noqa: E402
    Motif3Config, Motif3ForCausalLM, motif3_tiny)

# the program's own tiny preset as the benchmark's `model` dict: five layers
# [W, W, F, W, W], the first dense; 10 heads in 2 latent groups of 4 signal
# + 1 noise over a 16 + 8 row, a window of 8; 4 of 16 experts held, top-4
MODEL = {k: v for k, v in dataclasses.asdict(motif3_tiny()).items()
         if k not in ("dtype", "initializer_range")}
CHUNK = 16                  # the engine tests' prefill chunk: twice the window
SEED = 2**31 + 5
# float32 program against the float32 reference: what is left is the order
# of summation (3e-6 read); a bf16 rounding anywhere moves logits of order
# 1 by 1e-3 and more (test_a_bfloat16_rounding_fails_the_tolerance)
TOL = 1e-5


def family():
    from benchmark.families import motif3 as fam
    from benchmark.reference import motif3 as ref
    return fam, ref


def program(seed=SEED, arrays=None, model=MODEL, dtype="float32",
            **program_over):
    """(model object, the benchmark's arrays it was given, model dict).
    `program_over` changes the PROGRAM's configuration only."""
    fam, _ = family()
    if arrays is None:
        arrays = fam.make(model, seed, dtype)
    prog = Motif3ForCausalLM(Motif3Config(
        dtype=dtype, **dict(model, **program_over)))
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
    _, ref = family()
    key = id(model)
    if key not in _REF:
        _REF[key] = jax.jit(lambda w, i: ref.logits(w, i, model))
    return np.asarray(_REF[key](arrays, jnp.asarray(ids)))


def forward(prog, ids):
    return np.asarray(prog(paddle.to_tensor(ids)).value())


def logits_gap(prog, arrays, model, ids):
    return float(np.abs(forward(prog, ids)
                        - reference_logits(arrays, model, ids)).max())


IDS = ids_of(40, batch=2)     # past the window five times, past YaRN's 32


@pytest.mark.parametrize("n", [1, 40])
def test_full_forward_is_the_plain_reference(tiny, n):
    prog, arrays, model = tiny
    ids = IDS[:, :n]
    assert logits_gap(prog, arrays, model, ids) < TOL
    assert np.abs(reference_logits(arrays, model, ids)).std() > 0.5


def test_a_bfloat16_rounding_fails_the_tolerance(tiny):
    """The tolerance sees one bf16 rounding: the same arrays rounded to
    bf16 on their way into the program move the logits past it."""
    prog, arrays, model = tiny
    rounded = {k: v.astype(jnp.bfloat16).astype(jnp.float32)
               for k, v in arrays.items()}
    low, _, _ = program(arrays=rounded)
    assert logits_gap(low, arrays, model, IDS) > 100 * TOL


# a mechanism the program could leave out: its configuration changed (the
# arrays as they are), and the logits move far past the tolerance
TERMS = {"window": dict(sliding_window=64),
         "window_rotary": dict(swa_rope_theta=10000.0),
         "yarn": dict(rope_scaling=None),
         "sinkhorn": dict(mhc_sinkhorn_iters=1),
         "polynorm_scale": dict(polynorm_output_scale=1.0),
         "polynorm_bias_clamp": dict(polynorm_bias_clamp=0.05),
         "route_scale": dict(route_scale=1.0)}


@pytest.mark.parametrize("which", sorted(TERMS))
def test_every_mechanism_matters(tiny, which):
    _, arrays, model = tiny
    other, _, _ = program(arrays=arrays, **TERMS[which])
    assert logits_gap(other, arrays, model, IDS) > 1e-3


LEAVES = ("hc_a_norm", "hc_a_phi", "hc_a_alpha", "hc_a_bias", "hc_f_phi",
          "a_qb", "a_lam", "a_kvb", "a_o", "router_b", "exp_poly", "sh_poly",
          "exp_down", "sh_down")


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_leaf_of_a_routed_layer_reaches_the_logits(tiny, leaf):
    """Each array of layer 3 (a routed window layer) nudged moves the
    logits: none is read past (the gate's Wqb columns, Wlam, the mHC
    arrays, the PolyNorm numbers among them)."""
    prog, arrays, model = tiny
    k = f"l3.{leaf}"
    bump = dict(arrays)
    noise = np.random.default_rng(3).normal(size=arrays[k].shape)
    bump[k] = arrays[k] + 0.2 * jnp.asarray(noise, jnp.float32) \
        * (float(jnp.abs(arrays[k]).mean()) + 0.1)
    moved, _, _ = program(arrays=bump)
    assert float(np.abs(forward(moved, IDS) - forward(prog, IDS)).max()) \
        > 1e-4


def test_the_gate_columns_of_wqb_reach_the_logits(tiny):
    prog, arrays, model = tiny
    k = "l2.a_qb"
    cols = 10 * (16 + 8)
    bump = dict(arrays)
    bump[k] = arrays[k].at[:, cols:].multiply(3.0)
    moved, _, _ = program(arrays=bump)
    assert float(np.abs(forward(moved, IDS) - forward(prog, IDS)).max()) \
        > 1e-3


def test_the_layer_kinds_and_caches(tiny):
    prog, _, _ = tiny
    kinds = [b.dense for b in prog.model.layers]
    assert kinds == [True, False, False, False, False]
    assert [b.self_attn.window for b in prog.model.layers] == [8, 8, 0, 8, 8]
    spec = prog.decode_spec()
    assert [c.kind for c in spec.entries] == ["window", "window", "latent",
                                              "window", "window"]
    assert {c.head_dim for c in spec.latent_layers} == {16 + 8}
    assert {c.window for c in spec.window_layers} == {8}
    attn = prog.model.layers[0].self_attn
    assert (attn.groups, attn.per, attn.noise, attn.signal) == (2, 5, 2, 8)
    # the window layer rotates plainly at swa_rope_theta, the full one under
    # YaRN with no factor on the softmax scale (apply_yarn_scaling false)
    assert attn.theta == 500.0 and attn.scale == pytest.approx(24 ** -0.5)
    full = prog.model.layers[2].self_attn
    assert full.theta == 10000.0 and full.scale == pytest.approx(24 ** -0.5)
    assert full.inv is not None and attn.inv is None


def test_a_layer_types_list_is_required():
    with pytest.raises(ValueError, match="layer_types"):
        Motif3Config(num_hidden_layers=2, layer_types=None)
    with pytest.raises(ValueError, match="layer_types"):
        motif3_tiny(layer_types=["full_attention"] * 4)


def test_absorbed_decode_is_the_expanded_attention(tiny):
    """The decode step's form (the noise heads taken off in latent space)
    equals the expanded form position by position, a full layer over a
    shuffled pool and a window layer over a ring."""
    prog, _, _ = tiny
    rng = np.random.default_rng(5)
    b, n, bs = 3, 30, 4
    z = jnp.asarray(rng.normal(size=(b, n, 64)), jnp.float32)
    positions = jnp.arange(n)[None]
    for layer in (2, 1):
        attn = prog.model.layers[layer].self_attn
        q_nope, q_rope, rows, gate = attn._project(z, positions)
        lam = jax.nn.sigmoid(z @ attn.lambda_proj.value())
        want = attn._out(attn._expanded(q_nope, q_rope, rows, positions), z,
                         gate, lam)
        for t in (0, 3, 9, n - 1):
            if attn.window:     # ring row r holds the last position = r
                w = attn.window
                held = np.zeros((b, w, 128), np.float32)
                for p in range(max(0, t - w + 1), t + 1):
                    held[:, p % w, :24] = np.asarray(rows[:, p])
                table = 1 + np.arange(b * w // bs, dtype=np.int32).reshape(
                    b, w // bs)
                pool = np.zeros((1 + b * w // bs, bs, 128), np.float32)
                pool[1:] = held.reshape(-1, bs, 128)
                last = jnp.full((b,), min(t, w - 1), jnp.int32)
            else:
                mbs = -(-n // bs)
                table = rng.permutation(b * mbs).reshape(b, mbs).astype(
                    np.int32) + 1
                pool = np.zeros((b * mbs + 1, bs, 128), np.float32)
                padded = np.zeros((b, mbs * bs, 128), np.float32)
                padded[:, :n, :24] = np.asarray(rows)
                for i in range(b):
                    pool[table[i]] = padded[i].reshape(mbs, bs, 128)
                last = jnp.full((b,), t, jnp.int32)
            ctx = attn._absorbed(q_nope[:, t:t + 1], q_rope[:, t:t + 1],
                                 jnp.asarray(pool), jnp.asarray(table), last,
                                 lam[:, t:t + 1])
            got = attn._out(ctx, z[:, t:t + 1], gate[:, t:t + 1],
                            lam[:, t:t + 1])
            assert float(jnp.abs(got[:, 0] - want[:, t]).max()) < 2e-6, \
                (layer, t)


def test_heads_in_blocks_are_the_heads_at_once(tiny, monkeypatch):
    prog, arrays, model = tiny
    want = forward(prog, IDS)
    monkeypatch.setattr(latent_attention, "SCORE_BLOCK", 2 * 40 * 40)
    assert float(np.abs(forward(prog, IDS) - want).max()) < 1e-6


def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer(tiny):
    """16 experts over 4 shares of 4: the four partial blocks, attention,
    the streams and the shared expert counted ONCE, sum to the uncut
    block."""
    _, arrays, model = tiny
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(2, 11, 4, 64)), jnp.float32)

    def block_out(prog):
        blk = prog.model.layers[1]
        streams, (y, post, res), _ = blk.apply(x, None, None, jnp.int32(0),
                                               None)
        return streams, y

    whole = dict(model, num_experts=16, expert_offset=0)
    full_arrays = dict(arrays)
    for k, v in arrays.items():
        if ".exp_" in k:        # the other 12 experts, drawn alike
            extra = rng.normal(size=(12,) + v.shape[1:]) * float(v.std()) \
                + float(v.mean())
            full_arrays[k] = jnp.concatenate(
                [v, jnp.asarray(extra, jnp.float32)])
    s_full, full = block_out(program(arrays=full_arrays, model=whole)[0])
    parts = []
    for j in range(4):
        share = dict(model, num_experts=4, expert_offset=4 * j)
        cut = {k: (v[4 * j:4 * j + 4] if ".exp_" in k else v)
               for k, v in full_arrays.items()}
        s_part, part = block_out(program(arrays=cut, model=share)[0])
        assert float(jnp.abs(s_part - s_full).max()) == 0.0
        parts.append(part)
    silent = {k: (jnp.zeros_like(v) if k.endswith("exp_down") else v)
              for k, v in arrays.items()}
    common = block_out(program(arrays=silent)[0])[1]
    assert float(jnp.abs(parts[1] - common).max()) > 0.05   # experts matter
    total = common + sum(p - common for p in parts)
    assert float(jnp.abs(total - full).max()) < 1e-5


def test_parameter_count_of_the_tiny_share(tiny):
    prog, arrays, model = tiny
    fam, _ = family()
    n = sum(int(np.prod(p.shape)) for _, p in prog.named_parameters())
    assert n == fam.n_params(model) == sum(
        int(np.prod(a.shape)) for a in arrays.values())


# --------------------------------------------------- the mhc kernel

def boundary(seed, t=21, n=4, c=128, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    f32 = jnp.float32
    p = 2 * n + n * n
    x = jnp.asarray(rng.normal(size=(t, n, c)), dtype)
    y = jnp.asarray(rng.normal(size=(t, c)), dtype)
    post = jnp.asarray(rng.uniform(0, 2, (t, n)), f32)
    res = mhc.sinkhorn(jnp.asarray(rng.uniform(0.1, 1, (t, n, n)), f32), 20)
    weights = (jnp.asarray(rng.normal(1, 0.1, n * c), dtype),
               jnp.asarray(rng.normal(size=(p, n * c)) / np.sqrt(n * c),
                           dtype),
               jnp.asarray([1.1, 0.9, 1.2], dtype),
               jnp.asarray(rng.normal(0, 0.5, p), dtype))
    return x, y, post, res, weights


@pytest.mark.parametrize("mix", [True, False], ids=["write_and_read",
                                                    "first_read"])
def test_the_mhc_kernel_is_its_plain_form(mix):
    """The Pallas kernel through the interpreter (21 rows: a padded last
    block of 16) against the jax.numpy form. Both are float32 with the
    same products; what differs is the order of summation (1e-6 read),
    and the projection's rounding to bf16 would move it by 1e-3."""
    x, y, post, res, w = boundary(1)
    args = (x, y, post, res) if mix else (x, None, None, None)
    want = mhc.mhc_plain(*args, *w, eps=1e-5, clamp=1e6, iters=20)
    with mhc.force_interpret():
        got = mhc.mhc(*args, *w, eps=1e-5, clamp=1e6)
    for g, v in zip(got, want):
        assert g.shape == v.shape
        assert float(jnp.abs(g - v).max()) < 1e-5
    low = mhc.mhc_plain(*args, w[0], w[1].astype(jnp.bfloat16)
                        .astype(jnp.float32), *w[2:], eps=1e-5, clamp=1e6,
                        iters=20)
    assert float(jnp.abs(low[2] - want[2]).max()) > 1e-5


def test_h_res_is_doubly_stochastic_and_neither_identity_nor_uniform():
    """Twenty rounds, the last a column normalisation: columns sum to 1
    within float32 rounding (a few ulps of 1: 1e-6), rows within what
    twenty rounds leave of Sinkhorn's own convergence (1.5e-5 read at this
    spread of entries, e^+-3), for the kernel and the plain form."""
    x, _, _, _, w = boundary(2)
    with mhc.force_interpret():
        _, _, post, res = mhc.mhc(x, None, None, None, *w, eps=1e-5,
                                  clamp=1e6)
    plain = mhc.mhc_plain(x, None, None, None, *w, eps=1e-5, clamp=1e6,
                          iters=20)[3]
    for r in (res, plain):
        assert float(jnp.abs(r.sum(-1) - 1).max()) < 1e-4
        assert float(jnp.abs(r.sum(-2) - 1).max()) < 1e-6
        assert float(jnp.abs(r - jnp.eye(4)).max()) > 0.1
        assert float(jnp.abs(r - 0.25).max()) > 0.05
    assert float(post.min()) > 0.0 and float(post.max()) < 2.0


def test_the_clamp_bounds_the_streams():
    x, y, post, res, w = boundary(3)
    out = mhc.mhc_plain(x, 1e3 * y, post, res, *w, eps=1e-5, clamp=5.0,
                        iters=20)[0]
    assert float(jnp.abs(out).max()) == 5.0
    with mhc.force_interpret():
        got = mhc.mhc(x, 1e3 * y, post, res, *w, eps=1e-5, clamp=5.0)[0]
    assert float(jnp.abs(got - out).max()) < 1e-4


# ------------------------------------ the PolyNorm grouped expert kernel

def experts(seed, e=4, h=128, inter=256, t=40, k=3):
    rng = np.random.default_rng(seed)
    f32 = jnp.float32
    x = jnp.asarray(rng.normal(size=(t, h)), f32)
    ids = jnp.asarray(rng.integers(0, 8, (t, k)), jnp.int32)
    weights = jnp.asarray(rng.uniform(0.1, 1, (t, k)), f32)
    valid = jnp.asarray(rng.uniform(size=t) > 0.1)
    wg = jnp.asarray(rng.normal(size=(e, h, inter)) / np.sqrt(h), f32)
    wu = jnp.asarray(rng.normal(size=(e, h, inter)) / np.sqrt(h), f32)
    wd = jnp.asarray(rng.normal(size=(e, inter, h)) / np.sqrt(inter), f32)
    coef = jnp.asarray(np.column_stack([rng.normal(1 / 3, 0.15, (e, 3)),
                                        rng.normal(0, 0.6, e)]), f32)
    return x, ids, weights, valid, wg, wu, wd, coef


POLY = moe_grouped.Poly(0.5, 0.5, 1e-5)


@pytest.mark.parametrize("blocks", [1, 2])
def test_the_polynorm_grouped_kernel_is_its_plain_form(blocks, monkeypatch):
    """Held experts 2-5 of 8 through the kernel (interpreted) against every
    expert over every token: the intermediate row in one block, and walked
    in two whose power sums and four partial products are combined at the
    last one. Float32 both; the order of summation is what differs (1e-6
    read; a bf16 hidden row moves it by 1e-3)."""
    x, ids, weights, valid, wg, wu, wd, coef = experts(blocks)
    h, inter = x.shape[1], wg.shape[2]
    if blocks == 2:     # room for half the row's columns, twice, and no more
        monkeypatch.setattr(moe_grouped, "VMEM_LIMIT",
                            (4 << 20) + 2 * 3 * h * (inter // 2) * 4)
    assert moe_grouped.inter_block(h, inter, 4) == inter // blocks
    want = moe_grouped.dense_masked(x, ids, weights, valid, wg, wu, wd, 2,
                                    coef, POLY)
    with moe_grouped.force_interpret():
        got, counts = moe_grouped.moe_grouped(x, ids, weights, valid, wg, wu,
                                              wd, 2, coef=coef, poly=POLY)
    assert float(jnp.abs(got - want).max()) < 2e-5
    assert int(counts.sum()) == int(jnp.sum(
        (ids >= 2) & (ids < 6) & valid[:, None]))
    silu = moe_grouped.dense_masked(x, ids, weights, valid, wg, wu, wd, 2)
    assert float(jnp.abs(silu - want).max()) > 0.05


def test_polynorm_is_its_three_normalised_powers():
    g = jnp.asarray(np.random.default_rng(6).normal(size=(3, 64)),
                    jnp.float32)
    coef = jnp.asarray([0.2, 0.3, 0.5, 0.9], jnp.float32)

    def n(v):
        return v / jnp.sqrt(jnp.mean(v * v, -1, keepdims=True) + 1e-5)
    want = 0.5 * (0.2 * n(g) + 0.3 * n(g ** 2) + 0.5 * n(g ** 3) + 0.5)
    got = moe_grouped.poly_norm(g, coef, 0.5, 0.5, 1e-5)
    assert float(jnp.abs(got - want).max()) < 1e-6


def test_only_poly_norm_is_taken_besides_silu():
    with pytest.raises(NotImplementedError, match="hidden_act"):
        motif3_tiny(hidden_act="gelu")


# the SiLU kernel's program as the parent tree lowered it: the jaxpr of
# `_grouped_ffn` at an unblocked and a blocked geometry (128 and 4096 x
# 1280 experts), hashed. PolyNorm is a separate body; SiLU's is untouched
SILU_JAXPRS = {"whole": "766183150a629223", "blocked": "1d54291dae49111f"}


@pytest.mark.parametrize("geometry", sorted(SILU_JAXPRS))
def test_the_silu_grouped_kernel_is_lowered_as_before(geometry):
    h, inter = (128, 256) if geometry == "whole" else (4096, 1280)
    rows, e = 64, 4
    spec = jax.ShapeDtypeStruct
    bf = jnp.bfloat16
    jaxpr = jax.make_jaxpr(lambda xs, ex, used, g, u, d: moe_grouped
                           ._grouped_ffn(xs, ex, used, g, u, d, tile=16,
                                         interpret=False))(
        spec((rows, h), bf), spec((rows // 16,), jnp.int32),
        spec((), jnp.int32), spec((e, h, inter), bf),
        spec((e, h, inter), bf), spec((e, inter, h), bf))
    digest = hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16]
    assert digest == SILU_JAXPRS[geometry]
