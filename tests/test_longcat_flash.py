"""The LongCat-Flash decoder `models/longcat_flash.py` (two latent-attention
sublayers, two dense feed-forwards and one shortcut-connected expert layer
with zero-compute experts in every block) at a tiny size on the CPU,
float32, seeded weights: the model against the benchmark's independent
reference (`benchmark/reference/longcat_flash.py`), the absorbed decode form
against the expanded one, every term of the block and every factor shown to
matter, the expert-parallel shares against the uncut layer, and what the
wider router of `HeldExpertsMoE` does. The serving engine's side is
`test_longcat_flash_serving.py`, which shares this file's helpers."""
import dataclasses
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
from paddle_tpu.models.longcat_flash import (                  # noqa: E402
    LongCatFlashConfig, LongCatFlashForCausalLM, longcat_flash_tiny)

# the program's own tiny preset as the benchmark's `model` dict: two
# blocks; 4 heads of 16 + 8 over a latent of 16; 24 experts + 12 zero
# experts, top-4, factor 2.5
MODEL = {k: v for k, v in dataclasses.asdict(longcat_flash_tiny()).items()
         if k not in ("dtype", "initializer_range")}
CHUNK = 16                  # the engine tests' prefill chunk
SEED = 2**31 + 5


def family():
    from benchmark.families import longcat_flash as fam
    from benchmark.reference import longcat_flash as ref
    return fam, ref


def program(seed=SEED, arrays=None, model=MODEL, **program_over):
    """(model object, the benchmark's arrays it was given, model dict).
    `program_over` changes the PROGRAM's configuration only: the arrays and
    the returned dict stay `model`'s, which is what the reference is
    given."""
    fam, _ = family()
    if arrays is None:
        arrays = fam.make(model, seed, "float32")
    prog = LongCatFlashForCausalLM(LongCatFlashConfig(
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


# --------------------------------------------- against the plain reference

@pytest.mark.parametrize("n", [1, 7, 40])
def test_full_forward_is_the_plain_reference(tiny, n):
    prog, arrays, model = tiny
    _, ref = family()
    ids = ids_of(n, seed=n, batch=2)
    got = np.asarray(prog(paddle.to_tensor(ids)).value())
    want = np.asarray(ref.logits(arrays, jnp.asarray(ids), model))
    assert got.shape == (2, n, 512)
    assert float(np.abs(want).max()) > 1.0          # logits of order 1
    assert float(np.abs(got - want).max()) < 1e-5


TERMS = {
    # a changed program against the unchanged reference: each must show
    "no_q_scale": dict(mla_scale_q_lora=False),
    "no_kv_scale": dict(mla_scale_kv_lora=False),
    "other_factor": dict(routed_scaling_factor=1.0),
    "other_theta": dict(rope_theta=1e6),
    "other_eps": dict(rms_norm_eps=1e-2),
    "other_top_k": dict(moe_topk=3),
}


@pytest.mark.parametrize("which", sorted(TERMS))
def test_every_factor_of_the_block_matters(tiny, which):
    _, arrays, model = tiny
    _, ref = family()
    prog, _, _ = program(arrays=arrays, **TERMS[which])
    ids = ids_of(24, seed=3)
    got = np.asarray(prog(paddle.to_tensor(ids)).value())
    want = np.asarray(ref.logits(arrays, jnp.asarray(ids), model))
    assert float(np.abs(got - want).max()) > 1e-3, which


LEAVES = ["attn_norm_1", "ffn_norm_1", "attn_norm_2", "ffn_norm_2",
          "self_attn.0.q_a_layernorm", "self_attn.1.kv_a_layernorm",
          "self_attn.0.kv_b_proj", "self_attn.1.o_proj",
          "mlps.0.down_proj", "mlps.1.gate_proj", "mlp.gate",
          "mlp.gate_bias", "mlp.experts_down_proj"]


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_leaf_reaches_the_logits(tiny, leaf):
    """Each of the block's arrays doubled moves the logits: none is
    dropped, the choice bias among them (it flips a choice somewhere)."""
    prog, arrays, model = tiny
    ids = ids_of(40, seed=8, batch=4)
    base = np.asarray(prog(paddle.to_tensor(ids)).value())
    p = dict(prog.named_parameters())[f"model.layers.1.{leaf}"]
    kept = p._data
    try:
        p._data = kept * (40.0 if leaf == "mlp.gate_bias" else 2.0)
        moved = np.asarray(prog(paddle.to_tensor(ids)).value())
    finally:
        p._data = kept
    assert float(np.abs(moved - base).max()) > 1e-4, leaf


def test_the_shortcut_joins_after_the_second_feed_forward(tiny):
    """The expert layer reads N2(a1) and is added at the block's END: the
    second sublayer and the second feed-forward never see it. A block
    whose experts are silenced gives the same output less that term."""
    prog, _, _ = tiny
    block = prog.model.layers[0]
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 9, 64)),
                    jnp.float32)
    y, _ = block.apply(x, None, jnp.int32(0), None)
    a, _ = block.self_attn[0].apply(
        block._norm(block.attn_norm_1, x), None, jnp.int32(0), None)
    u = block._norm(block.ffn_norm_1, x + a)
    m = block.mlp.apply(u.reshape(-1, 64)).reshape(2, 9, 64)
    kept = block.mlp.apply
    try:
        block.mlp.apply = lambda t, valid=None: jnp.zeros_like(t)
        silent, _ = block.apply(x, None, jnp.int32(0), None)
    finally:
        block.mlp.apply = kept
    assert float(jnp.abs(m).max()) > 0.1
    assert float(jnp.abs(y - (silent + m)).max()) < 1e-5


# ----------------------------------------------------- latent attention

def test_absorbed_decode_is_the_expanded_attention(tiny):
    """The decode step's form (queries through Wkvb_k^T against the latent
    rows, context over the latent, then Wkvb_v) equals per-head keys and
    values, position by position, over a pool whose blocks are shuffled."""
    prog, _, _ = tiny
    attn = prog.model.layers[0].self_attn[1]
    rng = np.random.default_rng(5)
    b, n, bs = 3, 21, 4
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
    for t in (0, 3, 4, n - 1):
        got = attn._absorbed(q_nope[:, t:t + 1], q_rope[:, t:t + 1],
                             jnp.asarray(pool), jnp.asarray(table),
                             jnp.full((b,), t, jnp.int32))
        assert float(jnp.abs(got[:, 0] - want[:, t]).max()) < 2e-6, t


def test_heads_in_blocks_are_the_heads_at_once(tiny, monkeypatch):
    from paddle_tpu.models import latent_attention as la
    prog, _, _ = tiny
    ids = ids_of(33, seed=2)
    whole = np.asarray(prog(paddle.to_tensor(ids)).value())
    monkeypatch.setattr(la, "SCORE_BLOCK", 2 * 33 * 33)   # 2 heads a block
    blocks = np.asarray(prog(paddle.to_tensor(ids)).value())
    assert float(np.abs(whole - blocks).max()) < 1e-5


def test_what_a_layer_caches_is_two_latent_rows(tiny):
    prog, _, model = tiny
    fam, _ = family()
    spec = prog.decode_spec()
    assert [[c.kind for c in layer] for layer in spec.layers] == \
        [["latent", "latent"]] * 2
    assert len(spec.latent_layers) == 4 and not spec.kv_layers \
        and not spec.state_layers
    assert {c.head_dim for c in spec.latent_layers} == {16 + 8}
    assert fam.kv_bytes_per_token(model, elem=4) == 4 * 24 * 4
    with pytest.raises(NotImplementedError, match="latent"):
        spec.n_kv_heads          # no K/V geometry: the error names the kind


# ------------------------------------------------- the wider router

def moe_layer(**over):
    kw = dict(hidden_size=32, expert_width=16, n_routed=24, top_k=4,
              norm_topk_prob=False, n_zero=12, choice_bias=True,
              scaling=2.5, std=0.3, dtype="float32")
    kw.update(over)
    h, i = kw.pop("hidden_size"), kw.pop("expert_width")
    paddle.seed(11)
    return held.HeldExpertsMoE(h, i, kw.pop("n_routed"), kw.pop("top_k"),
                               **kw)


def tokens(n=64, h=32, seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(n, h)),
                       jnp.float32)


def test_the_choice_bias_steers_the_choice_and_not_the_weights():
    layer = moe_layer()
    x = tokens()
    w = layer.gate.value()
    rng = np.random.default_rng(3)
    bias = jnp.asarray(rng.normal(0, 0.02, 36), jnp.float32)
    ids0, w0 = held.route_topk(x, w, 4, False, None, 2.5)
    ids1, w1 = held.route_topk(x, w, 4, False, bias, 2.5)
    probs = jax.nn.softmax(jnp.dot(x, w, precision="highest"), axis=-1)
    # some choices flipped ...
    flipped = np.asarray(jnp.sort(ids0, -1) != jnp.sort(ids1, -1)).any(-1)
    assert 0 < flipped.sum() < len(flipped)
    # ... and every kept weight is still 2.5 x the softmax's own value
    for ids, wts in ((ids0, w0), (ids1, w1)):
        assert float(jnp.abs(wts - 2.5 * jnp.take_along_axis(
            probs, ids, -1)).max()) < 1e-6
    # no renormalisation: the kept weights do not sum to the factor
    assert float(jnp.abs(jnp.sum(w1, -1) - 2.5).min()) > 0.1


def test_a_token_all_of_whose_choices_are_zero_experts():
    """A bias that lifts every zero expert over every real one: each token
    keeps 4 zero experts, costs no expert matmul, and leaves as `sum(w) *
    x`."""
    layer = moe_layer()
    layer.gate_bias._data = jnp.concatenate(
        [jnp.zeros(24), jnp.ones(12)]).astype(jnp.float32)
    x = tokens(16)
    with held.collect_counters() as counted:
        out = layer.apply(x)
    ids, w = held.route_topk(x, layer.gate.value(), 4, False,
                             layer.gate_bias.value(), 2.5)
    assert bool(jnp.all(ids >= 24))
    assert float(jnp.abs(out - x * jnp.sum(w, -1, keepdims=True)).max()) \
        < 1e-6
    assert counted.total().tolist() == [64, 0, 0, 64]
    assert layer.counter_names == ("assignments", "local", "touched",
                                   "zero")


def test_a_router_without_zero_experts_traces_what_it_traced():
    """No `n_zero`, bias or factor: three counters, no bias leaf, the
    router's old jaxpr (top-k of the softmax itself, nothing scaled)."""
    layer = moe_layer(n_zero=0, choice_bias=False, scaling=1.0,
                      norm_topk_prob=True)
    assert layer.gate_bias is None and layer.gate.shape == [32, 24]
    assert "gate_bias" not in dict(layer.named_parameters())
    assert layer.counter_names == ("assignments", "local", "touched")
    x = tokens(8)
    with held.collect_counters() as counted:
        layer.apply(x)
    assert counted.total().shape == (3,)
    text = str(jax.make_jaxpr(lambda t: held.route_topk(
        t, layer.gate.value(), 4, True))(x))
    assert "gather" not in text and text.count("top_k") == 1


def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer(tiny):
    """24 experts + 12 zero experts over 4 shares of 6: the four partial
    blocks, with both attentions, both feed-forwards and the zero experts'
    term counted ONCE, sum to the uncut block."""
    _, arrays, model = tiny
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, 11, 64)),
                    jnp.float32)

    def block_out(prog):
        return prog.model.layers[0].apply(x, None, jnp.int32(0), None)[0]

    full = block_out(program(arrays=arrays)[0])
    fam, _ = family()
    parts = []
    for j in range(4):
        share = dict(model, num_experts=6, router_experts=24,
                     expert_offset=6 * j)
        cut = {k: (v[6 * j:6 * j + 6] if ".exp_" in k else v)
               for k, v in arrays.items()}
        assert fam.shapes(share)["l0.exp_gate"] == (6, 64, 32)
        parts.append(block_out(program(arrays=cut, model=share)[0]))
    # what every share computes alike: the block with its real experts
    # silent (zero down projections), i.e. dense parts + zero experts
    silent = {k: (jnp.zeros_like(v) if k.endswith("exp_down") else v)
              for k, v in arrays.items()}
    common = block_out(program(arrays=silent)[0])
    assert float(jnp.abs(parts[0] - common).max()) > 0.05   # experts matter
    assert float(jnp.abs(full - common).max()) > 0.05
    total = common + sum(p - common for p in parts)
    assert float(jnp.abs(total - full).max()) < 1e-5


def test_a_share_counts_its_own_assignments(tiny):
    """Over the four shares: `local` sums to the assignments that fell on
    real experts, `zero` is the same on each, `assignments` = tokens x k."""
    _, arrays, model = tiny
    u = tokens(40, 64, seed=6)
    counts = []
    for j in range(4):
        share = dict(model, num_experts=6, router_experts=24,
                     expert_offset=6 * j)
        cut = {k: (v[6 * j:6 * j + 6] if ".exp_" in k else v)
               for k, v in arrays.items()}
        layer = program(arrays=cut, model=share)[0].model.layers[0].mlp
        with held.collect_counters() as counted:
            layer.apply(u)
        counts.append(counted.total().tolist())
    assert {c[0] for c in counts} == {160} and len({c[3] for c in counts}) == 1
    zero = counts[0][3]
    assert 0 < zero < 160 and sum(c[1] for c in counts) == 160 - zero


# ------------------------------------------------- the grouped matmul

@pytest.mark.parametrize("tokens_n", [5, 40])
def test_an_expert_walked_in_blocks_is_the_expert_at_once(tokens_n,
                                                          monkeypatch):
    """`moe_grouped` over blocks of the intermediate width (an expert too
    wide for the kernel's fast memory) against the dense form, under the
    interpreter; tiles past the used ones keep the last block."""
    from paddle_tpu.kernels.pallas import moe_grouped as mg
    rng = np.random.default_rng(tokens_n)
    e, h, i, k = 5, 128, 512, 3
    f = lambda *s: jnp.asarray(rng.normal(size=s) / 8, jnp.float32)  # noqa
    x, wg, wu, wd = f(tokens_n, h) * 8, f(e, h, i), f(e, h, i), f(e, i, h)
    ids = jnp.asarray(np.stack([rng.permutation(9)[:k]
                                for _ in range(tokens_n)]), jnp.int32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, (tokens_n, k)), jnp.float32)
    valid = jnp.arange(tokens_n) != 2
    want = mg.dense_masked(x, ids, w, valid, wg, wu, wd, 2)
    monkeypatch.setattr(mg, "VMEM_LIMIT", (4 << 20) + 2 * 3 * h * 128 * 4)
    assert mg.inter_block(h, i, 4) == 128
    with mg.force_interpret():
        got, counts = mg.moe_grouped(x, ids, w, valid, wg, wu, wd, 2)
    assert float(jnp.abs(got - want).max()) < 1e-4
    assert int(counts.sum()) == int(jnp.sum(
        (ids >= 2) & (ids < 7) & valid[:, None]))


def test_block_width_is_whole_where_an_expert_fits():
    from paddle_tpu.kernels.pallas import moe_grouped as mg
    assert mg.inter_block(2048, 512, 2) == 512          # Qwen3-Next: whole
    assert mg.inter_block(6144, 2048, 2) == 512         # LongCat: quarters
    assert mg.inter_block(64, 32, 4) == 32


# ------------------------------------------------------- the parameters

def test_parameter_count_of_the_tiny_share(tiny):
    prog, arrays, model = tiny
    fam, _ = family()
    n = sum(int(np.prod(p.shape)) for _, p in prog.named_parameters())
    assert n == fam.n_params(model) == sum(
        int(np.prod(a.shape)) for a in arrays.values())
    names = {n for n, _ in prog.named_parameters()}
    assert names == set(fam.leaf_map(model))
