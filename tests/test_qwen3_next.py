"""The hybrid decoder `models/qwen3_next.py` (Gated DeltaNet + gated
attention + held experts) at a tiny size on the CPU, float32, seeded
weights: its kernels against their plain forms, the model against the
benchmark's independent reference (`benchmark/reference/qwen3_next.py`),
and the four shares of an expert layer against the uncut layer. The serving
engine's side is `test_qwen3_next_serving.py`, which shares this file's
helpers."""
import os
import sys

import numpy as np
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import paddle_tpu as paddle                                    # noqa: E402
from paddle_tpu.kernels.pallas import gdn, moe_grouped         # noqa: E402

# one period; 32 routed experts, top-4, 8 held by each of 4 shares
MODEL = dict(
    vocab_size=512, hidden_size=64, num_hidden_layers=4,
    full_attention_interval=4, num_attention_heads=4, num_key_value_heads=2,
    head_dim=32, partial_rotary_factor=0.25, rope_theta=1e7,
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=16, linear_conv_kernel_dim=4, num_experts=8,
    router_experts=32, expert_offset=0, num_experts_per_tok=4,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    norm_topk_prob=True, rms_norm_eps=1e-6, max_position_embeddings=256)
CHUNK = 16


def family():
    from benchmark.families import qwen3_next as fam
    from benchmark.reference import qwen3_next as ref
    return fam, ref


def program(seed=2**31 + 5, **over):
    """(model object, the benchmark's arrays it was given, model dict)."""
    from benchmark import system
    fam, _ = family()
    model = dict(MODEL, **over)
    arrays = fam.make(model, seed, "float32")
    prog = system.build_model(fam, {"model": model, "dtype": "float32"},
                              arrays)
    prog.eval()
    return prog, arrays, model


@pytest.fixture(scope="module")
def tiny():
    return program()


# ------------------------------------------------------------- kernels

def gdn_case(b, s, h=4, d=16, seed=0):
    rng = np.random.default_rng(seed)
    q, k = (jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
            for _ in range(2))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(d)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    g = -jnp.asarray(rng.random((b, s, h)), jnp.float32)
    beta = jnp.asarray(rng.random((b, s, h)), jnp.float32)
    state = jnp.asarray(rng.normal(size=(b, h, d, d)), jnp.float32)
    return q, k, v, g, beta, state


@pytest.mark.parametrize("s", [1, 3, 63, 64, 65, 150])
def test_chunked_delta_rule_is_the_sequential_recurrence(s):
    args = gdn_case(2, s, seed=s)
    o1, s1 = gdn.gdn_recurrent(*args)
    o2, s2 = gdn.gdn_chunked(*args)
    np.testing.assert_allclose(o2, o1, rtol=0, atol=2e-6)
    np.testing.assert_allclose(s2, s1, rtol=0, atol=5e-6)


def test_switched_off_positions_leave_the_state_alone():
    """g = 0 and beta = 0 on a padded tail: the state after the run is the
    state after the valid positions, in both forms."""
    q, k, v, g, beta, state = gdn_case(1, 40, seed=7)
    live = (jnp.arange(40) < 23)[None, :, None]
    g2, b2 = jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0)
    _, want = gdn.gdn_recurrent(q[:, :23], k[:, :23], v[:, :23], g[:, :23],
                                beta[:, :23], state)
    for form in (gdn.gdn_recurrent, gdn.gdn_chunked):
        _, got = form(q, k, v, g2, b2, state)
        np.testing.assert_allclose(got, want, rtol=0, atol=5e-6)


@pytest.mark.parametrize("h,d", [(4, 16), (8, 128)])
def test_decode_kernel_is_one_step_of_the_recurrence(h, d):
    q, k, v, g, beta, state = gdn_case(3, 1, h=h, d=d, seed=h)
    live = jnp.asarray([True, False, True])
    o_want, s_want = gdn.gdn_recurrent(q, k, v, g, beta, state)
    with gdn.force_interpret():
        o, new = gdn.gdn_decode_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                     beta[:, 0], state, live)
    np.testing.assert_allclose(o[0], o_want[0, 0], rtol=0, atol=2e-6)
    np.testing.assert_allclose(new[0], s_want[0], rtol=0, atol=2e-6)
    np.testing.assert_allclose(new[2], s_want[2], rtol=0, atol=2e-6)
    # a slot that is not live gets its state back bit for bit
    assert np.array_equal(np.asarray(new[1]), np.asarray(state[1]))
    # and so does the plain form
    _, plain = gdn.gdn_decode_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                   beta[:, 0], state, live)
    assert np.array_equal(np.asarray(plain[1]), np.asarray(state[1]))
    np.testing.assert_allclose(plain[0], new[0], rtol=0, atol=2e-6)


def moe_case(t, k=4, held=8, h=128, i=128, routed=32, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(t, h)), jnp.float32)
    ids = jnp.asarray(np.stack([rng.permutation(routed)[:k]
                                for _ in range(t)]), jnp.int32)
    w = jnp.asarray(rng.random((t, k)), jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(size=(held, h, i)) * 0.1, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(held, i, h)) * 0.1, jnp.float32)
    return x, ids, w, wg, wu, wd


@pytest.mark.parametrize("t,offset,dead", [(24, 0, 0), (24, 8, 5),
                                           (24, 24, 0), (1, 16, 0),
                                           (40, 8, 40)])
def test_grouped_matmul_is_the_dense_masked_form(t, offset, dead):
    x, ids, w, wg, wu, wd = moe_case(t, seed=t + offset)
    valid = jnp.arange(t) >= dead
    want = moe_grouped.dense_masked(x, ids, w, valid, wg, wu, wd, offset)
    with moe_grouped.force_interpret():
        got, counts = moe_grouped.moe_grouped(x, ids, w, valid, wg, wu, wd,
                                              offset)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
    local = np.asarray(ids) - offset
    held = (local >= 0) & (local < 8) & np.asarray(valid)[:, None]
    assert np.asarray(counts).tolist() == [
        int((held & (local == e)).sum()) for e in range(8)]
    if dead == t:
        assert float(jnp.max(jnp.abs(got))) == 0.0


def test_the_plan_sorts_by_expert_and_fetches_touched_experts_only():
    x, ids, w, *_ = moe_case(24, seed=3)
    p = moe_grouped.plan(ids, jnp.ones(24, bool), 8, 8, tile=16)
    counts, used = np.asarray(p["counts"]), int(p["used"])
    assert used == sum(-(-c // 16) for c in counts)
    experts = np.asarray(p["expert"])
    touched = [e for e in range(8) if counts[e]]
    # used tiles walk the touched experts in order; the rest repeat the
    # last one, so the pipeline fetches nothing more
    assert sorted(set(experts[:used])) == touched
    assert list(experts[:used]) == sorted(experts[:used])
    assert set(experts[used:]) <= {touched[-1]}
    # every held assignment has its own row, inside its expert's tiles
    row, token = np.asarray(p["row"]), np.asarray(p["token"])
    local = np.asarray(ids) - 8
    rows = row[(local >= 0) & (local < 8)]
    assert len(set(rows)) == len(rows) == counts.sum()
    for t in range(24):
        for j in range(4):
            if 0 <= local[t, j] < 8:
                assert token[row[t, j]] == t
                assert experts[row[t, j] // 16] == local[t, j]
            else:
                assert row[t, j] == len(token)


# ------------------------------------- the model against the reference

def test_full_forward_agrees_with_the_plain_reference(tiny):
    prog, arrays, model = tiny
    _, ref = family()
    ids = np.random.default_rng(1).integers(0, 512, (2, 70)).astype("int32")
    with paddle.no_grad():
        got = np.asarray(prog(paddle.to_tensor(ids)).value())
    want = np.asarray(ref.logits(arrays, jnp.asarray(ids), model))
    assert got.shape == want.shape == (2, 70, 512)
    assert np.max(np.abs(got - want)) < 2e-5 * max(1.0, np.abs(want).max())


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Shares 0-3 hold experts 0-7, 8-15, 16-23, 24-31 of one layer; every
    share routes over all 32 and computes its own experts' part. Their
    sum, the shared expert counted once (built into share 0 only), is what
    the reference gives for the layer holding all 32."""
    from paddle_tpu.incubate.distributed.models.moe import HeldExpertsMoE
    fam, ref = family()
    uncut = dict(MODEL, num_experts=32)
    w = fam.make(uncut, 11, "float32")
    x = jnp.asarray(np.random.default_rng(5).normal(size=(1, 37, 64)),
                    jnp.float32)
    p = {k: w[k][1] for k in ref.ALL_KEYS}
    want = np.asarray(ref.moe(x, p, uncut, ref.hi_dot))
    total, locals_ = 0.0, 0
    for share in range(4):
        lo = 8 * share
        layer = HeldExpertsMoE(64, 32, 32, 4, offset=lo, count=8,
                               shared_width=32 if share == 0 else 0)
        layer.gate._data = p["router"]
        layer.experts_gate_proj._data = p["exp_gate"][lo:lo + 8]
        layer.experts_up_proj._data = p["exp_up"][lo:lo + 8]
        layer.experts_down_proj._data = p["exp_down"][lo:lo + 8]
        if share == 0:
            layer.shared_gate_proj._data = p["sh_gate"]
            layer.shared_up_proj._data = p["sh_up"]
            layer.shared_down_proj._data = p["sh_down"]
            layer.shared_expert_gate._data = p["sh_mix"]
        from paddle_tpu.incubate.distributed.models.moe import \
            collect_counters
        with collect_counters() as c:
            total = total + layer.apply(x[0])
        locals_ += int(c.total()[1])
        assert int(c.total()[0]) == 37 * 4
        # the same share through the reference: the mask is its cut
        cut = dict(MODEL, expert_offset=lo)
        mine = dict(p, exp_gate=p["exp_gate"][lo:lo + 8],
                    exp_up=p["exp_up"][lo:lo + 8],
                    exp_down=p["exp_down"][lo:lo + 8])
        if share:
            mine.update(sh_down=jnp.zeros_like(p["sh_down"]))
        np.testing.assert_allclose(
            layer.apply(x[0]), ref.moe(x, mine, cut, ref.hi_dot)[0],
            rtol=0, atol=2e-6)
    assert locals_ == 37 * 4              # every assignment has one owner
    np.testing.assert_allclose(total, want[0], rtol=0, atol=5e-6)


def test_the_reference_gives_a_loss_and_a_gradient_for_every_leaf(tiny):
    """`loss_and_grads` is `jax.grad` of the forward the serving comparison
    uses: a random model's loss is about log(vocabulary), every stacked
    array has a finite gradient, and the held experts' gradient is zero
    exactly where no token was routed."""
    _, arrays, model = tiny
    fam, ref = family()
    ids = np.random.default_rng(12).integers(0, 512, (2, 24)).astype("int32")
    loss, grads = ref.loss_and_grads(arrays, jnp.asarray(ids), model)
    assert abs(float(loss) - np.log(512)) < 0.2
    assert sorted(grads) == sorted(fam.shapes(model))
    assert set(ref.LAYER_KEYS) | set(ref.TOP_KEYS) == set(grads)
    for k, g in grads.items():
        assert g.shape == arrays[k].shape and bool(jnp.all(jnp.isfinite(g)))
    assert float(jnp.abs(grads["lin_alog"]).max()) > 0
    assert float(jnp.abs(grads["att_kn"]).max()) > 0
    per_expert = jnp.abs(grads["exp_gate"]).max(axis=(2, 3))       # [L, E]
    assert float(per_expert.max()) > 0
